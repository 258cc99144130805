"""The control, edge-sampled estimates in the program's place, comes out
not correct on every cell's traffic; the exact reference there comes out
correct."""
import pytest

import checks
import control
import graphs
from conftest import tiny_cell


@pytest.mark.parametrize("name", ["slashdot.clique3", "gnutella.counts",
                                  "slashdot.tenants"])
@pytest.mark.parametrize("seed", [1, 2**31 + 5, 77])
def test_control_is_not_correct(name, seed):
    cell = tiny_cell(name, rate_per_s=10.0)
    if name == "gnutella.counts":
        cell["config_spec"]["generator"] = dict(
            cell["config_spec"]["generator"], m=3000)
    indptr, indices = graphs.build_graph(cell["config_spec"]["generator"],
                                         seed)
    est = control.Estimate(indptr, indices, seed)
    got = checks.compare(control.answers(cell, seed, 5.0, est), est.exact)
    assert not checks.correct(got)
    assert got["wrong_counts"] > 0
    est.sample = est.exact
    est.p = 1.0
    assert checks.correct(checks.compare(
        control.answers(cell, seed, 5.0, est), est.exact))
