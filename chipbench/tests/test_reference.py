"""The plain reference agrees with what the server answers, for every
shape of every mix, on tiny graphs: counts exactly, pages row for row."""
import numpy as np
import pytest

import checks
import graphs
import traffic
from conftest import TINY_GENERATORS
from reference import PATTERNS, Reference


def mixes():
    """(configuration, traffic request) for every shape of every mix."""
    import run
    out = []
    for cell in run.benchmark()["workloads"]:
        mix = run.load_cell(cell["name"])["mix"]
        reqs = (mix["requests"] if mix["loop"] == "open" else
                [{"kind": "count", "shape": s} for s in mix["shapes"]])
        out += [(cell["config"], r) for r in reqs
                if r["kind"] != "continuation"]
    # the harness's first pages, which no mix has yet
    return out + [("soc-Slashdot0811-like",
                   {"kind": "page", "shape": "3-path"})]


@pytest.fixture(scope="module")
def served():
    from repro.graphs.csr import CSRGraph
    from repro.serve import QueryServer
    out = {}
    for config, gen in TINY_GENERATORS.items():
        indptr, indices = graphs.build_graph(gen, seed=5)
        csr = CSRGraph(indptr=indptr, indices=indices,
                       n_nodes=indptr.shape[0] - 1)
        out[config] = (QueryServer(csr, page_rows=64),
                       Reference(indptr, indices))
    return out


@pytest.mark.parametrize("config,req", mixes(),
                         ids=lambda x: x if isinstance(x, str)
                         else f"{x['kind']}-{x['shape']}")
@pytest.mark.parametrize("selectivity", [3, 10])
def test_served_answers_match_the_reference(served, config, req,
                                            selectivity):
    from repro.serve import QueryRequest
    server, ref = served[config]
    limit = 64 if req["kind"] == "page" else None
    responses, cursor, page = [], None, 0
    while True:
        res = server.execute(QueryRequest(
            req["shape"], selectivity=selectivity, seed=11, limit=limit,
            cursor=cursor))
        r = {"kind": req["kind"], "shape": req["shape"], "status": "ok",
             "selectivity": selectivity, "pred_seed": 11,
             "count": res.count, "limit": limit, "page": page,
             "rows": res.rows, "row_vars": res.row_vars,
             "next_cursor": res.next_cursor}
        responses.append(r)
        if limit is None or res.next_cursor is None:
            break
        cursor, page = res.next_cursor, page + 1
    got = checks.compare(responses, ref)
    assert got == {"wrong_counts": 0, "wrong_pages": 0, "lost": 0,
                   "checked": len(responses)}
    if config == "soc-Slashdot0811-like":
        assert responses[0]["count"] > 0


@pytest.mark.parametrize("shape", sorted(PATTERNS))
def test_rows_and_counts_agree(shape):
    indptr, indices = graphs.build_graph(
        TINY_GENERATORS["soc-Slashdot0811-like"], seed=2)
    ref = Reference(indptr, indices)
    unary = graphs.request_unary(ref.n, 5, 3)
    cols = PATTERNS[shape].variables[::-1]
    rows = ref.rows(shape, unary, cols)
    assert rows.shape == (ref.count(shape, unary), len(cols))
    assert (np.diff(rows, axis=0) != 0).any(axis=1).all()
    assert np.array_equal(rows, rows[np.lexsort(rows.T[::-1])])


def test_relabelling_keeps_the_degrees():
    gen = TINY_GENERATORS["soc-Slashdot0811-like"]
    a = graphs.build_graph(gen, seed=1)
    b = graphs.build_graph(gen, seed=2)
    assert sorted(np.diff(a[0])) == sorted(np.diff(b[0]))
    assert not np.array_equal(a[1], b[1])
    assert Reference(*a).count("3-clique", {}) == \
        Reference(*b).count("3-clique", {})


def test_traffic_is_the_same_work_at_the_same_times():
    import run
    mix = run.load_cell("slashdot.tenants")["mix"]
    a = traffic.open_schedule(mix, 1, 20.0)
    b = traffic.open_schedule(mix, 2, 20.0)
    assert a == traffic.open_schedule(mix, 1, 20.0)
    assert a != b
    for key in ("kind", "shape", "t_due"):
        assert [r[key] for r in a] == [r[key] for r in b]
    assert [r["pred_seed"] for r in a] != [r["pred_seed"] for r in b]
    sels = [sorted(t["selectivity"] for t in traffic.tenants(mix, s))
            for s in (1, 2)]
    assert sels[0] == sels[1]
    assert all(0 <= r["t_due"] < 20.0 for r in a)
    big = traffic.open_schedule(mix, 3_000_000_000, 20.0)
    assert len(big) == len(a)
    rotation = traffic.closed_rotation(
        run.load_cell("gnutella.counts")["mix"], 5)
    other = traffic.closed_rotation(
        run.load_cell("gnutella.counts")["mix"], 6)
    assert sorted(r["shape"] for r in rotation) == sorted(
        r["shape"] for r in other)
