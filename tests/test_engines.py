"""Cross-engine agreement: every engine must produce identical counts.

The scalar LFTJ (validated against networkx oracles in test_graphs) is the
reference; Minesweeper, binary join, vectorized LFTJ, counting Yannakakis
and the hybrid must all agree on every paper query, including under
hypothesis-generated random graphs and samples.
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import (GraphDB, Minesweeper, PAPER_QUERIES, count,
                        get_query, pick_engine)
from repro.graphs import CSRGraph

from conftest import make_gdb

ALL_QUERIES = list(PAPER_QUERIES)


@pytest.fixture(scope="module")
def gdb():
    return make_gdb(50, 3, seed=3)


@pytest.mark.parametrize("qname", ALL_QUERIES)
def test_all_engines_agree(gdb, qname):
    q = get_query(qname)
    ref = count(q, gdb, engine="lftj_ref")
    assert count(q, gdb, engine="vlftj") == ref
    assert count(q, gdb, engine="binary") == ref
    assert count(q, gdb, engine="minesweeper_ref") == ref
    auto = pick_engine(q)
    assert count(q, gdb, engine=auto) == ref


def test_enumerate_agreement(gdb):
    from repro.core import LFTJ, VLFTJ
    for qname in ["3-clique", "3-path", "2-comb"]:
        q = get_query(qname)
        ref_engine = LFTJ(q, gdb.to_database())
        vec = VLFTJ(q, gdb, gao=ref_engine.gao)
        a = ref_engine.enumerate()
        b = vec.enumerate()
        a_sorted = a[np.lexsort(a.T[::-1])] if a.size else a
        b_sorted = b[np.lexsort(b.T[::-1])] if b.size else b
        np.testing.assert_array_equal(a_sorted, b_sorted)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10 ** 6), n=st.integers(8, 28),
       density=st.integers(1, 4))
def test_property_vectorized_matches_scalar(seed, n, density):
    rng = np.random.default_rng(seed)
    m = n * density
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    keep = src != dst
    if not keep.any():
        return
    g = CSRGraph.from_edges(src[keep], dst[keep], n_nodes=n)
    unary = {f"v{i}": rng.choice(n, max(1, n // 3), replace=False)
             for i in range(1, 5)}
    gdb = GraphDB(g, unary)
    for qname in ["3-clique", "4-cycle", "3-path", "2-comb",
                  "2-lollipop"]:
        q = get_query(qname)
        ref = count(q, gdb, engine="lftj_ref")
        assert count(q, gdb, engine="vlftj") == ref, qname
        assert count(q, gdb, engine="auto") == ref, qname


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10 ** 6))
def test_property_minesweeper_matches_scalar(seed):
    rng = np.random.default_rng(seed)
    n = 16
    m = 40
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    keep = src != dst
    if not keep.any():
        return
    g = CSRGraph.from_edges(src[keep], dst[keep], n_nodes=n)
    unary = {f"v{i}": rng.choice(n, 5, replace=False) for i in range(1, 5)}
    gdb = GraphDB(g, unary)
    for qname in ["3-clique", "3-path", "1-tree", "2-comb"]:
        q = get_query(qname)
        ref = count(q, gdb, engine="lftj_ref")
        assert count(q, gdb, engine="minesweeper_ref") == ref, qname


def test_minesweeper_idea_flags_preserve_counts(gdb):
    for qname in ["3-clique", "4-cycle", "3-path"]:
        q = get_query(qname)
        db = gdb.to_database()
        base = Minesweeper(q, db).count()
        assert Minesweeper(q, db, skip_probes=False).count() == base
        assert Minesweeper(q, db, use_skeleton=False).count() == base


def test_minesweeper_probe_skip_saves_probes(gdb):
    q = get_query("3-path")
    db = gdb.to_database()
    on = Minesweeper(q, db, skip_probes=True)
    on.count()
    off = Minesweeper(q, db, skip_probes=False)
    off.count()
    assert on.stats["probe_skips"] > 0
    assert on.stats["probes"] < off.stats["probes"]


def test_agm_bound_respected(gdb):
    from repro.core import agm_bound
    sizes = gdb.to_database().sizes()
    for qname in ALL_QUERIES:
        q = get_query(qname)
        c = count(q, gdb, engine="vlftj")
        assert c <= agm_bound(q, sizes) * 1.0000001, qname


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10 ** 6), path_len=st.integers(1, 3),
       clique_k=st.integers(3, 4))
def test_property_hybrid_generalized_lollipops(seed, path_len, clique_k):
    """§4.12 generalized: random tadpole queries (path of length 1-3 into
    a {3,4}-clique) — hybrid must agree with the scalar oracle."""
    from repro.core import Atom, LessThan, Query, HybridJoin

    path_vars = [f"p{i}" for i in range(path_len + 1)]
    clique_vars = [path_vars[-1]] + [f"c{i}" for i in range(clique_k - 1)]
    atoms = [Atom("v1", (path_vars[0],))]
    atoms += [Atom("edge", (path_vars[i], path_vars[i + 1]))
              for i in range(path_len)]
    atoms += [Atom("edge", (clique_vars[i], clique_vars[j]))
              for i in range(clique_k) for j in range(i + 1, clique_k)]
    filters = [LessThan(clique_vars[i], clique_vars[i + 1])
               for i in range(1, clique_k - 1)]
    q = Query(tuple(atoms), tuple(filters), f"tadpole-{path_len}-{clique_k}")

    rng = np.random.default_rng(seed)
    n, m = 24, 72
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    keep = src != dst
    if not keep.any():
        return
    g = CSRGraph.from_edges(src[keep], dst[keep], n_nodes=n)
    gdb = GraphDB(g, {"v1": rng.choice(n, 8, replace=False)})
    ref = count(q, gdb, engine="lftj_ref")
    hj = HybridJoin(q, gdb)
    assert hj.count() == ref
    # the decomposition should actually engage for these shapes
    assert hj.decomp.applicable, (path_len, clique_k)


def test_width_classes_ladder():
    from repro.core.plan import MIN_WIDTH, width_classes
    assert width_classes(2048) == tuple(MIN_WIDTH << i for i in range(7))
    assert width_classes(MIN_WIDTH) == (MIN_WIDTH,)
    assert width_classes(16) == (16,)


@pytest.mark.parametrize("qname", ["3-clique", "4-cycle", "3-path"])
def test_rows_dispatch_at_their_width_class(qname):
    """On a power-law graph most rows have a short probe segment: they run
    in narrow tiles, not at the hub's width, with the count unchanged."""
    from repro.core import VLFTJ
    gdb = make_gdb(400, 3, seed=5)
    q = get_query(qname)
    ex = VLFTJ(q, gdb)
    assert ex.width >= 128
    assert ex.count() == count(q, gdb, engine="lftj_ref")
    # at one width for all, every dispatched row would pay ex.width lanes
    assert ex.stats["candidates"] < ex.stats["rows_expanded"] * ex.width // 2
    # a mixed chunk of the final level dispatches at its widest row
    front = np.asarray(VLFTJ(q, gdb).advance(max_levels=len(ex.plan) - 1),
                       dtype=np.int32)
    widths = ex.row_widths(front)
    assert set(np.unique(widths)) <= set(range(32, ex.width + 1))
    counts = ex.last_level_counts(front)
    assert counts.sum() == ex.count()
