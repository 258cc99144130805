"""Counting Yannakakis' SpMV (``core/yannakakis.py`` ``_spmv``): exact int64
row sums over a source-sorted CSR, computed without a scatter.

Each case compares ``_spmv`` with a row-by-row sum in Python integers;
the wrapping case holds values near 2**61 on many edges, so the prefix
sum overflows int64 many times while every row sum fits, which only a
modular int64 form gets right.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import GraphDB, HybridGraphDB, HybridJoin, count, get_query
from repro.core.yannakakis import CountingYannakakis, _spmv
from repro.graphs import CSRGraph, erdos_renyi, powerlaw_cluster, zipf_graph

from conftest import make_gdb


def _csr(indptr, indices) -> CSRGraph:
    return CSRGraph(indptr=np.asarray(indptr, dtype=np.int64),
                    indices=np.asarray(indices, dtype=np.int64),
                    n_nodes=len(indptr) - 1)


def _hub_row(n: int) -> CSRGraph:
    """Row 0 holds an edge to every vertex; every other row is empty."""
    indptr = np.full(n + 1, n, dtype=np.int64)
    indptr[0] = 0
    return _csr(indptr, np.arange(n))


def _bounded_rows(n: int, seed: int) -> CSRGraph:
    """Many rows of 0 to 3 edges each, so values below 2**61 keep every
    row sum inside int64."""
    rng = np.random.default_rng(seed)
    deg = rng.integers(0, 4, size=n)
    rows = [np.sort(rng.choice(n, size=d, replace=False)) for d in deg]
    indptr = np.concatenate([[0], np.cumsum(deg)])
    return _csr(indptr, np.concatenate(rows).astype(np.int64))


def _random_rows(n: int, m: int, seed: int) -> CSRGraph:
    """``m`` edges with uniform sources and targets, sorted by source."""
    rng = np.random.default_rng(seed)
    src = np.sort(rng.integers(0, n, size=m))
    indptr = np.concatenate([[0], np.cumsum(np.bincount(src, minlength=n))])
    return _csr(indptr, rng.integers(0, n, size=m))


GRAPHS = {
    "empty": lambda: _csr([0], []),
    "all_rows_empty": lambda: _csr(np.zeros(9, dtype=np.int64), []),
    "hub_row": lambda: _hub_row(257),
    "one_edge_last_row": lambda: _csr([0, 0, 0, 1], [0]),
    "holme_kim": lambda: powerlaw_cluster(300, 4, seed=5),
    "gnm": lambda: erdos_renyi(400, 1200, seed=6),
    "zipf": lambda: zipf_graph(300, 1500, seed=7),
    "longer_than_a_scan_block": lambda: powerlaw_cluster(1500, 3, seed=8),
    "two_levels_of_scan_blocks": lambda: _random_rows(700, 1024 ** 2 + 3,
                                                      seed=12),
}


def _reference(g: CSRGraph, c: np.ndarray) -> list[int]:
    return [sum(int(c[z]) for z in g.indices[g.indptr[x]:g.indptr[x + 1]])
            for x in range(g.n_nodes)]


def _run(g: CSRGraph, c: np.ndarray) -> np.ndarray:
    gdb = GraphDB(g, {})
    y = _spmv(gdb.dev("indptr"), gdb.dev("indices"), gdb.dev("src_ids"),
              jnp.asarray(c, dtype=jnp.int64), num_segments=g.n_nodes)
    assert y.dtype == jnp.int64 and y.shape == (g.n_nodes,)
    return np.asarray(y)


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_spmv_matches_csr_matvec(name):
    g = GRAPHS[name]()
    rng = np.random.default_rng(len(name))
    c = rng.integers(-2 ** 40, 2 ** 40, size=g.n_nodes, dtype=np.int64)
    assert [int(v) for v in _run(g, c)] == _reference(g, c)


def test_spmv_exact_where_the_prefix_sum_wraps():
    g = _bounded_rows(3000, seed=9)
    rng = np.random.default_rng(10)
    c = rng.integers(2 ** 61 - 2 ** 20, 2 ** 61, size=g.n_nodes,
                     dtype=np.int64)
    ref = _reference(g, c)
    assert max(ref) > 2 ** 62 and max(ref) < 2 ** 63
    assert sum(int(c[z]) for z in g.indices) > 2 ** 70  # wraps many times
    assert [int(v) for v in _run(g, c)] == ref


def test_spmv_lowers_without_a_scatter():
    g = powerlaw_cluster(300, 4, seed=5)
    gdb = GraphDB(g, {})
    text = _spmv.lower(gdb.dev("indptr"), gdb.dev("indices"),
                       gdb.dev("src_ids"),
                       jnp.zeros(g.n_nodes, dtype=jnp.int64),
                       num_segments=g.n_nodes).as_text()
    assert "gather" in text
    assert "stablehlo.scatter" not in text


@pytest.fixture(scope="module")
def gdb():
    return make_gdb(60, 3, seed=11)


@pytest.mark.parametrize("qname", ["3-path", "4-path", "1-tree", "2-tree",
                                   "2-comb"])
def test_counting_yannakakis_matches_oracle(gdb, qname):
    q = get_query(qname)
    cy = CountingYannakakis(q, gdb)
    assert cy.count() == count(q, gdb, engine="lftj_ref")
    assert cy.stats["spmvs"] == len(q.variables) - 1


@pytest.mark.parametrize("layout", ["plain", "hybrid"])
@pytest.mark.parametrize("qname", ["2-lollipop", "3-lollipop"])
def test_hybrid_lollipop_matches_oracle(gdb, qname, layout):
    db = gdb if layout == "plain" else HybridGraphDB.build(gdb.csr,
                                                           gdb.unary)
    q = get_query(qname)
    hj = HybridJoin(q, db)
    assert hj.count() == count(q, db, engine="lftj_ref")
    assert hj.stats["spmvs"] > 0
