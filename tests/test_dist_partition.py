"""PartitionedJoin edge cases, partition/schedule invariants, the real
worker pool, and the QueryServer -> dist routing path (all single-device
host-side)."""
import math
import os

import jax
import numpy as np
import pytest

from repro.core import GraphDB, count, get_query
from repro.core.plan import executor_geometry, stripe_partition
from repro.dist.pool import WorkerPool, pick_backend
from repro.dist.sharded_join import PartitionedJoin, spmd_join_step
from repro.graphs import node_sample, powerlaw_cluster
from repro.serve import QueryRequest, QueryServer


@pytest.fixture(scope="module")
def gdb():
    g = powerlaw_cluster(300, 4, seed=11)
    unary = {f"v{i}": node_sample(g.n_nodes, 6, seed=i)
             for i in range(1, 5)}
    return GraphDB(g, unary)


def test_stripe_partition_balances_sizes_and_costs():
    rng = np.random.default_rng(0)
    costs = rng.pareto(1.5, size=97) + 1.0   # power-law skew
    parts = stripe_partition(costs, 8)
    assert len(parts) == 8
    all_items = np.sort(np.concatenate(parts))
    assert np.array_equal(all_items, np.arange(97))
    sizes = [len(p) for p in parts]
    assert max(sizes) - min(sizes) <= 1
    # no partition can beat the largest single item; the snake deal keeps
    # the spread within that bound
    loads = np.array([costs[p].sum() for p in parts])
    assert loads.max() - loads.min() <= costs.max()


def test_stripe_partition_more_parts_than_items():
    parts = stripe_partition(np.ones(3), 8)
    assert len(parts) == 8
    assert sum(len(p) for p in parts) == 3
    assert sum(len(p) == 0 for p in parts) == 5


@pytest.mark.parametrize("qname", ["3-clique", "4-cycle", "3-path"])
def test_partitioned_count_matches_planner_count(gdb, qname):
    ref = count(get_query(qname), gdb, engine="vlftj")
    pj = PartitionedJoin(get_query(qname), gdb, n_workers=3, granularity=2)
    assert pj.count() == ref


def test_empty_frontier_shard_counts_zero(gdb):
    pj = PartitionedJoin(get_query("3-clique"), gdb, n_workers=2,
                         granularity=1)
    c = pj.executor.seeded_count(np.empty(0, np.int32),
                                 np.empty(0, np.int64))
    assert c == 0


def test_empty_and_sparse_parts_still_exact(gdb):
    ref = count(get_query("3-clique"), gdb, engine="vlftj")
    pj = PartitionedJoin(get_query("3-clique"), gdb, n_workers=64,
                         granularity=8)   # 512 parts >> any balance
    assert pj.count() == ref
    assert pj.stats["parts"] == 512
    assert len(pj.stats["worker_time"]) == 64
    sizes = pj.stats["part_sizes"]
    assert max(sizes) - min(sizes) <= 1
    # with 300 nodes and 512 parts many shards are empty frontiers
    assert sum(s == 0 for s in sizes) > 0


def test_stats_invariants(gdb):
    pj = PartitionedJoin(get_query("3-clique"), gdb, n_workers=4,
                         granularity=3)
    pj.count()
    st = pj.stats
    assert st["parts"] == 12
    assert st["makespan"] <= st["total_time"] + 1e-9
    assert abs(sum(st["worker_time"]) - st["total_time"]) < 1e-9
    assert len(st["part_time"]) == 12 and len(st["part_counts"]) == 12
    # static deal: every worker owns exactly `granularity` parts
    assert all(len(v) == 3 for v in pj.schedule.values())
    # cost-balanced parts: sizes within one of each other
    assert max(st["part_sizes"]) - min(st["part_sizes"]) <= 1


def test_dead_worker_redeal_covers_all_parts(gdb):
    ref = count(get_query("3-path"), gdb, engine="vlftj")
    pj = PartitionedJoin(get_query("3-path"), gdb, n_workers=4,
                         granularity=2, dead={1})
    assert pj.count() == ref
    owned = sorted(p for parts in pj.schedule.values() for p in parts)
    assert owned == list(range(8))
    assert 1 not in pj.schedule
    assert pj.stats["worker_time"][1] == 0.0


def test_pool_equals_sequential_partitioned_join(gdb):
    """The satellite property: the concurrent pool computes exactly what
    the old sequential walk did, part for part."""
    for qname in ("3-clique", "3-path"):
        seq = PartitionedJoin(get_query(qname), gdb, n_workers=3,
                              granularity=2, backend="sequential")
        pool = PartitionedJoin(get_query(qname), gdb, n_workers=3,
                               granularity=2, backend="thread")
        assert seq.count() == pool.count()
        assert seq.stats["part_counts"] == pool.stats["part_counts"]
        assert seq.stats["backend"] == "sequential"
        assert pool.stats["backend"] == "thread"
        assert pool.stats["wall_time"] > 0


def test_auto_backend_routes_device_payload_to_threads(gdb):
    pj = PartitionedJoin(get_query("3-clique"), gdb, n_workers=2,
                         granularity=2)
    ref = count(get_query("3-clique"), gdb, engine="vlftj")
    assert pj.count() == ref
    # the join task closes over jitted/device state: never a process
    assert pj.stats["backend"] == "thread"
    assert pick_backend(pj._count_part, pj.parts[0]) == "thread"
    # a pure-python payload may cross a process boundary
    assert pick_backend(math.factorial, 5) == "process"


def test_worker_pool_process_backend_roundtrip():
    sched = {0: [0, 2], 1: [1, 3]}
    res, ptime, wall, backend = WorkerPool(sched, backend="auto").run(
        math.factorial, [5, 6, 7, 8])
    assert backend == "process"
    assert res == {0: 120, 1: 720, 2: 5040, 3: 40320}
    assert set(ptime) == {0, 1, 2, 3} and wall > 0


def test_process_workers_are_pinned_to_the_cpu(monkeypatch):
    # the parent may hold the chip: a spawned worker must never ask for it
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    res, _, _, backend = WorkerPool({0: [0], 1: [1]}, backend="process").run(
        os.getenv, ["JAX_PLATFORMS", "JAX_PLATFORMS"])
    assert backend == "process"
    assert res == {0: "cpu", 1: "cpu"}


def test_pool_respects_dead_worker_schedule(gdb):
    ref = count(get_query("3-path"), gdb, engine="vlftj")
    pj = PartitionedJoin(get_query("3-path"), gdb, n_workers=4,
                         granularity=2, dead={2}, backend="thread")
    assert pj.count() == ref
    assert pj.stats["worker_time"][2] == 0.0
    assert 2 not in pj.schedule


def test_spmd_join_step_pads_non_divisible_frontier(gdb):
    """Regression (satellite): callers no longer pre-pad the frontier to
    the shard multiple or hand-zero the padding's mult."""
    n_dev = jax.device_count()
    mesh = jax.make_mesh((n_dev,), ("data",))
    g = gdb.csr
    ea = g.edge_array()
    fr = ea[ea[:, 0] < ea[:, 1]].astype(np.int32)
    # odd length: under >1 device the wrapper must pad internally
    if fr.shape[0] % 2 == 0:
        fr = fr[:-1]
    width, _ = executor_geometry(gdb.max_degree)
    kw = dict(probe_cols=(0, 1), n_unary=0, lower_cols=(1,), upper_cols=(),
              width=width, n_iter=gdb.bsearch_iters, needs_degree=False)
    step = spmd_join_step(mesh, kw)
    mult = np.ones(fr.shape[0], np.int64)
    got = int(step(gdb.dev("indptr"), gdb.dev("indices"), fr, mult))
    # oracle: per-edge sorted-intersection triangle count over fr
    ind, ptr = g.indices, g.indptr
    ref = 0
    for a, b in fr:
        inter = np.intersect1d(ind[ptr[a]:ptr[a + 1]],
                               ind[ptr[b]:ptr[b + 1]], assume_unique=True)
        ref += int((inter > b).sum())
    assert got == ref


def test_query_server_routes_large_graphs_to_partitioned():
    g = powerlaw_cluster(300, 4, seed=3)
    plain = QueryServer(g)                       # threshold far above g
    routed = QueryServer(g, dist_edge_threshold=1)
    req = QueryRequest("3-clique", selectivity=8, seed=0, engine="vlftj")
    r_plain = plain.execute(req)
    r_routed = routed.execute(req)
    assert r_plain.engine == "vlftj"
    assert r_routed.engine == "vlftj+partitioned"
    assert r_routed.count == r_plain.count
    st = routed.last_dist_stats
    assert st is not None and st["parts"] == 8   # 4 workers x 2
    assert st["makespan"] <= st["total_time"] + 1e-9
    # non-vlftj plans never take the dist route
    r_y = routed.execute(QueryRequest("3-path", selectivity=8, seed=0,
                                      engine="yannakakis"))
    assert r_y.engine == "yannakakis"


def test_execute_many_keeps_dist_route():
    g = powerlaw_cluster(300, 4, seed=3)
    routed = QueryServer(g, dist_edge_threshold=1)
    res = routed.execute_many(
        [QueryRequest("3-clique", selectivity=8, seed=0, engine="vlftj")] * 2)
    assert all(r.engine == "vlftj+partitioned" for r in res)
    assert res[0].count == res[1].count
