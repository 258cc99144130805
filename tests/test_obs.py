"""Observability (repro.obs): tracing, metrics, EXPLAIN ANALYZE.

The contract under test, in tiers:

* **Schema** — every engine path emits the unified stats schema
  (``ENGINE_REQUIRED_KEYS``) through ``execute_stats``.
* **Zero-cost when off** — a disabled tracer adds *no* device
  dispatches: the vlftj dispatch meters (chunks / ll_calls /
  candidates) are identical with tracing on and off, and counts agree.
* **Complete traces end to end** — a scheduled query's trace carries
  preempt/resume (and restart) events; a dist-routed query's trace
  carries per-level exchange events; both with count parity against
  the untraced run.
* **EXPLAIN ANALYZE** — a Zipf-skewed triangle shows per-level
  est-vs-observed cardinality and a finite Q-error.
* **Registry** — counters/gauges/histograms aggregate by label and
  snapshot as flat prometheus-style keys; the server surfaces them.
"""
import json

import numpy as np
import pytest

from repro.core import (GraphDB, GraphStats, count, execute_stats,
                        get_query, plan_query)
from repro.dist.sharded_csr import ShardedGraphDB, sharded_count
from repro.graphs import node_sample, powerlaw_cluster
from repro.graphs.generators import zipf_graph
from repro.obs import (ENGINE_REQUIRED_KEYS, MetricsRegistry, QueryTrace,
                       current_trace, explain_analyze, normalize_engine_stats,
                       qerror)
from repro.serve import QuantumScheduler, QueryRequest, QueryServer

from conftest import make_gdb

# engine -> a query shape it supports (yannakakis needs β-acyclic)
SIX_ENGINES = [("vlftj", "3-clique"), ("lftj_ref", "3-clique"),
               ("binary", "3-clique"), ("minesweeper_ref", "3-clique"),
               ("yannakakis", "3-path"), ("hybrid", "2-lollipop")]


@pytest.fixture(scope="module")
def gdb():
    return make_gdb(60, 3, seed=5)


def zipf_gdb(n=500, m=2500, seed=0):
    g = zipf_graph(n, m, seed=seed)
    unary = {f"v{i}": node_sample(g.n_nodes, 4, seed=seed + i)
             for i in range(1, 5)}
    return GraphDB(g, unary)


# ---------------------------------------------------------------------------
# satellite 1: unified engine stats schema
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine,qname", SIX_ENGINES)
def test_every_engine_emits_unified_stats(gdb, engine, qname):
    q = get_query(qname)
    plan = plan_query(q, GraphStats.of(gdb), engine=engine)
    c, stats = execute_stats(plan, gdb)
    assert c == count(q, gdb, engine="lftj_ref")
    assert tuple(sorted(stats)) == tuple(sorted(ENGINE_REQUIRED_KEYS))
    assert stats["name"] == engine
    assert isinstance(stats["rows_expanded"], int)
    assert isinstance(stats["raw"], dict)
    for d in (stats["level_rows"], stats["level_wall_s"],
              stats["level_paths"]):
        assert all(isinstance(k, int) for k in d)


def test_normalize_is_total_on_empty_stats():
    out = normalize_engine_stats("mystery", None)
    assert tuple(sorted(out)) == tuple(sorted(ENGINE_REQUIRED_KEYS))
    assert out["rows_expanded"] == 0 and out["raw"] == {}


# ---------------------------------------------------------------------------
# satellite 3: tracing on/off parity + zero-dispatch guard
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine,qname", SIX_ENGINES)
def test_traced_count_matches_untraced(gdb, engine, qname):
    q = get_query(qname)
    plan = plan_query(q, GraphStats.of(gdb), engine=engine)
    ref, _ = execute_stats(plan, gdb)
    tr = QueryTrace(qname, plan.gao, engine)
    with tr.activate():
        traced, _ = execute_stats(plan, gdb)
    assert traced == ref
    assert tr.summary["count"] == ref


def test_disabled_tracer_adds_zero_device_dispatches(gdb):
    """The whole-point guard: with no active trace, the vlftj dispatch
    meters are identical to a run that never imported repro.obs —
    capture is host-side harvesting of counters vlftj keeps anyway."""
    q = get_query("4-cycle")
    plan = plan_query(q, GraphStats.of(gdb), engine="vlftj")
    assert current_trace() is None
    _, off = execute_stats(plan, gdb)
    tr = QueryTrace("4-cycle", plan.gao, "vlftj")
    with tr.activate():
        _, on = execute_stats(plan, gdb)
    for meter in ("chunks", "ll_calls", "candidates"):
        assert on["raw"][meter] == off["raw"][meter], meter
    assert on["kernel_dispatches"] == off["kernel_dispatches"]
    assert on["jit_calls"] == off["jit_calls"]
    # static agreement: the obs-device-free lint pass proves the same
    # property by construction — the harvest modules never touch jax,
    # so the runtime meter parity above is not a coincidence of this
    # query shape
    import ast as ast_mod
    from conftest import REPO_ROOT, load_lint_module
    lint = load_lint_module()
    rule = lint.ObsHostPurity()
    import os
    for rel in rule.scope:
        src = open(os.path.join(REPO_ROOT, rel), encoding="utf-8").read()
        assert rule.check(ast_mod.parse(src), rel, src) == [], rel


def test_vlftj_levels_carry_est_obs_and_paths(gdb):
    q = get_query("3-clique")
    plan = plan_query(q, GraphStats.of(gdb), engine="vlftj")
    tr = QueryTrace("3-clique", plan.gao, "vlftj")
    with tr.activate():
        c, _ = execute_stats(plan, gdb)
    assert len(plan.level_est_rows) == len(plan.gao)
    for lv in range(len(plan.gao)):
        rec = tr.levels[lv]
        assert rec["var"] == plan.gao[lv]
        assert rec["obs_rows"] >= 0
        assert rec["est_rows"] == pytest.approx(plan.level_est_rows[lv])
        assert rec["q_error"] >= 1.0
    # interior levels record which kernel path expanded their rows
    assert any("kernel" in tr.levels[lv] for lv in range(1, len(plan.gao)))
    assert tr.summary["count"] == c


# ---------------------------------------------------------------------------
# acceptance: scheduled query -> complete trace with preempt/resume
# ---------------------------------------------------------------------------

def test_scheduled_trace_has_preempt_resume_and_parity():
    csr = powerlaw_cluster(n=300, m_per_node=4, seed=0)
    server = QueryServer(csr, page_rows=256)
    sched = QuantumScheduler(server, quantum_rows=64)
    sched.submit(QueryRequest("3-path", engine="vlftj", trace=True))
    (res,) = sched.run()
    gdb = server._gdb_for(server.default_selectivity, 0)
    assert res.count == count(get_query("3-path"), gdb, engine="vlftj")
    tr = res.trace
    assert tr is not None
    preempts = tr.events_named("preempt")
    resumes = tr.events_named("resume")
    assert len(preempts) >= 1
    assert len(resumes) >= 1
    assert all("quantum" in e or "phase" in e for e in preempts)
    assert tr.summary["count"] == res.count
    assert tr.summary["quanta"] == res.stats["quanta"]
    # the full trace serializes: preempt/resume events survive JSONL
    back = QueryTrace.from_jsonl(tr.to_jsonl())
    assert len(back.events_named("preempt")) == len(preempts)
    assert back.summary["count"] == res.count
    # untraced request: no trace object, same count
    plain = QueryServer(csr, page_rows=256)
    s2 = QuantumScheduler(plain, quantum_rows=64)
    s2.submit(QueryRequest("3-path", engine="vlftj"))
    (r2,) = s2.run()
    assert r2.trace is None and r2.count == res.count


def test_restart_backoff_visible_in_stats_and_trace():
    """Satellite 6: eviction restarts double the quantum invisibly —
    now exposed as stats['quantum_rows_final'] and a per-restart trace
    event carrying the grown quantum."""
    csr = powerlaw_cluster(n=300, m_per_node=4, seed=0)
    server = QueryServer(csr, page_rows=256, max_open_cursors=2)
    sched = QuantumScheduler(server, quantum_rows=64)
    sched.submit(QueryRequest("3-path", engine="vlftj", trace=True))
    assert sched.step()                    # preempts; snapshot parked
    for s in range(3):                     # flood the LRU registry
        server.execute(QueryRequest("3-clique", engine="vlftj", limit=1,
                                    seed=s))
    while sched.step():
        pass
    (res,) = [j.result for j in sched._jobs]
    assert res.stats["restarts"] >= 1
    assert res.stats["quantum_rows_initial"] == 64
    assert (res.stats["quantum_rows_final"]
            == 64 * 2 ** res.stats["restarts"])
    restarts = res.trace.events_named("restart")
    assert len(restarts) == res.stats["restarts"]
    assert restarts[0]["quantum_rows"] == 128
    assert restarts[0]["reason"] in ("evicted", "quota")


def test_server_trace_flag_roundtrip():
    csr = powerlaw_cluster(n=200, m_per_node=3, seed=1)
    server = QueryServer(csr)
    res = server.execute(QueryRequest("3-clique", engine="vlftj",
                                      trace=True))
    assert res.trace is not None
    assert res.trace.summary["count"] == res.count
    assert res.stats["engine"]["name"] == "vlftj"
    off = server.execute(QueryRequest("3-clique", engine="vlftj"))
    assert off.trace is None and off.count == res.count


# ---------------------------------------------------------------------------
# acceptance: dist-routed query -> trace with exchange events
# ---------------------------------------------------------------------------

def test_sharded_trace_has_exchange_events_and_parity():
    g = zipf_graph(800, 4000, seed=2)
    unary = {f"v{i}": node_sample(g.n_nodes, 4, seed=i) for i in (1, 2)}
    sg = ShardedGraphDB(g, 4, unary)
    q = get_query("3-path")
    ref = sharded_count(q, sg)
    tr = QueryTrace("3-path", (), "sharded")
    sg2 = ShardedGraphDB(g, 4, unary)
    with tr.activate():
        traced = sharded_count(q, sg2)
    assert traced == ref
    ex = tr.events_named("exchange")
    assert len(ex) >= 2                       # one per level at least
    assert {e["level"] for e in ex} >= {0, 1}
    assert any(e["values"] > 0 for e in ex)   # adjacency actually shipped
    assert all(e["bytes"] == e["values"] * 8 for e in ex)
    # per-level observed cardinalities are recorded alongside
    assert tr.levels[0]["obs_rows"] > 0
    # the full trace serializes: exchange events survive JSONL
    back = QueryTrace.from_jsonl(tr.to_jsonl())
    assert len(back.events_named("exchange")) == len(ex)


# ---------------------------------------------------------------------------
# acceptance: EXPLAIN ANALYZE on a Zipf triangle
# ---------------------------------------------------------------------------

def test_explain_analyze_zipf_triangle():
    gdb = zipf_gdb()
    res = explain_analyze(get_query("3-clique"), gdb, engine="vlftj")
    assert res.count == count(get_query("3-clique"), gdb, engine="vlftj")
    assert len(res.levels) == 3
    for rec in res.levels:
        assert rec["est_rows"] is not None and rec["obs_rows"] is not None
        assert np.isfinite(rec["q_error"]) and rec["q_error"] >= 1.0
    text = res.render()
    assert "est=" in text and "obs=" in text and "q=" in text
    assert "max q-error" in text
    assert np.isfinite(res.max_q_error)


# ---------------------------------------------------------------------------
# trace object + JSONL round-trip
# ---------------------------------------------------------------------------

def test_qerror_edge_cases():
    assert qerror(10, 10) == 1.0
    assert qerror(5, 20) == 4.0
    assert qerror(20, 5) == 4.0
    assert qerror(0, 0) == 1.0
    assert qerror(0, 7) == float("inf")
    assert qerror(7, 0) == float("inf")


def test_trace_jsonl_roundtrip(tmp_path, gdb):
    q = get_query("3-path")
    plan = plan_query(q, GraphStats.of(gdb), engine="vlftj")
    tr = QueryTrace("3-path", plan.gao, "vlftj")
    with tr.activate():
        execute_stats(plan, gdb)
    tr.event("custom", detail="x")
    path = tmp_path / "t.jsonl"
    tr.to_jsonl(path)
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    kinds = [ln["kind"] for ln in lines]
    assert kinds[0] == "header" and kinds[-1] == "summary"
    assert kinds.count("level") == len(tr.levels)
    back = QueryTrace.from_jsonl(path)
    assert back.summary["count"] == tr.summary["count"]
    assert set(back.levels) == set(tr.levels)
    assert [e["name"] for e in back.events] == [e["name"] for e in tr.events]


def test_trace_inactive_by_default():
    assert current_trace() is None
    tr = QueryTrace("q", ("a",), "vlftj")
    with tr.activate():
        assert current_trace() is tr
        with QueryTrace("inner", ("b",), "vlftj").activate() as inner:
            assert current_trace() is inner
        assert current_trace() is tr
    assert current_trace() is None


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.counter("reqs", route="a").inc()
    reg.counter("reqs", route="a").inc(2)
    reg.counter("reqs", route="b").inc()
    reg.gauge("open").set(5)
    reg.gauge("open").dec(2)
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["reqs{route=a}"] == 3
    assert snap["reqs{route=b}"] == 1
    assert snap["open"] == 3
    assert snap["lat_count"] == 3
    assert snap["lat_sum"] == pytest.approx(5.55)
    assert snap["lat_bucket{le=0.1}"] == 1
    assert snap["lat_bucket{le=1}"] == 2
    assert snap["lat_bucket{le=+Inf}"] == 3
    with pytest.raises(ValueError):
        reg.counter("reqs", route="a").inc(-1)
    reg.reset()
    assert len(reg) == 0


def test_registry_handles_are_live():
    reg = MetricsRegistry()
    c = reg.counter("x")
    c.inc()
    assert reg.counter("x").value == 1     # same underlying series


def test_server_metrics_endpoint():
    csr = powerlaw_cluster(n=200, m_per_node=3, seed=1)
    reg = MetricsRegistry()
    server = QueryServer(csr, metrics=reg)
    server.execute(QueryRequest("3-clique", engine="vlftj"))
    server.execute(QueryRequest("3-clique", engine="vlftj"))
    snap = server.metrics()
    assert snap["server_plan_cache{outcome=miss}"] == 1
    assert snap["server_plan_cache{outcome=hit}"] == 1
    assert snap["server_plan_cache_size"] >= 1
    assert "server_open_cursors" in snap


def test_scheduler_quanta_counted_in_registry():
    csr = powerlaw_cluster(n=200, m_per_node=3, seed=1)
    reg = MetricsRegistry()
    server = QueryServer(csr, metrics=reg)
    sched = QuantumScheduler(server, quantum_rows=64)
    sched.submit(QueryRequest("3-clique", engine="vlftj"))
    sched.run()
    snap = server.metrics()
    assert snap["scheduler_quanta"] == sched.stats["quanta"]
    assert (snap.get("scheduler_preemptions", 0)
            == sched.stats["preemptions"])


def test_pool_worker_makespans_observed():
    from repro.dist.pool import WorkerPool
    from repro.obs import get_registry
    reg = get_registry()
    before = reg.snapshot().get(
        "pool_worker_seconds_count{backend=thread}", 0)
    pool = WorkerPool({0: [0, 2], 1: [1]}, backend="thread")
    results, part_time, _, backend = pool.run(lambda x: x * 2,
                                              [1, 2, 3])
    assert backend == "thread"
    assert results == {0: 2, 1: 4, 2: 6}
    after = reg.snapshot()["pool_worker_seconds_count{backend=thread}"]
    assert after == before + 2             # one observation per worker
