"""Device profiling (repro.obs.profile) + bench history/regression gate.

The contract under test, in tiers:

* **Zero-cost when off** — with no active profile the vlftj dispatch
  meters (chunks / ll_calls / candidates / kernel_dispatches) are
  identical to a run that never heard of profiling; same discipline as
  the PR 8 tracer guard.
* **Faithful when on** — an active profile sees every kernel dispatch
  (calls match the engine's own meters), samples live-buffer memory at
  level boundaries, and publishes into the trace/metrics surfaces.
* **Attribution** — scheduler quanta label AOT compiles
  (``sched-<job>/q<k>``), the pool records per-worker spans, the server
  stamps one ``trace_id`` through the request log, trace, and profile.
* **Isolation** — two concurrently scheduled traced queries keep their
  per-level observations apart (contextvar activation per quantum).
* **Bench history** — ``BenchRecord`` normalizes every bench row;
  ``tools/bench_compare.py`` passes on a clean clone, fails on an
  injected wall regression or count drift, and its ``--self-test``
  proves the gate can fail.
"""
import json
import pathlib
import re
import subprocess
import sys
import time

import pytest

from repro.core import GraphStats, count, execute_stats, get_query, plan_query
from repro.obs import (DeviceProfile, MetricsRegistry, NullProfile,
                       QueryTrace, current_profile)
from repro.graphs import powerlaw_cluster
from repro.serve import QuantumScheduler, QueryRequest, QueryServer

from conftest import make_gdb

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def gdb():
    return make_gdb(60, 3, seed=5)


# ---------------------------------------------------------------------------
# contextvar plumbing
# ---------------------------------------------------------------------------

def test_profile_inactive_by_default():
    assert current_profile() is None
    p = DeviceProfile("q", "vlftj")
    with p.activate():
        assert current_profile() is p
        with DeviceProfile().activate() as inner:
            assert current_profile() is inner
        assert current_profile() is p
    assert current_profile() is None


def test_null_profile_is_inert():
    n = NullProfile()
    n.record_jit_call()
    n.record_compile("k", 1.0)
    n.sample_memory()
    with n.activate():
        assert current_profile() is None       # never installed
    assert n.to_dict() == {}


# ---------------------------------------------------------------------------
# zero-dispatch guard (the whole point)
# ---------------------------------------------------------------------------

def test_disabled_profile_adds_zero_device_dispatches(gdb):
    """Profiling on vs off: identical vlftj dispatch meters and count —
    the hooks are host clock reads around dispatches that happen
    anyway, never new device work."""
    q = get_query("4-cycle")
    plan = plan_query(q, GraphStats.of(gdb), engine="vlftj")
    assert current_profile() is None
    c_off, off = execute_stats(plan, gdb)
    prof = DeviceProfile("4-cycle", "vlftj")
    with prof.activate():
        c_on, on = execute_stats(plan, gdb)
    assert c_on == c_off
    for meter in ("chunks", "ll_calls", "candidates"):
        assert on["raw"][meter] == off["raw"][meter], meter
    assert on["kernel_dispatches"] == off["kernel_dispatches"]
    assert on["jit_calls"] == off["jit_calls"]
    # static agreement: the obs harvest path (trace/schema/metrics) is
    # jax-free per the obs-device-free lint pass, so turning profiling
    # on cannot introduce device work through the harvest side either
    import ast as ast_mod
    import os
    from conftest import REPO_ROOT, load_lint_module
    lint = load_lint_module()
    rule = lint.ObsHostPurity()
    for rel in rule.scope:
        src = open(os.path.join(REPO_ROOT, rel), encoding="utf-8").read()
        assert rule.check(ast_mod.parse(src), rel, src) == [], rel


# ---------------------------------------------------------------------------
# faithful accounting when on
# ---------------------------------------------------------------------------

def test_profile_harvests_kernels_and_memory(gdb):
    plan = plan_query(get_query("3-clique"), GraphStats.of(gdb),
                      engine="vlftj")
    prof = DeviceProfile("3-clique", "vlftj")
    with prof.activate():
        c, stats = execute_stats(plan, gdb)
    assert c == count(get_query("3-clique"), gdb, engine="lftj_ref")
    # every chunk/final dispatch the engine metered is a recorded call
    assert prof.jit["calls"] == stats["raw"]["chunks"] \
        + stats["raw"]["ll_calls"]
    # memory watermark sampled at level boundaries, metadata only
    assert prof.memory["samples"] >= 1
    assert prof.memory["peak_live_bytes"] > 0
    assert prof.memory["peak_live_buffers"] >= 1
    # export is JSON-safe
    d = json.loads(json.dumps(prof.to_dict()))
    assert d["meta"]["query"] == "3-clique"
    assert d["jit"]["calls"] == prof.jit["calls"]


def test_profile_publish_into_trace_and_registry(gdb):
    plan = plan_query(get_query("3-clique"), GraphStats.of(gdb),
                      engine="vlftj")
    prof = DeviceProfile("3-clique", "vlftj")
    tr = QueryTrace("3-clique", plan.gao, "vlftj")
    with tr.activate(), prof.activate():
        execute_stats(plan, gdb)
    reg = MetricsRegistry()
    prof.publish(trace=tr, registry=reg)
    names = [s["name"] for s in tr.spans]
    assert "profile/jit" in names
    assert tr.summary["peak_live_bytes"] == prof.memory["peak_live_bytes"]
    snap = reg.snapshot()
    assert snap["profile_jit_calls"] == prof.jit["calls"]
    assert snap["profile_peak_live_bytes"] == prof.memory["peak_live_bytes"]


# ---------------------------------------------------------------------------
# attribution: scheduler quanta, pool workers, server trace ids
# ---------------------------------------------------------------------------

def test_scheduler_attributes_compiles_to_quanta():
    csr = powerlaw_cluster(n=300, m_per_node=4, seed=0)
    server = QueryServer(csr, page_rows=256)
    sched = QuantumScheduler(server, quantum_rows=64)
    sched.submit(QueryRequest("3-path", engine="vlftj", profile=True))
    (res,) = sched.run()
    prof = res.profile
    assert prof is not None
    assert res.count == count(
        get_query("3-path"),
        server._gdb_for(server.default_selectivity, 0), engine="vlftj")
    assert prof.jit["compiles"] >= 1
    assert prof.jit["compile_wall_s"] > 0.0
    assert len(prof.compile_events) == prof.jit["compiles"]
    for ev in prof.compile_events:
        assert re.fullmatch(r"sched-\d+/q\d+", ev["attribution"])
        assert ev["wall_s"] > 0.0
    # unprofiled request: no profile object, same count
    s2 = QuantumScheduler(QueryServer(csr, page_rows=256), quantum_rows=64)
    s2.submit(QueryRequest("3-path", engine="vlftj"))
    (r2,) = s2.run()
    assert r2.profile is None and r2.count == res.count


def test_pool_records_worker_spans():
    from repro.dist.pool import WorkerPool
    prof = DeviceProfile()
    pool = WorkerPool({0: [0, 2], 1: [1]}, backend="thread")
    with prof.activate():
        results, _, _, backend = pool.run(lambda x: x * 2, [1, 2, 3])
    assert backend == "thread"
    assert results == {0: 2, 1: 4, 2: 6}
    assert sorted(s["worker"] for s in prof.worker_spans) == [0, 1]
    assert all(s["backend"] == "thread" for s in prof.worker_spans)
    # off path records nothing
    pool.run(lambda x: x, [1, 2, 3])
    assert len(prof.worker_spans) == 2


def test_server_profile_flag_roundtrip():
    csr = powerlaw_cluster(n=200, m_per_node=3, seed=1)
    server = QueryServer(csr)
    res = server.execute(QueryRequest("3-clique", engine="vlftj",
                                      profile=True, trace=True))
    assert res.profile is not None
    assert res.profile.meta["trace_id"] == res.trace.meta["trace_id"]
    assert res.profile.jit["calls"] >= 1
    off = server.execute(QueryRequest("3-clique", engine="vlftj"))
    assert off.profile is None and off.count == res.count


def test_request_log_correlates_trace_ids(tmp_path):
    log = tmp_path / "requests.jsonl"
    csr = powerlaw_cluster(n=200, m_per_node=3, seed=1)
    reg = MetricsRegistry()
    server = QueryServer(csr, metrics=reg, request_log=str(log))
    ok = server.execute(QueryRequest("3-clique", engine="vlftj"))
    prof_res = server.execute(QueryRequest("3-clique", engine="vlftj",
                                           profile=True))
    with pytest.raises(Exception):
        server.execute(QueryRequest("no-such-query", engine="vlftj"))
    lines = [json.loads(ln) for ln in log.read_text().splitlines()]
    assert len(lines) == 3
    assert [ln["status"] for ln in lines] == ["ok", "ok", "error"]
    assert len({ln["trace_id"] for ln in lines}) == 3
    assert lines[0]["count"] == ok.count
    assert lines[0]["latency_s"] >= 0
    # the profiled request's log line carries the jit/memory digest and
    # the same trace_id stamped into the returned profile
    assert lines[1]["profile"]["jit_calls"] == prof_res.profile.jit["calls"]
    assert lines[1]["trace_id"] == prof_res.profile.meta["trace_id"]
    assert "error" in lines[2] and "count" not in lines[2]
    snap = reg.snapshot()
    assert snap["server_requests{status=ok}"] == 2
    assert snap["server_requests{status=error}"] == 1


# ---------------------------------------------------------------------------
# satellite 3: concurrent traced queries stay isolated
# ---------------------------------------------------------------------------

def test_concurrent_traced_queries_do_not_interleave():
    """Two simultaneously traced queries through the preemptive
    scheduler: each trace must match its solo-run per-level
    observations exactly — no span/level bleed through the contextvar."""
    csr = powerlaw_cluster(n=300, m_per_node=4, seed=0)

    def run(reqs):
        server = QueryServer(csr, page_rows=256)
        return server.execute_concurrent(reqs, quantum_rows=64)

    (solo_a,) = run([QueryRequest("3-path", engine="vlftj", trace=True)])
    (solo_b,) = run([QueryRequest("3-clique", engine="vlftj", trace=True)])
    both = run([QueryRequest("3-path", engine="vlftj", trace=True),
                QueryRequest("3-clique", engine="vlftj", trace=True)])
    pair = {r.request.query_name: r for r in both}
    assert set(pair) == {"3-path", "3-clique"}
    for solo, res in ((solo_a, pair["3-path"]), (solo_b, pair["3-clique"])):
        assert res.count == solo.count
        assert res.trace is not solo.trace
        assert res.trace.summary["count"] == solo.trace.summary["count"]
        assert set(res.trace.levels) == set(solo.trace.levels)
        for lv, rec in solo.trace.levels.items():
            assert res.trace.levels[lv]["obs_rows"] == rec["obs_rows"], lv
            assert res.trace.levels[lv]["var"] == rec["var"]


# ---------------------------------------------------------------------------
# satellite 1: histogram +Inf bucket + cumulative invariant
# ---------------------------------------------------------------------------

def test_histogram_snapshot_inf_bucket_and_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.005, 0.05, 0.5, 50.0):
        h.observe(v)
    s = h.snapshot()
    les = list(s["buckets"])
    assert all(isinstance(le, str) for le in les)
    assert les[-1] == "+Inf"
    counts = list(s["buckets"].values())
    assert counts == sorted(counts)            # cumulative, non-decreasing
    assert counts[-1] == s["count"] == 5       # +Inf bucket == total
    assert s["buckets"] == {"0.01": 2, "0.1": 3, "1": 4, "+Inf": 5}
    json.dumps(s)                              # JSON-safe keys throughout
    flat = reg.snapshot()
    assert flat["lat_bucket{le=+Inf}"] == 5


# ---------------------------------------------------------------------------
# bench history schema + regression gate
# ---------------------------------------------------------------------------

def _bench_common():
    from benchmarks.common import BenchRecord, append_history, write_baseline
    return BenchRecord, append_history, write_baseline


def test_bench_record_normalizes_counts():
    BenchRecord, _, _ = _bench_common()
    r = BenchRecord("t6/q/ds", 123.4, "count=42;edges=9", bench="cyclic")
    assert r.count == 42
    assert r.to_json() == {"bench": "cyclic", "name": "t6/q/ds",
                           "us_per_call": 123.4, "count": 42,
                           "derived": "count=42;edges=9"}
    # explicit count wins; no count= token -> None; inf wall -> null
    assert BenchRecord("x", 1.0, "count=9", bench="b", count=3).count == 3
    assert BenchRecord("x", 1.0, "speedup=2", bench="b").count is None
    blown = BenchRecord("x", float("inf"), "count=1", bench="b")
    assert blown.to_json()["us_per_call"] is None
    # `of` stamps the bench key on plain rows and keeps existing keys
    from benchmarks.common import Row
    rec = BenchRecord.of("gao", Row("t4/x", 5.0, "count=7"))
    assert (rec.bench, rec.count) == ("gao", 7)
    assert BenchRecord.of("other", rec).bench == "gao"


def test_bench_history_and_baseline_roundtrip(tmp_path):
    BenchRecord, append_history, write_baseline = _bench_common()
    recs = [BenchRecord("x/a", 1000.0, "count=5", bench="x"),
            BenchRecord("x/b", float("inf"), "count=3", bench="x")]
    hist = tmp_path / "h.jsonl"
    hdr = append_history(str(hist), recs)
    lines = [json.loads(ln) for ln in hist.read_text().splitlines()]
    assert len(lines) == 2
    assert all(ln["run_id"] == hdr["run_id"] for ln in lines)
    assert lines[0]["schema"] == 1 and lines[0]["quick"] is True
    assert lines[1]["us_per_call"] is None
    base = tmp_path / "b.json"
    doc = write_baseline(str(base), recs)
    assert doc == json.loads(base.read_text())
    assert [r["name"] for r in doc["records"]] == ["x/a", "x/b"]


def _compare(baseline, history, *extra):
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / "bench_compare.py"),
         "--baseline", str(baseline), "--history", str(history), *extra],
        capture_output=True, text=True, timeout=60)


def test_bench_compare_gate(tmp_path):
    BenchRecord, append_history, write_baseline = _bench_common()
    base_recs = [BenchRecord("x/slow", 1000.0, "count=5", bench="x"),
                 BenchRecord("x/tiny", 50.0, "count=2", bench="x"),
                 BenchRecord("x/blown", float("inf"), "", bench="x")]
    baseline = tmp_path / "BENCH_baseline.json"
    history = tmp_path / "BENCH_history.jsonl"
    write_baseline(str(baseline), base_recs)
    append_history(str(history), base_recs)
    ok = _compare(baseline, history)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "OK" in ok.stdout

    # a 2x wall regression on the slow record fails the gate; the tiny
    # record is under the noise floor and may drift freely
    time.sleep(0.005)          # distinct ts for the newer run
    bad_recs = [BenchRecord("x/slow", 2000.0, "count=5", bench="x"),
                BenchRecord("x/tiny", 500.0, "count=2", bench="x"),
                BenchRecord("x/blown", float("inf"), "", bench="x")]
    append_history(str(history), bad_recs)
    bad = _compare(baseline, history, "--min-us", "600")
    assert bad.returncode == 1
    assert "WALL x/x/slow" in bad.stdout
    assert "x/tiny" not in bad.stdout          # below --min-us: ignored

    # count drift is a parity failure regardless of wall
    time.sleep(0.005)
    drift = [BenchRecord("x/slow", 1000.0, "count=6", bench="x"),
             BenchRecord("x/tiny", 50.0, "count=2", bench="x"),
             BenchRecord("x/blown", float("inf"), "", bench="x")]
    append_history(str(history), drift)
    par = _compare(baseline, history)
    assert par.returncode == 1
    assert "PARITY x/x/slow" in par.stdout


def test_bench_compare_calibrate(tmp_path):
    """--calibrate divides out fleet-wide drift (cold-vs-warm, other
    machines) but still catches the one record that regressed against
    the fleet; count parity is never calibrated."""
    BenchRecord, append_history, write_baseline = _bench_common()
    base_recs = [BenchRecord(f"x/r{i}", 1000.0 + i, f"count={i}",
                             bench="x") for i in range(10)]
    baseline = tmp_path / "BENCH_baseline.json"
    history = tmp_path / "BENCH_history.jsonl"
    write_baseline(str(baseline), base_recs)
    # every record 1.5x slower (uniform drift), one of them 3x
    drifted = [BenchRecord(r.name, r.us_per_call * (3.0 if i == 4
                                                    else 1.5),
                           r.derived, bench="x")
               for i, r in enumerate(base_recs)]
    append_history(str(history), drifted)
    uncal = _compare(baseline, history)
    assert uncal.returncode == 1
    assert uncal.stdout.count("WALL") == 10   # raw gate: everything fails
    cal = _compare(baseline, history, "--calibrate")
    assert cal.returncode == 1
    assert cal.stdout.count("WALL") == 1      # drift divided out
    assert "WALL x/x/r4" in cal.stdout
    assert "median drift 1.50x" in cal.stdout
    # drift alone (no outlier) passes calibrated
    time.sleep(0.005)
    append_history(str(history),
                   [BenchRecord(r.name, r.us_per_call * 1.5, r.derived,
                                bench="x") for r in base_recs])
    clean = _compare(baseline, history, "--calibrate")
    assert clean.returncode == 0, clean.stdout


def test_bench_compare_self_test(tmp_path):
    """Acceptance: the gate demonstrably fails on an injected 2x
    slowdown (and passes a clean clone) via --self-test."""
    BenchRecord, _, write_baseline = _bench_common()
    baseline = tmp_path / "BENCH_baseline.json"
    write_baseline(str(baseline),
                   [BenchRecord("x/a", 1000.0, "count=5", bench="x")])
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "bench_compare.py"),
         "--self-test", "--baseline", str(baseline)],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "self-test OK" in out.stdout
