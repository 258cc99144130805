"""Program spans (repro.obs.span) on the profiler's clock.

The contract under test:

* **Catalogue** — under a ``jax.profiler`` trace, a served vlftj count,
  a served yannakakis count, a hybrid count, a cursor page and a
  ``QuantumScheduler`` run leave ``repro.<name>`` host events that
  carry their attributes and nest as ``docs/OBSERVABILITY.md`` says.
* **QueryTrace** — with a trace active, the level-and-phase spans land
  in ``trace.spans`` (and survive the JSONL round trip); the per-chunk
  spans go to the profiler only.
* **Zero device work** — spans add no device dispatch: the vlftj meters
  read the same with the profiler recording them as without.
"""
import glob
import os

import jax
import pytest

from repro.core import GraphStats, count, execute_stats, get_query, plan_query
from repro.core.engine import enumerate as enumerate_rows
from repro.graphs import powerlaw_cluster
from repro.obs import QueryTrace, span
from repro.serve import QuantumScheduler, QueryRequest, QueryServer

from conftest import make_gdb

PREFIX = "repro."


def read_spans(trace_dir: str) -> list[dict]:
    """Every program span of the profiler trace in ``trace_dir``:
    ``{"name", "start", "end", "attrs"}`` in ns on the trace's clock,
    sorted by start."""
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append({"name": ev.name[len(PREFIX):],
                                "start": ev.start_ns,
                                "end": ev.start_ns + ev.duration_ns,
                                "attrs": {k: v for k, v in ev.stats}})
    return sorted(out, key=lambda s: (s["start"], -s["end"]))


def named(spans, name, **attrs):
    return [s for s in spans if s["name"] == name
            and all(s["attrs"].get(k) == v for k, v in attrs.items())]


def within(child, parent) -> bool:
    return parent["start"] <= child["start"] and child["end"] <= parent["end"]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """One profiler trace over the served paths, and what they returned."""
    csr = powerlaw_cluster(n=200, m_per_node=3, seed=1)
    server = QueryServer(csr)
    out = {"server": server}
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(trace_dir)
    try:
        out["vlftj"] = server.execute(QueryRequest(
            "3-clique", engine="vlftj", tenant="t-clique"))
        out["yannakakis"] = server.execute(QueryRequest(
            "3-path", engine="yannakakis", tenant="t-path"))
        out["hybrid"] = server.execute(QueryRequest(
            "2-lollipop", engine="hybrid"))
        out["page"] = server.execute(QueryRequest(
            "3-path", engine="vlftj", limit=64))
        sched = QuantumScheduler(server, quantum_rows=64)
        out["token"] = sched.submit(QueryRequest(
            "3-path", engine="vlftj", tenant="t-sched", seed=3))
        out["scheduled"] = sched.run()
        gdb = server._gdb_for(server.default_selectivity, 0)
        with span("test.enumerate"):
            out["rows"] = {engine: enumerate_rows(get_query("3-path"), gdb,
                                                  engine=engine)
                           for engine in ("vlftj", "yannakakis")}
    finally:
        jax.profiler.stop_trace()
    out["spans"] = read_spans(trace_dir)
    return out


def test_served_vlftj_count_spans_carry_attributes(recorded):
    spans = recorded["spans"]
    res = recorded["vlftj"]
    gdb = recorded["server"]._gdb_for(recorded["server"].default_selectivity,
                                      0)
    assert res.count == count(get_query("3-clique"), gdb, engine="lftj_ref")
    (ex,) = named(spans, "server.execute", tenant="t-clique")
    assert ex["attrs"]["query"] == "3-clique"
    assert ex["attrs"]["req"].startswith("req-")
    inside = [s for s in spans if within(s, ex) and s is not ex]
    names = {s["name"] for s in inside}
    assert {"server.graph", "server.stats", "server.plan", "server.verify",
            "engine.build", "vlftj.level", "vlftj.split", "vlftj.chunk",
            "vlftj.compact"} <= names
    graph, = named(inside, "server.graph")
    assert graph["attrs"] == {"selectivity": 10.0, "seed": 0}
    assert named(inside, "engine.build")[0]["attrs"] == {"engine": "vlftj"}
    levels = named(inside, "vlftj.level")
    assert [lv["attrs"]["level"] for lv in levels] == [0, 1, 2]
    for chunk in named(inside, "vlftj.chunk"):
        assert set(chunk["attrs"]) == {"level", "width", "rows", "mode"}
        assert chunk["attrs"]["rows"] > 0
        assert chunk["attrs"]["width"] >= 32
        (lv,) = [lv for lv in levels if within(chunk, lv)]
        assert lv["attrs"]["level"] == chunk["attrs"]["level"]
    # the final level of a count tallies survivors: nothing to compact
    assert {c["attrs"]["level"] for c in named(inside, "vlftj.compact")} \
        == {1}


def test_served_yannakakis_count_span(recorded):
    spans = recorded["spans"]
    (ex,) = named(spans, "server.execute", tenant="t-path")
    assert recorded["yannakakis"].engine == "yannakakis"
    (yc,) = [s for s in named(spans, "yannakakis.count") if within(s, ex)]
    assert yc["attrs"] == {"query": "3-path"}
    build, = [s for s in named(spans, "engine.build") if within(s, ex)]
    assert build["attrs"] == {"engine": "yannakakis"}
    assert build["end"] <= yc["start"]


def test_enumeration_sort_and_semijoin_spans(recorded):
    spans = recorded["spans"]
    rows = recorded["rows"]
    n = len(rows["vlftj"].rows)
    assert n == len(rows["yannakakis"].rows) > 0
    assert named(spans, "vlftj.sort", rows=n)
    assert named(spans, "yannakakis.semijoin", query="3-path")


def test_hybrid_page_and_sort_spans(recorded):
    spans = recorded["spans"]
    assert recorded["hybrid"].engine == "hybrid"
    assert named(spans, "hybrid.count")
    page = recorded["page"]
    assert page.count == 64
    (take,) = named(spans, "cursor.take", rows=64)
    assert named(spans, "vlftj.final")
    finals = [s for s in named(spans, "vlftj.final") if within(s, take)]
    assert finals and all(set(f["attrs"]) == {"width", "rows"}
                          for f in finals)
    # a fresh executor lowers its final level once per geometry
    compiles = [s for s in named(spans, "vlftj.compile") if within(s, take)]
    assert compiles and all(any(within(c, f) for f in finals)
                            for c in compiles)


def test_scheduler_spans_pair_submit_and_quanta(recorded):
    spans = recorded["spans"]
    (res,) = recorded["scheduled"]
    assert res.stats["quanta"] >= 2
    job = int(recorded["token"].split("-")[1])
    (submit,) = named(spans, "scheduler.submit", tenant="t-sched")
    assert submit["attrs"]["job"] == job
    assert any(within(s, submit) for s in named(spans, "server.plan"))
    assert any(within(s, submit) for s in named(spans, "server.verify"))
    quanta = named(spans, "scheduler.quantum", job=job)
    assert [q["attrs"]["quantum"] for q in quanta] == list(
        range(1, res.stats["quanta"] + 1))
    assert submit["end"] <= quanta[0]["start"]
    levels = named(spans, "vlftj.level")
    assert any(within(lv, quanta[0]) for lv in levels)
    assert any(within(f, q) for q in quanta
               for f in named(spans, "vlftj.final"))


#: (child, parent): each served child span lies inside a parent; a
#: scheduler parent holds at least one of them
NESTING = [
    ("server.graph", "server.execute"), ("server.stats", "server.execute"),
    ("server.plan", "server.execute"), ("server.verify", "server.execute"),
    ("engine.build", "server.execute"), ("vlftj.level", "server.execute"),
    ("vlftj.split", "vlftj.level"), ("vlftj.chunk", "vlftj.level"),
    ("vlftj.compact", "vlftj.level"), ("yannakakis.count", "server.execute"),
    ("hybrid.count", "server.execute"), ("cursor.take", "server.execute"),
    ("vlftj.compile", "vlftj.final"), ("server.plan", "scheduler.submit"),
    ("server.verify", "scheduler.submit"),
    ("vlftj.level", "scheduler.quantum"), ("vlftj.final", "scheduler.quantum"),
]


@pytest.mark.parametrize("child,parent", NESTING,
                         ids=[f"{c}-in-{p}" for c, p in NESTING])
def test_catalogue_nesting(recorded, child, parent):
    spans = recorded["spans"]
    kids, parents = named(spans, child), named(spans, parent)
    assert kids and parents
    if parent.startswith("scheduler."):
        assert any(within(k, p) for k in kids for p in parents)
        return
    other = [s for s in spans if s["name"] in (
        "scheduler.submit", "scheduler.quantum", "test.enumerate")]
    served = [k for k in kids if not any(within(k, s) for s in other)]
    assert served
    for k in served:
        assert any(within(k, p) for p in parents), (child, k)


def test_trace_gets_level_and_phase_spans_not_chunks(tmp_path):
    gdb = make_gdb(60, 3, seed=5)
    plan = plan_query(get_query("3-clique"), GraphStats.of(gdb),
                      engine="vlftj")
    tr = QueryTrace("3-clique", plan.gao, "vlftj")
    with tr.activate():
        c, stats = execute_stats(plan, gdb)
    names = [s["name"] for s in tr.spans]
    assert names.count("vlftj.level") == len(plan.gao)
    assert "engine.build" in names and "vlftj.split" in names
    assert "vlftj.chunk" not in names and "vlftj.compact" not in names
    assert stats["raw"]["chunks"] >= 1
    levels = [s for s in tr.spans if s["name"] == "vlftj.level"]
    assert [s["level"] for s in levels] == list(range(len(plan.gao)))
    assert all(s["dur_s"] >= 0 for s in tr.spans)
    back = QueryTrace.from_jsonl(tr.to_jsonl(tmp_path / "t.jsonl") and
                                 (tmp_path / "t.jsonl"))
    assert [(s["name"], s.get("level")) for s in back.spans] == \
        [(s["name"], s.get("level")) for s in tr.spans]
    assert back.summary["count"] == c


def test_scheduled_trace_records_quanta():
    csr = powerlaw_cluster(n=200, m_per_node=3, seed=1)
    sched = QuantumScheduler(QueryServer(csr), quantum_rows=64)
    sched.submit(QueryRequest("3-path", engine="vlftj", trace=True))
    (res,) = sched.run()
    quanta = [s for s in res.trace.spans if s["name"] == "scheduler.quantum"]
    assert [s["quantum"] for s in quanta] == list(
        range(1, res.stats["quanta"] + 1))
    assert any(s["name"] == "vlftj.final" for s in res.trace.spans)
    assert not any(s["name"].startswith(("vlftj.chunk", "vlftj.compact"))
                   for s in res.trace.spans)


def test_spans_add_zero_device_dispatches(tmp_path):
    """Profiler on vs off: identical vlftj dispatch meters and count —
    a span is a host annotation around work that happens anyway."""
    gdb = make_gdb(60, 3, seed=5)
    plan = plan_query(get_query("4-cycle"), GraphStats.of(gdb),
                      engine="vlftj")
    c_off, off = execute_stats(plan, gdb)
    jax.profiler.start_trace(str(tmp_path))
    try:
        c_on, on = execute_stats(plan, gdb)
    finally:
        jax.profiler.stop_trace()
    assert named(read_spans(str(tmp_path)), "vlftj.chunk")
    assert c_on == c_off
    for meter in ("chunks", "ll_calls", "candidates"):
        assert on["raw"][meter] == off["raw"][meter], meter
    assert on["kernel_dispatches"] == off["kernel_dispatches"]
    assert on["jit_calls"] == off["jit_calls"]


def test_span_records_into_an_active_trace_only():
    tr = QueryTrace("q")
    with span("outside", a=1):
        pass
    with tr.activate():
        with span("phase", level=3, mode="tile"):
            with span("chunk", profiler_only=True, level=3):
                pass
        with pytest.raises(RuntimeError):
            with span("fails"):
                raise RuntimeError("boom")
    assert [s["name"] for s in tr.spans] == ["phase", "fails"]
    assert tr.spans[0]["level"] == 3 and tr.spans[0]["mode"] == "tile"
    assert all("dur_s" in s for s in tr.spans)
