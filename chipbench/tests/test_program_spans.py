"""The program's own spans (``repro.obs.span``: host events named
``repro.<name>``, their attributes as the event's stats) in a trace
recorded on a TPU v5e: they reach the profiler's trace on the device's
clock, carry their attributes, nest, and cover the device-idle time
inside the benchmark's ``execute`` spans.  ``tracereduce`` leaves them
out (PERF.md, Open questions)."""
import os

import pytest

import tracereduce
from conftest import HERE

TRACE = os.path.join(HERE, "data", "clique3_tiny_spans_v5e.xplane.pb")
PREFIX = "repro."


@pytest.fixture(scope="module")
def recorded():
    """``(program, raw)``: the trace's program spans ``(name, start, end,
    attributes)`` in seconds, and ``tracereduce.read_planes`` of it."""
    from jax.profiler import ProfileData
    program = []
    for plane in ProfileData.from_file(TRACE).planes:
        if tracereduce.CHIP_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = ev.start_ns * 1e-9
                    program.append((ev.name[len(PREFIX):], s,
                                    s + ev.duration_ns * 1e-9,
                                    {k: v for k, v in ev.stats}))
    return program, tracereduce.read_planes(TRACE)


def named(program, name):
    return [p for p in program if p[0] == name]


def inside(inner, outers) -> bool:
    return any(s <= inner[1] and inner[2] <= e for _, s, e, *_ in outers)


def test_recorded_spans_carry_their_attributes(recorded):
    program, _ = recorded
    assert {"server.execute", "server.plan", "server.verify",
            "engine.build", "vlftj.level", "vlftj.split", "vlftj.chunk",
            "vlftj.compact"} <= {p[0] for p in program}
    keys = {"server.execute": {"req", "query", "tenant"},
            "engine.build": {"engine"}, "vlftj.level": {"level", "rows"},
            "vlftj.split": {"level"}, "vlftj.compact": {"level"},
            "vlftj.chunk": {"level", "width", "rows", "mode"}}
    for name, want in keys.items():
        assert all(set(a) == want for *_, a in named(program, name)), name
    assert {a["query"] for *_, a in named(program, "server.execute")} == {
        "3-clique"}


def test_recorded_spans_nest(recorded):
    program, _ = recorded
    execute = named(program, "server.execute")
    for name in ("server.plan", "engine.build", "vlftj.level"):
        assert all(inside(p, execute) for p in named(program, name)), name
    levels = named(program, "vlftj.level")
    for name in ("vlftj.split", "vlftj.chunk", "vlftj.compact"):
        for p in named(program, name):
            assert any(inside(p, [lv]) and lv[3]["level"] == p[3]["level"]
                       for lv in levels), name


def test_each_level_program_runs_inside_its_chunk(recorded):
    # a chunk blocks on its result inside its span, so the executable's
    # midpoint lies in the span that dispatched it
    program, raw = recorded
    chunks = named(program, "vlftj.chunk")
    runs = [(s + e) / 2 for dev in raw["devices"].values()
            for n, s, e in dev["modules"]
            if tracereduce.module_name(n) == "jit__expand_level"]
    assert runs
    assert all(any(s <= mid <= e for _, s, e, _a in chunks) for mid in runs)


def test_recorded_spans_cover_the_idle_time_inside_execute(recorded):
    program, raw = recorded
    (t0, t1), = [(s, e) for n, s, e in raw["spans"] if n == "window"]
    dev, = raw["devices"].values()
    busy = tracereduce.union([(max(s, t0), min(e, t1))
                              for _, s, e in dev["ops"] if e > t0 and s < t1])
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    execute = tracereduce.union([(s, e) for n, s, e in raw["spans"]
                                 if n == "execute"])
    spans = tracereduce.union([(s, e) for _, s, e, _a in program])

    def overlap(a, b):
        return sum(max(0.0, min(e, f) - max(s, r))
                   for s, e in a for r, f in b)

    idle_in_execute = [(max(s, r), min(e, f)) for s, e in idle
                       for r, f in execute if min(e, f) > max(s, r)]
    total = sum(e - s for s, e in idle_in_execute)
    assert total > 0
    assert overlap(idle_in_execute, spans) / total >= 0.9
