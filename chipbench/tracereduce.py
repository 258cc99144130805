"""From a profiler trace to what the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Chip
planes are named ``/device:<platform>:<n>`` (a TPU trace also holds
planes such as ``/device:CUSTOM:Megascale Trace``, which are no chip and
are left out); on each chip's plane, the ``XLA Ops`` line
holds one event per operation run on the device and the ``XLA Modules``
line one event per executable run.  The benchmark's own spans
(``spans.PREFIX``) sit on host planes, on the same clock.

Busy time is the union of the operation intervals inside the benchmark's
``window`` span, averaged over the device planes; idle is the rest of the
window.  An idle gap is named by the innermost benchmark span open at its
midpoint: what the host was doing while the device waited.
"""
from __future__ import annotations

import re

from spans import PREFIX

CHIP_PLANE = re.compile(r"^/device:[^:]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"


def module_name(event_name: str) -> str:
    """An executable's name without the run or program id the trace
    appends (``jit__expand_level(42)`` -> ``jit__expand_level``)."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    """Disjoint, ascending intervals covering ``intervals``."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def read_planes(path: str) -> dict:
    """The raw pieces of one trace file, in seconds on the trace's clock:
    ``ops`` and ``modules`` per device plane, and the benchmark's
    ``spans``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: dict[str, dict] = {}
    spans = []
    for plane in data.planes:
        if CHIP_PLANE.match(plane.name):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    dev[key].append((ev.name, s, s + ev.duration_ns * 1e-9))
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = ev.start_ns * 1e-9
                    spans.append((ev.name[len(PREFIX):], s,
                                  s + ev.duration_ns * 1e-9))
    return {"devices": devices, "spans": spans}


def reduce(raw: dict, top: int = 10) -> dict:
    """The window, busy time and idle gaps of :func:`read_planes`' output,
    with times in seconds from the window's start.

    Returns ``window_s``, ``busy_s`` (mean over device planes),
    ``modules`` (``[name, start, end]`` of every executable run in the
    window, all planes), ``spans`` (the benchmark's, window excluded),
    ``device_ops`` (the ``top`` executables by device seconds) and
    ``idle_gaps`` (the ``top`` longest gaps of the first plane, each
    ``[span name, seconds]``).  ``None`` where the trace holds no window
    or no device plane."""
    windows = [(s, e) for n, s, e in raw["spans"] if n == WINDOW]
    if not windows or not raw["devices"]:
        return None
    t0, t1 = windows[0]
    spans = [(n, s - t0, e - t0) for n, s, e in raw["spans"] if n != WINDOW]
    busy, modules, first_gaps = [], [], None
    for plane in sorted(raw["devices"]):
        dev = raw["devices"][plane]
        ops = union(_clip([(s, e) for _, s, e in dev["ops"]], t0, t1))
        busy.append(sum(e - s for s, e in ops))
        modules += [[module_name(n), max(s, t0) - t0, min(e, t1) - t0]
                    for n, s, e in dev["modules"] if e > t0 and s < t1]
        if first_gaps is None:
            edges = [t0] + [x for iv in ops for x in iv] + [t1]
            first_gaps = [(edges[i] - t0, edges[i + 1] - t0)
                          for i in range(0, len(edges), 2)
                          if edges[i + 1] > edges[i]]
    per_module: dict[str, float] = {}
    for n, s, e in modules:
        per_module[n] = per_module.get(n, 0.0) + (e - s)

    def doing(mid: float) -> str:
        open_ = [(e - s, n) for n, s, e in spans if s <= mid <= e]
        return min(open_)[1] if open_ else "none"

    gaps = sorted(first_gaps, key=lambda g: g[0] - g[1])[:top]
    return {"window_s": t1 - t0, "busy_s": sum(busy) / len(busy),
            "modules": modules, "spans": spans,
            "device_ops": sorted(([n, s] for n, s in per_module.items()),
                                 key=lambda x: -x[1])[:top],
            "idle_gaps": [[doing((s + e) / 2), e - s] for s, e in gaps]}
