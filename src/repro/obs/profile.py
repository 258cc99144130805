"""Device-side profiling: program spans, jit compiles, memory watermarks.

:class:`~repro.obs.trace.QueryTrace` (PR 8) answers *what* a query did
per GAO level — est-vs-observed cardinality, kernel-path mix, scheduler
events.  This module answers *where the time went* one layer down:

* **spans** — :func:`span` names a stretch of host work (a server
  phase, a GAO level, one chunk's dispatch, a compaction) as a
  ``jax.profiler.TraceAnnotation`` called ``repro.<name>``.  Under a
  profiler trace the span lands on the same clock as the device's
  operations, so a device-idle gap can be attributed to the innermost
  span open across it; with no profiler running it costs a microsecond
  or two and records nothing.  Spans are always on: the catalogue
  is in ``docs/OBSERVABILITY.md``.
* **jit** — compile vs cached-call counts and compile wall seconds,
  harvested at the engine's two dispatch sites (the
  ``VLFTJ._final_level_call`` AOT cache and the interior chunked
  ``_expand_level`` dispatches);
* **memory** — live-buffer watermarks sampled at GAO level boundaries
  (``jax.live_arrays()`` metadata only — ``nbytes`` is shape×dtype
  arithmetic, no device sync), plus the backend allocator's
  ``peak_bytes_in_use`` when the platform exposes ``memory_stats()``
  (CPU typically does not; the field stays ``None``);
* **workers** — per-worker drain seconds from the dist pool;
* **compile events** — every AOT compile with wall seconds and an
  ``attribution`` label the quantum scheduler sets per slice
  (``sched-3/q2``), so a compile storm is attributable to the job and
  quantum that triggered it.

:class:`DeviceProfile` is off by default: every hook is ``prof =
current_profile(); if prof is None: <nothing>``.  Activation mirrors
tracing — a contextvar, so the scheduler, pool, and cursor find the
profile without signature threading.  :meth:`DeviceProfile.publish`
pushes the harvest into a :class:`~repro.obs.trace.QueryTrace` (as a
span) and a :class:`~repro.obs.metrics.MetricsRegistry` (as
histograms/counters) so one export surface carries all three layers.
"""
from __future__ import annotations

import contextlib
import contextvars
import time

from jax.profiler import TraceAnnotation

from .trace import current_trace

#: schema version stamped into every profile dict export.
PROFILE_SCHEMA_VERSION = 1

#: what every program span's profiler name starts with
SPAN_PREFIX = "repro."

_ACTIVE: contextvars.ContextVar["DeviceProfile | None"] = \
    contextvars.ContextVar("repro_obs_active_profile", default=None)


def current_profile() -> "DeviceProfile | None":
    """The profile active in this context, or None (profiling disabled)."""
    return _ACTIVE.get()


def span(name: str, *, profiler_only: bool = False, **attrs):
    """A context manager that names a stretch of program work.

    It always enters ``jax.profiler.TraceAnnotation("repro." + name,
    **attrs)``, which a running profiler records on the host's timeline
    with ``attrs`` as the event's stats (ints, floats and strings).
    When a :class:`~repro.obs.trace.QueryTrace` is active it also
    records the span there (:meth:`QueryTrace.span`), unless
    ``profiler_only``: spans opened once per chunk pass it, so that a
    traced request keeps one record per level and phase.

    Example::

        with span("vlftj.level", level=2, rows=frontier.shape[0]):
            ...
    """
    ann = TraceAnnotation(SPAN_PREFIX + name, **attrs)
    tr = None if profiler_only else current_trace()
    if tr is None:
        return ann
    return _in_both(ann, tr.span(name, **attrs))


@contextlib.contextmanager
def _in_both(annotation, trace_span):
    with annotation, trace_span:
        yield


class DeviceProfile:
    """One query execution's device-side resource accounting.

    All recording methods are plain host dict arithmetic; the only
    recorder that looks at device state is :meth:`sample_memory`, and it
    reads array *metadata* (``nbytes``) — no transfer, no sync.

    Attributes:
        jit: ``{"compiles", "calls", "compile_wall_s"}`` — ``calls``
            counts every jitted/AOT kernel dispatch; ``compiles`` counts
            observable (AOT) compilations and ``compile_wall_s`` their
            summed wall seconds.  Interior first-call trace+compile time
            is not separable host-side; a profiler trace shows it as
            JAX's own events inside that dispatch's ``vlftj.chunk``
            span.
        memory: live-buffer watermarks — ``peak_live_bytes`` /
            ``peak_live_buffers`` over the samples taken at level
            boundaries, ``samples``, and ``device_peak_bytes`` (backend
            allocator peak, None when unavailable).
        compile_events: ``[{"key", "wall_s", "attribution", "t"}]``.
        worker_spans: ``[{"worker", "backend", "dur_s"}]`` pool drains.
    """

    enabled = True

    def __init__(self, query_name: str = "", engine: str = ""):
        self.meta = {"query": query_name, "engine": engine,
                     "schema": PROFILE_SCHEMA_VERSION}
        self.jit = {"compiles": 0, "calls": 0, "compile_wall_s": 0.0}
        self.memory = {"samples": 0, "peak_live_bytes": 0,
                       "peak_live_buffers": 0, "device_peak_bytes": None}
        self.compile_events: list[dict] = []
        self.worker_spans: list[dict] = []
        self.attribution: str | None = None
        self._t0 = time.perf_counter()

    # -- recording -----------------------------------------------------------
    def _now(self) -> float:
        return round(time.perf_counter() - self._t0, 6)

    def set_meta(self, **kw) -> None:
        self.meta.update(kw)

    def record_jit_call(self, n: int = 1) -> None:
        self.jit["calls"] += n

    def record_compile(self, key: str, wall_s: float) -> None:
        """One observable (AOT) compilation: ``key`` names the compiled
        geometry, the event carries the current :attr:`attribution`."""
        self.jit["compiles"] += 1
        self.jit["compile_wall_s"] += float(wall_s)
        self.compile_events.append(
            {"key": str(key), "wall_s": round(float(wall_s), 6),
             "attribution": self.attribution, "t": self._now()})

    def record_worker(self, worker: int, backend: str,
                      dur_s: float) -> None:
        self.worker_spans.append({"worker": int(worker), "backend": backend,
                                  "dur_s": round(float(dur_s), 6)})

    def sample_memory(self) -> None:
        """Live-buffer watermark sample (GAO level boundaries).

        ``jax.live_arrays()`` enumerates the client's live buffers;
        summing ``nbytes`` is pure metadata arithmetic.  The backend
        allocator's ``memory_stats()`` (GPU/TPU) is consulted when
        present — on CPU it is absent/None and the field stays None.
        """
        try:
            import jax
            live = jax.live_arrays()
        except Exception:       # pragma: no cover - jax is a core dep
            return
        nbytes = 0
        for a in live:
            try:
                nbytes += int(a.nbytes)
            except Exception:   # deleted between enumeration and read
                continue
        mem = self.memory
        mem["samples"] += 1
        mem["peak_live_bytes"] = max(mem["peak_live_bytes"], nbytes)
        mem["peak_live_buffers"] = max(mem["peak_live_buffers"], len(live))
        # the highest allocator peak over every local device: a sharded
        # run places arrays on all of them, not on the first alone
        for dev in jax.local_devices():
            stats = dev.memory_stats()
            peak = (stats or {}).get("peak_bytes_in_use")
            if peak is not None:
                prev = mem["device_peak_bytes"] or 0
                mem["device_peak_bytes"] = max(prev, int(peak))

    # -- context -------------------------------------------------------------
    @contextlib.contextmanager
    def activate(self):
        """Install as :func:`current_profile` for the block's duration."""
        token = _ACTIVE.set(self)
        try:
            yield self
        finally:
            _ACTIVE.reset(token)

    @contextlib.contextmanager
    def attribute(self, label: str):
        """Label compiles recorded in the block (scheduler: per-quantum
        ``sched-<job>/q<k>`` attribution).  Nests; restores on exit."""
        prev = self.attribution
        self.attribution = label
        try:
            yield self
        finally:
            self.attribution = prev

    # -- export --------------------------------------------------------------
    def to_dict(self) -> dict:
        """JSON-serializable snapshot of the whole profile."""
        return {"meta": dict(self.meta),
                "jit": {**self.jit,
                        "compile_wall_s": round(self.jit["compile_wall_s"],
                                                6)},
                "memory": dict(self.memory),
                "compile_events": list(self.compile_events),
                "worker_spans": list(self.worker_spans)}

    def publish(self, trace=None, registry=None) -> None:
        """Push the harvest into the other observability surfaces.

        ``trace``: one ``profile/jit`` span (compile counts + wall), plus
        the memory watermark on the trace summary.  ``registry``:
        histogram ``profile_compile_seconds``, counter
        ``profile_jit_calls``, gauge ``profile_peak_live_bytes``.
        """
        if trace is not None:
            trace.spans.append({
                "name": "profile/jit", "t": 0.0,
                "compiles": self.jit["compiles"],
                "calls": self.jit["calls"],
                "dur_s": round(self.jit["compile_wall_s"], 6)})
            if self.memory["samples"]:
                trace.summary.setdefault(
                    "peak_live_bytes", self.memory["peak_live_bytes"])
        if registry is not None:
            for ev in self.compile_events:
                registry.histogram("profile_compile_seconds").observe(
                    ev["wall_s"])
            if self.jit["calls"]:
                registry.counter("profile_jit_calls").inc(self.jit["calls"])
            if self.memory["samples"]:
                g = registry.gauge("profile_peak_live_bytes")
                g.set(max(g.value, self.memory["peak_live_bytes"]))


class NullProfile:
    """Disabled profile: every recorder is a no-op.  Never installed as
    the context's profile — ``current_profile() is None`` is the normal
    disabled-path check — but code handed a profile directly can take
    this instead of branching on None."""

    enabled = False
    attribution = None

    def set_meta(self, **kw):
        pass

    def record_jit_call(self, n=1):
        pass

    def record_compile(self, key, wall_s):
        pass

    def record_worker(self, worker, backend, dur_s):
        pass

    def sample_memory(self):
        pass

    @contextlib.contextmanager
    def activate(self):
        yield self

    @contextlib.contextmanager
    def attribute(self, label):
        yield self

    def publish(self, trace=None, registry=None):
        pass

    def to_dict(self):
        return {}


NULL_PROFILE = NullProfile()
