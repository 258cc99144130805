"""Observability: query tracing, EXPLAIN ANALYZE, and process metrics.

The pieces (see ``docs/OBSERVABILITY.md`` for the full walkthrough):

* :class:`QueryTrace` / :func:`current_trace` — one query's span tree
  keyed by GAO levels: est-vs-observed frontier cardinality + Q-error
  per level, kernel paths, scheduler preempt/resume/restart events,
  cross-shard exchange traffic; JSONL export via ``to_jsonl``.
* :func:`explain_analyze` — run a query under a fresh trace and render
  the annotated plan tree.
* :class:`MetricsRegistry` / :func:`get_registry` — process-wide
  counters/gauges/histograms with labels, snapshotted by
  ``QueryServer.metrics()``.
* :func:`span` — the program's spans: a ``jax.profiler``
  annotation named ``repro.<name>`` around each server phase, GAO
  level, chunk dispatch and compaction, so a profiler trace can name
  what the host did while the device sat idle (also recorded into an
  active :class:`QueryTrace`, per level and phase).
* :class:`DeviceProfile` / :func:`current_profile` — device-side
  resource accounting one layer below the trace: jit compile/call
  counts and compile wall, and live-buffer memory watermarks sampled at
  GAO level boundaries.

Everything records host-resident numbers only: tracing, metrics, and
profiling add zero device dispatches (guarded by ``tests/test_obs.py``
and ``tests/test_profile.py``).
"""
from .explain import ExplainResult, explain_analyze
from .metrics import (Counter, DEFAULT_BUCKETS, Gauge, Histogram,
                      MetricsRegistry, get_registry)
from .profile import (DeviceProfile, NULL_PROFILE, NullProfile,
                      PROFILE_SCHEMA_VERSION, current_profile, span)
from .schema import (ENGINE_REQUIRED_KEYS, ENGINE_STATS_SOURCE_KEYS,
                     normalize_engine_stats)
from .trace import (NULL_TRACE, NullTrace, QueryTrace, TRACE_SCHEMA_VERSION,
                    current_trace, qerror)

__all__ = [
    "Counter", "DEFAULT_BUCKETS", "DeviceProfile", "ENGINE_REQUIRED_KEYS",
    "ENGINE_STATS_SOURCE_KEYS",
    "ExplainResult", "Gauge", "Histogram",
    "MetricsRegistry", "NULL_PROFILE", "NULL_TRACE", "NullProfile",
    "NullTrace", "PROFILE_SCHEMA_VERSION", "QueryTrace",
    "TRACE_SCHEMA_VERSION", "current_profile", "current_trace",
    "explain_analyze", "get_registry", "normalize_engine_stats", "qerror",
    "span",
]
