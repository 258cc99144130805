"""Helpers that the metric readers under ``metrics/`` share.

A reader is ``read(record) -> float | None``.  The run's ``record`` holds
``setup_s``, ``seconds`` (the window), ``responses`` (``loadgen``),
``spans`` (host clock, ``spans.Spans``), ``end_s`` (when the load
generator stopped, window and drain), ``plan_cache`` (the server's
``plan_cache_info()`` at the window's start and end) and ``trace``
(``tracereduce.reduce``, or ``None`` in an untraced run).
"""
from __future__ import annotations

import math


def completed(record: dict, cls: str) -> list[dict]:
    """Answered requests of class ``cls`` that returned inside the window."""
    return [r for r in record["responses"] if r["class"] == cls
            and r["status"] == "ok" and r["t_done"] <= record["seconds"]]


def latencies(record: dict) -> list[float]:
    """Seconds from due to answer of every interactive request due in the
    window; refused, failed and unanswered requests rank last (inf)."""
    return [r["t_done"] - r["t_due"] if r["status"] == "ok" else math.inf
            for r in record["responses"]
            if r["class"] == "interactive" and r["t_due"] < record["seconds"]]


def latency_ms(record: dict, q: float) -> float | None:
    """The nearest-rank ``q``-th percentile of :func:`latencies`, in ms.
    A rank that falls on a request without an answer reads as the load
    generator's whole run, window and drain."""
    values = latencies(record)
    if not values:
        return None
    v = sorted(values)[max(0, math.ceil(q / 100 * len(values)) - 1)]
    return 1000.0 * (record["end_s"] if math.isinf(v) else v)


def idle_share(record: dict) -> float | None:
    """Percent of the traced window in which no operation ran on the
    device."""
    tr = record["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
