"""The benchmark's spans around its calls into the server.

A span is ``(name, start, end)`` in host seconds from the start of the
window.  In a traced run each span is also a
``jax.profiler.TraceAnnotation`` named ``chipbench.<name>``, so that the
profiler's trace holds it on the same clock as the device's operations
(``tracereduce``).
"""
from __future__ import annotations

import contextlib
import time

PREFIX = "chipbench."


class Spans:
    """Spans kept in memory; ``annotate`` also writes them into the
    profiler's trace."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.origin = time.perf_counter()
        self.spans: list[tuple[str, float, float]] = []

    def now(self) -> float:
        return time.perf_counter() - self.origin

    @contextlib.contextmanager
    def span(self, name: str):
        with contextlib.ExitStack() as stack:
            if self.annotate:
                import jax
                stack.enter_context(
                    jax.profiler.TraceAnnotation(PREFIX + name))
            t0 = self.now()
            try:
                yield
            finally:
                self.spans.append((name, t0, self.now()))
