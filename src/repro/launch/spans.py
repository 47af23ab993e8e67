"""Named host spans, timed and written into the profiler trace alike.

``with rec.span(name, **meta):`` opens a ``jax.profiler.TraceAnnotation``
(so the span sits in a running profiler trace, on the device's clock, with
``meta`` as its stats), times the body with ``time.perf_counter``, and adds
the time to ``rec.seconds[name]`` and one to ``rec.count[name]``. With no
profiler running an annotation costs about a microsecond.
"""
from __future__ import annotations

import contextlib
import time

import jax


class SpanRecorder:
    """Calls and total seconds of each span name, in the order each name
    was first opened, for one caller."""

    def __init__(self):
        self.seconds: dict[str, float] = {}
        self.count: dict[str, int] = {}

    @contextlib.contextmanager
    def span(self, name: str, **meta):
        self.seconds.setdefault(name, 0.0)
        self.count.setdefault(name, 0)
        with jax.profiler.TraceAnnotation(name, **meta):
            t0 = time.perf_counter()
            yield
            self.seconds[name] += time.perf_counter() - t0
            self.count[name] += 1

    def totals(self) -> dict[str, tuple[int, float]]:
        """``{name: (count, seconds)}``."""
        return {name: (n, self.seconds[name]) for name, n in self.count.items()}
