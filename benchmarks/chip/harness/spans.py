"""The benchmark's own host spans around its calls into each layer.

Each span is kept in memory as ``(name, start_ns, end_ns)`` on
``time.perf_counter_ns`` and, while the profiler runs, is also written into
the profiler's trace as a ``jax.profiler.TraceAnnotation``, so idle gaps on
the device can be labelled with what the host was doing.
"""
from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self):
        self.records: list = []
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter_ns()
        if self.annotate:
            import jax  # noqa: PLC0415
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.records.append((name, t0, time.perf_counter_ns()))

    def total_s(self, name: str) -> float:
        return sum(b - a for n, a, b in self.records if n == name) / 1e9
