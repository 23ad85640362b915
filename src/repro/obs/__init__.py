"""Unified observability: structured tracing + metrics for every layer.

The measurement stack is five layers deep (plans -> WaveScheduler ->
MeasurementEngine -> BatchSimMachine -> device mesh -> service), and each
layer grew its own ad-hoc stats dict.  This package is the one substrate
they all report through:

* :mod:`repro.obs.tracer` — thread-safe hierarchical spans with monotonic
  clocks, per-span thread CPU time and a module-level no-op fast path
  (near-zero overhead when disabled; enable with ``REPRO_TRACE=1`` or
  ``Tracer(enabled=True)``).  While enabled, each span is also a
  ``jax.profiler`` annotation, so a profiler trace carries the spans on
  the device trace's clock.
* :mod:`repro.obs.metrics` — a counter/gauge/histogram registry with one
  ``snapshot()`` shape, absorbing the legacy stats dicts
  (``EngineStats.as_dict()``, ``device_stats()``, the server's per-endpoint
  summaries) behind canonical dotted instrument names.
* :mod:`repro.obs.export` — Chrome trace-event JSON (loads in Perfetto /
  ``chrome://tracing``: one track per thread, counter tracks) and a
  compact JSONL event log.

Per-wave bottleneck attribution over an exported trace lives in
:mod:`repro.analysis.wave_report` (``scripts/analyze.py --trace-report``).
"""
from repro.obs.tracer import (NULL_SPAN, Tracer, counter, enabled,
                              get_tracer, instant, set_tracer, span,
                              wait_lock)

__all__ = ["Tracer", "span", "instant", "counter", "wait_lock", "enabled",
           "get_tracer", "set_tracer", "NULL_SPAN"]
