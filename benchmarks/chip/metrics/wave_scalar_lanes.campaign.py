"""Percent of the traced part's wave lanes that ran on the scalar oracle:
the ``scalar_lanes`` over the ``lanes`` of the program's ``wave.run_batch``
spans (lanes of length chunks thinner than the batched kernel's
crossover)."""
from harness.attribution import span_arg_sum


def read(m):
    spans = m["program_spans"]
    scalar = span_arg_sum(spans, "wave.run_batch", "scalar_lanes")
    lanes = span_arg_sum(spans, "wave.run_batch", "lanes")
    return 100.0 * scalar / lanes if scalar is not None and lanes else None
