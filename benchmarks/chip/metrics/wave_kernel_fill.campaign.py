"""Share of the wave kernel's lane-steps that carry a real μop in the
traced campaign: the ``rows`` over the ``slots`` of the program's
``wave.dispatch`` spans (real μop rows over each shard's bucket lanes ×
bucket length, padding included)."""
from harness.attribution import span_arg_sum


def read(m):
    spans = m["program_spans"]
    rows = span_arg_sum(spans, "wave.dispatch", "rows")
    slots = span_arg_sum(spans, "wave.dispatch", "slots")
    if rows is None or not slots:
        return None
    return 100.0 * rows / slots
