"""Device microseconds per serial step of the wave kernel: the device time
of the kernel's executions in the traced campaign (``jit_run``, as the
roofline reads it) over the scan steps the program dispatched there (the
``steps`` of its ``wave.dispatch`` spans: each shard's bucket length)."""
from harness.attribution import span_arg_sum
from harness.readers import WAVE_PROGRAM


def read(m):
    dev_s = m["trace"]["modules_s"].get(WAVE_PROGRAM)
    steps = span_arg_sum(m["program_spans"], "wave.dispatch", "steps")
    if not dev_s or not steps:
        return None
    return 1e6 * dev_s / steps
