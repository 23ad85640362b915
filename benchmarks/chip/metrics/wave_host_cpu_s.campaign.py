"""Thread-CPU seconds per campaign in the wave path's lowering, packing and
extraction: CPU self time (each span's ``cpu``) of the program's
``wave.lower``, ``wave.pack`` and ``wave.extract`` spans in the traced
campaign, summed over its worker threads.  Beside
``wave_host_s.campaign``'s wall seconds, the difference is time those
threads waited inside the spans (mostly for the interpreter lock)."""
from harness.attribution import cpu_self_time_s
from harness.readers import WAVE_HOST_SPANS


def read(m):
    s = cpu_self_time_s(m["program_spans"], WAVE_HOST_SPANS)
    n = m["counters"].get("campaigns")
    return None if s is None or not n else s / n
