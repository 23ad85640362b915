"""Host milliseconds per 1,000 blocks in the wave path's lowering, packing
and extraction: self time of the program's ``wave.lower``, ``wave.pack``
and ``wave.extract`` spans in the traced wave."""
from harness.readers import wave_host_s


def read(m):
    s = wave_host_s(m)
    n = m["counters"].get("blocks")
    return None if s is None or not n else 1e3 * s / (n / 1e3)
