"""Host seconds per campaign in the wave path's lowering, packing and
extraction: self time of the program's ``wave.lower``, ``wave.pack`` and
``wave.extract`` spans in the traced campaign, summed over its three
worker threads."""
from harness.readers import wave_host_s


def read(m):
    s = wave_host_s(m)
    n = m["counters"].get("campaigns")
    return None if s is None or not n else s / n
