"""Median service-side time of a ``predict_batch`` request, from the
program's ``EndpointStats`` (it records each request's time per block, so
the per-block median times the blocks per request)."""


def read(m):
    c = m["counters"]
    p50 = c.get("endpoint_block_p50_us")
    if p50 is None:
        return None
    return p50 * c["blocks_per_request"] / 1e3
