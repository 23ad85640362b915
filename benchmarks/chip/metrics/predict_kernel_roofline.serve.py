"""The port-bound kernel's share of its memory roofline over the traced
window: the least bytes of the problem (the blocks and calls that reached
the device, each model's port combinations and min-cut candidates, see
``harness.roofline.port_bound_bytes``) over the HBM bandwidth, divided by
the device time of the kernel's executions (compiled program
``jit_port_bound_kernel``) in the profiler trace."""
from harness import roofline

PROGRAM = "jit_port_bound_kernel"


def read(m):
    c = m["counters"]
    dev_s = m["trace"]["modules_s"].get(PROGRAM)
    before, after = c.get("predictor_before"), c.get("predictor_after")
    if not dev_s or not before or not after:
        return None
    blocks = after.get("device_blocks", 0) - before.get("device_blocks", 0)
    calls = after.get("device_waves", 0) - before.get("device_waves", 0)
    if blocks <= 0 or calls <= 0:
        return None
    # the service's counters are summed over uarches: charge every call
    # the smallest model's tables, so the least bytes stay a lower bound
    combos, cands = min(c["model_shapes"].values())
    least = (roofline.port_bound_bytes(blocks, combos, cands)
             + (calls - 1) * 4 * (combos * cands + cands))
    return roofline.roofline_share(least, dev_s,
                                   m["peaks"]["hbm_bytes_per_s"])
