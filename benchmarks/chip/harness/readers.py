"""Reductions that several per-layer metrics share; each metric's own file
under ``metrics/`` names which one it reads and from what."""
from __future__ import annotations

from harness import roofline
from harness.spanstats import self_time_s

WAVE_HOST_SPANS = ("wave.lower", "wave.pack", "wave.extract")
WAVE_PROGRAM = "jit_run"     # the scan kernel: jax.jit of its ``run``


def device_idle(m):
    """Percent of the traced part in which no operation ran on the device
    (union of the device's op intervals, averaged over the chips used)."""
    t = m["trace"]
    if not t["devices"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def wave_host_s(m):
    """Self seconds of the wave path's lowering, packing and extraction
    spans in the traced part (summed over threads)."""
    return self_time_s(m["program_spans"], WAVE_HOST_SPANS)


def wave_kernel_roofline(m):
    """The wave kernel's percent of its memory roofline: the problem's
    least bytes over the HBM bandwidth, divided by the device time of the
    kernel's executions in the trace."""
    c = m["counters"]
    dev_s = m["trace"]["modules_s"].get(WAVE_PROGRAM)
    if not dev_s or not c.get("wave_lanes"):
        return None
    least = roofline.wave_kernel_bytes(c["wave_uops"], c["wave_reads"],
                                       c["wave_lanes"], c["wave_ports"])
    return roofline.roofline_share(least, dev_s,
                                   m["peaks"]["hbm_bytes_per_s"])
