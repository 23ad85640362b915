"""Peaks by device kind, and the bytes each device kernel must move.

The work behind a kernel's least time is counted from the problem it
computes (real lanes, μops, operand reads, blocks), never from the padded
bucket shapes the program happens to use, so a change of bucketing does not
move the yardstick.  Both kernels compute in int32 and the chip's integer
vector peak is not published, so the least time is the memory bound:
bytes over the HBM bandwidth of ``peaks.json``.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent.parent / "peaks.json"

# wave kernel, per real μop: issue cycle, allowed-port set id, latency and
# port occupancy (int32 each), a valid flag (1 byte) and its completion
# time out (int32); per operand read: producer position and extra delay
# (int32 each); per lane: the μop count of every port out (int32)
WAVE_BYTES_PER_UOP = 4 + 4 + 4 + 4 + 1 + 4
WAVE_BYTES_PER_READ = 4 + 4
WAVE_BYTES_PER_LANE_PORT = 4


def peaks(device_kind: str, path=PEAKS) -> dict:
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {path}")
    return table[device_kind]


def wave_kernel_bytes(uops: int, reads: int, lanes: int, ports: int) -> int:
    """Least bytes of the dispatch recurrence over ``lanes`` sequences with
    ``uops`` μops and ``reads`` operand reads in all, on ``ports`` ports."""
    return (uops * WAVE_BYTES_PER_UOP + reads * WAVE_BYTES_PER_READ
            + lanes * ports * WAVE_BYTES_PER_LANE_PORT)


def port_bound_bytes(blocks: int, combos: int, candidates: int) -> int:
    """Least bytes of the min-cut port bound for ``blocks`` blocks over a
    model with ``combos`` port combinations and ``candidates`` cut sets:
    each block's μop counts per combination in, the candidate masks and
    sizes once, and each block's winning ratio (numerator, denominator)
    out; int32 throughout."""
    return 4 * (blocks * combos + combos * candidates + candidates
                + 2 * blocks)


def roofline_share(least_bytes: int, device_s: float, bytes_per_s: float):
    """Percent of the memory roofline: least time over measured time."""
    if device_s <= 0:
        return None
    return 100.0 * (least_bytes / bytes_per_s) / device_s
