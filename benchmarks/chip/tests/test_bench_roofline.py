"""Roofline byte functions on hand-counted shapes, and the peaks table."""
from types import SimpleNamespace

import pytest

from harness import roofline


def test_wave_bytes_hand_counted():
    # 10 μops (21 B each), 4 operand reads (8 B each), 2 lanes x 8 ports
    assert roofline.wave_kernel_bytes(10, 4, 2, 8) == 210 + 32 + 64


def test_port_bound_bytes_hand_counted():
    # 3 blocks x 2 combos in, 2 x 3 masks + 3 sizes, 3 x 2 ratios out
    assert roofline.port_bound_bytes(3, 2, 3) == 4 * (6 + 6 + 3 + 6)


def test_groundtruth_counts_the_problem_not_the_bucket():
    """One block ``ADD op1 op2`` on sim_skl: one μop reading two operands,
    measured at 10 and 110 copies: 120 μops, 240 reads, 2 lanes."""
    from run_cell import load_module, BENCH
    gt = load_module(BENCH / "traffic" / "groundtruth.py", "gt_for_test")
    block = (("ADD_R64_R64", {"op1": "R1", "op2": "R2"}, "low"),)
    st = {"traced_wave": {"uarch": "sim_skl", "blocks": [block]}}
    got = gt.layer_inputs(st, SimpleNamespace())
    assert got == {"wave_lanes": 2, "wave_uops": 120, "wave_reads": 240,
                   "wave_ports": 8}
    assert roofline.wave_kernel_bytes(120, 240, 2, 8) == \
        120 * 21 + 240 * 8 + 2 * 8 * 4


def test_peaks_by_device_kind():
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_roofline_share():
    # 819 bytes take 1 ns at 819 GB/s: a 2 ns kernel is at 50%
    assert roofline.roofline_share(819, 2e-9, 819e9) == pytest.approx(50.0)
    assert roofline.roofline_share(819, 0.0, 819e9) is None
