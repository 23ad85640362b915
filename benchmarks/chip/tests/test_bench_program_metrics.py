"""The per-layer metrics read from the program's own spans, in a traced
run of the campaign cell on the CPU at test size (the set-up of
``test_bench_correct``): CPU self time and the dispatch counters are
read, the kernel's step time needs a device plane and is left out."""
from unittest import mock

from test_bench_correct import _run, cpu_as_tpu, small_bench  # noqa: F401


def test_traced_run_reads_program_metrics(small_bench,  # noqa: F811
                                          cpu_as_tpu, tmp_path):  # noqa: F811
    with mock.patch("harness.roofline.peaks",
                    return_value={"hbm_bytes_per_s": 819e9}):
        res = _run(small_bench, "campaign.sim3", 2147483659, trace=1,
                   tmp=tmp_path)
    assert res["correct"]
    m = res["metrics"]
    cpu = m["wave_host_cpu_s.campaign"]["value"]
    wall = m["wave_host_s.campaign"]["value"]
    assert cpu > 0 and wall > 0
    fill = m["wave_kernel_fill.campaign"]["value"]
    assert 0 < fill <= 100
    assert "wave_step_us.campaign" not in m
