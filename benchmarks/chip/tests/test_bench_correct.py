"""The comparison that decides ``correct``, driven through whole runs here
on the CPU with the chip check skipped (machines pick the jax kernels as
they do on a TPU), at a size a test run holds: a sound run is correct, and
the control and every planted fault the cell can have make it not."""
import json
import shutil
from types import SimpleNamespace
from unittest import mock

import pytest

import controls
import run_cell

SMALL = {
    "campaign.sim3": {"instructions": ["ADD_R64_R64", "ADC_R64_R64",
                                       "MOVQ2DQ_X_X", "DIV_R64",
                                       "MOV_R64_R64", "PEXTRQ_R64_X"],
                      "sample": 200, "sample_per_wave": 2},
    "groundtruth.corpus_w2048": {"wave_width": 128, "sample": 60},
    "serve.corpus_open": {"pool": 400, "rate": 5, "horizon_s": 4,
                          "conns": 2, "sample": 3},
}
SECONDS = {"serve.corpus_open": 2.0}


@pytest.fixture(scope="module")
def small_bench(tmp_path_factory):
    """A copy of the benchmark whose cells run at test size."""
    dst = tmp_path_factory.mktemp("bench") / "chip"
    shutil.copytree(run_cell.BENCH, dst,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name, params in SMALL.items():
        path = dst / "workloads" / f"{name}.json"
        wl = json.loads(path.read_text())
        wl["params"].update(params)
        path.write_text(json.dumps(wl))
    return dst


@pytest.fixture(scope="module")
def cpu_as_tpu():
    with mock.patch("repro.core.device_mesh.device_platform",
                    return_value="tpu"), \
            mock.patch("repro.core.device_mesh.enable_compile_cache",
                       return_value=None), \
            mock.patch.object(run_cell, "enable_compile_cache",
                              lambda meter_cls: meter_cls()):
        yield


def _run(bench, workload, seed, control=None, trace=0, tmp=None):
    import jax  # noqa: PLC0415
    args = SimpleNamespace(workload=workload, seed=seed,
                           seconds=SECONDS.get(workload, 0.1), trace=trace)
    kw = {"bench": bench}
    if tmp is not None:
        kw["trace_dir"] = tmp / "trace"
    if control is None:
        return run_cell.run(args, jax.devices(), **kw)["result"]
    with controls.applied(control):
        return run_cell.run(args, jax.devices(), **kw)["result"]


@pytest.mark.parametrize("workload,control,fails", [
    ("campaign.sim3", None, None),
    ("campaign.sim3", "tie_break", "answers_mismatch"),
    ("campaign.sim3", "answer_altered", "answers_mismatch"),
    ("campaign.sim3", "half_wave", "answers_mismatch"),
    ("campaign.sim3", "engine_neighbour", "answers_mismatch"),
    ("groundtruth.corpus_w2048", None, None),
    ("groundtruth.corpus_w2048", "float32", "predicted_mismatch"),
    ("groundtruth.corpus_w2048", "prediction_altered", "predicted_mismatch"),
    ("groundtruth.corpus_w2048", "answer_altered", "measured_mismatch"),
    ("groundtruth.corpus_w2048", "half_wave", "measured_mismatch"),
    ("serve.corpus_open", None, None),
    ("serve.corpus_open", "float32", "served_mismatch"),
    ("serve.corpus_open", "prediction_altered", "served_mismatch"),
])
def test_control_and_faults_fail(small_bench, cpu_as_tpu, workload, control,
                                 fails):
    res = _run(small_bench, workload, 4000000007, control)
    checks = res["checks"]
    if fails is None:
        assert res["correct"], checks
        assert all(c["value"] == 0 for c in checks.values()), checks
    else:
        assert not res["correct"]
        assert checks[fails]["value"] > checks[fails]["limit"], checks


def test_traced_run_reads_layer_metrics(small_bench, cpu_as_tpu, tmp_path):
    with mock.patch("harness.roofline.peaks",
                    return_value={"hbm_bytes_per_s": 819e9}):
        res = _run(small_bench, "campaign.sim3", 11, trace=1, tmp=tmp_path)
    assert res["correct"]
    m = res["metrics"]
    assert {"sched_wave_width.campaign", "engine_hit.campaign",
            "wave_host_s.campaign", "setup_compile_s"} <= set(m)
    assert m["sched_wave_width.campaign"]["value"] > 1
    # the CPU has no device plane: nothing to read, so nothing reported
    assert "device_idle.campaign" not in m
    assert "wave_kernel_roofline.campaign" not in m
    assert res["device"]["window_s"] > 0
