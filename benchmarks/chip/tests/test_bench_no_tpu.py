"""Without a TPU, or without the program, a run exits non-zero and prints
no result."""
import os
import shutil
import subprocess
import sys

import run_cell

ARGS = ["--workload", "campaign.sim3", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run_cell.py", *ARGS], cwd=root,
        env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_exits_nonzero_without_result():
    p = _run(run_cell.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(run_cell.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run_cell.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
