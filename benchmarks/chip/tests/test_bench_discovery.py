"""Cells, configurations, traffic drivers and metric readers are found by
name: a workload file dropped into a copy of ``workloads/`` runs with no
code edit."""
import json
import shutil

import pytest

import run_cell


@pytest.fixture()
def bench_copy(tmp_path):
    dst = tmp_path / "chip"
    shutil.copytree(run_cell.BENCH, dst,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return dst


def test_new_workload_file_is_discovered(bench_copy):
    (bench_copy / "workloads" / "groundtruth.corpus_w512.json").write_text(
        json.dumps({"config": "corpus_sim3", "traffic": "groundtruth",
                    "chips": 1, "why": "a narrower wave",
                    "params": {"wave_width": 512}}))
    cell = run_cell.Cell("groundtruth.corpus_w512", 7, bench=bench_copy)
    assert cell.params == {"wave_width": 512}
    assert cell.config["name"] == "corpus_sim3"
    assert callable(cell.driver.window) and callable(cell.driver.check)
    benchmark = json.loads((run_cell.ROOT / "BENCHMARK.json").read_text())
    benchmark["end_to_end"].append(
        {"name": "gt_blocks_per_s", "unit": "blocks/s", "better": "higher",
         "bound": 0.05, "source": "host_clock",
         "workloads": ["groundtruth.corpus_w512"]})
    e2e, layer = run_cell.metric_plan(cell, benchmark)
    assert {m["name"] for m in e2e} == {"setup_s", "gt_blocks_per_s"}
    assert "setup_compile_s" in {m["name"] for m in layer}


def test_new_metric_reader_is_discovered(bench_copy):
    (bench_copy / "metrics" / "blocks_traced.gt.py").write_text(
        "def read(m):\n    return m['counters'].get('blocks')\n")
    cell = run_cell.Cell("campaign.sim3", 1, bench=bench_copy)
    layer = [{"name": "blocks_traced.gt", "unit": "blocks"},
             {"name": "sched_wave_width.campaign", "unit": "lanes"}]
    got = run_cell.read_layer_metrics(cell, layer, {"counters": {
        "blocks": 2048}})
    assert got == {"blocks_traced.gt": {"value": 2048, "unit": "blocks"}}


def test_missing_workload_is_an_error(bench_copy):
    with pytest.raises(run_cell.CellError):
        run_cell.Cell("no.such.cell", 1, bench=bench_copy)
