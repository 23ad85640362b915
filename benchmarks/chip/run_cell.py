"""Run one benchmark cell once, on the chip it finds, and print its result.

    python3 benchmarks/chip/run_cell.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Every piece is found by name: the cell in ``workloads/<cell>.json``, its
configuration in ``configs/<config>.json``, its traffic driver in
``traffic/<driver>.py`` and each per-layer metric's reader in
``metrics/<metric>.py``; ``BENCHMARK.json`` at the checkout's root says
which metrics the cell reports.  A traffic driver exposes

* ``setup(cell) -> state``: inputs from the seed, the system built and
  every shape the window uses warmed up;
* ``window(state, cell, seconds) -> dict``: the measured work, with
  ``e2e`` (end-to-end metrics), ``attempted``, ``failed`` and ``counters``;
* ``layer_inputs(state, cell) -> dict``: counters the per-layer readers
  need that cost time to gather (traced runs only);
* ``release(state)``: drop the program's state before the check;
* ``check(state, cell) -> [(name, value, limit)]``: the comparison with
  the plain reference; the run is correct when every value is at most its
  limit.

The run exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.  JAX's persistent compilation cache is kept
in ``.jax_cache/`` in the checkout.  With ``--trace 1`` the part of the
window that the traffic driver wraps in ``cell.traced()`` runs under the
profiler and the program's own span tracer; the result then carries the
per-layer metrics, ``busy_s``/``window_s`` of that part and a breakdown
instead of the end-to-end metrics.  The numbers compared with the
reference, each with its limit, are the last lines on standard error and
the ``checks`` key of the result.  The last line on standard output is the
result, one JSON object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent.parent
COMPILE_CACHE = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench" / "trace"


class CellError(Exception):
    """The cell cannot be run here."""


def load_module(path: Path, name: str):
    if not path.is_file():
        raise CellError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One cell as its files state it, plus the run's seed and spans."""

    def __init__(self, name: str, seed: int, bench: Path = BENCH,
                 root: Path = ROOT):
        from harness.spans import Spans  # noqa: PLC0415
        self.name, self.seed, self.bench, self.root = name, seed, bench, root
        wl_path = bench / "workloads" / f"{name}.json"
        if not wl_path.is_file():
            raise CellError(f"no workload file {wl_path}")
        self.workload = json.loads(wl_path.read_text())
        self.chips = int(self.workload["chips"])
        self.params = self.workload.get("params", {})
        cfg_path = bench / "configs" / f"{self.workload['config']}.json"
        if not cfg_path.is_file():
            raise CellError(f"no configuration file {cfg_path}")
        self.config = json.loads(cfg_path.read_text())
        self.driver = load_module(
            bench / "traffic" / f"{self.workload['traffic']}.py",
            f"bench_traffic_{self.workload['traffic']}")
        self.spans = Spans()
        self.trace_dir = None    # set for a --trace 1 run
        self.tracer = None       # the program's span tracer while traced

    @contextlib.contextmanager
    def traced(self):
        """Run the body under the profiler and the program's span tracer,
        as the traced part of a ``--trace 1`` run (once: later calls, and
        every call in an untraced run, only run the body)."""
        if self.trace_dir is None or self.tracer is not None:
            yield False
            return
        import jax  # noqa: PLC0415
        from repro.obs import tracer as obs  # noqa: PLC0415
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.trace_dir.mkdir(parents=True)
        self.tracer = obs.Tracer(enabled=True)
        prev = obs.set_tracer(self.tracer)
        self.spans.annotate = True
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # keep the pure-Python host cheap
        opts.host_tracer_level = 1     # the benchmark's annotations only
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        try:
            with self.spans.span("bench.traced"):
                yield True
        finally:
            jax.profiler.stop_trace()
            obs.set_tracer(prev)
            self.spans.annotate = False


def metric_plan(cell: Cell, benchmark: dict) -> tuple[list, list]:
    """The end-to-end and per-layer metric entries this cell reports."""
    e2e = [m for m in benchmark["end_to_end"]
           if "workloads" not in m or cell.name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in benchmark["per_layer"]
             if (cell.name in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return e2e, layer


def read_layer_metrics(cell: Cell, layer: list, measures: dict) -> dict:
    """Each per-layer reader's value; a reader that finds nothing to read
    returns ``None`` and its metric is left out."""
    out = {}
    for m in layer:
        reader = load_module(cell.bench / "metrics" / f"{m['name']}.py",
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(measures)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_info(chips: int):
    """The devices of this run; raises :class:`CellError` without a TPU
    or with too few chips."""
    import jax  # noqa: PLC0415
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise CellError(f"no TPU: jax platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise CellError(f"the cell needs {chips} chips, jax sees "
                        f"{len(devices)}")
    return devices


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def enable_compile_cache(meter_cls):
    """Fix JAX's persistent cache at ``.jax_cache/`` in the checkout and
    keep every compile there; hand the same directory to the program."""
    import jax  # noqa: PLC0415
    meter = meter_cls()
    COMPILE_CACHE.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: a size limit in the environment turns on JAX's LRU
    # bookkeeping, which on a v5e host raised on its ``-atime`` files and
    # dropped the entries it had written
    jax.config.update("jax_compilation_cache_max_size", -1)
    from repro.core.device_mesh import enable_compile_cache as program_cache
    program_cache()
    return meter


def run(args, devices, *, bench: Path = BENCH,
        trace_dir: Path = TRACE_DIR) -> dict:
    """One run of ``args.workload`` on ``devices``; ``bench`` is the
    directory the cell's files are found in."""
    from harness.compile_meter import CompileMeter  # noqa: PLC0415
    benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = Cell(args.workload, args.seed, bench=bench)
    if len(devices) < cell.chips:
        raise CellError(f"the cell needs {cell.chips} chips, jax sees "
                        f"{len(devices)}")
    e2e_plan, layer_plan = metric_plan(cell, benchmark)
    meter = enable_compile_cache(CompileMeter)
    snap0 = meter.snapshot()
    state = cell.driver.setup(cell)
    setup_compile = meter.since(snap0)
    snap1 = meter.snapshot()
    cell.trace_dir = trace_dir if args.trace else None
    # what set-up left behind is not the window's garbage to collect
    gc.collect()
    gc.freeze()
    t_window = time.perf_counter()
    setup_s = t_window - T_START
    with cell.spans.span("bench.window"):
        result = cell.driver.window(state, cell, args.seconds)
    window_wall = time.perf_counter() - t_window
    window_compile = meter.since(snap1)
    mem = memory_peak(devices[:cell.chips])
    measures = None
    if args.trace:
        if cell.tracer is None:
            raise CellError("the traffic driver traced no part of its window")
        measures = trace_measures(cell, state, result, devices,
                                  setup_compile)
    cell.driver.release(state)
    t_check = time.perf_counter()
    checks = cell.driver.check(state, cell)
    check_s = time.perf_counter() - t_check
    metrics = {}
    if args.trace:
        metrics = read_layer_metrics(cell, layer_plan, measures)
    else:
        values = dict(result["e2e"], setup_s=setup_s)
        for m in e2e_plan:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    out = {"correct": all(v <= lim for _, v, lim in checks),
           "attempted": result["attempted"], "failed": result["failed"],
           "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = measures["trace"]["busy_s"]
        device["window_s"] = measures["trace"]["window_s"]
        out["breakdown"] = measures["trace"]["breakdown"]
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    notes = {"setup_s": setup_s, "window_wall_s": window_wall,
             "check_s": check_s,
             "setup_compile": setup_compile,
             "window_compile": window_compile,
             **result.get("notes", {}), **state.get("check_notes", {})}
    if args.trace:
        notes["trace"] = {k: measures["trace"][k] for k in (
            "xplane_bytes", "reduce_s", "devices", "per_device_busy_s")}
        notes["trace"]["modules_s"] = measures["trace"]["modules_s"]
    return {"result": out, "notes": notes}


def trace_measures(cell, state, result, devices, setup_compile) -> dict:
    """What the per-layer readers read: the traffic driver's counters, the
    program's spans, the reduced profiler trace and the peaks."""
    from harness import roofline  # noqa: PLC0415
    from harness.trace import (TraceEvents, find_xplane,  # noqa: PLC0415
                               reduce_trace, window_of)
    t0 = time.perf_counter()
    path = find_xplane(cell.trace_dir)
    size = os.path.getsize(path)
    events = TraceEvents(path)
    reduced = reduce_trace(events, window_of(events, "bench.traced"))
    shutil.rmtree(cell.trace_dir, ignore_errors=True)
    reduced["xplane_bytes"] = size
    reduced["reduce_s"] = time.perf_counter() - t0
    counters = dict(result.get("counters", {}))
    counters.update(cell.driver.layer_inputs(state, cell))
    return {"cell": cell.name, "counters": counters,
            "program_spans": cell.tracer.events(),
            "setup_compile": setup_compile, "trace": reduced,
            "peaks": roofline.peaks(devices[0].device_kind)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    # the program and JAX both take the benchmark's cache directory
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE)
    try:
        devices = device_info(1)
        import repro  # noqa: F401, PLC0415
        out = run(args, devices)
    except (CellError, ImportError, FileNotFoundError) as e:
        print(f"run_cell: {e}; nothing was measured", file=sys.stderr)
        return 1
    notes = out["notes"]
    print("notes " + json.dumps(notes, sort_keys=True, default=str),
          file=sys.stderr)
    for name, c in out["result"]["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
