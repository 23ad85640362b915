"""Controls and planted faults for the comparison that decides ``correct``.

A control puts the plain reference in the program's place, with one of the
configuration's guarantees broken; a fault breaks the program's timed path
underneath.  Each must make a run come out not correct:

* ``tie_break`` — the wave kernel's answers come from the reference core
  with the least-load port tie-break dropped (first free port wins), the
  simplification a faster kernel would be tempted by;
* ``float32`` — predictions come from the reference predictor computed in
  float32, the precision below the float64 the predictor states;
* ``answer_altered`` — every other answer of a wave is one cycle late
  where the wave kernel produces it (a constant on every lane would cancel
  in Algorithm 2's differencing, and rightly pass);
* ``half_wave`` — the wave kernel computes the first half of each wave's
  lanes and hands their answers to the second half too;
* ``engine_neighbour`` — the measurement engine answers each cache hit
  with the cached answer stored next to it (the entry before, or after
  for the first);
* ``prediction_altered`` — every prediction is moved by one unit in the
  last place where the predictor produces it.

On the chip, at a cell's own size (no per-run chip check is skipped):

    python3 benchmarks/chip/controls.py --control tie_break \
        --workload campaign.sim3 --seconds 5 --seed 5 6 7

The benchmark's own runs never apply any of these.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
from pathlib import Path
from types import SimpleNamespace
from unittest import mock

BENCH = Path(__file__).resolve().parent


def _tie_break():
    from reference import oracle  # noqa: PLC0415
    from repro.core.simulator import Counters, SimMachine  # noqa: PLC0415
    isa, uarches = oracle.load_tables()
    refs = {n: oracle.Oracle(u, isa, tie_break="lowest_port")
            for n, u in uarches.items()}

    def run_batch(self, codes, kernel_lock=None):
        out = []
        for code in codes:
            cyc, ports = refs[self.name].run(
                [(i.spec, i.regs, i.value_hint) for i in code])
            out.append(Counters(cyc, ports))
        return out

    return mock.patch.object(SimMachine, "run_batch", run_batch)


def _float32():
    import numpy as np  # noqa: PLC0415
    from reference import models, oracle  # noqa: PLC0415
    from reference import predictor as refpred  # noqa: PLC0415
    from repro.service.batch_predictor import BatchPredictor  # noqa: PLC0415
    isa, uarches = oracle.load_tables()
    data = {n: models.model_data(isa, u) for n, u in uarches.items()}

    def predict_batch(self, blocks, on_error="raise"):
        model = data[self.model.uarch]
        out = []
        for code in blocks:
            p = refpred.predict(model, isa, [(i.spec, i.regs, i.value_hint)
                                             for i in code], np.float32)
            out.append(SimpleNamespace(port_pressure={}, **p))
        return out

    return mock.patch.object(BatchPredictor, "predict_batch", predict_batch)


def _wave_fault(kind: str):
    from repro.core.batch_sim import BatchSimMachine  # noqa: PLC0415
    from repro.core.simulator import Counters  # noqa: PLC0415
    real = BatchSimMachine.run_batch

    def run_batch(self, codes, kernel_lock=None):
        codes = list(codes)
        if kind == "half_wave" and len(codes) > 1:
            half = (len(codes) + 1) // 2
            out = real(self, codes[:half], kernel_lock)
            return out + out[:len(codes) - half]
        out = real(self, codes, kernel_lock)
        return [Counters(c.cycles + (i % 2), dict(c.port_uops))
                for i, c in enumerate(out)]

    return mock.patch.object(BatchSimMachine, "run_batch", run_batch)


def _engine_neighbour():
    from repro.core.engine import MeasurementEngine  # noqa: PLC0415
    real = MeasurementEngine.submit

    def submit(self, experiments, kernel_lock=None):
        experiments = list(experiments)
        order = list(self.cache)
        pos = {k: i for i, k in enumerate(order)}
        keys = [e.cache_key(self.machine.name) for e in experiments]
        out = real(self, experiments, kernel_lock)
        for i, k in enumerate(keys):
            j = pos.get(k)
            if j is None or len(order) < 2:
                continue
            nb = self.cache.get(order[j - 1] if j else order[1])
            if nb is not None:
                out[i] = type(nb)(nb.cycles, dict(nb.port_uops))
        return out

    return mock.patch.object(MeasurementEngine, "submit", submit)


def _prediction_altered():
    from repro.service.batch_predictor import BatchPredictor  # noqa: PLC0415
    real = BatchPredictor.predict_batch

    def predict_batch(self, blocks, on_error="raise"):
        out = real(self, blocks, on_error)
        for p in out:
            p.cycles = math.nextafter(p.cycles, math.inf)
        return out

    return mock.patch.object(BatchPredictor, "predict_batch", predict_batch)


PATCHES = {
    "tie_break": _tie_break,
    "float32": _float32,
    "answer_altered": lambda: _wave_fault("answer_altered"),
    "half_wave": lambda: _wave_fault("half_wave"),
    "engine_neighbour": _engine_neighbour,
    "prediction_altered": _prediction_altered,
}


def applied(name: str):
    """The named control or fault, as a context manager."""
    return PATCHES[name]()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--control", required=True,
                    choices=sorted(PATCHES) + ["none"],
                    help="none: the sound program, for the lower readings")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True, nargs="+",
                    help="one run per seed, all in this process")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import run_cell  # noqa: PLC0415
    sys.path.insert(0, str(run_cell.ROOT / "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run_cell.COMPILE_CACHE)
    try:
        devices = run_cell.device_info(1)
    except run_cell.CellError as e:
        print(f"controls: {e}", file=sys.stderr)
        return 1
    for seed in args.seed:
        run_args = SimpleNamespace(workload=args.workload, seed=seed,
                                   seconds=args.seconds, trace=0)
        with (applied(args.control) if args.control != "none"
              else contextlib.nullcontext()):
            out = run_cell.run(run_args, devices)
        res = out["result"]
        print(json.dumps({"control": args.control, "workload": args.workload,
                          "seed": seed, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
