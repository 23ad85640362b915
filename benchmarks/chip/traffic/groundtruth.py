"""Corpus ground truth: waves of blocks measured and predicted.

Set-up draws ``wave_width`` blocks per uarch from the seed
(``harness.blocks``), loads each uarch's model (stated by the
configuration's tables, ``reference/models.py``) with the program's
``model_io.load_xml`` into a ``BatchPredictor`` attached to a
``SimMachine``, and runs one pass over the uarches, which compiles or loads
every kernel shape.  The window runs waves round-robin over the uarches,
each ``simulate_batch`` then ``predict_batch`` on the whole wave, as
``corpus/evaluate._flush`` does.  Before each pass every predictor gets a
fresh machine, so no wave is answered by a warm measurement engine or
lowering cache.  The window stops at the first wave boundary after
``seconds``; ``gt_blocks_per_s`` is the blocks measured and predicted over
the window's time.  In a traced run the first wave is the traced part.

``check`` draws ``sample`` blocks from the seed, spread over the last
wave of each uarch that the window ran, and
compares the measured cycles with the plain reference core (Algorithm 2:
10 and 110 copies, differenced) and the predictions with the plain
reference predictor, exactly.  ``device_path_faults`` counts machines or
predictors that did not run on the chip's kernels, degraded chunks and
device fallbacks.
"""
from __future__ import annotations

import random
import time

from harness import blocks as gen
from reference import models, oracle
from reference import predictor as refpred

N_SMALL, N_LARGE = 10, 110


def setup(cell) -> dict:
    from repro.core import model_io  # noqa: PLC0415
    from repro.core.isa import TEST_ISA  # noqa: PLC0415
    from repro.service.batch_predictor import BatchPredictor  # noqa: PLC0415
    from repro.service.protocol import parse_block  # noqa: PLC0415
    cfg = cell.config
    isa, uarches = oracle.load_tables()
    width = int(cell.params.get("wave_width", cfg["wave_width"]))
    st = {"isa": TEST_ISA, "waves": [], "last": {}, "passes": 0}
    with cell.spans.span("bench.inputs"):
        for name in cfg["uarches"]:
            ua = uarches[name]
            bl = gen.generate(isa, ua, width, cell.seed,
                              min_len=cfg["min_len"], max_len=cfg["max_len"])
            codes = [parse_block(gen.text(b)) for b in bl]
            model = model_io.load_xml(models.model_xml(isa, ua))
            bp = BatchPredictor(model, TEST_ISA)
            st["waves"].append({"uarch": name, "blocks": bl,
                                "codes": codes, "bp": bp})
    with cell.spans.span("bench.warmup"):
        _fresh_machines(st)
        for w in st["waves"]:
            _wave(st, w, cell)
    _fresh_machines(st)
    st["last"].clear()
    return st


def _fresh_machines(st: dict) -> None:
    from repro.core.simulator import SimMachine  # noqa: PLC0415
    from repro.core.uarch import SIM_UARCHES  # noqa: PLC0415
    for w in st["waves"]:
        w["bp"].machine = SimMachine(SIM_UARCHES[w["uarch"]], st["isa"])


def _wave(st: dict, w: dict, cell) -> None:
    bp, codes = w["bp"], w["codes"]
    with cell.spans.span("bench.simulate"):
        measured = bp.simulate_batch(codes)
    with cell.spans.span("bench.predict"):
        predicted = bp.predict_batch(codes)
    st["last"][w["uarch"]] = (measured, predicted, bp.machine,
                              dict(bp.backend_stats()))


def window(st: dict, cell, seconds: float) -> dict:
    t0 = time.perf_counter()
    n_blocks = 0
    traced_blocks = 0
    while True:
        for w in st["waves"]:
            with cell.traced() as traced:
                _wave(st, w, cell)
            n_blocks += len(w["codes"])
            if traced:
                traced_blocks = len(w["codes"])
                st["traced_wave"] = w
            if time.perf_counter() - t0 >= seconds:
                break
        else:
            st["passes"] += 1
            _fresh_machines(st)
            continue
        break
    wall = time.perf_counter() - t0
    return {"e2e": {"gt_blocks_per_s": n_blocks / wall},
            "attempted": n_blocks, "failed": 0,
            "counters": {"blocks": traced_blocks or n_blocks},
            "notes": {"blocks": n_blocks, "passes": st["passes"]}}


def layer_inputs(st: dict, cell) -> dict:
    """The problem the wave kernel solved in the traced wave: two lanes
    per block (10 and 110 copies), with their μops and operand reads
    counted from the configuration's tables."""
    w = st.get("traced_wave")
    if w is None:
        return {}
    _, uarches = oracle.load_tables()
    ua = uarches[w["uarch"]]
    uops = reads = 0
    for b in w["blocks"]:
        bu = sum(len(ua.behaviors[s].uops) for s, _, _ in b)
        br = sum(len(u.reads) for s, _, _ in b
                 for u in ua.behaviors[s].uops)
        uops += (N_SMALL + N_LARGE) * bu
        reads += (N_SMALL + N_LARGE) * br
    return {"wave_lanes": 2 * len(w["blocks"]), "wave_uops": uops,
            "wave_reads": reads, "wave_ports": len(ua.ports)}


def release(st: dict) -> None:
    for w in st["waves"]:
        w["bp"] = None


def check(st: dict, cell) -> list:
    isa, uarches = oracle.load_tables()
    rng = random.Random(f"bench-gt-sample/{cell.seed}")
    done = [w for w in st["waves"] if w["uarch"] in st["last"]]
    per = max(1, int(cell.params.get("sample", 300)) // len(done))
    measured_bad = predicted_bad = faults = checked = 0
    for w in done:
        name = w["uarch"]
        measured, predicted, machine, pstats = st["last"][name]
        ua = uarches[name]
        ref = oracle.Oracle(ua, isa)
        model = models.model_data(isa, ua)
        for i in rng.sample(range(len(w["blocks"])), per):
            b = list(w["blocks"][i])
            small, _ = ref.run(b * N_SMALL)
            large, _ = ref.run(b * N_LARGE)
            if measured[i] != (large - small) / (N_LARGE - N_SMALL):
                measured_bad += 1
            want = refpred.predict(model, isa, b)
            p = predicted[i]
            got = {"cycles": p.cycles, "port_bound": p.port_bound,
                   "latency_bound": p.latency_bound,
                   "frontend_bound": p.frontend_bound,
                   "bottleneck": p.bottleneck}
            if got != want:
                predicted_bad += 1
            checked += 1
        batch = machine.batch_machine()
        if not batch or batch.backend != "jax" or \
                machine.device_stats().get("kernel_calls", 0) == 0:
            faults += 1
        faults += sum(machine.degraded_stats().values())
        if pstats["backend"] != "jax" or pstats["device_waves"] == 0:
            faults += 1
        faults += pstats["device_fallbacks"]
    st["check_notes"] = {"blocks_checked": checked}
    return [("measured_mismatch", measured_bad, 0),
            ("predicted_mismatch", predicted_bad, 0),
            ("device_path_faults", faults, 0)]
