"""Plain reference of the block throughput predictor.

The semantics of ``core/predictor.py`` ``predict`` (commit 4c81949),
written again over :func:`models.model_data` so that it imports nothing of
the program: cycles per iteration are the largest of

* the port bound — the least makespan of the block's μops over their
  allowed ports, by Hall's condition the largest ``demand(S) / |S|`` over
  port sets ``S``, where ``demand(S)`` counts the μops that can only run
  inside ``S`` (every union of the block's port combinations is tried);
* the latency bound — the loop-carried dependency length per iteration,
  iterating the block's dataflow 24 times over the model's per-operand-pair
  latencies and taking the last increment;
* the front-end bound — μops over the issue width (4).

``dtype`` is the arithmetic: Python floats (float64), or ``numpy.float32``
for the control in ``tests/test_control.py``.
"""
from __future__ import annotations

from .oracle import FLAGS, IMM, MEM

ISSUE_WIDTH = 4
ITERS = 24


def port_bound(usage: dict, dtype=float) -> float:
    combos = [(frozenset(pc), n) for pc, n in usage.items() if n > 0]
    if not combos:
        return dtype(0.0)
    unions: set = set()
    for pc, _ in combos:
        unions |= {pc} | {pc | s for s in unions}
    best = dtype(0.0)
    for s in unions:
        demand = dtype(sum(n for pc, n in combos if pc <= s))
        best = max(best, dtype(demand / dtype(len(s))))
    return best


def _latency_bound(model: dict, isa: dict, code, dtype) -> float:
    t: dict = {}
    prev = dtype(0.0)
    inc = dtype(0.0)
    for _ in range(ITERS):
        for spec_name, ins_regs, _hint in code:
            spec = isa[spec_name]
            lat_map = model[spec_name]["latency"]
            regs = dict(ins_regs)
            for o in spec.operands:
                regs.setdefault(o.name, "FLAGS" if o.otype == FLAGS
                                else f"IMPL_{o.name}")
            ex = [regs[o.name] for o in spec.explicit
                  if o.otype not in (IMM, MEM, FLAGS)]
            idiom = (len(ex) >= 2 and len(set(ex)) == 1
                     and any(same is not None and same < 0.5
                             for _, same in lat_map.values()))
            for d in spec.dests:
                ready = dtype(0.0)
                for s in () if idiom else spec.sources:
                    if s.otype == IMM or (s.name, d.name) not in lat_map:
                        continue
                    cyc, same = lat_map[(s.name, d.name)]
                    if (same is not None and s.name != d.name
                            and regs.get(s.name) == regs.get(d.name)):
                        if same < 0.5:
                            continue
                        lat = dtype(same)
                    else:
                        lat = dtype(cyc)
                    key = ("MEM_" + regs[s.name] if s.otype == MEM
                           else regs[s.name])
                    ready = max(ready, dtype(t.get(key, dtype(0.0)) + lat))
                key = ("MEM_" + regs[d.name] if d.otype == MEM
                       else regs[d.name])
                t[key] = ready
        cur = max(t.values(), default=dtype(0.0))
        inc = dtype(cur - prev)
        prev = cur
    return inc


def predict(model: dict, isa: dict, code, dtype=float) -> dict:
    """Prediction for one block ``code`` of ``(spec, regs, hint)``."""
    usage: dict = {}
    uops = dtype(0.0)
    for spec_name, _regs, _hint in code:
        rec = model[spec_name]
        uops = dtype(uops + dtype(rec["uops"]))
        for pc, n in rec["usage"].items():
            usage[pc] = usage.get(pc, 0) + n
    pb = port_bound(usage, dtype)
    fe = dtype(uops / dtype(ISSUE_WIDTH))
    lat = _latency_bound(model, isa, code, dtype)
    cycles = max(pb, lat, fe)
    if pb >= cycles - 1e-9:
        bottleneck = "ports"
    elif lat >= cycles - 1e-9:
        bottleneck = "latency"
    else:
        bottleneck = "frontend"
    return {"cycles": float(cycles), "port_bound": float(pb),
            "latency_bound": float(lat), "frontend_bound": float(fe),
            "bottleneck": bottleneck}
