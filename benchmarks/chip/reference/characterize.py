"""Plain reference of the latencies and throughputs a campaign exports.

The paper's measurements (§5.2 latency per operand pair, §5.3.1 measured
throughput), built again as instruction sequences and run on the reference
core (:mod:`oracle`) with Algorithm 2's differencing: each sequence runs
with 10 and with 110 copies, and the difference over 100 is one copy's
cycles.  It imports nothing of the program.

Latency, per (source, destination) pair of a characterized variant:

* register to register of one type: a cyclic chain closed by a MOVSX
  (general purpose) or by each of PSHUFD and MOVSHDUP (vector), less the
  chain instruction's own latency, the least over the chains; when two
  explicit operands share the type, also the loop with both in one
  register (``same_reg``);
* flags to a register: TEST closes the loop; a register to flags: SETC and
  a MOVSX close it; flags to flags: the variant alone;
* memory to a register: the double-XOR address trick; to a vector, an
  upper bound through each vector-to-GPR mover;
* a register to memory: a store-load round trip;
* across register types: an upper bound through each mover.

Dependencies not under test are cut with TEST on a spare register (flags)
and a register overwrite.  For a divider each exact pair is run again with
high operand values (``high``).  Measured throughput is the least cycles
per instance over 1, 2, 4 and 8 independent instances (fresh registers,
round-robin over 16 per type); a divider also with high values.
``tp_ports`` is :func:`predictor.port_bound` of the stated port usage.
"""
from __future__ import annotations

import itertools

from .models import stated
from .oracle import FLAGS, GPR, IMM, MEM, VEC
from .predictor import port_bound

N_SMALL, N_LARGE = 10, 110
SEQ_LENS = (1, 2, 4, 8)
CHAIN_GPR = ("R24", "R25")
CHAIN_VEC = ("X24", "X25")
BREAK_GPR = "R26"
AUX_GPR = ("R27", "R28")
AUX_VEC = ("X27", "X28")
MEM_BASE = "R23"


def _ins(name, regs, hint="low"):
    return (name, dict(regs), hint)


class Reference:
    """The exported numbers of one uarch, from the reference core."""

    def __init__(self, orc, isa: dict):
        self.orc, self.isa = orc, isa
        self.movsx = self.cycles([
            _ins("MOVSX_R64_R32", {"op1": "R24", "op2": "R25"}),
            _ins("MOVSX_R64_R32", {"op1": "R25", "op2": "R24"})]) / 2
        self.vec_chains = {
            nm: self.cycles([_ins(nm, {"op1": "X24", "op2": "X25"}),
                             _ins(nm, {"op1": "X25", "op2": "X24"})]) / 2
            for nm in ("PSHUFD_X_X", "MOVSHDUP_X_X") if nm in isa}
        self.xor = (self.cycles([_ins("XOR_R64_R64", {"op1": "R24",
                                                      "op2": AUX_GPR[0]})])
                    if "XOR_R64_R64" in isa else 1.0)
        self.setc_mv = ("MOVSX_R64_R8" if "MOVSX_R64_R8" in isa
                        else "MOVSX_R64_R32")
        self.setc = 1.0
        if "TEST_R64_R64" in isa and "SETC_R8" in isa:
            cyc = self.cycles([
                _ins("TEST_R64_R64", {"op1": "R24", "op2": "R24"}),
                _ins("SETC_R8", {"op1": "R25"}),
                _ins(self.setc_mv, {"op1": "R24", "op2": "R25"})])
            self.setc = max(cyc - 1.0 - self.movsx, 0.0)
        self.movers = {"to_gpr": [], "to_vec": []}
        for s in isa.values():
            ops = s.explicit
            if len(ops) != 2 or any(o.otype == IMM for o in ops):
                continue
            d, src = ops
            if d.written and not d.read and src.read:
                if d.otype == GPR and src.otype == VEC:
                    self.movers["to_gpr"].append(s.name)
                elif d.otype == VEC and src.otype == GPR:
                    self.movers["to_vec"].append(s.name)

    def cycles(self, seq) -> float:
        c1, _ = self.orc.run(seq * N_SMALL)
        c2, _ = self.orc.run(seq * N_LARGE)
        return (c2 - c1) / (N_LARGE - N_SMALL)

    # -- sequence pieces -----------------------------------------------------
    @staticmethod
    def _flags_break():
        return _ins("TEST_R64_R64", {"op1": BREAK_GPR, "op2": BREAK_GPR})

    @staticmethod
    def _reg_break(reg, otype):
        if otype == GPR:
            return _ins("MOV_R64_R64", {"op1": reg, "op2": BREAK_GPR})
        return _ins("PCMPGTQ_X_X", {"op1": reg, "op2": reg})

    def _breakers(self, spec, skip):
        return [self._flags_break() for o in spec.operands
                if o.name not in skip and o.read and o.written
                and o.otype == FLAGS]

    @staticmethod
    def _assign(spec, fixed):
        regs = dict(fixed)
        gi = vi = 0
        for o in spec.explicit:
            if o.name in regs or o.otype == IMM:
                continue
            if o.otype == GPR:
                regs[o.name] = AUX_GPR[gi % 2]
                gi += 1
            elif o.otype == VEC:
                regs[o.name] = AUX_VEC[vi % 2]
                vi += 1
            elif o.otype == MEM:
                regs[o.name] = MEM_BASE
        return regs

    # -- latency ---------------------------------------------------------------
    def latencies(self, spec) -> dict:
        """(src, dst) -> ``(kind, cycles, same_reg, high)``."""
        out = {}
        for s in spec.sources:
            if s.otype == IMM:
                continue
            for d in spec.dests:
                e = self._pair(spec, s, d, "low")
                if e is None:
                    continue
                high = None
                if spec.uses_divider and e[0] == "exact":
                    eh = self._pair(spec, s, d, "high")
                    high = eh[1] if eh is not None else None
                out[(s.name, d.name)] = e + (high,)
        return out

    def _pair(self, spec, s, d, hint):
        if s.otype == FLAGS and d.otype == FLAGS:
            return ("exact", self.cycles([_ins(spec.name,
                                               self._assign(spec, {}))]),
                    None)
        if s.otype == FLAGS:
            if d.otype != GPR:
                return None
            seq = [_ins("TEST_R64_R64", {"op1": "R24", "op2": "R24"})]
            if d.read:
                seq.append(self._reg_break("R24", GPR))
            seq.append(_ins(spec.name, self._assign(spec, {d.name: "R24"})))
            return ("exact", self.cycles(seq) - 1.0, None)
        if d.otype == FLAGS:
            if s.otype != GPR:
                return None
            seq = self._breakers(spec, {s.name, d.name}) + [
                _ins(spec.name, self._assign(spec, {s.name: "R24"})),
                _ins("SETC_R8", {"op1": "R25"}),
                _ins(self.setc_mv, {"op1": "R24", "op2": "R25"})]
            return ("exact", self.cycles(seq) - self.setc - self.movsx, None)
        if s.otype == MEM:
            return self._mem_to_reg(spec, s, d)
        if d.otype == MEM:
            return self._reg_to_mem(spec, s, d)
        if s.otype == d.otype:
            return self._reg_reg(spec, s, d, hint)
        return self._cross(spec, s, d)

    def _reg_reg(self, spec, s, d, hint):
        ca, cb = CHAIN_GPR if s.otype == GPR else CHAIN_VEC
        chains = ({"MOVSX_R64_R32": self.movsx} if s.otype == GPR
                  else self.vec_chains)
        per = []
        for cname, clat in chains.items():
            if s.name == d.name:
                seq = self._breakers(spec, {s.name}) + [
                    _ins(spec.name, self._assign(spec, {s.name: ca}), hint)]
                per.append(self.cycles(seq))
                continue
            seq = self._breakers(spec, {s.name, d.name})
            if d.read:
                seq.append(self._reg_break(cb, s.otype))
            seq += [_ins(spec.name, self._assign(spec, {s.name: ca,
                                                        d.name: cb}), hint),
                    _ins(cname, {"op1": ca, "op2": cb})]
            per.append(self.cycles(seq) - clat)
        same = None
        if s.name != d.name and sum(1 for o in spec.explicit
                                    if o.otype == s.otype) >= 2:
            seq = self._breakers(spec, {s.name, d.name}) + [
                _ins(spec.name, self._assign(spec, {s.name: ca, d.name: ca}),
                     hint)]
            same = self.cycles(seq)
        return ("exact", min(per), same)

    def _mem_to_reg(self, spec, s, d):
        regs = self._assign(spec, {s.name: MEM_BASE})
        if d.otype == VEC:
            per = []
            for mv in self.movers["to_gpr"]:
                seq = [self._reg_break(regs[d.name], VEC)] if d.read else []
                seq += [_ins(spec.name, regs),
                        _ins(mv, {"op1": "R24", "op2": regs[d.name]}),
                        _ins("XOR_R64_R64", {"op1": MEM_BASE, "op2": "R24"}),
                        _ins("XOR_R64_R64", {"op1": MEM_BASE, "op2": "R24"}),
                        self._flags_break()]
                per.append(self.cycles(seq) - 2 * self.xor)
            return ("upper_bound", max(min(per) - 1, 0), None)
        rd = regs.get(d.name)
        seq = self._breakers(spec, {s.name, d.name}) + [
            _ins(spec.name, regs),
            _ins("XOR_R64_R64", {"op1": MEM_BASE, "op2": rd}),
            _ins("XOR_R64_R64", {"op1": MEM_BASE, "op2": rd}),
            self._flags_break()]
        return ("exact", self.cycles(seq) - 2 * self.xor, None)

    def _reg_to_mem(self, spec, s, d):
        if s.otype == VEC:
            if "MOVAPS_X_M" not in self.isa:
                return None
            load, (ca, cb) = "MOVAPS_X_M", CHAIN_VEC
            chain = next(iter(self.vec_chains), None)
            clat = self.vec_chains.get(chain, 1.0)
        else:
            load, (ca, cb) = "MOV_R64_M64", CHAIN_GPR
            chain, clat = "MOVSX_R64_R32", self.movsx
        seq = [_ins(spec.name, self._assign(spec, {s.name: ca,
                                                   d.name: MEM_BASE})),
               _ins(load, {"op1": cb, "mem": MEM_BASE})]
        if chain:
            seq.append(_ins(chain, {"op1": ca, "op2": cb}))
        return ("roundtrip", self.cycles(seq) - clat, None)

    def _cross(self, spec, s, d):
        if d.otype == VEC and s.otype == GPR:
            movers, rs, rd = self.movers["to_gpr"], "R24", "X24"
        elif d.otype == GPR and s.otype == VEC:
            movers, rs, rd = self.movers["to_vec"], "X24", "R24"
        else:
            return None
        per = []
        for mv in movers:
            seq = self._breakers(spec, {s.name, d.name})
            if d.read:
                seq.append(self._reg_break(rd, d.otype))
            seq += [_ins(spec.name, self._assign(spec, {s.name: rs,
                                                        d.name: rd})),
                    _ins(mv, {"op1": rs, "op2": rd})]
            per.append(self.cycles(seq))
        if not per:
            return None
        return ("upper_bound", max(min(per) - 1, 0), None)

    # -- throughput ------------------------------------------------------------
    def _independent(self, spec, n, hint):
        pools = {GPR: [f"R{i}" for i in range(16)],
                 VEC: [f"X{i}" for i in range(16)],
                 MEM: [f"RB{i}" for i in range(8)], FLAGS: ["FLAGS"]}
        its = {}
        seq = []
        for _ in range(n):
            regs = {}
            for o in spec.explicit:
                if o.otype == IMM:
                    continue
                names = pools.get(o.otype, ["IMM"])
                it = its.setdefault(o.otype, itertools.cycle(names))
                r = next(it)
                while r in regs.values():
                    r = next(it)
                regs[o.name] = r
            seq.append(_ins(spec.name, regs, hint))
        return seq

    def throughput(self, spec, behavior) -> tuple:
        """``(tp_measured, tp_ports, tp_high)``, None where not exported."""
        measured = min(self.cycles(self._independent(spec, n, "low")) / n
                       for n in SEQ_LENS)
        high = (min(self.cycles(self._independent(spec, n, "high")) / n
                    for n in SEQ_LENS) if spec.uses_divider else None)
        usage, _ = stated(spec, behavior)
        ports = (None if spec.uses_divider or not usage
                 else port_bound(usage))
        return measured, ports, high
