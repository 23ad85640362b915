"""Plain reference of the simulated core: one μop per Python step.

A copy of the scalar oracle ``SimMachine.run`` and ``_exec_uops`` (from
``src/repro/core/simulator.py`` at commit 4c81949), rewritten over the JSON
tables beside this file so that it imports nothing of the program.  It
gives, for one instruction sequence, the elapsed cycles (with the constant
measurement-harness overhead) and the μops executed on each port.

An instruction is ``(spec, regs, hint)``: the variant name, a dict from
operand name to architectural register, and ``"low"``/``"high"`` (divider
operand class).  ``tie_break="least_load"`` is the configuration's port
choice; ``"lowest_port"`` drops the least-load tie-break and is the control
that ``tests/test_control.py`` shows the comparison rejects.
"""
from __future__ import annotations

import json
from pathlib import Path

TABLES = Path(__file__).with_name("sim3_tables.json")

GPR, VEC, FLAGS, MEM, IMM = "gpr", "vec", "flags", "mem", "imm"


class Operand:
    __slots__ = ("name", "otype", "read", "written", "implicit", "width")

    def __init__(self, name, otype, mode, implicit, width):
        self.name, self.otype = name, otype
        self.read, self.written = "r" in mode, "w" in mode
        self.implicit, self.width = bool(implicit), width


class Spec:
    def __init__(self, name: str, rec: dict):
        self.name = name
        self.mnemonic = rec["mnemonic"]
        self.extension = rec["extension"]
        self.operands = tuple(Operand(*o) for o in rec["operands"])
        for flag in ("uses_divider", "serializing", "system", "control_flow",
                     "may_eliminate", "zero_idiom", "is_nop"):
            setattr(self, flag, bool(rec.get(flag)))
        self.sources = tuple(o for o in self.operands if o.read)
        self.dests = tuple(o for o in self.operands if o.written)
        self.explicit = tuple(o for o in self.operands if not o.implicit)

    @property
    def characterized(self) -> bool:
        """The paper's tool leaves out NOPs, serializing, system and
        control-flow instructions (§8)."""
        return not (self.is_nop or self.serializing or self.system
                    or self.control_flow)


class Uop:
    __slots__ = ("ports", "reads", "writes", "latency", "occupancy")

    def __init__(self, ports, reads, writes, latency, occupancy):
        self.ports = tuple(sorted(ports))
        self.reads, self.writes = tuple(reads), tuple(writes)
        self.latency, self.occupancy = latency, occupancy


class Behavior:
    def __init__(self, rec: dict):
        self.uops = tuple(Uop(*u) for u in rec["uops"])
        self.same_reg = Behavior(rec["same_reg"]) if rec["same_reg"] else None
        self.elim_period = rec["elim_period"]
        self.zero_uop_same_reg = rec["zero_uop_same_reg"]
        self.divider_extra = rec["divider_extra"]


class UArch:
    def __init__(self, name: str, rec: dict):
        self.name = name
        self.ports = tuple(rec["ports"])
        self.issue_width = rec["issue_width"]
        self.store_forward_latency = rec["store_forward_latency"]
        self.overhead_cycles = rec["overhead_cycles"]
        self.partial_stall_penalty = rec["partial_stall_penalty"]
        self.behaviors = {k: Behavior(v) for k, v in rec["behaviors"].items()}


def load_tables(path=TABLES) -> tuple[dict, dict]:
    """``(isa, uarches)``: name -> :class:`Spec`, name -> :class:`UArch`."""
    data = json.loads(Path(path).read_text())
    isa = {n: Spec(n, r) for n, r in data["isa"].items()}
    uarches = {n: UArch(n, r) for n, r in data["uarches"].items()}
    return isa, uarches


def _implicit_reg(opname: str, otype: str) -> str:
    if otype == FLAGS:
        return "FLAGS"
    return {"hi": "RDX", "op1": "RAX"}.get(opname, "R_IMPL_" + opname)


def _same_reg(spec: Spec, regs: dict) -> bool:
    ex = [o for o in spec.explicit if o.otype not in (IMM, MEM, FLAGS)]
    return len(ex) >= 2 and len({regs[o.name] for o in ex}) == 1


class Oracle:
    """Runs sequences on one simulated core of the tables."""

    def __init__(self, uarch: UArch, isa: dict,
                 tie_break: str = "least_load"):
        if tie_break not in ("least_load", "lowest_port"):
            raise ValueError(f"unknown tie_break {tie_break!r}")
        self.ua, self.isa, self.tie_break = uarch, isa, tie_break

    def run(self, code) -> tuple[float, dict]:
        """``(cycles, {port: μops})`` for one execution of ``code``."""
        ua = self.ua
        st = _State(ua)
        uop_index = 0
        t_end = 0.0
        elim_counter: dict = {}
        for spec_name, ins_regs, hint in code:
            spec = self.isa[spec_name]
            behavior = ua.behaviors[spec_name]
            regs = dict(ins_regs)
            for o in spec.operands:
                if o.name not in regs and o.otype != IMM:
                    regs[o.name] = _implicit_reg(o.name, o.otype)
            same = _same_reg(spec, regs)
            if behavior.same_reg is not None and same:
                behavior = behavior.same_reg
            if spec.zero_idiom and same:
                if behavior.zero_uop_same_reg:
                    for o in spec.dests:
                        st.reg_ready[regs[o.name]] = 0.0
                    continue
                self._exec(behavior.uops, regs, spec, st, uop_index,
                           ignore_reads=True)
                uop_index += len(behavior.uops)
                continue
            if spec.may_eliminate and behavior.elim_period:
                c = elim_counter.get(spec_name, 0)
                elim_counter[spec_name] = c + 1
                if c % behavior.elim_period != 0:
                    src = next(o for o in spec.sources if o.otype != IMM)
                    st.reg_ready[regs[spec.dests[0].name]] = \
                        st.reg_ready.get(regs[src.name], 0.0)
                    continue
            extra = behavior.divider_extra if hint == "high" else 0
            done = self._exec(behavior.uops, regs, spec, st, uop_index,
                              divider_extra=extra)
            uop_index += len(behavior.uops)
            t_end = max(t_end, done)
        t_end = max([t_end] + list(st.reg_ready.values())
                    + list(st.mem_ready.values()))
        return t_end + ua.overhead_cycles, st.port_count

    def _exec(self, uops, regs, spec, st, uop_index, ignore_reads=False,
              divider_extra=0):
        ua = self.ua
        tmp_ready: dict = {}
        done_max = 0.0
        mem_ops = {o.name: o for o in spec.operands if o.otype == MEM}
        # every μop reads the source values from before this instruction;
        # a read wider than the register's last partial write stalls
        snap = {}
        for o in spec.operands:
            if o.otype == MEM:
                continue
            r = regs.get(o.name, o.name)
            t = st.reg_ready.get(r, 0.0)
            if o.read and o.otype == GPR and o.width > st.reg_width.get(r, 64):
                t += ua.partial_stall_penalty
            snap[o.name] = t
        for u in uops:
            ready = float(uop_index // ua.issue_width)
            if not ignore_reads:
                for r in u.reads:
                    if r.startswith("%"):
                        ready = max(ready, tmp_ready.get(r, 0.0))
                    elif r in mem_ops and mem_ops[r].read:
                        base = regs[r]
                        ready = max(ready, st.reg_ready.get(base, 0.0),
                                    st.mem_ready.get(base, 0.0))
                    elif r in snap:
                        ready = max(ready, snap[r])
                    else:
                        ready = max(ready, st.reg_ready.get(regs.get(r, r),
                                                            0.0))
            lat = u.latency + divider_extra
            occ = u.occupancy + divider_extra
            if any(r in mem_ops and mem_ops[r].read for r in u.reads):
                base = next(regs[r] for r in u.reads if r in mem_ops)
                if st.mem_stored.get(base):
                    lat = min(lat, ua.store_forward_latency)
            best_port, best_t = None, None
            for p in u.ports:
                t = max(ready, st.port_free[p])
                if best_t is None or t < best_t or (
                        self.tie_break == "least_load" and t == best_t
                        and st.port_count[p] < st.port_count[best_port]):
                    best_port, best_t = p, t
            if best_port is None:
                continue
            st.port_free[best_port] = best_t + (occ if occ > 1 else 1)
            st.port_count[best_port] += 1
            done = best_t + lat
            done_max = max(done_max, done)
            for w in u.writes:
                if w.startswith("%"):
                    tmp_ready[w] = done
                elif w in mem_ops:
                    st.mem_ready[regs[w]] = done
                    st.mem_stored[regs[w]] = True
                else:
                    rw = regs.get(w, w)
                    st.reg_ready[rw] = done
                    wop = next((o for o in spec.operands if o.name == w),
                               None)
                    if wop is not None:
                        st.reg_width[rw] = wop.width
            uop_index += 1
        return done_max


class _State:
    def __init__(self, ua: UArch):
        self.reg_ready: dict = {}
        self.reg_width: dict = {}
        self.mem_ready: dict = {}
        self.mem_stored: dict = {}
        self.port_free = {p: 0.0 for p in ua.ports}
        self.port_count = {p: 0 for p in ua.ports}
