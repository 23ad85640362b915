"""Performance models stated by the configuration's hidden tables.

Two uses, both from ``sim3_tables.json`` alone:

* :func:`expected_ports` — what a characterization campaign must recover
  for each instruction: its μop count and its port usage (§5.1), in the
  XML notation (``1*p0156+1*p06``).  Instructions the tool leaves out (§8)
  have no entry.  A move that the reorder buffer eliminates runs one μop
  in ``elim_period`` instances, so the campaign states that fraction of a
  μop, rounded to 0.01, and no port.
* :func:`model_xml` / :func:`model_data` — the uops.info-style model the
  ground-truth and serving cells load into the program: port usage and
  μop count as above, and per operand pair the latency along the μops'
  dataflow (``same_reg`` where the same-register behaviour differs, 0 for
  a dependency-breaking zero idiom).
"""
from __future__ import annotations

import xml.etree.ElementTree as ET
from xml.dom import minidom

from .oracle import FLAGS, IMM, MEM


def _usage(behavior) -> dict:
    usage: dict = {}
    for u in behavior.uops:
        pc = "".join(u.ports)
        usage[pc] = usage.get(pc, 0) + 1
    return usage


def notation(usage: dict) -> str:
    if not usage:
        return "0"
    return "+".join(f"{n}*p{pc}" for pc, n in sorted(usage.items(),
                                                      key=lambda kv: kv[0]))


def stated(spec, behavior) -> tuple[dict, float]:
    """``(port usage, μop count)`` that the model states for one variant."""
    if spec.may_eliminate and behavior.elim_period:
        return {}, round(len(behavior.uops) / behavior.elim_period, 2)
    return _usage(behavior), float(len(behavior.uops))


def expected_ports(isa, uarch) -> dict:
    """name -> ``(ports notation, μops)`` for every characterized variant."""
    out = {}
    for name, spec in isa.items():
        if name in uarch.behaviors and spec.characterized:
            usage, uops = stated(spec, uarch.behaviors[name])
            out[name] = (notation(usage), uops)
    return out


def _path_latency(behavior, src: str) -> dict:
    """dest operand -> longest μop path from operand ``src``."""
    avail = {src: 0}
    out: dict = {}
    for u in behavior.uops:
        ins = [avail[r] for r in u.reads if r in avail]
        if not ins:
            continue
        done = max(ins) + u.latency
        for w in u.writes:
            if not w.startswith("%"):
                out[w] = max(out.get(w, 0), done)
            avail[w] = done
    return out


def latencies(spec, behavior) -> dict:
    """(src, dst) -> ``(cycles, same_reg or None)``."""
    regs = [o.name for o in spec.explicit
            if o.otype not in (IMM, MEM, FLAGS)]
    out = {}
    for s in spec.sources:
        if s.otype == IMM:
            continue
        lat = _path_latency(behavior, s.name)
        alt = (_path_latency(behavior.same_reg, s.name)
               if behavior.same_reg else None)
        for d in spec.dests:
            if d.name not in lat:
                continue
            same = None
            if s.name != d.name and s.name in regs and d.name in regs:
                if spec.zero_idiom:
                    same = 0.0
                elif alt is not None and d.name in alt:
                    same = float(alt[d.name])
            out[(s.name, d.name)] = (float(lat[d.name]), same)
    return out


def model_data(isa, uarch) -> dict:
    """name -> ``{"usage", "uops", "latency"}`` for every characterized
    variant of ``uarch`` (the reference predictor's view of the model)."""
    out = {}
    for name, spec in isa.items():
        if name not in uarch.behaviors or not spec.characterized:
            continue
        b = uarch.behaviors[name]
        usage, uops = stated(spec, b)
        out[name] = {"usage": usage, "uops": uops,
                     "latency": latencies(spec, b)}
    return out


def _fmt(x) -> str:
    return f"{x:.6f}".rstrip("0").rstrip(".") if isinstance(x, float) \
        else str(x)


def model_xml(isa, uarch) -> str:
    """The model as uops.info-style XML (the program's registry reads it)."""
    from .predictor import port_bound  # noqa: PLC0415
    root = ET.Element("root")
    ET.SubElement(root, "architecture", name=uarch.name)
    for name, rec in sorted(model_data(isa, uarch).items()):
        spec = isa[name]
        el = ET.SubElement(root, "instruction", name=name,
                           mnemonic=spec.mnemonic, extension=spec.extension)
        for o in spec.operands:
            ET.SubElement(el, "operand", name=o.name, type=o.otype,
                          r=str(int(o.read)), w=str(int(o.written)),
                          implicit=str(int(o.implicit)), width=str(o.width))
        m = ET.SubElement(el, "measurement", arch=uarch.name,
                          uops=_fmt(rec["uops"]),
                          ports=notation(rec["usage"]),
                          tp_measured=_fmt(port_bound(rec["usage"])))
        for (s, d), (cyc, same) in sorted(rec["latency"].items()):
            le = ET.SubElement(m, "latency", src=s, dst=d, cycles=_fmt(cyc),
                               kind="exact")
            if same is not None:
                le.set("same_reg", _fmt(same))
    return minidom.parseString(ET.tostring(root)).toprettyxml(indent=" ")
