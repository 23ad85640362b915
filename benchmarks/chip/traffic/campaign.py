"""Back-to-back cold characterization campaigns.

Each campaign builds fresh ``SimMachine``s for the configuration's uarches
(so their measurement engines and lowering caches start empty), runs
``Campaign.run`` over the configuration's ISA and exports every model with
``model_io.to_xml``.  The seed orders the instructions handed to the
campaign (the same set for every seed).  Set-up runs one campaign, which
compiles or loads every kernel shape the window's campaigns use.  The window
runs campaigns until ``seconds`` have passed and finishes the one in
flight; ``campaign_s`` is the window over the campaigns it completed.  In
a traced run the first campaign is the traced part, and the per-layer
counters describe it.

``check`` compares the last campaign with the plain references, over
everything the campaign exports and the answers behind it:

* ``ports_mismatch``: exported instructions whose port usage or μop count
  differs from the tables (or that one side lacks);
* ``latency_mismatch``: exported latency edges (kind, cycles, same-register
  and high-value cycles) that differ from :mod:`reference.characterize`,
  which runs the paper's chains on the reference core, counting edges that
  one side lacks;
* ``throughput_mismatch``: instructions whose measured, port-computed or
  high-value throughput differs from the reference;
* ``blocking_mismatch``: blocking instructions for which the tables state
  no single μop on exactly their port set;
* ``answers_mismatch``: a seeded sample of the answers the measurement
  engine handed to the campaign (cache and dedup hits included), re-run on
  the reference core with Algorithm 2's differencing: at least
  ``sample_per_wave`` of every submitted wave, ``sample`` in all;
* ``device_path_faults``: machines whose waves did not run on the chip's
  kernel, degraded chunks and quarantined experiments.
"""
from __future__ import annotations

import random
import time
import xml.etree.ElementTree as ET

from reference import characterize, models, oracle


def _recorder_class():
    from repro.core.engine import MeasurementEngine  # noqa: PLC0415
    from repro.core.simulator import SimMachine  # noqa: PLC0415

    class Engine(MeasurementEngine):
        """The program's engine, keeping every wave it answers."""

        def submit(self, experiments, kernel_lock=None):
            experiments = list(experiments)
            out = super().submit(experiments, kernel_lock)
            self.machine.bench_answers.append((experiments, out))
            return out

    class Recorder(SimMachine):
        """The program's machine, keeping each kernel wave's sequences
        (the roofline's byte count) and each engine wave's answers (the
        check).  ``as_engine`` attaches the engine made here."""

        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            self.bench_waves: list = []
            self.bench_answers: list = []
            self._engine = Engine(self)

        def run_batch(self, codes, kernel_lock=None):
            codes = list(codes)
            out = super().run_batch(codes, kernel_lock=kernel_lock)
            self.bench_waves.append((codes, out))
            return out

    return Recorder


def setup(cell) -> dict:
    from repro.core.isa import TEST_ISA  # noqa: PLC0415
    cfg = cell.config
    if cfg["isa"] != "TEST_ISA":
        raise ValueError(f"unknown ISA {cfg['isa']!r}")
    names = list(cell.params.get("instructions") or TEST_ISA.names())
    random.Random(f"bench-campaign/{cell.seed}").shuffle(names)
    st = {"isa": TEST_ISA, "names": names, "uarches": list(cfg["uarches"]),
          "Recorder": _recorder_class(), "done": []}
    with cell.spans.span("bench.warmup"):
        _campaign(st, cell)
    st["done"].clear()
    return st


def _campaign(st: dict, cell) -> None:
    from repro.core import model_io  # noqa: PLC0415
    from repro.core.engine import Campaign  # noqa: PLC0415
    from repro.core.uarch import SIM_UARCHES  # noqa: PLC0415
    isa = st["isa"]
    with cell.spans.span("bench.machines"):
        machines = [st["Recorder"](SIM_UARCHES[u], isa)
                    for u in st["uarches"]]
    with cell.spans.span("bench.campaign"):
        res = Campaign(instr_names=st["names"]).run(machines, isa)
    with cell.spans.span("bench.export"):
        xml = {m.name: model_io.to_xml(res.models[m.name], isa)
               for m in machines}
    for d in st["done"][-1:]:
        if not d.get("traced"):   # only the last and the traced are read
            d["machines"] = d["xml"] = None
    st["done"].append({"result": res, "xml": xml, "machines": machines})


def window(st: dict, cell, seconds: float) -> dict:
    t0 = time.perf_counter()
    while True:
        with cell.traced() as traced:
            _campaign(st, cell)
        st["done"][-1]["traced"] = traced
        if time.perf_counter() - t0 >= seconds:
            break
    wall = time.perf_counter() - t0
    done = st["done"]
    n = len(done)
    layer = _layer_campaigns(st)
    return {
        "e2e": {"campaign_s": wall / n},
        "attempted": n,
        "failed": sum(1 for d in done if d["result"].quarantine),
        "counters": {
            "campaigns": len(layer),
            "mean_wave_width": sum(d["result"].mean_wave_width
                                   for d in layer) / len(layer),
            "engine_hit_rate": sum(d["result"].hit_rate
                                   for d in layer) / len(layer),
        },
        "notes": {"campaigns": n, "campaign_walls_s": [
            d["result"].wall_seconds for d in done]},
    }


def _layer_campaigns(st: dict) -> list:
    """The campaigns the per-layer metrics describe: the traced one in a
    traced run, else all."""
    traced = [d for d in st["done"] if d.get("traced")]
    return traced or st["done"]


def layer_inputs(st: dict, cell) -> dict:
    """The problem the wave kernel solved in the traced campaign: lanes,
    μops and operand reads of every sequence in a wave that reached the
    batched backend, counted from the configuration's tables."""
    isa, uarches = oracle.load_tables()
    per: dict = {}
    lanes = uops = reads = 0
    ports = 0
    for d in _layer_campaigns(st):
        for m in d["machines"]:
            ua = uarches[m.name]
            ports = max(ports, len(ua.ports))
            cnt = per.setdefault(m.name, {
                name: (len(b.uops), sum(len(u.reads) for u in b.uops))
                for name, b in ua.behaviors.items()})
            for codes, _ in m.bench_waves:
                if len(codes) < 4:      # the program runs these scalar
                    continue
                lanes += len(codes)
                for code in codes:
                    for ins in code:
                        u, r = cnt[ins.spec]
                        uops += u
                        reads += r
    return {"wave_lanes": lanes, "wave_uops": uops, "wave_reads": reads,
            "wave_ports": ports}


def release(st: dict) -> None:
    """Keep the last campaign's records for the check; drop the rest."""
    st["done"][:-1] = []


def _fmt(x) -> str | None:
    """A number as the XML writes it (six decimals, trailing zeros cut)."""
    if x is None:
        return None
    return f"{float(x):.6f}".rstrip("0").rstrip(".")


def _exported(xml_text: str) -> tuple[dict, dict]:
    """``(instructions, blocking)`` of one exported model."""
    root = ET.fromstring(xml_text)
    out = {}
    for el in root.findall("instruction"):
        m = el.find("measurement")
        lat = {(le.get("src"), le.get("dst")): (
            le.get("kind"), le.get("cycles"), le.get("same_reg"),
            le.get("high")) for le in m.findall("latency")}
        out[el.get("name")] = {
            "ports": (m.get("ports"), float(m.get("uops"))),
            "latency": lat,
            "throughput": (m.get("tp_measured"), m.get("tp_ports"),
                           m.get("tp_high"))}
    blocking = {b.get("ports"): b.get("instr")
                for b in root.iter("blocking")}
    return out, blocking


def _sample(machines, rng, per_wave: int, total: int) -> list:
    picked, rest = [], []
    for m in machines:
        for exps, out in m.bench_answers:
            idx = list(range(len(exps)))
            rng.shuffle(idx)
            picked += [(m.name, exps[i], out[i]) for i in idx[:per_wave]]
            rest += [(m.name, exps[i], out[i]) for i in idx[per_wave:]]
    return picked + rng.sample(rest, max(0, min(len(rest),
                                                total - len(picked))))


def _replayed(orc, exp) -> tuple[float, dict]:
    """One experiment on the reference core, differenced as Algorithm 2."""
    code = [(i.spec, i.regs, i.value_hint) for i in exp.code]
    c1, p1 = orc.run(code * exp.n_small)
    c2, p2 = orc.run(code * exp.n_large)
    d = exp.n_large - exp.n_small
    return (c2 - c1) / d, {p: (p2.get(p, 0) - p1.get(p, 0)) / d
                           for p in set(p1) | set(p2)}


def check(st: dict, cell) -> list:
    isa, uarches = oracle.load_tables()
    last = st["done"][-1]
    names = set(st["names"])
    refs = {n: oracle.Oracle(uarches[n], isa) for n in uarches}
    ports_bad = lat_bad = tp_bad = block_bad = 0
    edges = 0
    for ua_name, xml_text in last["xml"].items():
        ua = uarches[ua_name]
        got, blocking = _exported(xml_text)
        want = {n: v for n, v in models.expected_ports(isa, ua).items()
                if n in names}
        ports_bad += sum(1 for n in set(want) | set(got)
                         if want.get(n) != (got[n]["ports"] if n in got
                                            else None))
        ref = characterize.Reference(refs[ua_name], isa)
        for n in sorted(set(want) & set(got)):
            lat = {k: (kind, _fmt(c), _fmt(same), _fmt(high))
                   for k, (kind, c, same, high)
                   in ref.latencies(isa[n]).items()}
            edges += len(set(lat) | set(got[n]["latency"]))
            lat_bad += sum(1 for k in set(lat) | set(got[n]["latency"])
                           if lat.get(k) != got[n]["latency"].get(k))
            tp = tuple(_fmt(v) for v in ref.throughput(isa[n],
                                                       ua.behaviors[n]))
            tp_bad += tp != got[n]["throughput"]
        for pc, instr in blocking.items():
            b = ua.behaviors.get(instr)
            usage = models.stated(isa[instr], b)[0] if b else None
            block_bad += (usage or {}).get(pc.removeprefix("p")) != 1
    rng = random.Random(f"bench-campaign-sample/{cell.seed}")
    picked = _sample(last["machines"], rng,
                     int(cell.params.get("sample_per_wave", 2)),
                     int(cell.params.get("sample", 600)))
    answers_bad = 0
    for ua_name, exp, got in picked:
        cyc, ports = _replayed(refs[ua_name], exp)
        answers_bad += got.cycles != cyc or dict(got.port_uops) != ports
    faults = 0
    for m in last["machines"]:
        batch = m.batch_machine()
        if not batch or batch.backend != "jax" or \
                m.device_stats().get("kernel_calls", 0) == 0:
            faults += 1
        faults += sum(m.degraded_stats().values())
    faults += last["result"].quarantined
    st["check_notes"] = {"answers_checked": len(picked),
                         "latency_edges_checked": edges,
                         "instructions_exported": sum(
                             len(_exported(x)[0])
                             for x in last["xml"].values())}
    return [("ports_mismatch", ports_bad, 0),
            ("latency_mismatch", lat_bad, 0),
            ("throughput_mismatch", tp_bad, 0),
            ("blocking_mismatch", block_bad, 0),
            ("answers_mismatch", answers_bad, 0),
            ("device_path_faults", faults, 0)]
