"""Back-to-back cold campaigns over a paper-scale generated ISA.

The configuration names the program's generator, its seed, the variant
count and the port layout; set-up builds that ISA and its one Skylake-like
machine.  Each campaign builds a fresh machine (so its measurement engine
and lowering cache start empty), runs ``Campaign.run`` over every variant
in a seed-shuffled order on one worker thread, and exports the model with
``model_io.to_xml``.  Before each campaign the one before it lets go of
its machine (unless it is the traced part) and the garbage is collected,
so every campaign starts on the same heap and meets the same collections;
the collection's time counts in the window.  Set-up runs one campaign, which compiles or loads
every kernel shape the window's campaigns use.  The window runs campaigns
until ``seconds`` have passed and finishes the one in flight;
``campaign_s`` is the window over the campaigns it completed.  In a traced
run the first campaign is the traced part.

``check`` makes the six comparisons of ``traffic/campaign.py`` with the
plain references, over every exported instruction, against the
configuration's ``tables`` (the generated ISA and hidden tables, dumped
once from the program's generator): port usage and μop counts, latency
edges, throughputs, blocking instructions, a seeded sample of the engine's
answers re-run on the reference core, and device-path faults.  A program
without the generator fails in set-up.
"""
from __future__ import annotations

import gc
import random
import time

from reference import characterize, models, oracle
from traffic.campaign import (_exported, _fmt, _layer_campaigns,
                              _recorder_class, _replayed, _sample)
from traffic.campaign import release  # noqa: F401  (keeps the last campaign)


def setup(cell) -> dict:
    from repro.core.uarch import generate_isa_and_uarch  # noqa: PLC0415
    cfg = cell.config
    isa, ua = generate_isa_and_uarch(cfg["isa_seed"], cfg["isa_variants"],
                                     cfg["layout"])
    names = list(cell.params.get("instructions") or isa.names())
    random.Random(f"bench-campaign-paper/{cell.seed}").shuffle(names)
    st = {"isa": isa, "uarch": ua, "names": names,
          "tables": cell.bench / cfg["tables"],
          "Recorder": _recorder_class(), "done": []}
    with cell.spans.span("bench.warmup"):
        _campaign(st, cell)
    st["done"].clear()
    return st


def _campaign(st: dict, cell) -> None:
    from repro.core import model_io  # noqa: PLC0415
    from repro.core.engine import Campaign  # noqa: PLC0415
    isa = st["isa"]
    for d in st["done"][-1:]:
        if not d.get("traced"):   # only the last and the traced are read
            d["machines"] = d["xml"] = None
    with cell.spans.span("bench.gc"):
        gc.collect()    # every campaign starts on the same heap
    with cell.spans.span("bench.machines"):
        machine = st["Recorder"](st["uarch"], isa)
    with cell.spans.span("bench.campaign"):
        res = Campaign(instr_names=st["names"]).run([machine], isa)
    with cell.spans.span("bench.export"):
        xml = {machine.name: model_io.to_xml(res.models[machine.name], isa)}
    st["done"].append({"result": res, "xml": xml, "machines": [machine]})


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
    layer = _layer_campaigns(st)
    return {
        "e2e": {"campaign_s": wall / len(done)},
        "attempted": len(done),
        "failed": sum(1 for d in done if d["result"].quarantine),
        "counters": {
            "campaigns": len(layer),
            "mean_wave_width": sum(d["result"].mean_wave_width
                                   for d in layer) / len(layer),
            "engine_hit_rate": sum(d["result"].hit_rate
                                   for d in layer) / len(layer),
        },
        "notes": {"campaigns": len(done), "campaign_walls_s": [
            d["result"].wall_seconds for d in done]},
    }


def layer_inputs(st: dict, cell) -> dict:
    """The problem the wave kernel solved in the traced campaign: lanes,
    μops and operand reads of every sequence in a wave that reached the
    batched backend, counted from the configuration's tables."""
    _, uarches = oracle.load_tables(st["tables"])
    lanes = uops = reads = ports = 0
    for d in _layer_campaigns(st):
        for m in d["machines"]:
            ua = uarches[m.name]
            ports = max(ports, len(ua.ports))
            cnt = {name: (len(b.uops), sum(len(u.reads) for u in b.uops))
                   for name, b in ua.behaviors.items()}
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


def check(st: dict, cell) -> list:
    isa, uarches = oracle.load_tables(st["tables"])
    last = st["done"][-1]
    names = set(st["names"])
    refs = {n: oracle.Oracle(uarches[n], isa) for n in uarches}
    ports_bad = lat_bad = tp_bad = block_bad = 0
    edges = exported = 0
    for ua_name, xml_text in last["xml"].items():
        ua = uarches.get(ua_name)
        got, blocking = _exported(xml_text)
        exported += len(got)
        if ua is None:                  # a machine the tables lack
            ports_bad += len(got)
            continue
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
    rng = random.Random(f"bench-campaign-paper-sample/{cell.seed}")
    picked = _sample(last["machines"], rng,
                     int(cell.params.get("sample_per_wave", 2)),
                     int(cell.params.get("sample", 600)))
    answers_bad = 0
    for ua_name, exp, got in picked:
        if ua_name not in refs:
            answers_bad += 1
            continue
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
                         "instructions_exported": exported}
    return [("ports_mismatch", ports_bad, 0),
            ("latency_mismatch", lat_bad, 0),
            ("throughput_mismatch", tp_bad, 0),
            ("blocking_mismatch", block_bad, 0),
            ("answers_mismatch", answers_bad, 0),
            ("device_path_faults", faults, 0)]
