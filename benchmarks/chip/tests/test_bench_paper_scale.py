"""The paper-scale campaign cell: found by name, its reference tables equal
to the program's generator at the configuration's seed, its two per-layer
readers, and its comparison rejecting a planted fault.

To write the reference tables again (only if the generator changes on
purpose, which also changes what the cell measures):

    PYTHONPATH=src:benchmarks/chip:benchmarks/chip/tests python3 -c \
        "import test_bench_paper_scale as t; t.write_tables()"
"""
import json
import shutil

import pytest

import run_cell
from reference import oracle
from test_bench_correct import _run, cpu_as_tpu  # noqa: F401

CELL = "campaign.paper_scale"
SPEC_FLAGS = ("uses_divider", "serializing", "system", "control_flow",
              "may_eliminate", "zero_idiom", "is_nop")
ABOUT = ("Hidden ground truth of one generated Skylake-like core and its "
         "generated ISA, dumped from the program's "
         "core/uarch.generate_isa_and_uarch at the configuration's isa_seed, "
         "isa_variants and layout, in sim3_tables.json's schema. Operand "
         "mode is r, w or rw; a uop is [ports, reads, writes, latency, "
         "occupancy].")


def _config():
    return json.loads((run_cell.BENCH / "configs" / "skl_paper_isa.json")
                      .read_text())


def _behavior(b):
    if b is None:
        return None
    return {"uops": [["".join(sorted(u.ports)), list(u.reads),
                      list(u.writes), u.latency, u.occupancy]
                     for u in b.uops],
            "same_reg": _behavior(b.same_reg), "elim_period": b.elim_period,
            "dep_breaking_same_reg": b.dep_breaking_same_reg,
            "zero_uop_same_reg": b.zero_uop_same_reg,
            "divider_extra": b.divider_extra}


def tables_of(isa, uarches) -> dict:
    """The program's ISA and uarches in ``sim3_tables.json``'s schema."""
    out = {"about": ABOUT, "isa": {}, "uarches": {}}
    for s in isa:
        rec = {"mnemonic": s.mnemonic, "extension": s.extension,
               "operands": [[o.name, o.otype,
                             ("r" if o.read else "") + ("w" if o.written
                                                        else ""),
                             int(o.implicit), o.width] for o in s.operands]}
        rec.update({f: True for f in SPEC_FLAGS if getattr(s, f)})
        out["isa"][s.name] = rec
    for ua in uarches:
        out["uarches"][ua.name] = {
            "ports": "".join(ua.ports), "issue_width": ua.issue_width,
            "load_latency": ua.load_latency,
            "store_forward_latency": ua.store_forward_latency,
            "overhead_cycles": ua.overhead_cycles,
            "partial_stall_penalty": ua.partial_stall_penalty,
            "behaviors": {n: _behavior(b) for n, b in ua.behaviors.items()}}
    return out


def _generated(cfg) -> dict:
    from repro.core.uarch import generate_isa_and_uarch
    isa, ua = generate_isa_and_uarch(cfg["isa_seed"], cfg["isa_variants"],
                                     cfg["layout"])
    return tables_of(isa, [ua])


def _dump(tables) -> str:
    """One line per variant and per behavior, so a change diffs small."""
    def block(d):
        return ",\n".join(f"{json.dumps(k)}: {json.dumps(v)}"
                          for k, v in d.items())
    uas = []
    for name, ua in tables["uarches"].items():
        head = {k: v for k, v in ua.items() if k != "behaviors"}
        uas.append(f"{json.dumps(name)}: {json.dumps(head)[:-1]}, "
                   f"\"behaviors\": {{\n{block(ua['behaviors'])}}}}}")
    sep = ",\n"
    return (f"{{\"about\": {json.dumps(tables['about'])},\n"
            f"\"isa\": {{\n{block(tables['isa'])}}},\n"
            f"\"uarches\": {{\n{sep.join(uas)}}}}}\n")


def write_tables(bench=run_cell.BENCH) -> None:
    cfg = json.loads((bench / "configs" / "skl_paper_isa.json").read_text())
    (bench / cfg["tables"]).write_text(_dump(_generated(cfg)))


def test_cell_and_driver_are_discovered():
    cell = run_cell.Cell(CELL, 7)
    assert cell.config["name"] == "skl_paper_isa"
    assert cell.chips == 1
    assert cell.params == {"sample": 600, "sample_per_wave": 2}
    for fn in ("setup", "window", "layer_inputs", "release", "check"):
        assert callable(getattr(cell.driver, fn)), fn
    benchmark = json.loads((run_cell.ROOT / "BENCHMARK.json").read_text())
    e2e, layer = run_cell.metric_plan(cell, benchmark)
    assert {m["name"] for m in e2e} == {"setup_s", "campaign_s"}
    assert {"export_s.campaign", "wave_scalar_lanes.campaign",
            "wave_kernel_roofline.campaign", "device_idle.campaign",
            "sched_wave_width.campaign"} <= {m["name"] for m in layer}
    assert {c["name"]: c["file"] for c in benchmark["configs"]}[
        "skl_paper_isa"] == "benchmarks/chip/configs/skl_paper_isa.json"


def test_reference_tables_equal_the_generator():
    """The benchmark's copy of the generated ISA and hidden tables cannot
    drift from the program's generator at the configuration's seed."""
    cfg = _config()
    want = _generated(cfg)
    got = json.loads((run_cell.BENCH / cfg["tables"]).read_text())
    assert got["isa"] == want["isa"]
    assert got["uarches"] == want["uarches"]
    assert len(got["isa"]) == cfg["isa_variants"]
    assert list(got["uarches"]) == cfg["uarches"]


def test_reference_tables_load_in_the_plain_reference():
    from reference import models, oracle
    cfg = _config()
    isa, uarches = oracle.load_tables(run_cell.BENCH / cfg["tables"])
    expected = models.expected_ports(isa, uarches["gen_skl"])
    assert set(expected) == {n for n, s in isa.items() if s.characterized}
    assert len(expected) > 0.9 * cfg["isa_variants"]


def _candidates(isa, ua, extensions) -> set:
    """Port sets with a 1-μop blocking candidate (§5.1.1) whose instances
    are independent: no implicit operand both read and written, no memory
    write, not a divider, a PAUSE or a move the core eliminates."""
    out = set()
    for name, spec in isa.items():
        b = ua.behaviors[name]
        if (spec.extension not in extensions or not spec.characterized
                or spec.uses_divider or spec.mnemonic == "PAUSE"
                or len(b.uops) != 1 or (spec.may_eliminate and b.elim_period)
                or any(o.otype == oracle.MEM and o.written
                       for o in spec.operands)
                or any(o.implicit and o.read and o.written
                       for o in spec.operands)):
            continue
        out.add(b.uops[0].ports)
    return out


def test_reference_tables_hold_their_own_terms():
    """The tables checked on their own terms with the benchmark's code
    alone, since a generator fault would reach the program and the tables
    alike: the test ISA keeps ``sim3_tables.json``'s sim_skl tables;
    drawn variants have 1–4 μops, latencies of at most 5 cycles (dividers
    excepted) and the excluded classes in the test ISA's share; every port
    set a characterized variant uses has a 1-μop blocking candidate in the
    BASE/SSE and in the BASE/AVX set (§5.1.1)."""
    cfg = _config()
    isa, uarches = oracle.load_tables(run_cell.BENCH / cfg["tables"])
    (ua,) = uarches.values()
    raw = json.loads((run_cell.BENCH / cfg["tables"]).read_text())
    sim3 = json.loads(oracle.TABLES.read_text())
    skl = sim3["uarches"]["sim_skl"]
    gen = raw["uarches"]["gen_skl"]
    assert {k: v for k, v in gen.items() if k != "behaviors"} == \
        {k: v for k, v in skl.items() if k != "behaviors"}
    for name, rec in sim3["isa"].items():
        assert raw["isa"][name] == rec, name
        assert gen["behaviors"][name] == skl["behaviors"][name], name
    drawn = [n for n in isa if n not in sim3["isa"]]
    assert len(isa) == cfg["isa_variants"]
    for name in drawn:
        spec, b = isa[name], ua.behaviors[name]
        if spec.is_nop:
            continue
        assert 1 <= len(b.uops) <= 4, name
        assert all(u.latency <= 5 for u in b.uops) or spec.uses_divider, name
    test_share = sum(not isa[n].characterized for n in sim3["isa"]) / len(
        sim3["isa"])
    share = sum(not isa[n].characterized for n in drawn) / len(drawn)
    assert 0.5 * test_share <= share <= 1.5 * test_share
    used = {u.ports for n, s in isa.items() if s.characterized
            for u in ua.behaviors[n].uops}
    stores = {("4",), ("2", "3", "7")}       # the register->memory MOV's
    for ext in (("BASE", "SSE"), ("BASE", "AVX")):
        assert used - stores <= _candidates(isa, ua, ext), ext


def _span(name, dur_ns, **args):
    return {"ph": "X", "name": name, "tid": 1, "t0": 0, "dur": dur_ns,
            "args": args}


@pytest.mark.parametrize("spans,campaigns,want", [
    ([_span("model.export", 2e8, instructions=474, bytes=248037),
      _span("model.export", 1e8, instructions=63, bytes=40000),
      _span("wave.lower", 5e9)], 1, 0.3),
    ([_span("model.export", 4e8), _span("model.export", 2e8)], 2, 0.3),
    ([_span("wave.lower", 5e9)], 1, None),          # a program without it
    ([_span("model.export", 2e8)], 0, None),
])
def test_export_s_reader(spans, campaigns, want):
    reader = run_cell.load_module(
        run_cell.BENCH / "metrics" / "export_s.campaign.py", "export_s")
    got = reader.read({"program_spans": spans,
                       "counters": {"campaigns": campaigns}})
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("spans,want", [
    ([_span("wave.run_batch", 1, lanes=1000, chunks=3, scalar_lanes=6),
      _span("wave.run_batch", 1, lanes=1000, chunks=1, scalar_lanes=0)],
     0.3),
    ([_span("wave.run_batch", 1, lanes=40, chunks=1, scalar_lanes=0)], 0.0),
    ([_span("wave.run_batch", 1, lanes=40)], None),  # a program without it
    ([], None),
])
def test_wave_scalar_lanes_reader(spans, want):
    reader = run_cell.load_module(
        run_cell.BENCH / "metrics" / "wave_scalar_lanes.campaign.py",
        "wave_scalar_lanes")
    got = reader.read({"program_spans": spans, "counters": {}})
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.fixture(scope="module")
def small_paper_bench(tmp_path_factory):
    """A copy of the benchmark whose paper-scale cell runs a 96-variant ISA
    of the same generator, seed and layout, with its tables dumped."""
    dst = tmp_path_factory.mktemp("bench") / "chip"
    shutil.copytree(run_cell.BENCH, dst,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg_path = dst / "configs" / "skl_paper_isa.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["isa_variants"] = 96
    cfg_path.write_text(json.dumps(cfg))
    write_tables(dst)
    wl_path = dst / "workloads" / f"{CELL}.json"
    wl = json.loads(wl_path.read_text())
    wl["params"].update(sample=100)
    wl_path.write_text(json.dumps(wl))
    return dst


@pytest.mark.parametrize("control,fails", [
    (None, None),
    ("answer_altered", "answers_mismatch"),
])
def test_sound_run_and_planted_fault(small_paper_bench,
                                     cpu_as_tpu,  # noqa: F811
                                     control, fails):
    res = _run(small_paper_bench, CELL, 4000000007, control)
    checks = res["checks"]
    if fails is None:
        assert res["correct"], checks
        assert all(c["value"] == 0 for c in checks.values()), checks
    else:
        assert not res["correct"]
        assert checks[fails]["value"] > checks[fails]["limit"], checks
