"""Throughput (§4.2 / §5.3): measured vs LP-computed, and the LP itself."""
import random

import pytest

from repro.core.isa import TEST_ISA
from repro.core.lp import (CUT_COMBO_CAP, _bisect_flow, closed_form_applies,
                           cut_bound, port_bound_from_usage, throughput_lp,
                           union_closure)
from repro.core.model_io import _fmt, to_xml
from repro.core.port_usage import PortUsage
from repro.core.throughput import computed_throughput, measure_throughput


def test_lp_single_uop():
    assert throughput_lp({frozenset("0156"): 1}) == pytest.approx(0.25)
    assert throughput_lp({frozenset("0"): 1}) == pytest.approx(1.0)


def test_lp_overlapping_combos():
    # 2 uops on p01 + 2 uops on p0 -> load: p0 gets 2, p1 gets 2 -> z=2
    u = {frozenset("01"): 2, frozenset("0"): 2}
    assert throughput_lp(u) == pytest.approx(2.0)
    # 1*p0+1*p015: p0:1, split the other over p1/p5 -> z=1
    u2 = {frozenset("0"): 1, frozenset("015"): 1}
    assert throughput_lp(u2) == pytest.approx(1.0)


def test_lp_matches_maxflow_fallback():
    cases = [
        {frozenset("01"): 3, frozenset("12"): 2, frozenset("2"): 1},
        {frozenset("0156"): 4, frozenset("06"): 2},
        {frozenset("0"): 5},
    ]
    for u in cases:
        ports = sorted(set().union(*u))
        assert throughput_lp(u) == pytest.approx(
            _bisect_flow(u, ports), abs=1e-4)


def test_cut_bound_equals_lp_on_random_usages():
    """The min-cut closed form (service fast path) is the LP optimum."""
    rng = random.Random(0)
    ports = "01234567"
    for _ in range(150):
        usage = {frozenset(rng.sample(ports, rng.randint(1, 4))):
                 rng.randint(1, 6)
                 for _ in range(rng.randint(1, 5))}
        assert cut_bound(usage) == pytest.approx(throughput_lp(usage),
                                                 abs=1e-6)
        assert port_bound_from_usage(usage) == pytest.approx(
            throughput_lp(usage), abs=1e-6)


def test_union_closure():
    combos = [frozenset("01"), frozenset("2"), frozenset("01")]
    closed = union_closure(combos)
    assert set(closed) == {frozenset("01"), frozenset("2"),
                           frozenset("012")}
    assert union_closure([frozenset(str(i)) for i in range(20)],
                         cap=100) is None
    assert union_closure([]) == []


def test_measured_throughput_alu(skl_machine):
    r = measure_throughput(skl_machine, TEST_ISA, "ADD_R64_R64")
    assert r.measured == pytest.approx(0.25, abs=0.02)
    assert set(r.by_seq_len) == {1, 2, 4, 8}
    # a single instance chains with itself through op1 (rw): slower
    assert r.by_seq_len[1] >= r.by_seq_len[8]


def test_implicit_flags_limit_fog_throughput(skl_machine):
    """Def. 2 throughput of CMC is 1 (flags RMW serializes); Intel-definition
    (from ports) is 0.25 — the two definitions genuinely differ (§4.2)."""
    r = measure_throughput(skl_machine, TEST_ISA, "CMC")
    assert r.measured == pytest.approx(1.0, abs=0.05)


def test_breaker_variant_helps_adc(skl_machine):
    r = measure_throughput(skl_machine, TEST_ISA, "ADC_R64_R64")
    assert r.with_breakers is not None
    # without breakers the flags chain forces ~1 cycle/instr; the breaker
    # variant beats it despite consuming execution resources itself
    assert r.measured == pytest.approx(1.0, abs=0.1)
    assert r.with_breakers < r.measured


def test_divider_high_low(skl_machine):
    r = measure_throughput(skl_machine, TEST_ISA, "DIV_R64")
    assert r.high_value is not None
    assert r.high_value > r.measured


def test_computed_throughput_from_ports(skl_model):
    im = skl_model["ADD_R64_R64"]
    assert im.throughput.computed_from_ports == pytest.approx(0.25, abs=0.01)
    # dividers are excluded from LP computation (not fully pipelined)
    assert skl_model["DIV_R64"].throughput.computed_from_ports is None


def test_intel_vs_fog_definitions_diverge(skl_model):
    """CMC: computed-from-ports 0.25 vs measured 1.0."""
    im = skl_model["CMC"]
    assert im.throughput.computed_from_ports == pytest.approx(0.25, abs=0.01)
    assert im.throughput.measured == pytest.approx(1.0, abs=0.05)


def _characterized(which):
    """A whole ISA characterized on one machine, with the ``characterize``
    span's attributes."""
    import xml.etree.ElementTree as ET

    from repro.core.characterize import characterize
    from repro.core.simulator import SimMachine
    from repro.core.uarch import SIM_SKL, generate_isa_and_uarch
    from repro.obs.tracer import Tracer, set_tracer

    if which == "test_isa":
        isa, ua = TEST_ISA, SIM_SKL
    else:
        isa, ua = generate_isa_and_uarch(1810, 128, "skl")
    tr = Tracer(enabled=True)
    prev = set_tracer(tr)
    try:
        model = characterize(SimMachine(ua, isa), isa)
    finally:
        set_tracer(prev)
    [span] = [e["args"] for e in tr.events() if e["name"] == "characterize"]
    exported = {el.get("name"): m.get("tp_ports")
                for el in ET.fromstring(to_xml(model, isa)).iter(
                    "instruction")
                for m in el.iter("measurement")
                if m.get("tp_ports") is not None}
    return isa, model, span, exported


@pytest.mark.parametrize("which", ["test_isa", "generated_128"])
def test_campaign_port_bound_is_the_closed_form(which):
    """The campaign's computed throughput is the predictors' closed form,
    equal to the LP within 1e-9 and exported exactly as the LP's value
    formats; the span counts every bound as closed-form."""
    isa, model, span, exported = _characterized(which)
    bounded = 0
    for name, im in model.instructions.items():
        usage = im.port_usage.usage
        tp = im.throughput.computed_from_ports
        if isa[name].uses_divider or not usage:
            assert tp is None, name
            continue
        bounded += 1
        assert tp == port_bound_from_usage(usage), name
        lp = throughput_lp(usage)
        assert tp == pytest.approx(lp, abs=1e-9), name
        assert exported[name] == _fmt(lp), name
    assert bounded > 0 and len(exported) == bounded
    assert span["tp_closed"] == bounded
    assert span["tp_lp_fallbacks"] == 0


def test_port_bound_above_combo_cap_uses_the_lp(monkeypatch):
    """More than ``CUT_COMBO_CAP`` distinct combinations: the bound comes
    from the LP, over the canonical (sorted) variable order."""
    import repro.core.lp as lp_mod

    rng = random.Random(16)
    ports = "0123456789AB"
    combos = set()
    while len(combos) < CUT_COMBO_CAP + 1:
        combos.add(frozenset(rng.sample(ports, rng.randint(1, 3))))
    usage = {pc: rng.randint(1, 4) for pc in combos}
    assert not closed_form_applies(usage)
    calls = []
    real_lp = lp_mod.throughput_lp

    def spy(u, ports=None):
        calls.append(list(u))
        return real_lp(u, ports)

    monkeypatch.setattr(lp_mod, "throughput_lp", spy)
    spec = TEST_ISA["ADD_R64_R64"]
    tp = computed_throughput(PortUsage(usage=usage), spec)
    assert calls == [sorted(usage, key=sorted)]
    assert tp == real_lp(dict(sorted(usage.items(),
                                     key=lambda kv: sorted(kv[0]))))
    assert tp == pytest.approx(cut_bound(usage), abs=1e-9)
    # at the cap itself the closed form still applies: no LP call
    calls.clear()
    capped = dict(list(usage.items())[:CUT_COMBO_CAP])
    assert closed_form_applies(capped)
    assert computed_throughput(PortUsage(usage=capped), spec) == \
        cut_bound(capped)
    assert calls == []
