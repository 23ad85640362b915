"""The seeded paper-scale ISA generator (§6.1) and its hidden tables: it is
deterministic, holds all of TEST_ISA, draws every operand form and special
semantics, keeps §5.1.1's blocking-instruction assumption on both port
layouts, leaves TEST_ISA and the SIM cores untouched, and a campaign over
it recovers the hidden port usage exactly while its latencies and
throughputs equal the scalar oracle's."""
import hashlib

import pytest

from repro.core.blocking import find_blocking_instructions
from repro.core.engine import Campaign, machine_fingerprint
from repro.core.isa import (EXCLUDED_TAGS, FORM_TAGS, MEM, SEMANTIC_TAGS,
                            TEST_ISA, generate_isa, variant_tags)
from repro.core.latency import LatencyAnalyzer
from repro.core.simulator import SimMachine
from repro.core.throughput import measure_throughput
from repro.core.uarch import (LAYOUTS, P4, P237, SIM_UARCHES,
                              generate_isa_and_uarch, port_usage_of)

# content hashes of TEST_ISA and of the SIM cores' hidden tables as they
# stood before the generator existed
TEST_ISA_SHA256 = \
    "c06c9a8115a0ac7e1cbb9c9b61e414309e4169a4b36732964e90e50b465ce3fe"
SIM_FINGERPRINTS = {
    "sim_skl":
        "d94f7c29815c9a49e5175a3fc976658cf59384da831f6119691b563e3e6ede91",
    "sim_hsw":
        "c6e25b5f904c6cfa7387340b2485a1bb61cadd3ec6ed971284e1016a9304eb77",
    "sim_snb":
        "9ee44bd881829638aedbe6b86bb43f28c64327740a4ca68e292c934157f48ed5",
}


def _unchanged_test_isa_and_cores():
    assert hashlib.sha256(repr(list(TEST_ISA)).encode()).hexdigest() == \
        TEST_ISA_SHA256
    assert {n: machine_fingerprint(SimMachine(u, TEST_ISA))
            for n, u in SIM_UARCHES.items()} == SIM_FINGERPRINTS


def test_test_isa_and_sim_cores_unchanged():
    _unchanged_test_isa_and_cores()
    for layout in LAYOUTS:
        generate_isa_and_uarch(3, 200, layout)
    _unchanged_test_isa_and_cores()


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_generator_is_deterministic_by_seed(layout):
    isa_a, ua_a = generate_isa_and_uarch(11, 300, layout)
    isa_b, ua_b = generate_isa_and_uarch(11, 300, layout)
    isa_c, ua_c = generate_isa_and_uarch(12, 300, layout)
    assert list(isa_a) == list(isa_b)
    assert machine_fingerprint(SimMachine(ua_a, isa_a)) == \
        machine_fingerprint(SimMachine(ua_b, isa_b))
    assert isa_a.names() != isa_c.names()
    # the ISA depends on the seed alone; the layout only moves the tables
    assert list(isa_a) == list(generate_isa(11, 300))
    assert len(isa_a) == 300 and ua_a.ports == LAYOUTS[layout].core.ports


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_contains_all_of_test_isa_with_its_core_tables(layout):
    core = LAYOUTS[layout].core
    isa, ua = generate_isa_and_uarch(5, 1024, layout)
    assert list(isa)[:len(TEST_ISA)] == list(TEST_ISA)
    for name in TEST_ISA.names():
        assert ua.behaviors[name] == core.behaviors[name], name
    assert (ua.issue_width, ua.overhead_cycles, ua.load_latency) == \
        (core.issue_width, core.overhead_cycles, core.load_latency)


def test_every_form_and_semantics_is_drawn():
    drawn = list(generate_isa(1810, 1024))[len(TEST_ISA):]
    tags = set().union(*(variant_tags(s) for s in drawn))
    assert set(FORM_TAGS + SEMANTIC_TAGS + EXCLUDED_TAGS) <= tags
    # stratified: a small ISA still holds every form and semantics
    small = list(generate_isa(1810, 96))[len(TEST_ISA):]
    assert set(FORM_TAGS + SEMANTIC_TAGS) <= set().union(
        *(variant_tags(s) for s in small))
    # excluded classes keep TEST_ISA's share (5 of 68)
    excluded = sum(not s.characterized for s in drawn)
    assert excluded == round(len(drawn) * 5 / 68)


def _uop_counts_ok(isa, ua):
    for spec in isa:
        if spec.name in TEST_ISA or spec.is_nop:
            continue
        b = ua.behaviors[spec.name]
        assert 1 <= len(b.uops) <= 4, spec.name
        for u in b.uops:
            assert u.latency <= 5 or spec.uses_divider, spec.name


def _blocking_candidates(isa, ua, extensions) -> set:
    """Port sets with a 1-μop candidate (§5.1.1) whose instances are
    independent: no implicit operand both read and written, no memory
    write, not a divider or an eliminable move."""
    out = set()
    for spec in isa:
        b = ua.behaviors[spec.name]
        if (spec.extension not in extensions or not spec.characterized
                or spec.uses_divider or spec.mnemonic == "PAUSE"
                or len(b.uops) != 1 or (spec.may_eliminate and b.elim_period)
                or any(o.otype == MEM and o.written for o in spec.operands)
                or any(o.implicit and o.rmw for o in spec.operands)):
            continue
        out.add(b.uops[0].ports)
    return out


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_every_port_set_has_a_blocking_candidate(layout):
    """§5.1.1's assumption, in the BASE/SSE and in the BASE/AVX set;
    store-data and store-address take the register->memory MOV."""
    isa, ua = generate_isa_and_uarch(1810, 1024, layout)
    _uop_counts_ok(isa, ua)
    used = {u.ports for s in isa if s.characterized
            for u in ua.behaviors[s.name].uops}
    for ext in (("BASE", "SSE"), ("BASE", "AVX")):
        assert used - {P4, P237} <= _blocking_candidates(isa, ua, ext), ext
    # a small ISA keeps it too, and the search finds every port set a
    # characterized variant uses
    isa, ua = generate_isa_and_uarch(1810, 96, layout)
    used = {u.ports for s in isa if s.characterized
            for u in ua.behaviors[s.name].uops}
    for ext in (("BASE", "SSE"), ("BASE", "AVX")):
        assert used - {P4, P237} <= _blocking_candidates(isa, ua, ext), ext
    found = find_blocking_instructions(SimMachine(ua, isa), isa)
    assert used <= set(found.instrs)


class _ScalarOracle:
    """The plain reference: ``SimMachine.run`` one sequence at a time (no
    ``run_batch``, so the engine drives it with the scalar loop)."""

    def __init__(self, machine):
        self.name, self.ports, self.uarch = (machine.name, machine.ports,
                                             machine.uarch)
        self.run = machine.run


def test_campaign_recovers_a_generated_isa_exactly():
    """A 96-variant Skylake-like ISA through ``Campaign.run`` on the
    platform's batched backend: port usage and μop counts equal the hidden
    tables; every latency edge and throughput equals the scalar oracle's."""
    isa, ua = generate_isa_and_uarch(7, 96, "skl")
    machine = SimMachine(ua, isa)
    model = Campaign().run([machine], isa).models[ua.name]
    assert machine.batch_machine().backend in ("numpy", "jax")
    specs = [s for s in isa if s.characterized]
    assert set(model.instructions) == {s.name for s in specs}
    oracle = _ScalarOracle(machine)
    lat = LatencyAnalyzer(oracle, isa)
    for spec in specs:
        b = ua.behaviors[spec.name]
        im = model[spec.name]
        if spec.may_eliminate and b.elim_period:
            assert im.port_usage.usage == {}
            assert im.uops == round(len(b.uops) / b.elim_period, 2)
        else:
            assert im.port_usage.usage == port_usage_of(b), spec.name
            assert im.uops == len(b.uops), spec.name
        want = lat.analyze(spec.name).entries
        assert set(im.latency.entries) == set(want), spec.name
        for k, e in want.items():
            got = im.latency.entries[k]
            assert (got.kind, got.value, got.same_reg, got.high_value) == \
                (e.kind, e.value, e.same_reg, e.high_value), (spec.name, k)
        tp = measure_throughput(oracle, isa, spec.name)
        assert (im.throughput.measured, im.throughput.high_value) == \
            (tp.measured, tp.high_value), spec.name
