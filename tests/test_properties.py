"""Property-based tests (hypothesis): the paper's central correctness claims
made mechanically checkable against randomly drawn hidden ground truths."""
import pytest

pytest.importorskip("hypothesis")

from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core.blocking import find_blocking_instructions
from repro.core.isa import ANCHORS, GPR, ISA, TEST_ISA, InstrSpec, op
from repro.core.latency import LatencyAnalyzer
from repro.core.lp import _bisect_flow, throughput_lp
from repro.core.port_usage import infer_port_usage
from repro.core.simulator import SimMachine
from repro.core.throughput import measure_throughput
from repro.core.uarch import (LAYOUTS, InstrBehavior, UArch,
                              generate_isa_and_uarch, port_usage_of, uop)
from random_truth import random_uarch_and_isa

SET = settings(max_examples=20, deadline=None,
               suppress_health_check=[HealthCheck.too_slow])


@given(seed=st.integers(0, 10_000))
@SET
def test_algorithm1_recovers_random_port_usage(seed):
    """For ANY hidden ground truth (with blocking instructions available),
    Algorithm 1 recovers the exact port-usage multiset."""
    ua, isa, truth = random_uarch_and_isa(seed)
    m = SimMachine(ua, isa)
    blocking = find_blocking_instructions(m, isa, extensions=("BASE",))
    for name, expect in truth.items():
        got = infer_port_usage(m, isa, name, blocking, max_latency=4).usage
        assert got == expect, (name, got, expect)


def _drawn(seed: int, n: int, layout: str = "skl"):
    """A generated ISA with ``n`` drawn variants beyond TEST_ISA and its
    anchors; returns (uarch, isa, the drawn characterized variants)."""
    isa, ua = generate_isa_and_uarch(seed, len(TEST_ISA) + len(ANCHORS) + n,
                                     layout)
    drawn = [s for s in list(isa)[len(TEST_ISA):] if s.characterized]
    return ua, isa, drawn


def _eliminated(spec, ua) -> bool:
    return spec.may_eliminate and ua.behaviors[spec.name].elim_period > 0


@given(seed=st.integers(0, 10_000), layout=st.sampled_from(sorted(LAYOUTS)))
@SET
def test_algorithm1_recovers_generated_port_usage(seed, layout):
    """The same on the program's generated ISAs (which keep a 1-μop
    blocking instruction for every port set they use): Algorithm 1
    recovers the exact port-usage multiset (none for an eliminated move);
    blockRep is sized from the sum of the μop latencies, an upper bound
    on every operand pair's."""
    ua, isa, drawn = _drawn(seed, 4, layout)
    m = SimMachine(ua, isa)
    blocking = find_blocking_instructions(m, isa)
    for spec in drawn:
        b = ua.behaviors[spec.name]
        expect = {} if _eliminated(spec, ua) else port_usage_of(b)
        got = infer_port_usage(m, isa, spec.name, blocking,
                               max_latency=sum(u.latency for u in b.uops)
                               ).usage
        assert got == expect, (spec.name, got, expect)


def _chain_isa(seed: int, lats):
    """ISA with a MOVSX-like chain instr + one multi-uop instr whose
    per-pair latencies are the hidden parameters."""
    isa = ISA()
    isa.add(InstrSpec("MOVSX_R64_R32", "MOVSX",
                      (op("op1", GPR, "w"), op("op2", GPR, "r", width=32))))
    isa.add(InstrSpec("TGT", "TGT",
                      (op("op1", GPR, "w"), op("op2", GPR, "r"))))
    l1, l2 = lats
    behaviors = {
        "MOVSX_R64_R32": InstrBehavior((uop(frozenset("01"), ("op2",),
                                            ("op1",)),)),
        "TGT": InstrBehavior((
            uop(frozenset("0"), ("op2",), ("%0",), l1),
            uop(frozenset("01"), ("%0",), ("op1",), l2),
        )),
    }
    return ISA([s for s in isa]), UArch(f"lat{seed}", tuple("012"), 4,
                                        behaviors, overhead_cycles=30)


@given(l1=st.integers(1, 9), l2=st.integers(1, 9))
@SET
def test_chain_latency_recovers_random_values(l1, l2):
    """Dependency-chain inference recovers lat(op2,op1) = l1+l2 exactly."""
    isa, ua = _chain_isa(0, (l1, l2))
    m = SimMachine(ua, isa)
    la = LatencyAnalyzer(m, isa)
    r = la.analyze("TGT")
    assert r.get("op2", "op1").value == pytest.approx(l1 + l2, abs=0.05)


@given(st.dictionaries(
    keys=st.frozensets(st.sampled_from("012345"), min_size=1, max_size=4),
    values=st.integers(1, 5), min_size=1, max_size=4))
@SET
def test_lp_equals_maxflow(usage):
    """The §5.3.2 LP agrees with the independent bisection+max-flow solver."""
    ports = sorted(set().union(*usage))
    assert throughput_lp(usage) == pytest.approx(
        _bisect_flow(usage, ports), abs=1e-4)


@given(st.dictionaries(
    keys=st.frozensets(st.sampled_from("0123"), min_size=1, max_size=3),
    values=st.integers(1, 4), min_size=1, max_size=3))
@SET
def test_lp_lower_bounds(usage):
    """z* >= total/|ports| and z* >= μ(pc)/|pc| for every combination, and
    z* <= total μops (trivial upper bound)."""
    z = throughput_lp(usage)
    total = sum(usage.values())
    ports = set().union(*usage)
    assert z >= total / len(ports) - 1e-6
    for pc, mu in usage.items():
        assert z >= mu / len(pc) - 1e-6
    assert z <= total + 1e-6


@given(seed=st.integers(0, 3000))
@SET
def test_measured_throughput_ge_lp(seed):
    """Fog-measured throughput can never beat the Intel/LP bound (§4.2:
    Def. 2 yields higher cycle counts than Def. 1)."""
    ua, isa, truth = random_uarch_and_isa(seed, n_instr=3)
    m = SimMachine(ua, isa)
    for name, usage in truth.items():
        meas = measure_throughput(m, isa, name).measured
        lp = throughput_lp(usage)
        assert meas >= lp - 0.05, (name, meas, lp)


@given(seed=st.integers(0, 3000))
@SET
def test_measured_throughput_ge_lp_on_generated_isa(seed):
    """The same on the program's generated ISAs, for every instruction
    whose μops all execute (an eliminated move runs fewer)."""
    ua, isa, drawn = _drawn(seed, 3)
    m = SimMachine(ua, isa)
    for spec in drawn:
        if _eliminated(spec, ua):
            continue
        usage = port_usage_of(ua.behaviors[spec.name])
        meas = measure_throughput(m, isa, spec.name).measured
        lp = throughput_lp(usage)
        assert meas >= lp - 0.05, (spec.name, meas, lp)


@given(seed=st.integers(0, 3000))
@SET
def test_simulator_port_counts_conserve_uops(seed):
    """Per-port counters sum to the total μop count of the program."""
    ua, isa, truth = random_uarch_and_isa(seed, n_instr=4)
    m = SimMachine(ua, isa)
    from repro.core.machine import RegPool, independent_seq

    pool = RegPool()
    for name, usage in truth.items():
        seq = independent_seq(isa[name], pool, 5)
        c = m.run(seq)
        assert c.total_uops == 5 * sum(usage.values())


@given(seed=st.integers(0, 3000))
@SET
def test_simulator_port_counts_conserve_uops_on_generated_isa(seed):
    """The same on the program's generated ISAs (loads, stores and
    multi-μop chains included), for every instruction whose μops all
    execute."""
    ua, isa, drawn = _drawn(seed, 4)
    m = SimMachine(ua, isa)
    from repro.core.machine import RegPool, independent_seq

    pool = RegPool()
    for spec in drawn:
        if _eliminated(spec, ua):
            continue
        usage = port_usage_of(ua.behaviors[spec.name])
        c = m.run(independent_seq(spec, pool, 5))
        assert c.total_uops == 5 * sum(usage.values())


# ---------------------------------------------------------------------------
# service protocol: textual block format round-trips
# ---------------------------------------------------------------------------

_IDENT = st.text(alphabet=st.sampled_from(
    "ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_"), min_size=1, max_size=12)


@st.composite
def _blocks(draw):
    from repro.core.simulator import Instr
    n = draw(st.integers(0, 6))
    code = []
    for _ in range(n):
        spec = draw(_IDENT)
        regs = draw(st.dictionaries(_IDENT, _IDENT, max_size=4))
        hint = draw(st.sampled_from(["low", "high"]))
        code.append(Instr(spec, regs, hint))
    return code


@given(code=_blocks())
@SET
def test_format_block_is_exact_inverse_of_parse_block(code):
    """format_block ∘ parse_block == id on the block domain: every
    formattable block (any spec/operand identifiers, any value hint)
    survives a serialize→parse round trip exactly."""
    from repro.service.protocol import format_block, parse_block

    text = format_block(code)
    assert parse_block(text) == code
    # and the canonical text form is a fixed point of the round trip
    assert format_block(parse_block(text)) == text
