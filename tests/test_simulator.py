"""Simulated-machine behaviors the paper's algorithms must contend with."""
import pytest

from repro.core.batch_sim import BACKENDS, BatchSimMachine
from repro.core.isa import TEST_ISA
from repro.core.machine import RegPool, independent_seq, measure
from repro.core.simulator import Instr, SimMachine
from repro.core.uarch import SIM_SKL


@pytest.fixture(scope="module")
def m():
    return SimMachine(SIM_SKL, TEST_ISA)


def test_overhead_cancellation(m):
    """Raw runs include harness overhead; Algorithm-2 differencing removes
    it exactly (deterministic machine)."""
    seq = [Instr("ADD_R64_R64", {"op1": "R0", "op2": "R1"})]
    raw = m.run(seq * 10)
    assert raw.cycles > SIM_SKL.overhead_cycles
    c = measure(m, seq)
    assert c.cycles == pytest.approx(1.0, abs=0.05)  # dependent chain: lat 1


def test_zero_idiom_breaks_dependency(m):
    """XOR R,R is dependency-breaking AND executes zero μops on SKL-like."""
    slow = [Instr("IMUL_R64_R64", {"op1": "R0", "op2": "R1"})]
    mixed = [Instr("IMUL_R64_R64", {"op1": "R0", "op2": "R1"}),
             Instr("XOR_R64_R64", {"op1": "R0", "op2": "R0"})]
    c_slow = measure(m, slow)
    c_mixed = measure(m, mixed)
    assert c_slow.cycles == pytest.approx(3.0, abs=0.05)
    assert c_mixed.cycles < c_slow.cycles  # chain broken
    assert c_mixed.total_uops == pytest.approx(1.0, abs=0.05)  # XOR: 0 μops


def test_move_elimination_partial(m):
    """In a chained MOV sequence about 1/3 execute (the paper's observation
    motivating MOVSX for chains)."""
    seq = [Instr("MOV_R64_R64", {"op1": f"R{(i + 1) % 8}", "op2": f"R{i % 8}"})
           for i in range(8)]
    c = measure(m, seq)
    frac_executed = c.total_uops / len(seq)
    assert 0.25 < frac_executed < 0.45


def test_movsx_never_eliminated(m):
    seq = [Instr("MOVSX_R64_R32", {"op1": f"R{(i + 1) % 8}", "op2": f"R{i % 8}"})
           for i in range(8)]
    c = measure(m, seq)
    assert c.total_uops / len(seq) == pytest.approx(1.0, abs=0.02)
    assert c.cycles / len(seq) == pytest.approx(1.0, abs=0.02)


def test_divider_not_pipelined(m):
    """Independent DIVs are limited by divider occupancy, not port count."""
    pool = RegPool()
    # give each DIV a distinct implicit-free setup: op2 distinct, but the
    # implicit RDX dependency still serializes -> measured >> occupancy
    seq = independent_seq(TEST_ISA["DIV_R64"], pool, 4)
    c = measure(m, seq)
    assert c.cycles / 4 >= 6  # occupancy floor


def test_divider_value_dependence(m):
    lo = [Instr("DIV_R64", {"op1": "R0", "op2": "R1"}, "low")]
    hi = [Instr("DIV_R64", {"op1": "R0", "op2": "R1"}, "high")]
    assert measure(m, hi).cycles > measure(m, lo).cycles


def test_store_to_load_forwarding(m):
    """Store->load round trip is faster than store + full load latency."""
    rt = measure(m, [
        Instr("MOV_M64_R64", {"mem": "RB0", "op1": "R1"}),
        Instr("MOV_R64_M64", {"op1": "R1", "mem": "RB0"}),
    ])
    assert rt.cycles < 1 + SIM_SKL.load_latency + 2
    assert rt.cycles >= SIM_SKL.store_forward_latency


def test_port_counters_sum(m):
    """Counters attribute each μop to exactly one port."""
    seq = independent_seq(TEST_ISA["PADDD_X_X"], RegPool(), 6)
    c = measure(m, seq)
    assert c.total_uops == pytest.approx(6.0, abs=0.05)
    used = {p for p, v in c.port_uops.items() if v > 0.05}
    assert used == {"0", "1", "5"}


def test_frontend_issue_width_limits(m):
    """More μops than width*cycles cannot retire: NOP-free ALU flood."""
    pool = RegPool()
    seq = independent_seq(TEST_ISA["ADD_R64_R64"], pool, 16)
    c = measure(m, seq)
    # 4 ALU ports but issue width 4 -> 4/cycle
    assert c.cycles / 16 >= 0.24


def test_partial_register_stall(m):
    """§5.2.1: reading a 64-bit register after an 8-bit write stalls; a
    width-matched MOVSX read does not — the reason the paper's chains use
    MOVSX variants."""
    from repro.core.uarch import SIM_SKL as UA

    # SETC writes 8 bits of R1; ADD reads 64 bits of R1 -> stall
    stalled = measure(m, [
        Instr("SETC_R8", {"op1": "R1"}),
        Instr("ADD_R64_R64", {"op1": "R2", "op2": "R1"}),
        Instr("TEST_R64_R64", {"op1": "R2", "op2": "R2"}),  # close flags loop
    ])
    # width-matched: MOVSX reads only the written byte
    clean = measure(m, [
        Instr("SETC_R8", {"op1": "R1"}),
        Instr("MOVSX_R64_R8", {"op1": "R2", "op2": "R1"}),
        Instr("TEST_R64_R64", {"op1": "R2", "op2": "R2"}),
    ])
    assert stalled.cycles == pytest.approx(
        clean.cycles + UA.partial_stall_penalty, abs=0.1)


@pytest.mark.parametrize("build", [
    lambda: BatchSimMachine(SIM_SKL, TEST_ISA, backend="pallas"),
    lambda: SimMachine(SIM_SKL, TEST_ISA, backend="pallas").batch_machine(),
], ids=["batch_sim", "simmachine"])
def test_removed_pallas_backend_is_unknown(build):
    """The wave kernels are numpy and the jax scan; any other name fails
    construction on every platform, through ``SimMachine`` too — never a
    silent fallback to another kernel."""
    assert BACKENDS == ("numpy", "jax")
    with pytest.raises(ValueError, match="unknown backend 'pallas'"):
        build()
