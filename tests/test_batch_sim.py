"""Batched measurement substrate: the vectorized backend must be
bit-identical (cycles AND per-port μop counts) to the scalar oracle
``SimMachine.run`` on every uarch, every wave shape, and random hidden
ground truths — plus the compiled-table and divider-occupancy seams."""
import random

import numpy as np
import pytest

from repro.core.batch_sim import BatchSimMachine, _body_period
from repro.core.engine import Campaign, as_engine
from repro.core.isa import GPR, MEM, TEST_ISA, VEC
from repro.core.machine import RegPool, independent_seq
from repro.core.simulator import Instr, SimMachine
from repro.core.uarch import (SIM_SKL, SIM_UARCHES, UArch, beh,
                              generate_isa_and_uarch, make_tpu_sim, uop)
from repro.core.uarch_compile import UopTableIndex, compile_uarch
from random_truth import random_uarch_and_isa

try:
    import hypothesis  # noqa: F401
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def assert_wave_matches(ua, isa, codes, backend="numpy"):
    scalar = SimMachine(ua, isa)
    # min_lanes=1: force every chunk through the array kernel (the default
    # routes thin chunks to the scalar oracle, which would test nothing)
    batch = BatchSimMachine(ua, isa, backend=backend, min_lanes=1)
    got = batch.run_batch(codes)
    assert len(got) == len(codes)
    for i, code in enumerate(codes):
        ref = scalar.run(list(code))
        assert got[i].cycles == ref.cycles, (i, code[:4])
        assert got[i].port_uops == ref.port_uops, (i, code[:4])


def _interesting_wave(isa):
    """Sequences exercising every special path: zero idioms (both kinds),
    move elimination, same-register variants, dividers (both value
    classes), loads/stores + forwarding, partial-register stalls, flags
    chains, NOP-likes — unrolled the way Algorithm 2 unrolls them."""
    codes = []
    for spec in ("ADD_R64_R64", "MOV_R64_R64", "XOR_R64_R64", "DIV_R64",
                 "SHLD_R64_R64_I8", "MOV_M64_R64", "AESDEC_X_X",
                 "MOVQ2DQ_X_X", "ADC_R64_R64", "MUL_R64", "PCMPGTQ_X_X",
                 "PAUSE", "ADD_R64_M64"):
        body = independent_seq(isa[spec], RegPool(), 3)
        codes.append(body * 10)
        codes.append(body * 110)
    codes += [
        [Instr("SHLD_R64_R64_I8", {"op1": "R0", "op2": "R0"})] * 30,
        [Instr("DIV_R64", {"op1": "R0", "op2": "R1"}, "high")] * 15,
        [Instr("XOR_R64_R64", {"op1": "R3", "op2": "R3"}),
         Instr("IMUL_R64_R64", {"op1": "R3", "op2": "R4"})] * 40,
        [Instr("MOV_R64_R64", {"op1": f"R{(i + 1) % 8}", "op2": f"R{i % 8}"})
         for i in range(8)] * 9,
        [Instr("SETC_R8", {"op1": "R1"}),
         Instr("ADD_R64_R64", {"op1": "R2", "op2": "R1"}),
         Instr("TEST_R64_R64", {"op1": "R2", "op2": "R2"})] * 35,
        [Instr("MOV_M64_R64", {"mem": "RB0", "op1": "R1"}),
         Instr("MOV_R64_M64", {"op1": "R1", "mem": "RB0"})] * 20,
    ]
    return codes


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("uarch", sorted(SIM_UARCHES))
def test_batch_identical_to_scalar_on_sim_uarches(uarch, backend):
    if backend != "numpy":
        pytest.importorskip("jax")
    ua = SIM_UARCHES[uarch]
    assert_wave_matches(ua, TEST_ISA, _interesting_wave(TEST_ISA),
                        backend=backend)


def test_batch_identical_on_tpu_unit_model():
    ua, isa, truth = make_tpu_sim()
    names = list(truth)
    codes = [[Instr(names[(i + j) % len(names)],
                    {"op1": f"R{j % 4}", "op2": f"R{(j + 1) % 4}"})
              for j in range(4)] * reps for i, reps in
             enumerate((1, 10, 30, 110))]
    assert_wave_matches(ua, isa, codes)


def _random_wave(ua_seed, wave_seed, n_codes=8):
    ua, isa, truth = random_uarch_and_isa(ua_seed)
    rng = random.Random(wave_seed)
    names = list(truth)
    codes = []
    for _ in range(n_codes):
        body = [Instr(rng.choice(names),
                      {"op1": f"R{rng.randint(0, 5)}",
                       "op2": f"R{rng.randint(0, 5)}"})
                for _ in range(rng.randint(1, 5))]
        codes.append(body * rng.choice([1, 3, 10, 37, 110]))
    return ua, isa, codes


@pytest.mark.parametrize("seed", range(6))
def test_batch_identical_on_random_ground_truths(seed):
    """Seeded fallback for the hypothesis property below — always runs."""
    ua, isa, codes = _random_wave(seed, seed + 100)
    assert_wave_matches(ua, isa, codes)


def _generated_wave(seed, n_codes=8):
    """Random bodies over a small generated ISA (either layout): any of
    its variants, registers drawn from small pools per operand type (so
    operands often coincide), low or high divider values."""
    layout = ("skl", "snb")[seed % 2]
    isa, ua = generate_isa_and_uarch(seed, len(TEST_ISA) + 13, layout)
    rng = random.Random(seed + 100)
    names = isa.names()
    pools = {GPR: "R", VEC: "X", MEM: "RB"}

    def instr():
        spec = isa[rng.choice(names)]
        regs = {o.name: f"{pools[o.otype]}{rng.randint(0, 3)}"
                for o in spec.explicit_operands if o.otype in pools}
        return Instr(spec.name, regs, rng.choice(("low", "high")))

    codes = []
    for _ in range(n_codes):
        body = [instr() for _ in range(rng.randint(1, 5))]
        codes.append(body * rng.choice([1, 3, 10, 37, 110]))
    return ua, isa, codes


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("seed", range(6))
def test_batch_identical_on_generated_ground_truths(seed, backend):
    """Generated ISAs bring memory operands, vector registers, dividers,
    zero idioms and eliminable moves into the wave."""
    if backend != "numpy":
        pytest.importorskip("jax")
    ua, isa, codes = _generated_wave(seed)
    assert_wave_matches(ua, isa, codes, backend=backend)


if HAVE_HYPOTHESIS:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @given(ua_seed=st.integers(0, 500), wave_seed=st.integers(0, 10_000))
    @settings(max_examples=15, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    def test_batch_identical_property(ua_seed, wave_seed):
        """For ANY hidden ground truth and ANY wave, the array program and
        the scalar interpreter agree bit-for-bit."""
        ua, isa, codes = _random_wave(ua_seed, wave_seed, n_codes=4)
        assert_wave_matches(ua, isa, codes)


# ---------------------------------------------------------------------------
# wave-shape edge cases
# ---------------------------------------------------------------------------


def test_empty_wave():
    assert BatchSimMachine(SIM_SKL, TEST_ISA).run_batch([]) == []


def test_empty_and_single_instruction_sequences():
    codes = [[],                                              # empty code
             [Instr("ADD_R64_R64", {"op1": "R0", "op2": "R1"})],  # single
             [Instr("NOP", {})] * 50]                         # 0-μop body
    assert_wave_matches(SIM_SKL, TEST_ISA, codes)


def test_ragged_wave_lengths():
    body = independent_seq(TEST_ISA["IMUL_R64_R64"], RegPool(), 4)
    codes = [body * 1, body * 37, [], body * 110,
             [Instr("ADD_R64_R64", {"op1": "R0", "op2": "R1"})], body * 10]
    assert_wave_matches(SIM_SKL, TEST_ISA, codes)


@pytest.mark.parametrize("backend", ["jax"])
def test_device_backends_match_when_available(backend):
    pytest.importorskip("jax")
    body = independent_seq(TEST_ISA["ADD_R64_R64"], RegPool(), 3)
    codes = [body * 4, body * 11,
             [Instr("DIV_R64", {"op1": "R0", "op2": "R1"}, "high")] * 6,
             []]
    assert_wave_matches(SIM_SKL, TEST_ISA, codes, backend=backend)


@pytest.mark.parametrize("backend", ["jax"])
def test_device_backends_on_interesting_wave(backend):
    pytest.importorskip("jax")
    assert_wave_matches(SIM_SKL, TEST_ISA, _interesting_wave(TEST_ISA),
                        backend=backend)


@pytest.mark.parametrize("platform,want", [("tpu", "jax"), ("cpu", "numpy")])
def test_platform_picks_wave_backend(monkeypatch, platform, want):
    """With no backend given, the observed platform picks the kernel: the
    jax scan on a TPU, numpy elsewhere; an explicit backend still wins."""
    pytest.importorskip("jax")
    from repro.core import device_mesh
    monkeypatch.delenv("REPRO_SIM_BACKEND", raising=False)
    monkeypatch.setattr(device_mesh, "device_platform", lambda: platform)
    assert SimMachine(SIM_SKL, TEST_ISA).batch_machine().backend == want
    assert SimMachine(SIM_SKL, TEST_ISA, backend="numpy").batch_machine() \
        .backend == "numpy"


def test_unknown_instruction_raises_keyerror_like_scalar():
    b = BatchSimMachine(SIM_SKL, TEST_ISA)
    with pytest.raises(KeyError):
        b.run_batch([[Instr("NO_SUCH_INSTR", {})] * 4])


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_wide_port_machine_counts_exact(backend):
    """A uarch with more than 16 ports: the kernel's packed dispatch key
    must keep port counts and tie-breaks exact (regression: the port
    axis once shared bit space with the μop counts)."""
    if backend != "numpy":
        pytest.importorskip("jax")
    from repro.core.isa import GPR, ISA, InstrSpec, op
    ports = tuple(f"p{i:02d}" for i in range(18))
    b = {"WADD": beh(uop(frozenset(ports), ("op2",), ("op1",)))}
    ua = UArch("sim_wide", ports, 8, b, overhead_cycles=0)
    isa = ISA([InstrSpec("WADD", "WADD",
                         (op("op1", GPR, "w"), op("op2", GPR, "r")))])
    codes = [[Instr("WADD", {"op1": f"R{i}", "op2": f"R{i + 20}"})
              for i in range(20)] * reps for reps in (1, 5, 11)]
    assert_wave_matches(ua, isa, codes, backend=backend)


def test_body_period_detection():
    a = [Instr("ADD_R64_R64", {"op1": "R0", "op2": "R1"}) for _ in range(3)]
    assert _body_period([id(x) for x in a * 40]) == 3
    assert _body_period([id(x) for x in a]) == 3  # distinct objects
    assert _body_period([id(a[0])] * 7) == 1


# ---------------------------------------------------------------------------
# satellite: dispatch tie-break equivalence at port-count boundaries
# ---------------------------------------------------------------------------


def _tie_wave(ports, isa_ports=None):
    """A wave engineered so several ports repeatedly tie on *both*
    dispatch time and cumulative μop count: independent single-μop
    instructions whose port mask spans many ports, plus a narrower mask
    sharing a boundary port, issued wider than the port set so counts
    wrap around and re-equalize."""
    from repro.core.isa import GPR, ISA, InstrSpec, op
    wide_mask = frozenset(ports)
    narrow_mask = frozenset(list(sorted(ports))[:2])
    b = {"TIEW": beh(uop(wide_mask, ("op2",), ("op1",))),
         "TIEN": beh(uop(narrow_mask, ("op2",), ("op1",)))}
    ua = UArch("sim_tie", tuple(ports), 8, b, overhead_cycles=0)
    isa = ISA([InstrSpec("TIEW", "TIEW",
                         (op("op1", GPR, "w"), op("op2", GPR, "r"))),
               InstrSpec("TIEN", "TIEN",
                         (op("op1", GPR, "w"), op("op2", GPR, "r")))])
    codes = []
    for reps in (1, 3, 11):
        codes.append([Instr("TIEW", {"op1": f"R{i}", "op2": f"R{i + 32}"})
                      for i in range(3 * len(ports))] * reps)
        codes.append([Instr(("TIEW", "TIEN")[i % 2],
                            {"op1": f"R{i}", "op2": f"R{i + 40}"})
                      for i in range(2 * len(ports))] * reps)
    return ua, isa, codes


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_tie_break_equivalence_at_port_count_boundaries(backend):
    """The numpy kernel breaks dispatch ties with one packed
    (time, count, port) argmin key; the device kernel uses a two-pass min.
    On waves where several ports tie on both time and count, every backend
    must pick the same port as the scalar oracle — checked on the widest
    SIM_UARCHES port set and on an 18-port machine (so the port axis
    exceeds 16 and the packed key's field widths are exercised)."""
    if backend != "numpy":
        pytest.importorskip("jax")
    widest = max(SIM_UARCHES.values(), key=lambda u: len(u.ports))
    ua, isa, codes = _tie_wave(sorted(widest.ports))
    assert_wave_matches(ua, isa, codes, backend=backend)
    ua18, isa18, codes18 = _tie_wave([f"p{i:02d}" for i in range(18)])
    assert_wave_matches(ua18, isa18, codes18, backend=backend)


# ---------------------------------------------------------------------------
# satellite: lowering cache (hits/misses in engine_stats, eviction bound,
# bit-identical warm re-runs)
# ---------------------------------------------------------------------------


def _random_codes(seed, n_bodies=6):
    rng = random.Random(seed)
    names = ["ADD_R64_R64", "IMUL_R64_R64", "SHLD_R64_R64_I8",
             "MOV_R64_M64", "ADC_R64_R64", "DIV_R64"]
    codes = []
    for _ in range(n_bodies):
        body = independent_seq(TEST_ISA[rng.choice(names)], RegPool(),
                               rng.randint(2, 6))
        codes.append(body * 10)
        codes.append(body * 110)
    return codes


def test_lowering_cache_warm_wave_is_bit_identical():
    m = BatchSimMachine(SIM_SKL, TEST_ISA, min_lanes=1)
    rng = random.Random(7)
    names = ["ADD_R64_R64", "IMUL_R64_R64", "SHLD_R64_R64_I8",
             "MOV_R64_M64", "ADC_R64_R64", "DIV_R64"]
    bodies = [independent_seq(TEST_ISA[rng.choice(names)], RegPool(),
                              rng.randint(2, 6)) for _ in range(6)]
    codes = [b * n for b in bodies for n in (10, 110)]
    cold = m.run_batch(codes)
    assert m.lowering_stats["misses"] > 0
    misses0 = m.lowering_stats["misses"]
    # fresh Instr objects (a new wave of content-identical Experiments,
    # unrolled body * n the way the engine does): lowering is skipped
    bodies2 = [[Instr(i.spec, dict(i.regs), i.value_hint) for i in b]
               for b in bodies]
    codes2 = [b * n for b in bodies2 for n in (10, 110)]
    warm = m.run_batch(codes2)
    assert m.lowering_stats["misses"] == misses0
    assert m.lowering_stats["hits"] >= misses0
    for a, b in zip(cold, warm):
        assert a.cycles == b.cycles and a.port_uops == b.port_uops


def test_lowering_cache_hits_when_engine_misses_on_params():
    """The ISSUE scenario: two Experiments share a body but differ in
    Algorithm-2 params — the engine cache misses (different key) but the
    machine's lowering cache hits, and the counters surface through
    engine_stats."""
    from repro.core.engine import Experiment, MeasurementEngine

    m = SimMachine(SIM_SKL, TEST_ISA, min_lanes=1)
    eng = MeasurementEngine(m)
    bodies = [tuple(independent_seq(TEST_ISA[n], RegPool(), 4))
              for n in ("IMUL_R64_R64", "ADC_R64_R64", "SHLD_R64_R64_I8")]
    eng.submit([Experiment.of(b) for b in bodies])
    s = eng.stats.as_dict()
    assert s["lowering_misses"] > 0
    # same bodies, different unroll params: engine miss, lowering hit on
    # the already-lowered n=110 cut (n=30 is a new prefix cut)
    eng.submit([Experiment.of(b, n_small=30, n_large=110)
                for b in bodies])
    s2 = eng.stats.as_dict()
    assert s2["executions"] == 6          # engine cache missed on params
    assert s2["lowering_hits"] > s["lowering_hits"]
    assert s2["lowering_misses"] <= s["lowering_misses"] + len(bodies)


def test_lowering_counters_are_per_engine_deltas():
    """A fresh engine on a warm (reused) machine must report only its own
    share of the backend's lowering work — not the machine's lifetime
    totals, which include prior engines' campaigns."""
    from repro.core.engine import Experiment, MeasurementEngine

    m = SimMachine(SIM_SKL, TEST_ISA, min_lanes=1)
    bodies = [tuple(independent_seq(TEST_ISA[n], RegPool(), 4))
              for n in ("IMUL_R64_R64", "ADC_R64_R64")]
    eng1 = MeasurementEngine(m)
    eng1.submit([Experiment.of(b) for b in bodies])
    assert eng1.stats.lowering_misses == m.lowering_stats["misses"] > 0
    eng2 = MeasurementEngine(m)         # fresh engine, warm machine
    eng2.submit([Experiment.of(b) for b in bodies])
    # identical wave: every lowering probe hits, so THIS engine's miss
    # count is zero even though the machine's totals are not
    assert eng2.stats.lowering_misses == 0
    assert eng2.stats.lowering_hits > 0
    assert m.lowering_stats["misses"] == eng1.stats.lowering_misses


def test_lowering_deltas_survive_backend_rebuild():
    """``set_table_index`` rebuilds the machine's batched backend, whose
    counters restart at zero; a previously attached engine must
    re-baseline (the stats dict identity changed) instead of reporting
    negative lowering deltas against its stale snapshot."""
    from repro.core.engine import Experiment, MeasurementEngine

    m = SimMachine(SIM_SKL, TEST_ISA, min_lanes=1)
    eng = MeasurementEngine(m)
    bodies = [tuple(independent_seq(TEST_ISA[n], RegPool(), 4))
              for n in ("IMUL_R64_R64", "ADC_R64_R64")]
    eng.submit([Experiment.of(b) for b in bodies])
    assert eng.stats.lowering_misses > 0
    m.set_table_index(UopTableIndex.for_isa(TEST_ISA))   # resets backend
    eng.submit([Experiment.of(b, n_small=20) for b in bodies])
    assert eng.stats.lowering_misses >= 0
    assert eng.stats.lowering_hits >= 0


def test_lowering_cache_eviction_bound():
    m = BatchSimMachine(SIM_SKL, TEST_ISA, min_lanes=1,
                        lower_cache_entries=3)
    codes = _random_codes(11, n_bodies=5)   # 10 (body, cut) entries
    ref = [SimMachine(SIM_SKL, TEST_ISA).run(list(c)) for c in codes]
    got = m.run_batch(codes)
    assert len(m._lower_cache) <= 3
    assert m.lowering_stats["evictions"] > 0
    for a, b in zip(ref, got):
        assert a.cycles == b.cycles and a.port_uops == b.port_uops
    # a second pass still returns correct results (some entries evicted)
    got2 = m.run_batch(codes)
    for a, b in zip(ref, got2):
        assert a.cycles == b.cycles and a.port_uops == b.port_uops


# ---------------------------------------------------------------------------
# the wave pass: steady-state copies unrolled once per wave
# ---------------------------------------------------------------------------


def _random_periodic_body(rng):
    """A body of a few parts: independent instructions, eliminated-move
    chains (period 3 on sim_skl), zero idioms feeding a multiply, store ->
    load round trips through one memory cell (forwarded), and
    partial-register writes read whole (nonzero producer deltas)."""
    names = ["ADD_R64_R64", "IMUL_R64_R64", "SHLD_R64_R64_I8", "DIV_R64",
             "MOV_R64_M64", "ADD_R64_M64", "MOVAPS_X_X", "PADDD_X_X"]
    reg = lambda: f"R{rng.randrange(6)}"   # noqa: E731
    body = []
    for _ in range(rng.randint(1, 3)):
        kind = rng.choice(["indep", "elim", "zero", "store", "partial"])
        if kind == "indep":
            body += independent_seq(TEST_ISA[rng.choice(names)], RegPool(),
                                    rng.randint(1, 3))
        elif kind == "elim":
            k = rng.randint(2, 4)
            spec = rng.choice(["MOV_R64_R64", "MOVZX_R64_R16"])
            body += [Instr(spec, {"op1": f"R{(i + 1) % k}",
                                  "op2": f"R{i % k}"}) for i in range(k)]
        elif kind == "zero":
            r = reg()
            body += [Instr("XOR_R64_R64", {"op1": r, "op2": r}),
                     Instr("IMUL_R64_R64", {"op1": r, "op2": reg()})]
        elif kind == "store":
            r = reg()
            body += [Instr("MOV_M64_R64", {"mem": "RB0", "op1": r}),
                     Instr("MOV_R64_M64", {"op1": r, "mem": "RB0"})]
        else:
            r = reg()
            body += [Instr("SETC_R8", {"op1": r}),
                     Instr("ADD_R64_R64", {"op1": reg(), "op2": r})]
    return body


def _plain_unrolling(m, code):
    """``code`` lowered as one copy: its period forced to its length, so
    the walk emits every row and the wave pass tiles nothing."""
    from repro.core.batch_sim import _materialize, _WaveRows
    acc = _WaveRows()
    walk = m._lower(code, {}, acc, [1], len(code))
    assert walk.tile is None
    (made,), _ = _materialize(acc, [walk])
    return made[1]


def _assert_same_prog(got, want):
    assert (got.n_rows, got.max_r) == (want.n_rows, want.max_r)
    for f in ("issue", "mask", "lat", "blk", "vis", "prod", "delta",
              "finals"):
        a, b = getattr(got, f), getattr(want, f)
        assert a.dtype == b.dtype and a.shape == b.shape, f
        assert np.array_equal(a, b), f


def _steady_cuts(m, base):
    """Cuts 1, c1 - 1, c1, c1 + 1, 10 and 110 of ``base``, where copy c1
    is where its walk finds the steady state (none: 1, 10, 110)."""
    from repro.core.batch_sim import _WaveRows
    walk = m._lower(base * 110, {}, _WaveRows(), [110], len(base))
    cuts = {1, 10, 110}
    if walk.tile is not None:
        c1 = walk.tile[1]
        cuts |= {c for c in (c1 - 1, c1, c1 + 1) if 1 <= c <= 110}
    return sorted(cuts), walk.tile


@pytest.mark.parametrize("seed", range(4))
def test_wave_pass_equals_plain_unrolling(seed):
    from repro.core.batch_sim import _code_period
    rng = random.Random(seed)
    m = BatchSimMachine(SIM_SKL, TEST_ISA, min_lanes=1)
    bases, tiles = [], []
    for _ in range(6):
        body = _random_periodic_body(rng)
        p = _code_period(body * 110)
        bases.append(body[:p])
    # a body that never repeats: one copy, nothing tiled
    names = ["ADD_R64_R64", "IMUL_R64_R64", "ADC_R64_R64", "SHLD_R64_R64_I8"]
    bases.append([Instr(rng.choice(names), {"op1": f"R{i % 6}",
                                            "op2": f"R{(i * 7 + 3) % 6}"})
                  for i in range(rng.randint(7, 13))])
    codes, owner = [], []
    for b, base in enumerate(bases):
        cuts, tile = _steady_cuts(m, base)
        tiles.append(tile)
        if b == len(bases) - 1:
            cuts = [1]
        for nc in cuts:
            codes.append(base * nc)
            owner.append(b)
    assert any(t is not None and t[3] for t in tiles)   # rows were tiled
    progs = m._lower_wave(codes, [list(range(len(codes)))])
    assert m.lowering_stats["hits"] == 0
    for i, code in enumerate(codes):
        _assert_same_prog(progs[i], _plain_unrolling(m, code))
    # one body's cuts are prefixes of one set of rows
    longest = {owner[i]: i for i in range(len(codes))}
    for i in range(len(codes)):
        j = longest[owner[i]]
        if progs[i].n_rows:
            assert np.shares_memory(progs[i].issue, progs[j].issue)
    assert any(progs[i].delta.any() for i in range(len(codes)))

    # a second wave: content-equal copies of half the bodies at their old
    # cuts (cache hits), at new cuts (misses), and new bodies (misses)
    fresh = [[Instr(x.spec, dict(x.regs), x.value_hint) for x in base]
             for base in bases[:3]]
    codes2 = [f * nc for f in fresh for nc in (1, 10, 110, 37, 64)]
    codes2 += [b * nc for b in (_random_periodic_body(rng)
                                for _ in range(3)) for nc in (10, 110)]
    h0, m0 = m.lowering_stats["hits"], m.lowering_stats["misses"]
    progs2 = m._lower_wave(codes2, [list(range(len(codes2)))])
    assert m.lowering_stats["hits"] - h0 == 9
    assert m.lowering_stats["misses"] - m0 > 6
    for i, code in enumerate(codes2):
        _assert_same_prog(progs2[i], _plain_unrolling(m, code))


def test_wave_lower_span_counts_rows():
    """``wave.lower`` carries the rows the loop walked and the rows the
    wave pass wrote: one unrolling per missing body, at its largest
    missing cut."""
    rng = random.Random(11)
    m = BatchSimMachine(SIM_SKL, TEST_ISA, min_lanes=1)
    bodies = [_random_periodic_body(rng) for _ in range(4)]
    codes = [b * nc for b in bodies for nc in (10, 110)]
    progs, events = _traced(lambda: m._lower_wave(codes, [list(range(8))]))
    # second wave: bodies 0-1 hit at 10 and 110 and miss at 50; 2-3 hit
    codes2 = [b * nc for b in bodies for nc in (10, 110)]
    codes2 += [b * 50 for b in bodies[:2]]
    progs2, events2 = _traced(lambda: m._lower_wave(
        codes2, [list(range(len(codes2)))]))
    (sp,), (sp2,) = ([e["args"] for e in ev if e["name"] == "wave.lower"]
                     for ev in (events, events2))
    assert sp["rows_out"] == sum(progs[2 * b + 1].n_rows for b in range(4))
    assert 0 < sp["rows_native"] <= sp["rows_out"]
    assert (sp["hits"], sp["misses"]) == (0, 8)
    assert sp2["rows_out"] == progs2[8].n_rows + progs2[9].n_rows
    assert 0 < sp2["rows_native"] <= sp2["rows_out"]
    assert (sp2["hits"], sp2["misses"]) == (8, 2)


# ---------------------------------------------------------------------------
# satellite: min_lanes is a constructor parameter on both machines
# ---------------------------------------------------------------------------


def test_min_lanes_constructor_parameter():
    codes = _random_codes(3, n_bodies=2)
    ref = [SimMachine(SIM_SKL, TEST_ISA).run(list(c)) for c in codes]
    for m in (SimMachine(SIM_SKL, TEST_ISA, min_lanes=1),
              SimMachine(SIM_SKL, TEST_ISA, min_lanes=10 ** 6),
              BatchSimMachine(SIM_SKL, TEST_ISA, min_lanes=1),
              BatchSimMachine(SIM_SKL, TEST_ISA, min_lanes=10 ** 6)):
        got = m.run_batch(codes)
        for a, b in zip(ref, got):
            assert a.cycles == b.cycles and a.port_uops == b.port_uops
    forced = BatchSimMachine(SIM_SKL, TEST_ISA, min_lanes=10 ** 6)
    forced.run_batch(codes)
    assert forced._scalar is not None       # everything went scalar
    kerneled = BatchSimMachine(SIM_SKL, TEST_ISA, min_lanes=1)
    kerneled.run_batch(codes)
    assert kerneled._scalar is None         # everything took the kernel
    # SimMachine forwards its min_lanes to the lazily-built backend
    sm = SimMachine(SIM_SKL, TEST_ISA, min_lanes=5)
    sm.run_batch(codes)
    assert sm._batch.min_lanes == 5


# ---------------------------------------------------------------------------
# satellite/tentpole: device-kernel compile accounting (one per bucket)
# ---------------------------------------------------------------------------


def test_jax_kernel_compiles_at_most_once_per_bucket():
    pytest.importorskip("jax")
    codes = _random_codes(19)
    m = BatchSimMachine(SIM_SKL, TEST_ISA, backend="jax", min_lanes=1)
    m.run_batch(codes)
    st = m.device_stats()
    assert st["compiles"] <= len(st["buckets"])
    compiles0 = st["compiles"]
    calls0 = st["kernel_calls"]
    m.run_batch(codes)                       # warm: same shape buckets
    st2 = m.device_stats()
    assert st2["compiles"] == compiles0, "warm wave recompiled a kernel"
    assert st2["kernel_calls"] > calls0
    # a fresh machine over the same shapes shares the module-wide cache
    m2 = BatchSimMachine(SIM_SKL, TEST_ISA, backend="jax", min_lanes=1)
    m2.run_batch(codes)
    assert m2.device_stats()["compiles"] == 0


def test_backend_env_selection(monkeypatch):
    pytest.importorskip("jax")
    monkeypatch.setenv("REPRO_SIM_BACKEND", "jax")
    m = SimMachine(SIM_SKL, TEST_ISA)
    codes = _random_codes(23, n_bodies=3)
    got = m.run_batch(codes)
    assert m._batch.backend == "jax"
    ref = [SimMachine(SIM_SKL, TEST_ISA, backend="numpy").run(list(c))
           for c in codes]
    for a, b in zip(ref, got):
        assert a.cycles == b.cycles and a.port_uops == b.port_uops


# ---------------------------------------------------------------------------
# tentpole: the kernel lock serializes kernels, not host prep
# ---------------------------------------------------------------------------


class _CountingLock:
    def __init__(self):
        import threading
        self._lock = threading.Lock()
        self.entries = 0

    def __enter__(self):
        self._lock.acquire()
        self.entries += 1
        return self

    def __exit__(self, *exc):
        self._lock.release()
        return False


def test_kernel_lock_reaches_the_machine():
    from repro.core.engine import Experiment, MeasurementEngine

    # numpy explicitly: only the GIL-bound kernels take the campaign
    # kernel lock — device backends serialize dispatch on their own
    # per-device-subset locks instead (see core/device_mesh.py)
    m = SimMachine(SIM_SKL, TEST_ISA, backend="numpy", min_lanes=1)
    eng = MeasurementEngine(m)
    lock = _CountingLock()
    exps = [Experiment.of(independent_seq(TEST_ISA[n], RegPool(), 3))
            for n in ("ADD_R64_R64", "IMUL_R64_R64", "LEA_R64",
                      "ADC_R64_R64")]
    res = eng.submit(exps, kernel_lock=lock)
    assert lock.entries > 0
    ref = eng.submit(exps)   # cached now; also: results sane without lock
    for a, b in zip(res, ref):
        assert a.cycles == b.cycles and a.port_uops == b.port_uops


def test_scheduler_execute_lock_travels_as_kernel_lock():
    from repro.core.engine import Experiment, MeasurementEngine
    from repro.core.plan import WaveScheduler

    # numpy explicitly: the execute lock only serializes GIL-bound kernels
    m = SimMachine(SIM_SKL, TEST_ISA, backend="numpy", min_lanes=1)
    lock = _CountingLock()
    sched = WaveScheduler(MeasurementEngine(m), execute_lock=lock)

    def plan():
        c = yield [Experiment.of(independent_seq(
            TEST_ISA["ADD_R64_R64"], RegPool(), 4))]
        return c[0].cycles

    out = sched.run([plan(), plan()])
    assert out[0] == out[1] > 0
    assert lock.entries > 0


def test_legacy_run_batch_without_kernel_lock_param_still_works():
    """Machines predating the kernel-lock protocol run entirely under the
    lock (machine_run_batch introspects the signature)."""
    from repro.core.engine import machine_run_batch

    class OldMachine:
        name = "sim_skl"

        def __init__(self):
            self._m = SimMachine(SIM_SKL, TEST_ISA)

        def run_batch(self, codes):
            return self._m.run_batch(codes)

    lock = _CountingLock()
    codes = _random_codes(5, n_bodies=2)
    got = machine_run_batch(OldMachine(), codes, kernel_lock=lock)
    assert lock.entries == 1
    ref = [SimMachine(SIM_SKL, TEST_ISA).run(list(c)) for c in codes]
    for a, b in zip(ref, got):
        assert a.cycles == b.cycles and a.port_uops == b.port_uops


# ---------------------------------------------------------------------------
# machine-level protocol: SimMachine routes waves to the batched backend
# ---------------------------------------------------------------------------


def test_simmachine_run_batch_matches_scalar_loop():
    m = SimMachine(SIM_SKL, TEST_ISA)
    codes = _interesting_wave(TEST_ISA)[:10]
    got = m.run_batch(codes)
    for c, code in zip(got, codes):
        ref = m.run(list(code))
        assert c.cycles == ref.cycles and c.port_uops == ref.port_uops


# ---------------------------------------------------------------------------
# satellite: divider-occupancy gate (occ includes the value-dependent extra)
# ---------------------------------------------------------------------------


def _slow_div_uarch():
    """A divider-like μop with *occupancy 1* whose value-dependent extra
    must still block the port: the old ``u.occupancy > 1`` gate dropped
    the blocking entirely for this shape."""
    b = {"SDIV_R64_R64": beh(
        uop(frozenset("0"), ("op2",), ("op1",), lat=5, occ=1),
        divider_extra=10),
        "LEA_R64": beh(uop(frozenset("1"), ("op2",), ("op1",)))}
    return UArch("sim_slowdiv", tuple("01"), 4, b, overhead_cycles=0)


def _sdiv_isa():
    from repro.core.isa import GPR, ISA, InstrSpec, op
    isa = ISA()
    isa.add(InstrSpec("SDIV_R64_R64", "SDIV",
                      (op("op1", GPR, "w"), op("op2", GPR, "r")),
                      uses_divider=True))
    isa.add(InstrSpec("LEA_R64", "LEA",
                      (op("op1", GPR, "w"), op("op2", GPR, "r"))))
    return isa


def test_high_value_divide_occupies_port_on_single_occupancy_uop():
    ua, isa = _slow_div_uarch(), _sdiv_isa()
    m = SimMachine(ua, isa)
    # two independent high-value divides on the same port: the second must
    # wait out the first's effective occupancy (1 + 10), then lat 5 + 10
    hi = [Instr("SDIV_R64_R64", {"op1": "R0", "op2": "R1"}, "high"),
          Instr("SDIV_R64_R64", {"op1": "R2", "op2": "R3"}, "high")]
    assert m.run(hi).cycles == 11 + 15
    # low values: fully pipelined, second dispatches one cycle later
    lo = [Instr("SDIV_R64_R64", {"op1": "R0", "op2": "R1"}),
          Instr("SDIV_R64_R64", {"op1": "R2", "op2": "R3"})]
    assert m.run(lo).cycles == 1 + 5
    # and the batched backend agrees on the whole regression wave
    assert_wave_matches(ua, isa, [hi * 12, lo * 12, hi * 110])


# ---------------------------------------------------------------------------
# compiled tables: round-trip + campaign-wide sharing
# ---------------------------------------------------------------------------


def test_compiled_tables_round_trip_behaviors():
    comp = compile_uarch(SIM_SKL, TEST_ISA)
    index = comp.index
    assert comp.ports == tuple(sorted(SIM_SKL.ports))
    for name, behavior in SIM_SKL.behaviors.items():
        i = index.idx[name]
        off, cnt = comp.behavior_rows(i, same_reg=False)
        assert cnt == len(behavior.uops)
        for j, u in enumerate(behavior.uops):
            row = off + j
            mask = {p for b, p in enumerate(comp.ports)
                    if comp.port_mask[row] >> b & 1}
            assert mask == set(u.ports)
            assert comp.latency[row] == u.latency
            assert comp.occupancy[row] == u.occupancy
            reads = [comp.decode_slot(i, s) for s in comp.reads[row]
                     if s >= 0]
            writes = [comp.decode_slot(i, s) for s in comp.writes[row]
                      if s >= 0]
            assert tuple(reads) == u.reads
            assert tuple(writes) == u.writes
        assert comp.elim_period[i] == behavior.elim_period
        assert comp.divider_extra[i] == behavior.divider_extra
        if behavior.same_reg is not None:
            sr_off, sr_cnt = comp.behavior_rows(i, same_reg=True)
            assert sr_cnt == len(behavior.same_reg.uops)


def test_campaign_shares_one_table_index_across_uarches():
    machines = [SimMachine(ua, TEST_ISA) for ua in SIM_UARCHES.values()]
    Campaign(instr_names=["ADD_R64_R64"]).run(machines, TEST_ISA)
    indexes = {id(m._table_index) for m in machines}
    assert len(indexes) == 1 and None not in {m._table_index
                                              for m in machines}
    # the shared index drives each machine's compiled tables
    comps = [compile_uarch(m.uarch, TEST_ISA, m._table_index)
             for m in machines]
    assert all(c.index is comps[0].index for c in comps)
    assert all(c.index.names == comps[0].index.names for c in comps)


def test_engine_submits_waves_through_run_batch():
    """The measurement engine's miss-set reaches the machine as ONE wave
    (not a per-experiment loop) when the machine speaks the protocol."""
    from repro.core.engine import Experiment, MeasurementEngine

    class WaveRecorder:
        name = "sim_skl"
        counters_available = True

        def __init__(self):
            self._m = SimMachine(SIM_SKL, TEST_ISA)
            self.waves = []

        def run_batch(self, codes):
            self.waves.append(len(codes))
            return self._m.run_batch(codes)

    rec = WaveRecorder()
    eng = MeasurementEngine(rec)
    exps = [Experiment.of(independent_seq(TEST_ISA[n], RegPool(), 3))
            for n in ("ADD_R64_R64", "IMUL_R64_R64", "LEA_R64")]
    eng.submit(exps + exps)   # duplicates dedup away
    assert rec.waves == [6]   # 3 unique experiments x (n_small, n_large)


@pytest.mark.parametrize("backend", ["jax"])
def test_sharded_chunk_with_uniform_lengths(backend):
    """Regression: two lane shards of one chunk map to the SAME shape
    bucket when every sequence has the same length — the shards must not
    share a packing-buffer slot (the second pack would overwrite the
    first's inputs before either kernel dispatches)."""
    pytest.importorskip("jax")
    from repro.core.batch_sim import _DeviceExec
    rng = random.Random(41)
    lanes = 2 * _DeviceExec._SHARD_MIN_LANES
    names = ["ADD_R64_R64", "IMUL_R64_R64", "ADC_R64_R64", "MULPS_X_X"]
    codes = [independent_seq(TEST_ISA[rng.choice(names)], RegPool(), 4) * 12
             for _ in range(lanes)]
    assert_wave_matches(SIM_SKL, TEST_ISA, codes, backend=backend)


@pytest.mark.parametrize("backend", ["jax"])
def test_all_zero_uop_shard(backend):
    """Regression: a lane shard whose programs all lower to zero μops
    (zero-idiom bodies) must fill in overhead-only Counters instead of
    crashing extraction — the all-empty guard has to run per shard, not
    just per chunk."""
    pytest.importorskip("jax")
    from repro.core.batch_sim import _DeviceExec
    lanes = _DeviceExec._SHARD_MIN_LANES
    adds = [[Instr("ADD_R64_R64", {"op1": f"R{i % 8}",
                                   "op2": f"R{i % 8 + 8}"})] * 8
            for i in range(lanes)]
    zeros = [[Instr("XOR_R64_R64", {"op1": f"R{i % 8}",
                                    "op2": f"R{i % 8}"})] * 8
             for i in range(lanes)]
    assert_wave_matches(SIM_SKL, TEST_ISA, adds + zeros, backend=backend)


@pytest.mark.parametrize("backend", ["jax"])
def test_buffer_reuse_with_narrower_read_width(backend):
    """Regression: a reused device buffer whose previous occupant had a
    wider per-μop read width (max_r) must not leak stale producer columns
    into a later lane with a narrower width at the same rows — the
    kernels read ALL R producer columns of every valid row."""
    pytest.importorskip("jax")
    m = BatchSimMachine(SIM_SKL, TEST_ISA, backend=backend, min_lanes=1)
    scalar = SimMachine(SIM_SKL, TEST_ISA)
    # wave A: partial-register-stall pairs — the ADD's second producer
    # column carries a nonzero stall *delta*, which the kernels add
    # unconditionally (not gated on producer >= 0)
    wave_a = [[Instr("SETC_R8", {"op1": f"R{i + 1}"}),
               Instr("ADD_R64_R64",
                     {"op1": f"R{i + 8}", "op2": f"R{i + 1}"})] * 24
              for i in range(6)]   # 48 rows — BSWAP below is 2 μops/instr
    # wave B: identical (S, E, R) bucket — one two-read lane keeps the R
    # bucket at 2 — but the other lanes are fully independent single-read
    # BSWAPs (max_r 1) whose rows overlap wave A's stale delta column; a
    # leaked stall delta inflates their ready times above the real
    # issue-limited critical path
    wave_b = wave_a[:1] + \
             [[Instr("BSWAP_R64", {"op1": f"Q{lane}_{j}"})
               for j in range(24)] for lane in range(5)]
    for wave in (wave_a, wave_b, wave_b):   # later passes reuse slots
        got = m.run_batch(wave)
        for c, code in zip(got, wave):
            ref = scalar.run(list(code))
            assert c.cycles == ref.cycles and c.port_uops == ref.port_uops


def test_device_slot_leased_until_extraction():
    """Regression: a packing-buffer slot must stay leased until its
    chunk's results have been *extracted* (``release()`` in
    ``_finalize_device``), not merely until its kernel future resolves —
    ``_extract`` reads ``pk.vis``, which aliases the slot's vis buffer,
    so freeing the slot at dispatch let a fast same-bucket chunk k+1
    re-zero it mid-extraction and corrupt chunk k's cycle counts."""
    pytest.importorskip("jax")
    from repro.core.batch_sim import _DeviceExec

    m = BatchSimMachine(SIM_SKL, TEST_ISA, backend="jax", min_lanes=1)
    dev = _DeviceExec(m._comp)
    s1 = dev.acquire(8, 8, 1)
    # with no kernel in flight at all (the state a resolved future used
    # to leave behind), a leased slot must never be handed out again
    s2 = dev.acquire(8, 8, 1)
    assert s2 is not s1
    assert s1.leased and s2.leased
    s1.release()                        # extraction completed
    assert dev.acquire(8, 8, 1) is s1   # only now is the slot reusable


def test_kernel_failure_releases_slots(monkeypatch):
    """A device kernel failure must not leak leased buffer slots: the
    error path waits out in-flight shard kernels and releases every
    slot — and the wave then *recovers* by degrading down the backend
    chain (jax -> numpy), with bit-identical results and the reroute
    counted in ``degraded_stats()``."""
    pytest.importorskip("jax")
    import repro.core.batch_sim as bs

    m = BatchSimMachine(SIM_SKL, TEST_ISA, backend="jax", min_lanes=1)
    codes = _random_codes(12, n_bodies=4)
    real = bs._run_kernel
    calls = []

    def boom(fn, args):
        calls.append(1)
        raise RuntimeError("transient kernel failure")

    monkeypatch.setattr(bs, "_run_kernel", boom)
    ref = [SimMachine(SIM_SKL, TEST_ISA).run(list(c)) for c in codes]
    with pytest.warns(UserWarning, match="degraded jax->numpy"):
        got = m.run_batch(codes)     # degrades to numpy, does not raise
    assert calls
    for ring in m._device._rings.values():
        assert all(not s.leased for s in ring)
    assert m.degraded_stats().get("jax->numpy", 0) >= 1
    for a, b in zip(ref, got):
        assert a.cycles == b.cycles and a.port_uops == b.port_uops
    # with the kernel healthy again the device path serves the next wave
    monkeypatch.setattr(bs, "_run_kernel", real)
    got2 = m.run_batch(codes)
    for a, b in zip(ref, got2):
        assert a.cycles == b.cycles and a.port_uops == b.port_uops


def test_simmachine_degenerate_wave_respects_min_lanes():
    body = independent_seq(TEST_ISA["ADD_R64_R64"], RegPool(), 3)
    m = SimMachine(SIM_SKL, TEST_ISA, min_lanes=1)
    got = m.run_batch([body * 10, body * 110])   # 2 codes: < 4, >= min
    assert m._batch is not None                   # kernel path was taken
    ref = SimMachine(SIM_SKL, TEST_ISA)
    for c, code in zip(got, (body * 10, body * 110)):
        r = ref.run(list(code))
        assert c.cycles == r.cycles and c.port_uops == r.port_uops
    plain = SimMachine(SIM_SKL, TEST_ISA)
    plain.run_batch([body * 10, body * 110])
    assert plain._batch is None                   # default still scalar


def test_characterize_xml_identical_across_backends():
    """End-to-end: a characterization driven through the device backend
    exports byte-identical model XML to the numpy backend (the whole
    pipeline — scheduler fusion, engine cache, lowering cache, bucketed
    kernels, pipelined dispatch — preserves every measured number)."""
    pytest.importorskip("jax")
    from repro.core import model_io
    from repro.core.characterize import characterize
    from repro.core.engine import MeasurementEngine

    names = ["ADD_R64_R64", "MOVQ2DQ_X_X", "DIV_R64", "SHLD_R64_R64_I8",
             "MUL_R64", "AESDEC_X_X"]
    ref = characterize(
        MeasurementEngine(SimMachine(SIM_SKL, TEST_ISA, backend="numpy")),
        TEST_ISA, names)
    ref_xml = model_io.to_xml(ref, TEST_ISA)
    m = SimMachine(SIM_SKL, TEST_ISA, backend="jax")
    model = characterize(MeasurementEngine(m), TEST_ISA, names)
    assert model_io.to_xml(model, TEST_ISA) == ref_xml
    assert m.lowering_stats["misses"] > 0
    assert m.device_stats()["compiles"] <= len(m.device_stats()["buckets"])


def test_campaign_runs_on_device_backend(monkeypatch):
    """A threaded multi-uarch campaign with the jax wave-execution backend
    (selected via REPRO_SIM_BACKEND): the shared execute lock rides down
    to the kernels, host prep overlaps, results match the numpy campaign."""
    pytest.importorskip("jax")
    names = ["ADD_R64_R64", "MUL_R64", "ADC_R64_R64"]
    ref = Campaign(instr_names=names).run(
        [SimMachine(ua, TEST_ISA) for ua in SIM_UARCHES.values()],
        TEST_ISA)
    monkeypatch.setenv("REPRO_SIM_BACKEND", "jax")
    res = Campaign(instr_names=names).run(
        [SimMachine(ua, TEST_ISA) for ua in SIM_UARCHES.values()],
        TEST_ISA)
    assert set(res.models) == set(SIM_UARCHES)
    for name, model in res.models.items():
        for n in names:
            assert model[n].port_usage.usage == \
                ref.models[name][n].port_usage.usage
            assert model[n].uops == ref.models[name][n].uops


def test_legacy_measure_results_unchanged_by_batch_default():
    """measure() through the engine equals a hand-rolled scalar
    Algorithm-2 differencing."""
    from repro.core.machine import measure

    seq = independent_seq(TEST_ISA["ADC_R64_R64"], RegPool(), 4)
    m = SimMachine(SIM_SKL, TEST_ISA)
    got = measure(m, seq)
    s = SimMachine(SIM_SKL, TEST_ISA)
    c1, c2 = s.run(seq * 10), s.run(seq * 110)
    assert got.cycles == (c2.cycles - c1.cycles) / 100
    assert as_engine(m).stats.executions == 1


# ---------------------------------------------------------------------------
# tracing: the dispatch counters, one dispatch path, the kernels' names
# ---------------------------------------------------------------------------


def _traced(fn):
    from repro.obs.tracer import Tracer, set_tracer
    tr = Tracer(enabled=True)
    prev = set_tracer(tr)
    try:
        return fn(), tr.events()
    finally:
        set_tracer(prev)


def test_dispatch_span_counts_match_packed_shards(monkeypatch):
    """``wave.dispatch`` carries the shards' bucket steps, lane-steps and
    real μop rows, as counted by hand from the packed buffers."""
    pytest.importorskip("jax")
    from repro.core.batch_sim import _DeviceExec
    m = BatchSimMachine(SIM_SKL, TEST_ISA, backend="jax", min_lanes=1)
    m._device_exec().n_workers = 3      # several shards per chunk
    seen = []
    real = _DeviceExec.dispatch

    def dispatch(self, jobs, kernel_lock=None):
        seen.append((len(jobs),
                     sum(pk.issue.shape[1] for pk, _ in jobs),
                     sum(pk.issue.size for pk, _ in jobs),
                     sum(int(pk.valid.sum()) for pk, _ in jobs)))
        return real(self, jobs, kernel_lock)

    monkeypatch.setattr(_DeviceExec, "dispatch", dispatch)
    rng = random.Random(5)
    lanes = 3 * _DeviceExec._SHARD_MIN_LANES
    codes = [independent_seq(TEST_ISA[rng.choice(["ADD_R64_R64",
                                                  "IMUL_R64_R64"])],
                             RegPool(), rng.randint(2, 3))
             * rng.randint(10, 20) for _ in range(lanes)]   # one chunk
    _, events = _traced(lambda: m.run_batch(codes))
    got = [(e["args"]["shards"], e["args"]["steps"], e["args"]["slots"],
            e["args"]["rows"])
           for e in events if e["name"] == "wave.dispatch"]
    assert got == seen
    assert max(n for n, *_ in seen) > 1
    assert all(0 < rows <= slots for *_, slots, rows in seen)


def test_traced_and_untraced_waves_take_one_path():
    """Tracing changes neither the answers nor the executor's stats."""
    pytest.importorskip("jax")
    codes = _interesting_wave(TEST_ISA)
    plain = BatchSimMachine(SIM_SKL, TEST_ISA, backend="jax", min_lanes=1)
    want = plain.run_batch(codes)
    traced = BatchSimMachine(SIM_SKL, TEST_ISA, backend="jax", min_lanes=1)
    got, events = _traced(lambda: traced.run_batch(codes))
    assert [(c.cycles, c.port_uops) for c in got] == \
        [(c.cycles, c.port_uops) for c in want]
    a, b = plain.device_stats(), traced.device_stats()
    assert set(a) == set(b)
    assert a["kernel_calls"] == b["kernel_calls"]
    assert {k: set(v) for k, v in a["per_device"].items()} == \
        {k: set(v) for k, v in b["per_device"].items()}
    names = {e["name"] for e in events}
    assert {"wave.pack", "wave.dispatch", "wave.result_wait",
            "wave.extract"} <= names
    assert all(isinstance(e["tid"], int) for e in events)


def test_device_kernel_module_names():
    """The profiler names a kernel's device time by its HLO module; the
    benchmark's readers key on these two names."""
    pytest.importorskip("jax")
    from repro.core.batch_sim import lower_kernel
    from repro.service.batch_predictor import lower_port_bound
    M, P = compile_uarch(SIM_SKL, TEST_ISA).mask_table.shape
    wave = lower_kernel(32, 8, 2, M, P).compile().as_text()
    bound = lower_port_bound(8, 3, 4).compile().as_text()
    assert wave.startswith("HloModule jit_run,")
    assert bound.startswith("HloModule jit_port_bound_kernel,")


def test_run_batch_span_counts_chunks_and_scalar_lanes():
    """``wave.run_batch`` carries the length chunks that run batched and
    the lanes of thin chunks sent to the scalar oracle."""
    body = independent_seq(TEST_ISA["IMUL_R64_R64"], RegPool(), 4)
    short = [Instr("ADD_R64_R64", {"op1": "R0", "op2": "R1"})]
    codes = [body * 110] * 5 + [short] * 2     # 2 short lanes: a thin chunk
    m = BatchSimMachine(SIM_SKL, TEST_ISA, min_lanes=4)
    out, events = _traced(lambda: m.run_batch(codes))
    (sp,) = [e["args"] for e in events if e["name"] == "wave.run_batch"]
    assert (sp["lanes"], sp["chunks"], sp["scalar_lanes"]) == (7, 1, 2)
    scalar = SimMachine(SIM_SKL, TEST_ISA)
    assert [c.cycles for c in out] == [scalar.run(c).cycles for c in codes]


def test_export_span_counts_instructions_and_bytes():
    from repro.core import model_io
    from repro.core.characterize import characterize
    model = characterize(SimMachine(SIM_SKL, TEST_ISA), TEST_ISA,
                         ["ADD_R64_R64", "MUL_R64"])
    xml, events = _traced(lambda: model_io.to_xml(model, TEST_ISA))
    (sp,) = [e["args"] for e in events if e["name"] == "model.export"]
    assert sp == {"uarch": "sim_skl", "instructions": 2, "bytes": len(xml)}
