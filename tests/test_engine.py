"""Batched experiment engine: caching, dedup, campaigns (Algorithm 2 layer).

The load-bearing claims: (1) the content-addressed cache never changes an
inference result — characterize() with the cache enabled is byte-identical
to a cache-disabled run on every simulated uarch; (2) deduplication executes
each unique experiment exactly once, so a characterization issues zero
duplicate simulator executions; (3) caches persist through model_io and make
re-runs incremental; (4) campaigns shard across machines and report stats.
"""
import pytest

from repro.core import model_io
from repro.core.blocking import find_blocking_instructions
from repro.core.characterize import characterize
from repro.core.engine import (Campaign, Experiment, MeasurementEngine,
                               as_engine, canonical_code,
                               machine_fingerprint)
from repro.core.isa import ANCHORS, TEST_ISA
from repro.core.machine import RegPool, independent_seq, measure
from repro.core.port_usage import infer_port_usage
from repro.core.simulator import Instr, SimMachine
from repro.core.uarch import (SIM_UARCHES, generate_isa_and_uarch,
                              port_usage_of)
from random_truth import random_uarch_and_isa

SUBSET = ["ADD_R64_R64", "ADC_R64_R64", "MOVQ2DQ_X_X", "MUL_R64",
          "SHLD_R64_R64_I8", "MOV_M64_R64", "DIV_R64"]


class CountingMachine:
    """Wraps a SimMachine, recording every raw run's canonical code."""

    def __init__(self, machine):
        self._m = machine
        self.name = machine.name
        self.ports = machine.ports
        self.runs = []

    def run(self, code):
        self.runs.append(canonical_code(code))
        return self._m.run(code)


def _machine(name="sim_skl"):
    return SimMachine(SIM_UARCHES[name], TEST_ISA)


# ---------------------------------------------------------------------------
# dedup / cache mechanics
# ---------------------------------------------------------------------------


def test_dedup_executes_each_unique_experiment_exactly_once():
    cm = CountingMachine(_machine())
    engine = MeasurementEngine(cm)
    seq_a = independent_seq(TEST_ISA["ADD_R64_R64"], RegPool(), 4)
    seq_b = independent_seq(TEST_ISA["IMUL_R64_R64"], RegPool(), 4)
    ea, eb = Experiment.of(seq_a), Experiment.of(seq_b)
    out = engine.submit([ea, eb, ea, ea, eb])
    assert engine.stats.requests == 5
    assert engine.stats.executions == 2        # one per unique experiment
    assert engine.stats.dedup_hits == 3
    assert engine.stats.machine_runs == 4      # 2 runs (n_small/n_large) each
    assert len(cm.runs) == 4
    # duplicates got the same measurement
    assert out[0].cycles == out[2].cycles == out[3].cycles
    assert out[1].cycles == out[4].cycles
    # a later submission of a known experiment is a cache hit, not a run
    engine.measure(ea)
    assert engine.stats.executions == 2
    assert engine.stats.cache_hits == 1
    assert len(cm.runs) == 4


def test_cached_counters_are_isolated_copies():
    engine = MeasurementEngine(_machine())
    exp = Experiment.of(independent_seq(TEST_ISA["ADD_R64_R64"],
                                        RegPool(), 2))
    c1 = engine.measure(exp)
    c1.port_uops.clear()  # a hostile caller must not corrupt the cache
    c2 = engine.measure(exp)
    assert c2.port_uops, "cache entry was mutated through a returned value"


def test_legacy_measure_path_shares_the_machine_engine():
    m = _machine()
    seq = independent_seq(TEST_ISA["ADD_R64_R64"], RegPool(), 4)
    c1 = measure(m, seq)
    c2 = measure(m, list(seq))
    engine = as_engine(m)
    assert engine.stats.executions == 1
    assert engine.stats.cache_hits == 1
    assert c1.cycles == c2.cycles


# ---------------------------------------------------------------------------
# characterize(): zero duplicate executions, cache-invariant results
# ---------------------------------------------------------------------------


def test_characterize_issues_zero_duplicate_simulator_executions():
    cm = CountingMachine(_machine())
    engine = MeasurementEngine(cm)
    characterize(engine, TEST_ISA, SUBSET)
    assert len(cm.runs) == len(set(cm.runs)), \
        "identical benchmark executed more than once at the machine level"
    # engine-counter view of the same invariant
    assert engine.stats.executions == len(engine.cache)
    assert engine.stats.machine_runs == 2 * engine.stats.executions
    assert engine.stats.cache_hits + engine.stats.dedup_hits > 0


@pytest.mark.parametrize("uarch", sorted(SIM_UARCHES))
def test_characterize_cached_byte_identical_to_uncached(uarch):
    """The cache may only ever change *when* a benchmark runs, not what the
    inference concludes: byte-identical exported models per uarch."""
    m = _machine(uarch)
    blocking = find_blocking_instructions(as_engine(m), TEST_ISA)
    cached = characterize(MeasurementEngine(m), TEST_ISA, SUBSET,
                          blocking=blocking)
    uncached = characterize(MeasurementEngine(m, enabled=False), TEST_ISA,
                            SUBSET, blocking=blocking)
    assert model_io.to_xml(cached, TEST_ISA) == \
        model_io.to_xml(uncached, TEST_ISA)


@pytest.mark.parametrize("seed", [0, 7, 1234])
def test_port_usage_cache_invariant_on_random_ground_truths(seed):
    ua, isa, truth = random_uarch_and_isa(seed)
    m = SimMachine(ua, isa)
    blocking = find_blocking_instructions(as_engine(m), isa,
                                          extensions=("BASE",))
    for name in truth:
        with_cache = infer_port_usage(MeasurementEngine(m), isa, name,
                                      blocking, max_latency=4).usage
        without = infer_port_usage(MeasurementEngine(m, enabled=False), isa,
                                   name, blocking, max_latency=4).usage
        assert with_cache == without == truth[name]


@pytest.mark.parametrize("seed", [0, 7, 1234])
def test_port_usage_cache_invariant_on_generated_ground_truths(seed):
    isa, ua = generate_isa_and_uarch(seed, len(TEST_ISA) + len(ANCHORS) + 4)
    m = SimMachine(ua, isa)
    blocking = find_blocking_instructions(as_engine(m), isa)
    truth = {s.name: port_usage_of(ua.behaviors[s.name])
             for s in list(isa)[len(TEST_ISA):]
             if s.characterized and not s.may_eliminate}
    for name in truth:
        lat = sum(u.latency for u in ua.behaviors[name].uops)
        with_cache = infer_port_usage(MeasurementEngine(m), isa, name,
                                      blocking, max_latency=lat).usage
        without = infer_port_usage(MeasurementEngine(m, enabled=False), isa,
                                   name, blocking, max_latency=lat).usage
        assert with_cache == without == truth[name]


# ---------------------------------------------------------------------------
# cache-key stability
# ---------------------------------------------------------------------------


# Golden keys: Experiment.cache_key is the address of every persisted
# measurement. An accidental change to the canonicalization (operand
# ordering, hint formatting, separator choice, run-param encoding) would
# silently invalidate every on-disk cache — these constants make it loud.
GOLDEN_KEYS = [
    (lambda: Experiment.of([Instr("ADD_R64_R64",
                                  {"op1": "R0", "op2": "R1"})]),
     "280217329b7a9fccd0f54dcdc2e6056076776171b82513e68e72192200dbf6eb"),
    # operand-order independence: same key as above
    (lambda: Experiment.of([Instr("ADD_R64_R64",
                                  {"op2": "R1", "op1": "R0"})]),
     "280217329b7a9fccd0f54dcdc2e6056076776171b82513e68e72192200dbf6eb"),
    # value hint is part of the address
    (lambda: Experiment.of([Instr("DIV_R64", {"op1": "R0"}, "high")]),
     "a108431400fe6d72d07a82e1e0395078182855979d03197cbf85d571fa3a4e9a"),
    # multi-instruction sequence
    (lambda: Experiment.of([Instr("IMUL_R64_R64", {"op1": "R2",
                                                   "op2": "R3"}),
                            Instr("TEST_R64_R64", {"op1": "R4",
                                                   "op2": "R4"})]),
     "0f67b8bf24d4bc8c2460773ed583bdaf23b0b2692290e06f241357aa6bd43717"),
    # Algorithm-2 run params are part of the address
    (lambda: Experiment.of([Instr("ADD_R64_R64",
                                  {"op1": "R0", "op2": "R1"})],
                           n_small=5, n_large=55),
     "56c1d66c9089660fd2ac27dac3380809b5dc94b461beaa56a91771bc33789ad8"),
]


@pytest.mark.parametrize("make,expect",
                         GOLDEN_KEYS, ids=[f"golden{i}" for i in
                                           range(len(GOLDEN_KEYS))])
def test_cache_key_golden_values(make, expect):
    assert make().cache_key("sim_skl") == expect


def test_cache_key_depends_on_uarch():
    e = GOLDEN_KEYS[0][0]()
    assert e.cache_key("sim_hsw") == \
        "c035a4ae88d8ddaee06741943afe537983ce0ab3a332512c3a2d9fc9f6d5f646"
    assert e.cache_key("sim_hsw") != e.cache_key("sim_skl")


# ---------------------------------------------------------------------------
# persistence + campaigns
# ---------------------------------------------------------------------------


def test_persistent_cache_makes_rerun_incremental(tmp_path):
    m1 = _machine()
    e1 = MeasurementEngine(m1)
    model1 = characterize(e1, TEST_ISA, SUBSET)
    path = tmp_path / "skl.meas.json"
    model_io.save_measurement_cache(path, e1)

    m2 = _machine()
    e2 = MeasurementEngine(m2, cache=model_io.load_measurement_cache(path))
    model2 = characterize(e2, TEST_ISA, SUBSET)
    assert e2.stats.executions == 0, "warm cache still executed benchmarks"
    assert e2.stats.hit_rate == 1.0
    assert model_io.to_xml(model2, TEST_ISA) == model_io.to_xml(model1,
                                                               TEST_ISA)


def test_stale_cache_from_changed_uarch_is_not_replayed(tmp_path):
    """A persisted cache is only valid for the exact machine parameters that
    produced it: an edited uarch must re-measure, not replay."""
    m = _machine()
    e = MeasurementEngine(m)
    characterize(e, TEST_ISA, ["ADD_R64_R64"])
    path = tmp_path / "sim_skl.meas.json"
    model_io.save_measurement_cache(path, e)
    # same machine: accepted
    assert model_io.load_measurement_cache(
        path, expect_fingerprint=machine_fingerprint(m))
    # "edited" uarch (same name, different hidden tables): rejected
    changed = SimMachine(SIM_UARCHES["sim_skl"].replace(issue_width=2),
                         TEST_ISA)
    assert machine_fingerprint(changed) != machine_fingerprint(m)
    with pytest.raises(ValueError, match="fingerprint"):
        model_io.load_measurement_cache(
            path, expect_fingerprint=machine_fingerprint(changed))
    # the campaign treats the mismatch as a cold start, then re-persists
    with pytest.warns(UserWarning, match="unusable measurement cache"):
        res = Campaign(instr_names=["ADD_R64_R64"],
                       cache_dir=tmp_path).run([changed], TEST_ISA)
    assert res.stats["sim_skl"]["executions"] > 0
    assert model_io.load_measurement_cache(
        path, expect_fingerprint=machine_fingerprint(changed))


def test_campaign_treats_corrupt_cache_as_cold(tmp_path):
    (tmp_path / "sim_skl.meas.json").write_text("garbage{{{")
    with pytest.warns(UserWarning, match="unusable measurement cache"):
        res = Campaign(instr_names=["ADD_R64_R64"],
                       cache_dir=tmp_path).run([_machine()], TEST_ISA)
    assert "ADD_R64_R64" in res.models["sim_skl"].instructions
    # the save path rewrote a valid cache
    assert model_io.load_measurement_cache(tmp_path / "sim_skl.meas.json")


def test_campaign_shards_across_uarches(tmp_path):
    machines = [_machine(n) for n in ("sim_skl", "sim_snb")]
    camp = Campaign(instr_names=SUBSET, cache_dir=tmp_path)
    res = camp.run(machines, TEST_ISA)
    assert set(res.models) == {"sim_skl", "sim_snb"}
    assert res.models["sim_skl"].blocking != res.models["sim_snb"].blocking
    for name in res.models:
        assert (tmp_path / f"{name}.meas.json").exists()
        assert 0.0 <= res.stats[name]["hit_rate"] <= 1.0
        assert res.phase_seconds[name].keys() >= {"blocking", "latency",
                                                  "ports", "throughput"}
    # models match a plain single-machine characterization
    direct = characterize(MeasurementEngine(_machine("sim_snb")), TEST_ISA,
                          SUBSET)
    assert model_io.to_xml(res.models["sim_snb"], TEST_ISA) == \
        model_io.to_xml(direct, TEST_ISA)
    assert "sim_skl" in res.report()

    # second campaign from the persisted caches: pure replay
    res2 = Campaign(instr_names=SUBSET, cache_dir=tmp_path).run(
        [_machine(n) for n in ("sim_skl", "sim_snb")], TEST_ISA)
    assert res2.hit_rate == 1.0
    assert all(s["executions"] == 0 for s in res2.stats.values())


# ---------------------------------------------------------------------------
# LRU bound on the in-memory cache
# ---------------------------------------------------------------------------


def _exps(*names):
    return [Experiment.of(independent_seq(TEST_ISA[n], RegPool(), 3))
            for n in names]


def test_cache_bound_evicts_oldest_and_counts_evictions():
    engine = MeasurementEngine(_machine(), max_entries=2)
    ea, eb, ec = _exps("ADD_R64_R64", "IMUL_R64_R64", "LEA_R64")
    engine.submit([ea, eb, ec])
    assert len(engine.cache) == 2
    assert engine.stats.evictions == 1
    assert engine.stats.as_dict()["evictions"] == 1
    # the evicted (oldest) experiment re-executes; the retained ones hit
    engine.submit([ea])
    assert engine.stats.executions == 4
    engine.submit([ec])
    assert engine.stats.cache_hits == 1


def test_cache_bound_is_lru_not_fifo():
    engine = MeasurementEngine(_machine(), max_entries=2)
    ea, eb, ec = _exps("ADD_R64_R64", "IMUL_R64_R64", "LEA_R64")
    engine.submit([ea, eb])
    engine.submit([ea])       # touch: ea becomes most-recent
    engine.submit([ec])       # evicts eb, not ea
    hits0 = engine.stats.cache_hits
    engine.submit([ea])
    assert engine.stats.cache_hits == hits0 + 1
    assert engine.stats.executions == 3


def test_unbounded_cache_never_evicts():
    engine = MeasurementEngine(_machine(), max_entries=None)
    engine.submit(_exps("ADD_R64_R64", "IMUL_R64_R64", "LEA_R64",
                        "MUL_R64", "CMC"))
    assert engine.stats.evictions == 0
    assert len(engine.cache) == 5
