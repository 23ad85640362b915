"""Deterministic fault injection + end-to-end resilience.

The contracts under test:
  * a seeded :class:`FaultPlan` makes identical decisions on identical
    check sequences (every chaos failure replays from its spec);
  * engine waves with a poisoned experiment bisect down to it and
    quarantine it — typed records, NaN sentinels, campaign completes;
  * device/host kernel faults degrade down the backend chain with
    per-transition counters, results stay bit-identical to the oracle;
  * torn/corrupt persistence (measurement cache, corpus shards, shard
    results) is detected typed and recovered cold, never trusted;
  * wire corruption keeps framing intact: peers fail typed, never hang;
  * the service drains gracefully, reports health, and survives worker
    crashes with futures resolved, not abandoned;
  * with no plan installed, characterization output is byte-identical.
"""
import importlib
import io
import json
import math
import random
import sys
import time
import warnings

import pytest

from repro.core import model_io
from repro.core.batch_sim import BatchSimMachine
from repro.core.characterize import characterize
from repro.core.engine import (Campaign, Experiment, MeasurementEngine,
                               is_quarantined)
from repro.core.isa import TEST_ISA
from repro.core.machine import RegPool, independent_seq
from repro.core.simulator import Instr, SimMachine
from repro.core.uarch import SIM_UARCHES
from repro.corpus.evaluate import _load_resumed, _write_rows
from repro.faults import plan as fplan
from repro.faults.plan import POINTS, FaultPlan, InjectedFault
from repro.faults.tolerance import StragglerDetector
from repro.service import protocol
from repro.service.client import (ServiceClient, ServiceDraining,
                                  ServiceError, local_service)
from repro.service.server import ResilientPool, WorkerCrashed

try:
    import jax  # noqa: F401
    HAVE_JAX = True
except ImportError:
    HAVE_JAX = False


@pytest.fixture(autouse=True)
def _no_ambient_plan():
    """Each test starts with injection disabled; restore after."""
    prev = fplan.set_plan(None)
    yield
    fplan.set_plan(prev)


# ---------------------------------------------------------------------------
# plan: spec grammar, determinism, firing discipline
# ---------------------------------------------------------------------------


def test_spec_grammar_round_trip():
    p = FaultPlan.from_spec(
        "seed=42; wave.kernel:raise:p=0.25:match=AESDEC:backend=numpy; "
        "engine.cache_io:torn:max=1:after=2; wire.frame:corrupt; "
        "device.dispatch:latency:ms=5.5")
    assert p.seed == 42 and len(p.rules) == 4
    r = p.rules[0]
    assert (r.point, r.mode, r.p, r.match, r.backend) == \
        ("wave.kernel", "raise", 0.25, "AESDEC", "numpy")
    assert p.rules[1].max_fires == 1 and p.rules[1].after == 2
    assert p.rules[3].ms == 5.5


def test_spec_errors():
    with pytest.raises(ValueError, match="needs"):
        FaultPlan.from_spec("wave.kernel")
    with pytest.raises(ValueError, match="unknown fault mode"):
        FaultPlan.from_spec("wave.kernel:explode")
    with pytest.raises(ValueError, match="unknown fault option"):
        FaultPlan.from_spec("wave.kernel:raise:zap=1")
    with pytest.raises(ValueError, match="not key=value"):
        FaultPlan.from_spec("wave.kernel:raise:p")


def test_disabled_fast_path_is_noop():
    assert not fplan.active()
    fplan.check("wave.kernel", key="anything")
    fplan.check_wave("wave.kernel", ["a", "b"])
    assert fplan.filter_bytes("wire.frame", b"payload") == b"payload"
    assert fplan.get_plan() is None


def test_seeded_replay_determinism():
    def drive(plan):
        for key in ("k1", "k2", "k3", "k1"):
            try:
                plan.check("wave.kernel", key=key)
            except InjectedFault:
                pass
        return [(f.point, f.mode, f.occurrence, f.key)
                for f in plan.fired]

    spec = "seed=7;wave.kernel:raise:p=0.5"
    a, b = drive(FaultPlan.from_spec(spec)), drive(FaultPlan.from_spec(spec))
    assert a == b  # same seed, same checks -> same firings
    rep = FaultPlan.from_spec(spec)
    drive(rep)
    r = rep.report()
    assert r["seed"] == 7 and r["checks"]["wave.kernel"] == 4
    assert all(f["point"] == "wave.kernel" for f in r["fired"])


def test_filter_bytes_corrupt_and_torn_deterministic():
    data = bytes(range(200))
    c1 = FaultPlan.from_spec("seed=9;wire.frame:corrupt").filter_bytes(
        "wire.frame", data, key="x")
    c2 = FaultPlan.from_spec("seed=9;wire.frame:corrupt").filter_bytes(
        "wire.frame", data, key="x")
    assert c1 == c2 != data and len(c1) == len(data)
    assert sum(1 for a, b in zip(c1, data) if a != b) == 3  # 3 byte flips
    t = FaultPlan.from_spec("seed=9;wire.frame:torn").filter_bytes(
        "wire.frame", data, key="x")
    assert len(t) < len(data) and data.startswith(t)


def test_max_fires_caps_transients():
    p = FaultPlan.from_spec("wave.kernel:raise:max=2")
    for _ in range(2):
        with pytest.raises(InjectedFault):
            p.check("wave.kernel")
    p.check("wave.kernel")  # cap reached: transient fault is over
    assert len(p.fired) == 2


def test_latency_mode_sleeps():
    p = FaultPlan.from_spec("engine.cache_io:latency:ms=30")
    t0 = time.perf_counter()
    p.check("engine.cache_io")
    assert time.perf_counter() - t0 >= 0.02
    assert p.fired[0].mode == "latency"


def test_backend_restriction():
    p = FaultPlan.from_spec("wave.kernel:raise:backend=numpy")
    p.check("wave.kernel", key="k", backend="scalar")  # other backend: no-op
    with pytest.raises(InjectedFault):
        p.check("wave.kernel", key="k", backend="numpy")


# ---------------------------------------------------------------------------
# engine: bisecting retry, quarantine, degradation counters
# ---------------------------------------------------------------------------

EXP_NAMES = ("ADD_R64_R64", "XOR_R64_R64", "IMUL_R64_R64",
             "SHLD_R64_R64_I8")
POISON = "AESDEC_X_X"  # 'AESDEC' appears in no other experiment's key


def _machine(backend="numpy"):
    return BatchSimMachine(SIM_UARCHES["sim_skl"], TEST_ISA,
                           backend=backend, min_lanes=1)


def _experiments(poison=True):
    names = EXP_NAMES + ((POISON,) if poison else ())
    return [Experiment.of(independent_seq(TEST_ISA[n], RegPool(), 2), 4, 8)
            for n in names]


def _reference():
    return MeasurementEngine(_machine()).submit(_experiments())


def test_bisection_isolates_poisoned_experiment():
    ref = _reference()
    plan = fplan.set_plan(
        FaultPlan.from_spec(f"wave.kernel:raise:match={POISON}"))
    assert plan is None
    engine = MeasurementEngine(_machine())
    exps = _experiments()
    with pytest.warns(UserWarning, match="quarantined experiment"):
        got = engine.submit(exps)
    fplan.set_plan(None)
    # poison slot is a NaN sentinel, every other slot is bit-identical
    assert is_quarantined(got[-1]) and math.isnan(got[-1].cycles)
    for g, r in zip(got[:-1], ref[:-1]):
        assert g.cycles == r.cycles and g.port_uops == r.port_uops
    s = engine.stats
    assert s.quarantined == 1 and s.bisect_retries >= 1
    assert len(s.quarantine) == 1
    rec = s.quarantine[0]
    assert rec.uarch == "sim_skl" and POISON in rec.code
    assert "InjectedFault" in rec.error
    d = s.as_dict()
    assert d["quarantined"] == 1 and d["quarantine"][0]["uarch"] == "sim_skl"
    # the sentinel was never cached: clean slots replay from cache, the
    # poisoned one re-executes (and re-quarantines) on resubmit
    with pytest.warns(UserWarning, match="quarantined"):
        fplan.set_plan(FaultPlan.from_spec(
            f"wave.kernel:raise:match={POISON}"))
        again = engine.submit(exps)
    assert is_quarantined(again[-1])
    assert engine.stats.cache_hits >= len(exps) - 1


def test_transient_kernel_fault_recovers_without_quarantine():
    # numpy chain is numpy -> scalar: max=2 survives degradation once,
    # fails the wave, and is spent by the time bisection re-runs
    fplan.set_plan(FaultPlan.from_spec(
        f"wave.kernel:raise:match={POISON}:max=2"))
    engine = MeasurementEngine(_machine())
    with pytest.warns(UserWarning, match="degraded numpy->scalar"):
        got = engine.submit(_experiments())
    assert engine.stats.quarantined == 0
    assert engine.stats.bisect_retries >= 1
    for g, r in zip(got, _reference()):
        assert g.cycles == r.cycles and g.port_uops == r.port_uops


def test_backend_restricted_fault_degrades_not_quarantines():
    fplan.set_plan(FaultPlan.from_spec(
        f"wave.kernel:raise:match={POISON}:backend=numpy"))
    engine = MeasurementEngine(_machine())
    with pytest.warns(UserWarning, match="degraded numpy->scalar"):
        got = engine.submit(_experiments())
    s = engine.stats
    assert s.quarantined == 0
    assert s.degraded_chunks >= 1
    assert s.degraded.get("numpy->scalar", 0) >= 1
    assert s.as_dict()["degraded"] == s.degraded
    assert engine.machine.degraded_stats() == s.degraded
    for g, r in zip(got, _reference()):  # scalar oracle is the reference
        assert g.cycles == r.cycles and g.port_uops == r.port_uops


def test_pack_fault_degrades_to_scalar():
    fplan.set_plan(FaultPlan.from_spec("wave.pack:raise:max=1"))
    m = _machine()
    codes = [e.code for e in _experiments(poison=False)]
    with pytest.warns(UserWarning, match="degraded numpy->scalar"):
        got = m.run_batch([list(c) for c in codes])
    scalar = SimMachine(SIM_UARCHES["sim_skl"], TEST_ISA)
    for g, code in zip(got, codes):
        ref = scalar.run(list(code))
        assert g.cycles == ref.cycles and g.port_uops == ref.port_uops
    assert m.degraded_stats().get("numpy->scalar", 0) >= 1


@pytest.mark.skipif(not HAVE_JAX, reason="jax backend unavailable")
def test_dispatch_fault_degrades_device_to_numpy():
    fplan.set_plan(FaultPlan.from_spec("device.dispatch:raise:max=1"))
    m = _machine(backend="jax")
    codes = [e.code for e in _experiments(poison=False)]
    with pytest.warns(UserWarning, match="degraded jax->numpy"):
        got = m.run_batch([list(c) for c in codes])
    scalar = SimMachine(SIM_UARCHES["sim_skl"], TEST_ISA)
    for g, code in zip(got, codes):
        ref = scalar.run(list(code))
        assert g.cycles == ref.cycles and g.port_uops == ref.port_uops
    assert m.degraded_stats().get("jax->numpy", 0) >= 1


@pytest.mark.skipif(not HAVE_JAX, reason="jax backend unavailable")
def test_device_and_numpy_faults_land_on_scalar_oracle():
    """The whole chain on a jax machine: a failed dispatch reroutes to
    numpy, a failed numpy kernel to the scalar oracle, and the wave's
    results are still bit-identical, each transition counted once per
    chunk it rerouted."""
    fplan.set_plan(FaultPlan.from_spec(
        "device.dispatch:raise;wave.kernel:raise:backend=numpy"))
    m = _machine(backend="jax")
    codes = [list(e.code) for e in _experiments(poison=False)]
    with pytest.warns(UserWarning) as rec:
        got = m.run_batch(codes)
    said = " ".join(str(w.message) for w in rec)
    assert "degraded jax->numpy" in said and "degraded numpy->scalar" in said
    scalar = SimMachine(SIM_UARCHES["sim_skl"], TEST_ISA)
    for g, code in zip(got, codes):
        ref = scalar.run(list(code))
        assert g.cycles == ref.cycles and g.port_uops == ref.port_uops
    st = m.degraded_stats()
    assert set(st) == {"jax->numpy", "numpy->scalar"}
    assert st["jax->numpy"] == st["numpy->scalar"] >= 1


def test_campaign_completes_with_quarantine():
    fplan.set_plan(FaultPlan.from_spec(
        f"wave.kernel:raise:match={POISON}"))
    with pytest.warns(UserWarning, match="quarantined"):
        res = Campaign(instr_names=["ADD_R64_R64", "XOR_R64_R64",
                                    POISON]).run([_machine()], TEST_ISA)
    assert "sim_skl" in res.models  # no abort: the campaign finished
    assert res.quarantined >= 1
    recs = res.quarantine["sim_skl"]
    assert all(POISON in r["code"] for r in recs)
    assert "quarantined experiments" in res.report()


# ---------------------------------------------------------------------------
# persistence: torn measurement cache, shard results, corpus shards
# ---------------------------------------------------------------------------


def test_measurement_cache_torn_write_recovers_cold(tmp_path):
    names = ["ADD_R64_R64", "XOR_R64_R64"]
    mk = lambda: SimMachine(SIM_UARCHES["sim_skl"], TEST_ISA)  # noqa: E731
    camp = Campaign(instr_names=names, cache_dir=tmp_path)
    fplan.set_plan(FaultPlan.from_spec("engine.cache_io:torn:match=save"))
    torn = camp.run([mk()], TEST_ISA)
    fplan.set_plan(None)
    path = tmp_path / "sim_skl.meas.json"
    assert path.exists()
    with pytest.raises(ValueError):
        model_io.load_measurement_cache(path)
    # next run detects the torn cache, warns, re-measures cold -- and the
    # rewritten cache is whole again
    with pytest.warns(UserWarning, match="unusable measurement cache"):
        clean = camp.run([mk()], TEST_ISA)
    assert (model_io.to_xml(clean.models["sim_skl"], TEST_ISA)
            == model_io.to_xml(torn.models["sim_skl"], TEST_ISA))
    assert model_io.load_measurement_cache(path)


def test_measurement_cache_save_failure_is_soft(tmp_path):
    camp = Campaign(instr_names=["ADD_R64_R64"], cache_dir=tmp_path)
    fplan.set_plan(FaultPlan.from_spec("engine.cache_io:raise:match=save"))
    with pytest.warns(UserWarning, match="cache save failed"):
        res = camp.run([SimMachine(SIM_UARCHES["sim_skl"], TEST_ISA)],
                       TEST_ISA)
    assert "ADD_R64_R64" in res.models["sim_skl"].instructions
    assert not (tmp_path / "sim_skl.meas.json").exists()


def test_shard_result_write_fault_warns_and_continues(tmp_path):
    shard = {"name": "sim_skl-00000.jsonl", "sha256": "cafe"}
    rows = [{"id": 0, "family": "f", "block": "b",
             "predicted": 1.0, "measured": 1.0}]
    fplan.set_plan(FaultPlan.from_spec("corpus.shard_write:raise"))
    with pytest.warns(UserWarning, match="rows kept in memory"):
        _write_rows(tmp_path, shard, rows)
    assert _load_resumed(tmp_path, shard) is None  # cold resume
    # torn write: file lands but is rejected on resume, not trusted
    fplan.set_plan(FaultPlan.from_spec("corpus.shard_write:torn"))
    _write_rows(tmp_path, shard, rows)
    assert _load_resumed(tmp_path, shard) is None
    fplan.set_plan(None)
    _write_rows(tmp_path, shard, rows)
    assert _load_resumed(tmp_path, shard) == rows


def test_corpus_shard_corruption_detected(tmp_path):
    from repro.corpus.generate import CorpusSpec, generate_corpus
    from repro.corpus.store import load_manifest, read_shard

    spec = CorpusSpec(uarches=("sim_skl",), blocks_per_uarch=16,
                      shard_size=8, seed=5)
    fplan.set_plan(FaultPlan.from_spec("corpus.shard_write:corrupt:"
                                       "match=.jsonl"))
    generate_corpus(tmp_path, spec)
    fplan.set_plan(None)
    manifest = load_manifest(tmp_path)
    with pytest.raises(ValueError, match="does not match manifest"):
        for sh in manifest["shards"]:
            read_shard(tmp_path, sh)


# ---------------------------------------------------------------------------
# wire: corruption stays framed, peers fail typed (never hang)
# ---------------------------------------------------------------------------


def test_json_wire_corruption_is_typed():
    fplan.set_plan(FaultPlan.from_spec("seed=3;wire.frame:corrupt"))
    buf = io.BytesIO()
    protocol.send_msg(buf, {"op": "ping", "payload": "x" * 64})
    raw = buf.getvalue()
    assert raw.endswith(b"\n") and raw.count(b"\n") == 1  # framing intact
    fplan.set_plan(None)
    with pytest.raises(ValueError):
        protocol.recv_msg(io.BytesIO(raw))


def test_json_wire_torn_body_is_typed():
    fplan.set_plan(FaultPlan.from_spec("seed=3;wire.frame:torn"))
    buf = io.BytesIO()
    protocol.send_msg(buf, {"op": "stats", "pad": list(range(32))})
    fplan.set_plan(None)
    assert buf.getvalue().endswith(b"\n")
    with pytest.raises(ValueError):
        protocol.recv_msg(io.BytesIO(buf.getvalue()))


def test_binary_frame_corruption_is_typed():
    payload = protocol.pack_value({"op": "stats"})
    fplan.set_plan(FaultPlan.from_spec("seed=3;wire.frame:corrupt"))
    raw = protocol.frame(protocol.K_MSG, payload)
    fplan.set_plan(None)
    kind, got = protocol.read_frame(io.BytesIO(raw))
    assert kind == protocol.K_MSG and len(got) == len(payload)
    with pytest.raises(protocol.BinaryProtocolError):
        protocol.unpack_value(got)


# ---------------------------------------------------------------------------
# service: health, drain, worker-crash recovery (live server)
# ---------------------------------------------------------------------------

SERVICE_NAMES = ["ADD_R64_R64", "XOR_R64_R64", "IMUL_R64_R64"]
BLOCK = [Instr("ADD_R64_R64", {"op1": "R0", "op2": "R1"})]


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    res = Campaign(instr_names=SERVICE_NAMES).run(
        [SimMachine(SIM_UARCHES["sim_skl"], TEST_ISA)], TEST_ISA)
    out = tmp_path_factory.mktemp("fault_models")
    (out / "sim_skl.xml").write_text(
        model_io.to_xml(res.models["sim_skl"], TEST_ISA))
    return out


def test_health_op(model_dir):
    with local_service(model_dir) as client:
        h = client.health()
        assert h["status"] == "ok" and h["draining"] is False
        assert h["workers"]["alive"] == h["workers"]["configured"] > 0
        assert h["workers"]["crashed"] == 0
        assert h["queue_depth"] >= 0 and h["uptime_s"] >= 0
        assert h["registry"]


@pytest.mark.parametrize("wire", ["json", "binary"])
def test_drain_refuses_work_keeps_introspection(model_dir, wire):
    with local_service(model_dir, wire=wire) as client:
        assert client.predict("sim_skl", BLOCK)["cycles"] > 0
        d = client.drain()
        assert d["draining"] is True and d["was_draining"] is False
        with pytest.raises(ServiceDraining) as ei:
            client.predict("sim_skl", BLOCK)
        assert ei.value.error["retry_after_ms"] > 0
        with pytest.raises(ServiceDraining):
            client.predict_batch("sim_skl", [BLOCK, BLOCK])
        with pytest.raises(ServiceDraining):
            client.predict_corpus("sim_skl", [[BLOCK]])
        # introspection survives the drain; drain is idempotent
        assert client.ping()
        assert client.health()["status"] == "draining"
        assert client.stats() is not None
        assert client.drain()["was_draining"] is True


def test_binary_wire_corruption_live_server(model_dir):
    with local_service(model_dir, wire="binary") as client:
        assert client.ping()
        # corrupt exactly one frame: the client's next request; the
        # server answers a *typed* error envelope on a clean frame
        plan = FaultPlan.from_spec("seed=11;wire.frame:corrupt:max=1")
        fplan.set_plan(plan)
        with pytest.raises(ServiceError):
            client.stats()
        fplan.set_plan(None)
        assert len(plan.fired) == 1
        assert client.ping()  # connection survived, stream in sync


def test_resilient_pool_recovers_from_worker_crash():
    pool = ResilientPool(2, thread_name_prefix="t-fault")
    try:
        assert pool.submit(lambda: 42).result(timeout=5) == 42
        # a normal exception resolves the future, the thread survives
        with pytest.raises(ValueError):
            pool.submit(_raise, ValueError("boom")).result(timeout=5)
        assert pool.liveness()["crashed"] == 0
        # a BaseException kills the thread: the future resolves typed
        # and the pool replenishes
        with pytest.raises(WorkerCrashed, match="SystemExit"):
            pool.submit(_raise, SystemExit(3)).result(timeout=5)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            live = pool.liveness()
            if live["alive"] == live["configured"]:
                break
            time.sleep(0.01)
        live = pool.liveness()
        assert live["alive"] == live["configured"] == 2
        assert live["crashed"] == 1
        assert pool.submit(lambda: "ok").result(timeout=5) == "ok"
    finally:
        pool.shutdown()


def _raise(exc):
    raise exc


# ---------------------------------------------------------------------------
# client: full-jitter backoff, retry_after_ms hint
# ---------------------------------------------------------------------------


def test_backoff_full_jitter_bounds(model_dir):
    with local_service(model_dir) as client:
        client._rng = random.Random(0)
        for attempt in range(5):
            seen = {client._backoff_delay(attempt) for _ in range(50)}
            hi = client.backoff_s * (2 ** attempt)
            assert all(0.0 <= d <= hi for d in seen)
            assert len(seen) > 1  # jittered, not the old fixed schedule
        # the server's hint floors the jittered delay
        assert client._backoff_delay(0, retry_after_ms=500.0) >= 0.5


def test_retry_overloaded_budget_honors_drain(model_dir):
    with local_service(model_dir) as client:
        client.drain()
        client.retry_overloaded = 2
        client.backoff_s = 0.001
        client._rng = random.Random(1)
        t0 = time.perf_counter()
        with pytest.raises(ServiceDraining):
            # retries the budget, then surfaces the drain (ping itself is
            # introspection and still answers — prediction does not)
            client.predict("sim_skl", BLOCK)
        assert time.perf_counter() - t0 >= 0.0  # returned, no hang
        assert client.ping()  # introspection never blocked


# ---------------------------------------------------------------------------
# stragglers, deprecation shim, disabled-path identity
# ---------------------------------------------------------------------------


def test_straggler_detector_flags_slow_device():
    det = StragglerDetector()
    for _ in range(5):
        det.observe("device:0", 0.10)
        det.observe("device:1", 0.10)
        det.observe("device:2", 1.00)
    snap = det.snapshot()
    assert snap["flagged"] == ["device:2"]
    assert snap["ewma_s"]["device:2"] > 2 * snap["median_s"]


def test_runtime_fault_tolerance_shim_warns():
    sys.modules.pop("repro.runtime.fault_tolerance", None)
    with pytest.warns(DeprecationWarning, match="repro.faults.tolerance"):
        mod = importlib.import_module("repro.runtime.fault_tolerance")
    from repro.faults import tolerance
    assert mod.StragglerDetector is tolerance.StragglerDetector
    assert mod.FleetMonitor is tolerance.FleetMonitor


def test_characterization_identical_with_armed_never_firing_plan():
    """Every injection point evaluated (p=0 rules at all points) must not
    perturb results: the XML is byte-identical to a plan-free run."""
    names = ["ADD_R64_R64", "XOR_R64_R64", "MUL_R64"]
    clean = characterize(MeasurementEngine(_machine()), TEST_ISA, names)
    spec = ";".join(f"{p}:raise:p=0" for p in POINTS) + ";" + \
        ";".join(f"{p}:corrupt:p=0" for p in POINTS)
    plan = FaultPlan.from_spec(spec)
    fplan.set_plan(plan)
    armed = characterize(MeasurementEngine(_machine()), TEST_ISA, names)
    fplan.set_plan(None)
    assert plan.occurrences() > 0     # the hooks really were traversed
    assert not plan.fired             # and none of them fired
    assert (model_io.to_xml(armed, TEST_ISA)
            == model_io.to_xml(clean, TEST_ISA))
