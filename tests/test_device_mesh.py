"""Device-mesh substrate: resolution, placement, locks, graceful fallback.

These run under the suite's normal single-device jax, so they cover the
spec/placement machinery and the single-device degradation of every knob
(the ``devices=4``-on-a-1-device-host case must silently stay on the PR-5
path).  True multi-device behavior — mesh sharding, bit-identity at 2 and
4 forced host devices, disjoint campaign placement — lives in
``test_multidevice.py`` (subprocesses, XLA_FLAGS must precede jax import).
"""
import pytest

from repro.core.batch_sim import BatchSimMachine
from repro.core import device_mesh
from repro.core.device_mesh import (COMPILE_CACHE_DIR, ENV_COMPILE_CACHE,
                                    ENV_DEVICES, dispatch_lock,
                                    enable_compile_cache, jax_devices,
                                    lane_mesh, partition, resolve_devices)
from repro.core.isa import TEST_ISA
from repro.core.machine import RegPool, independent_seq
from repro.core.simulator import SimMachine
from repro.core.uarch import SIM_SKL

jax = pytest.importorskip("jax")


def _wave(n=12, seed=0):
    import random
    rng = random.Random(seed)
    specs = ["ADD_R64_R64", "IMUL_R64_R64", "MULPS_X_X", "DIV_R64"]
    out = []
    for _ in range(n):
        body = independent_seq(TEST_ISA[rng.choice(specs)], RegPool(),
                               rng.randint(3, 8))
        out.append(body * rng.randint(2, 5))
    return out


# ---------------------------------------------------------------------------
# resolve_devices: every accepted spelling, clamped to the host
# ---------------------------------------------------------------------------


def test_resolve_devices_spellings(monkeypatch):
    devs = jax_devices()
    assert devs == tuple(jax.devices())
    monkeypatch.delenv(ENV_DEVICES, raising=False)
    assert resolve_devices(None) == devs          # default: all
    assert resolve_devices("all") == devs
    assert resolve_devices(len(devs)) == devs
    assert resolve_devices(str(len(devs))) == devs
    assert resolve_devices(1) == devs[:1]
    # over-ask degrades gracefully to everything the host has
    assert resolve_devices(64) == devs
    assert resolve_devices(0) == devs[:1]         # clamped up to 1
    # explicit sequences pass through untouched
    assert resolve_devices(devs[:1]) == devs[:1]


def test_resolve_devices_env(monkeypatch):
    devs = jax_devices()
    monkeypatch.setenv(ENV_DEVICES, "1")
    assert resolve_devices(None) == devs[:1]
    monkeypatch.setenv(ENV_DEVICES, "all")
    assert resolve_devices(None) == devs
    # the env knob only fills in for spec=None
    assert resolve_devices(len(devs)) == devs


# ---------------------------------------------------------------------------
# partition / locks / meshes
# ---------------------------------------------------------------------------


def test_partition_shapes():
    devs = list(range(4))   # ids suffice: partition never touches jax
    assert partition(devs, 2) == [(0, 1), (2, 3)]
    assert partition(devs, 3) == [(0,), (1,), (2, 3)]
    assert partition(devs, 4) == [(0,), (1,), (2,), (3,)]
    # fewer devices than machines: round-robin shared singletons
    assert partition(devs[:2], 5) == [(0,), (1,), (0,), (1,), (0,)]
    # no devices (no jax): empty groups, machines keep default placement
    assert partition((), 3) == [(), (), ()]
    assert partition(devs, 0) == []
    # disjointness whenever there are enough devices
    groups = partition(devs, 2)
    assert not (set(groups[0]) & set(groups[1]))


def test_dispatch_lock_identity():
    devs = jax_devices()
    a = dispatch_lock(devs[:1])
    assert dispatch_lock(devs[:1]) is a           # same subset, same lock
    assert dispatch_lock(()) is dispatch_lock(())   # host fallback lock
    assert dispatch_lock(()) is not a


def test_lane_mesh_memoized():
    devs = jax_devices()
    m = lane_mesh(devs[:1])
    assert lane_mesh(devs[:1]) is m
    assert m.n == 1 and m.key == (devs[0].id,)
    assert m.mesh.axis_names == ("lanes",)


# ---------------------------------------------------------------------------
# graceful single-device fallback + knob threading
# ---------------------------------------------------------------------------


def test_devices_overask_falls_back_single_device():
    """devices=4 on a 1-device host must stay on the single-device path
    and produce numpy-identical results (CPU CI without forced devices)."""
    codes = _wave()
    base = BatchSimMachine(SIM_SKL, TEST_ISA, backend="numpy")
    m = BatchSimMachine(SIM_SKL, TEST_ISA, backend="jax", devices=4)
    a = base.run_batch(codes)
    b = m.run_batch(codes)
    assert all(x.cycles == y.cycles and x.port_uops == y.port_uops
               for x, y in zip(a, b))
    st = m.device_stats()
    if len(jax_devices()) == 1:
        assert st["mesh"] is False
    assert st["devices"] == [d.id for d in resolve_devices(4)]
    # per-device counters attribute every real lane
    assert sum(c["lanes"] for c in st["per_device"].values()) >= len(codes)
    assert all(c["compiles"] <= len(c["buckets"])
               for c in st["per_device"].values())


def test_set_devices_rebuilds_executor():
    m = BatchSimMachine(SIM_SKL, TEST_ISA, backend="jax", devices=1)
    codes = _wave(8)
    first = m.run_batch(codes)
    assert m.device_stats() != {}
    m.set_devices("all")
    assert m.device_stats() == {}      # executor dropped, rebuilt lazily
    assert [c.cycles for c in m.run_batch(codes)] == \
        [c.cycles for c in first]


def test_sim_machine_forwards_devices():
    sm = SimMachine(SIM_SKL, TEST_ISA, backend="jax", min_lanes=1,
                    devices=1)
    codes = _wave(8)
    got = sm.run_batch(codes)
    assert sm._batch.devices == 1
    sm.set_devices("all")
    assert sm._batch.devices == "all"
    ref = SimMachine(SIM_SKL, TEST_ISA).run_batch(codes)
    assert [c.cycles for c in got] == [c.cycles for c in ref]


def test_batch_predictor_devices_knob(skl_model):
    from repro.service.batch_predictor import BatchPredictor
    sm = SimMachine(SIM_SKL, TEST_ISA, backend="jax", min_lanes=1)
    bp = BatchPredictor(skl_model, TEST_ISA, machine=sm)
    blocks = _wave(6)
    a = bp.simulate_batch(blocks)
    b = bp.simulate_batch(blocks, devices=1)
    assert sm.devices == 1
    assert a == b


# ---------------------------------------------------------------------------
# telemetry surfacing: EngineStats.as_dict / characterize numeric guard
# ---------------------------------------------------------------------------


def test_engine_stats_surfaces_device_telemetry():
    from repro.core.engine import Experiment, MeasurementEngine
    sm = SimMachine(SIM_SKL, TEST_ISA, backend="jax", min_lanes=1)
    eng = MeasurementEngine(sm)
    eng.submit([Experiment.of(c) for c in _wave(6)])
    d = eng.stats.as_dict()["device"]
    assert d["backend"] == "jax" and d["kernel_calls"] >= 1
    assert set(d["per_device"]) == {dev.id for dev in resolve_devices()}


def test_characterize_engine_stats_with_device_snapshot():
    """The engine-stats delta in characterize must skip the non-numeric
    device snapshot instead of crashing on dict arithmetic."""
    from repro.core.characterize import characterize
    from repro.core.engine import MeasurementEngine
    sm = SimMachine(SIM_SKL, TEST_ISA, backend="jax", min_lanes=1)
    model = characterize(MeasurementEngine(sm), TEST_ISA,
                         ["ADD_R64_R64", "MUL_R64"])
    es = model.engine_stats
    assert es["requests"] > 0
    assert isinstance(es["device"], dict)
    assert es["device"].get("backend") == "jax"


# ---------------------------------------------------------------------------
# persistent compile cache: where it lives, and when it is turned on
# ---------------------------------------------------------------------------


def _record_config_updates(monkeypatch) -> list:
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: calls.append((name, val)))
    return calls


@pytest.mark.parametrize("platform", [None, "cpu"])
def test_enable_compile_cache_sets_nothing_off_tpu(monkeypatch, platform):
    monkeypatch.delenv(ENV_COMPILE_CACHE, raising=False)
    monkeypatch.setattr(device_mesh, "device_platform", lambda: platform)
    calls = _record_config_updates(monkeypatch)
    assert enable_compile_cache() is None
    assert calls == []


def test_enable_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_COMPILE_CACHE, str(tmp_path))
    monkeypatch.setattr(device_mesh, "device_platform", lambda: "tpu")
    calls = _record_config_updates(monkeypatch)
    assert enable_compile_cache() == str(tmp_path)
    assert calls == []                           # JAX reads the variable


def test_enable_compile_cache_on_tpu_uses_checkout_dir(monkeypatch):
    monkeypatch.delenv(ENV_COMPILE_CACHE, raising=False)
    monkeypatch.setattr(device_mesh, "device_platform", lambda: "tpu")
    calls = _record_config_updates(monkeypatch)
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(compilation_cache, "reset_cache", lambda: None)
    assert enable_compile_cache() == str(COMPILE_CACHE_DIR)
    assert ("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR)) in calls
    # a fixed directory inside the checkout, the same every run
    assert COMPILE_CACHE_DIR.name == ".jax_cache"
    assert (COMPILE_CACHE_DIR.parent / "src" / "repro" / "core"
            / "device_mesh.py").exists()
