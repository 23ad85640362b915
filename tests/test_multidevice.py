"""Multi-device correctness tests.

These must run with >1 device while the rest of the suite sees exactly one,
so each test spawns a subprocess with XLA_FLAGS=--xla_force_host_platform_
device_count=N and asserts on its output. Covered:

  * mesh-sharded wave execution bit-identical to the numpy backend at 1, 2
    and 4 devices (ragged/empty waves, jax backend), with the thin-chunk
    crossover clamping the mesh width per-device shard,
  * characterize-to-XML byte-identical across device counts and to the
    scalar oracle for every SIM_UARCH,
  * Campaign placing machines on disjoint device subsets with unchanged
    models,
  * MoE shard_map EP path == dense reference (loss parity),
  * GPipe pipeline over an axis == sequential layer stack,
  * int8-compressed psum ≈ exact psum (and exact for int values),
  * decode attention with a sequence-sharded KV cache == unsharded,
  * production mesh construction (both shapes).
"""
import os
import subprocess
import sys
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_py(code: str, devices: int = 4, timeout: int = 480) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_wave_bit_identity_across_device_counts():
    """Mesh-sharded wave execution (jax at 1/2/4 devices) is
    bit-identical to the numpy backend on a ragged wave with empty
    sequences; the thin-chunk crossover routes on *per-device* shard
    width (mesh width clamps so no shard drops below min_lanes); warm
    waves never recompile."""
    out = run_py("""
import random
from repro.core.batch_sim import BatchSimMachine
from repro.core.isa import TEST_ISA
from repro.core.machine import RegPool, independent_seq
from repro.core.uarch import SIM_SKL

rng = random.Random(0)
specs = ["ADD_R64_R64", "IMUL_R64_R64", "MOV_R64_R64", "PADDD_X_X",
         "DIV_R64", "MULPS_X_X", "ADC_R64_R64"]
codes = []
for _ in range(40):
    body = independent_seq(TEST_ISA[rng.choice(specs)], RegPool(),
                           rng.randint(3, 9))
    codes.append(body * rng.randint(2, 6))
codes.append([])                       # empty sequence inside the wave

base = BatchSimMachine(SIM_SKL, TEST_ISA, backend="numpy")
ref = base.run_batch(codes)
for nd in (1, 2, 4):
    m = BatchSimMachine(SIM_SKL, TEST_ISA, backend="jax", devices=nd)
    got = m.run_batch(codes)
    assert all(a.cycles == b.cycles and a.port_uops == b.port_uops
               for a, b in zip(ref, got)), nd
    st = m.device_stats()
    assert st["mesh"] == (nd > 1), st
    assert st["devices"] == list(range(nd)), st
    assert sum(c["lanes"] for c in st["per_device"].values()) >= 40
    c0 = st["compiles"]
    m.run_batch(codes)                 # warm wave: zero recompiles
    assert m.device_stats()["compiles"] == c0, nd
    assert m.run_batch([]) == []

# per-device-shard crossover: 8 lanes / min_lanes 4 on 4 devices must
# clamp to a 2-device mesh (each shard keeps >= min_lanes lanes), and a
# sub-crossover chunk stays off the mesh entirely
d = m._device
assert d.mesh_width(8) == 2 and d.mesh_width(64) == 4
assert d.mesh_width(3) == 1
m2 = BatchSimMachine(SIM_SKL, TEST_ISA, backend="jax", devices=4,
                     min_lanes=4)
body = independent_seq(TEST_ISA["ADD_R64_R64"], RegPool(), 4)
thin = [body * 4] * 8                  # one 8-lane chunk, uniform length
got = m2.run_batch(thin)
assert all(a.cycles == b.cycles and a.port_uops == b.port_uops
           for a, b in zip(base.run_batch(thin), got))
widths = {k[3] for k in m2._device._rings}   # slot keys carry mesh width
assert widths == {2}, widths
print("WAVE_MESH_OK")
""")
    assert "WAVE_MESH_OK" in out


def test_characterize_xml_identical_across_device_counts():
    """characterize-to-XML is byte-identical on 1, 2 and 4 forced host
    devices and to the scalar oracle, for every SIM_UARCH."""
    out = run_py("""
from repro.core import model_io
from repro.core.characterize import characterize
from repro.core.engine import MeasurementEngine
from repro.core.isa import TEST_ISA
from repro.core.simulator import SimMachine
from repro.core.uarch import SIM_UARCHES

SUBSET = ["ADD_R64_R64", "ADC_R64_R64", "MUL_R64", "SHLD_R64_R64_I8",
          "MOV_M64_R64", "PADDD_X_X"]
for name in sorted(SIM_UARCHES):
    ua = SIM_UARCHES[name]
    oracle = SimMachine(ua, TEST_ISA)    # scalar/numpy reference
    want = model_io.to_xml(
        characterize(MeasurementEngine(oracle), TEST_ISA, SUBSET), TEST_ISA)
    for nd in (1, 2, 4):
        m = SimMachine(ua, TEST_ISA, backend="jax", min_lanes=1, devices=nd)
        got = model_io.to_xml(
            characterize(MeasurementEngine(m), TEST_ISA, SUBSET), TEST_ISA)
        assert got == want, (name, nd)
print("XML_MESH_OK")
""")
    assert "XML_MESH_OK" in out


def test_campaign_disjoint_device_placement():
    """Campaign.run places its machines on disjoint device subsets (each
    with its own dispatch lock) and the resulting models match a
    single-machine characterization."""
    out = run_py("""
from repro.core import model_io
from repro.core.characterize import characterize
from repro.core.engine import Campaign, MeasurementEngine
from repro.core.isa import TEST_ISA
from repro.core.simulator import SimMachine
from repro.core.uarch import SIM_HSW, SIM_SKL

SUBSET = ["ADD_R64_R64", "MUL_R64", "PADDD_X_X"]
machines = [SimMachine(SIM_SKL, TEST_ISA, backend="jax", min_lanes=1),
            SimMachine(SIM_HSW, TEST_ISA, backend="jax", min_lanes=1)]
res = Campaign(instr_names=SUBSET).run(machines, TEST_ISA)
subsets = [m.device_stats()["devices"] for m in machines]
assert subsets == [[0, 1], [2, 3]], subsets
assert not (set(subsets[0]) & set(subsets[1]))
for m in machines:
    solo = SimMachine(m.uarch, TEST_ISA)
    want = model_io.to_xml(
        characterize(MeasurementEngine(solo), TEST_ISA, SUBSET), TEST_ISA)
    assert model_io.to_xml(res.models[m.name], TEST_ISA) == want, m.name
    st = res.stats[m.name]["device"]
    assert st["mesh"] is True and st["kernel_calls"] >= 1
print("CAMPAIGN_MESH_OK")
""")
    assert "CAMPAIGN_MESH_OK" in out


def test_moe_shard_map_matches_dense():
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import load_config
from repro.models import model as MF
from repro.models.sharding import MeshAxes
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
cfg = load_config("phi3_5_moe_42b", smoke=True)
axes = MeshAxes(batch=("data",), model="model", enabled=True)
m_sh = MF.build_model(cfg, axes, mesh)
m_ref = MF.build_model(cfg)
params = m_ref.init(jax.random.PRNGKey(1))
batch = {"tokens": jnp.ones((4, 16), jnp.int32),
         "labels": jnp.ones((4, 16), jnp.int32)}
with jax.set_mesh(mesh):
    l_sh, _ = jax.jit(m_sh.loss)(params, batch)
l_ref, _ = jax.jit(m_ref.loss)(params, batch)
assert abs(float(l_sh) - float(l_ref)) < 2e-2, (l_sh, l_ref)
print("MOE_OK", float(l_sh), float(l_ref))
""")
    assert "MOE_OK" in out


def test_pipeline_matches_sequential():
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.train.pipeline import pipeline_apply, split_stages
mesh = jax.make_mesh((4,), ("pod",),
                     axis_types=(jax.sharding.AxisType.Auto,))
L, D = 8, 16
ks = jax.random.split(jax.random.PRNGKey(0), L)
layers = {"w": jnp.stack([jax.random.normal(k, (D, D)) * 0.2 for k in ks]),
          "b": jnp.zeros((L, D))}

def apply_stack(params, x):
    def body(h, lp):
        return jnp.tanh(h @ lp["w"] + lp["b"]), None
    h, _ = jax.lax.scan(body, x, params)
    return h

xs = jax.random.normal(jax.random.PRNGKey(1), (6, 3, D))  # 6 microbatches
seq = jnp.stack([apply_stack(layers, xs[i]) for i in range(6)])
staged = split_stages(layers, 4)
with jax.set_mesh(mesh):
    out = jax.jit(lambda p, x: pipeline_apply(
        apply_stack, p, x, mesh, axis="pod"))(staged, xs)
np.testing.assert_allclose(np.asarray(out), np.asarray(seq), atol=1e-5,
                           rtol=1e-5)
print("PIPE_OK")
""")
    assert "PIPE_OK" in out


def test_compressed_psum_close_to_exact():
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim.compression import psum_int8
mesh = jax.make_mesh((4,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 1024))

def f(x):
    return psum_int8(x[0], "data"), jax.lax.psum(x[0], "data")

with jax.set_mesh(mesh):
    approx, exact = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P("data"), out_specs=P(),
        check_vma=False))(x)
err = float(jnp.max(jnp.abs(approx - exact)))
scale = float(jnp.max(jnp.abs(exact)))
assert err < 4 * scale / 127, (err, scale)
print("PSUM_OK", err, scale)
""")
    assert "PSUM_OK" in out


def test_seq_sharded_decode_matches_unsharded():
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.models.layers import decode_attention
from repro.models.sharding import MeshAxes, SINGLE
from repro.configs.base import load_config
mesh = jax.make_mesh((4,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))
cfg = load_config("minitron_8b", smoke=True).replace(
    compute_dtype=jnp.float32)
B, S, Hq, Hkv, Dh = 2, 64, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (B, 1, Hq, Dh))
kc = jax.random.normal(ks[1], (B, S, Hkv, Dh))
vc = jax.random.normal(ks[2], (B, S, Hkv, Dh))
ref = decode_attention(q, kc, vc, jnp.int32(50), cfg, SINGLE)
axes = MeshAxes(batch=(), model="model", enabled=True, kv_partition="seq")
with jax.set_mesh(mesh):
    got = jax.jit(lambda *a: decode_attention(*a, cfg, axes))(
        q, kc, vc, jnp.int32(50))
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4,
                           rtol=1e-4)
print("DECODE_OK")
""")
    assert "DECODE_OK" in out


def test_production_mesh_shapes():
    out = run_py("""
from repro.launch.mesh import make_production_mesh
m1 = make_production_mesh()
assert dict(m1.shape) == {"data": 16, "model": 16}, m1.shape
m2 = make_production_mesh(multi_pod=True)
assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
print("MESH_OK", m1.axis_names, m2.axis_names)
""", devices=512)
    assert "MESH_OK" in out


def test_train_step_on_small_mesh():
    """Two sharded train steps on a 2x2 mesh (full jit path with
    in_shardings + donation), loss finite and decreasing-ish."""
    out = run_py("""
import jax, jax.numpy as jnp
from repro.configs.base import load_config, ShapeSpec
from repro.launch.train import train
from repro.launch.mesh import make_host_mesh
from repro.optim import adamw
cfg = load_config("qwen3_8b", smoke=True)
mesh = make_host_mesh(2, 2)
shape = ShapeSpec("t", 32, 4, "train")
with jax.set_mesh(mesh):
    _, _, losses = train(cfg, shape, steps=6,
                         opt_cfg=adamw.AdamWConfig(lr=1e-3, warmup_steps=2,
                                                   total_steps=6),
                         mesh=mesh, log_every=2, log_fn=lambda *a: None)
import math
assert all(math.isfinite(l) for _, l in losses)
print("TRAIN_MESH_OK", losses[-1][1])
""")
    assert "TRAIN_MESH_OK" in out


def test_vocab_parallel_ce_matches_gather():
    out = run_py("""
import jax, jax.numpy as jnp
from repro.configs.base import load_config
from repro.models import model as MF
from repro.models.sharding import MeshAxes
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,)*2)
cfg_g = load_config("qwen3_8b", smoke=True).replace(
    param_dtype=jnp.float32, compute_dtype=jnp.float32)
cfg_v = cfg_g.replace(ce_impl="vocab_parallel", embed_sharding="model_only")
axes = MeshAxes(batch=("data",), model="model", enabled=True)
m_g = MF.build_model(cfg_g, axes, mesh)
m_v = MF.build_model(cfg_v, axes, mesh)
params = m_g.init(jax.random.PRNGKey(0))
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 500),
         "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, 500)}
with jax.set_mesh(mesh):
    lg, _ = jax.jit(m_g.loss)(params, batch)
    lv, _ = jax.jit(m_v.loss)(params, batch)
assert abs(float(lg) - float(lv)) < 1e-4, (lg, lv)
print("VP_CE_OK")
""")
    assert "VP_CE_OK" in out
