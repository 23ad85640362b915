"""Compile the main path's device kernels for a TPU v5e that is not attached.

The TPU compiler is installed alongside jax, so each kernel is lowered for a
described ``v5e:2x2`` topology and compiled at real bucket shapes: what the
chip's compiler would refuse (an unsupported primitive, a program that does
not fit the 16 GB of HBM) fails here without a chip.  Nothing runs, so these
tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every xdist worker imports
this file.
"""
import pytest

from repro.core import batch_sim
from repro.core.device_mesh import LaneMesh
from repro.core.isa import TEST_ISA
from repro.core.lp import union_closure
from repro.core.uarch import SIM_SKL
from repro.core.uarch_compile import compile_uarch
from repro.service.batch_predictor import lower_port_bound

HBM_BYTES = 16 * 10**9          # one v5e chip
R = 4                           # producer slots per μop (bucketed pow2)


@pytest.fixture(scope="module")
def topo():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs outside
    try:
        from jax.experimental import topologies
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: no TPU compiler
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    try:
        yield desc
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def lut_shape():
    """(M, P): the real μop port-mask LUT of a SIM uarch."""
    return compile_uarch(SIM_SKL, TEST_ISA).mask_table.shape


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


@pytest.mark.parametrize("S,E", [(1536, 256), (256, 2048)])
def test_scan_kernel_compiles_for_v5e(one_chip, lut_shape, S, E):
    compiled = batch_sim.lower_kernel(S, E, R, *lut_shape,
                                      sharding=one_chip).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def test_scan_kernel_compiles_on_four_chip_mesh(topo, lut_shape):
    mesh = LaneMesh(topo.devices)
    assert mesh.n == 4
    lowered = batch_sim.lower_kernel(1536, 256, R, *lut_shape, mesh=mesh)
    compiled = lowered.compile()
    # lanes are independent: the partitioned program needs no collective
    hlo = compiled.as_text()
    assert "all-reduce" not in hlo and "all-gather" not in hlo
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def test_predictor_kernel_compiles_for_v5e(one_chip):
    combos = sorted({frozenset(u.ports) for b in SIM_SKL.behaviors.values()
                     for u in b.uops if u.ports},
                    key=lambda pc: (len(pc), sorted(pc)))
    cands = union_closure(combos)
    assert cands
    compiled = lower_port_bound(2048, len(combos), len(cands),
                                sharding=one_chip).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES

