"""Compile rehearsal for the chip that is not attached: the Pallas
kernels of the solver's main path, lowered and compiled for a described
``v5e:2x2`` at their real widths (``on-chip-measurement`` guide, §2.3).

Nothing runs — a compile that passes says the chip's compiler accepts
the kernel (tiling, VMEM, partitioning), not that its results are right;
the interpret-mode parity tests and ``chip_smoke.py`` cover that.

The topology is described ONLY inside the module-scoped fixture below:
describing it loads libtpu, which one process at a time may hold, so it
must never happen at import, in ``conftest.py`` or in another test file
(a second file can land on another xdist worker, where the fixture
would skip every case in silence).

Not here on purpose: ``fused_amr_bicgstab(kernels=True)``.  Its stages
abort the chip's compiler (SIGABRT in ``VectorLayoutInferer::
inferReshape``, not a Python exception), which would take the xdist
worker and every test scheduled on it; ``amr_ops.build_amr_poisson_
solver`` refuses that path on a TPU backend until the re-layout lands.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from cup3d_tpu.grid.uniform import BC, UniformGrid


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep it off here
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_count(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("lanes", [4096, 215])
def test_getz_cg_kernel_compiles(one_chip, lanes):
    """``_cg_tiles_pallas`` at 128^3 (4096 tiles) and at a forest bucket
    capacity (215, padded to the kernel's lane tile like the callers)."""
    from cup3d_tpu.ops import getz_pallas as gp

    T = min(gp.TILE_T, lanes)
    n_pad = -(-lanes // T) * T
    bt = jax.ShapeDtypeStruct((8, 8, 8, n_pad), jnp.float32,
                              sharding=one_chip)
    shift = jax.ShapeDtypeStruct((1, 1, 1, n_pad), jnp.float32,
                                 sharding=one_chip)
    compiled = gp._cg_tiles_pallas.lower(bt, shift, iters=24).compile()
    assert _kernel_count(compiled) == 1


@pytest.mark.parametrize("store", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_fused_bicgstab_kernels_compile_128(one_chip, store):
    """The fused uniform iteration with its native stages at 128^3."""
    from cup3d_tpu.ops import fused_bicgstab as fb

    n = 128
    grid = UniformGrid((n, n, n), (1.0, 1.0, 1.0), (BC.periodic,) * 3)
    bt = jax.ShapeDtypeStruct((8, 8, 8, (n // 8) ** 3), jnp.float32,
                              sharding=one_chip)

    def solve(b):
        return fb.fused_bicgstab(grid, b, maxiter=50, store_dtype=store,
                                 kernels=True)

    compiled = jax.jit(solve).lower(bt).compile()
    assert _kernel_count(compiled) >= 3  # update / getZ+lap / finish


def test_ring_remote_copy_compiles_on_four_chips(topo):
    """``_ring_shift_pallas`` under shard_map on the 2x2 host, on the
    ``(lanes=1, x=4)`` mesh the megaloop builds (``CUP3D_MESH_X=4``):
    one x-slab halo message of the 128^3 case (3 ghost planes)."""
    from cup3d_tpu.parallel import ring
    from cup3d_tpu.parallel import topology as topology_layer
    from cup3d_tpu.parallel.compat import shard_map

    mesh = topology_layer.make_mesh2d(lanes=1, x=4,
                                      devices=list(topo.devices))

    def shift(x):
        return ring._ring_shift_pallas(x, "x", 1, 4)

    f = jax.jit(shard_map(shift, mesh, in_specs=(P("x"),),
                          out_specs=P("x")))
    x = jax.ShapeDtypeStruct((4 * 3, 128, 128), jnp.float32,
                             sharding=NamedSharding(mesh, P("x")))
    compiled = f.lower(x).compile()
    text = compiled.as_text()
    assert _kernel_count(compiled) == 1
    assert "collective-permute" not in text
