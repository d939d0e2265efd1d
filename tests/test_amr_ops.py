"""AMR operators: refluxed Laplacian, advection-diffusion on blocks, AMR
Poisson solve (reference FluxCorrection + ComputeLHS + PoissonSolverAMR)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cup3d_tpu.grid.blocks import BlockGrid
from cup3d_tpu.grid.flux import build_flux_tables
from cup3d_tpu.grid.octree import Octree, TreeConfig
from cup3d_tpu.grid.uniform import BC, UniformGrid
from cup3d_tpu.ops import amr_ops
from tests._grids import two_level_grid
from tests.test_blocks import BS, blocks_from_dense


def _uniform_block_grid(n_blocks=2):
    t = Octree(TreeConfig((n_blocks,) * 3, 1, (True,) * 3), 0)
    return BlockGrid(t, (float(n_blocks),) * 3, (BC.periodic,) * 3, bs=BS)


def test_laplacian_uniform_topology_matches_dense():
    g = _uniform_block_grid()
    rng = np.random.default_rng(0)
    dense = rng.standard_normal([2 * BS] * 3).astype(np.float32)
    f = jnp.asarray(blocks_from_dense(g, dense, 0))
    out = np.asarray(
        amr_ops.laplacian_blocks(g, f, g.lab_tables(1), build_flux_tables(g))
    )

    from cup3d_tpu.ops import krylov

    ug = UniformGrid((2 * BS,) * 3, (2.0,) * 3, (BC.periodic,) * 3)
    ref = np.asarray(krylov.make_laplacian(ug)(jnp.asarray(dense)))
    ref_blocks = blocks_from_dense(g, ref, 0)
    np.testing.assert_allclose(out, ref_blocks, rtol=0, atol=1e-3)


def test_refluxed_laplacian_is_conservative():
    """sum over the domain of lap(f) h^3 must vanish on a periodic 2-level
    grid — the defining property of conservative refluxing (reference
    FillBlockCases, main.cpp:729-801)."""
    g = two_level_grid(2.0)
    rng = np.random.default_rng(1)
    f = jnp.asarray(rng.standard_normal((g.nb, BS, BS, BS)).astype(np.float32))
    vol = (g.h**3).reshape(g.nb, 1, 1, 1)

    out_nofix = amr_ops.laplacian_blocks(g, f, g.lab_tables(1), None)
    out_fix = amr_ops.laplacian_blocks(
        g, f, g.lab_tables(1), build_flux_tables(g)
    )
    total_nofix = float(jnp.sum(out_nofix * vol))
    total_fix = float(jnp.sum(out_fix * vol))
    scale = float(jnp.sum(jnp.abs(out_fix) * vol))
    assert abs(total_fix) / scale < 1e-5, (total_fix, scale)
    # and the correction matters: without it conservation genuinely fails
    assert abs(total_nofix) > 100 * abs(total_fix)


def test_laplacian_two_level_linear_exact():
    """lap of a linear field is zero everywhere, including at coarse-fine
    interfaces (ghosts and refluxing are exact for linears)."""
    g = two_level_grid(2.0)
    xc = g.cell_centers(np.float64)
    f = jnp.asarray(
        (1.0 + 0.5 * xc[..., 0] - 0.25 * xc[..., 1]).astype(np.float32)
    )
    out = np.asarray(
        amr_ops.laplacian_blocks(g, f, g.lab_tables(1), build_flux_tables(g))
    )
    # periodic seam: a linear field wraps; exclude blocks on the seam rows
    interior = []
    for s, (l, i, j, k) in enumerate(g.keys):
        n = [b << l for b in g.tree.cfg.bpd]
        if 0 < i < n[0] - 1 and 0 < j < n[1] - 1 and 0 < k < n[2] - 1:
            interior.append(s)
    if interior:
        np.testing.assert_allclose(out[interior], 0.0, atol=2e-3)
    # interior cells of every block (stencil never leaves the block) are
    # exactly zero regardless of the seam
    np.testing.assert_allclose(out[:, 2:-2, 2:-2, 2:-2], 0.0, atol=2e-3)


def test_advdiff_uniform_topology_matches_dense():
    g = _uniform_block_grid()
    rng = np.random.default_rng(2)
    dense = rng.standard_normal([2 * BS] * 3 + [3]).astype(np.float32)
    f = np.zeros((g.nb, BS, BS, BS, 3), np.float32)
    for c in range(3):
        f[..., c] = blocks_from_dense(g, dense[..., c], 0)

    nu = 0.05
    uinf = jnp.zeros(3, jnp.float32)
    dt = jnp.float32(1e-3)
    out = np.asarray(
        amr_ops.rk3_step_blocks(
            g, jnp.asarray(f), dt, nu, uinf, g.lab_tables(3), build_flux_tables(g)
        )
    )

    from cup3d_tpu.ops.advection import rk3_step

    ug = UniformGrid((2 * BS,) * 3, (2.0,) * 3, (BC.periodic,) * 3)
    ref = np.asarray(rk3_step(ug, jnp.asarray(dense), dt, nu, uinf))
    ref_b = np.zeros_like(out)
    for c in range(3):
        ref_b[..., c] = blocks_from_dense(g, ref[..., c], 0)
    np.testing.assert_allclose(out, ref_b, rtol=0, atol=1e-5)


def test_amr_poisson_solver_converges():
    g = two_level_grid(2.0)
    xc = g.cell_centers(np.float64)
    rhs = np.sin(np.pi * xc[..., 0]) * np.cos(np.pi * xc[..., 1]) * np.cos(
        2 * np.pi * xc[..., 2]
    )
    rhs = jnp.asarray(rhs.astype(np.float32))
    solve = amr_ops.build_amr_poisson_solver(g, tol_abs=1e-6, tol_rel=1e-5)
    p = jax.jit(solve)(rhs)

    tab = g.lab_tables(1)
    ftab = build_flux_tables(g)
    vol = jnp.asarray((g.h**3).reshape(g.nb, 1, 1, 1), jnp.float32)
    b = rhs - jnp.sum(rhs * vol) / (jnp.sum(vol) * BS**3)
    res = amr_ops.laplacian_blocks(g, p, tab, ftab) - b
    rel = float(jnp.linalg.norm(res.ravel()) / jnp.linalg.norm(b.ravel()))
    assert rel < 1e-4, rel
