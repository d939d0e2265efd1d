"""FaceTables (grid/faces.py): the face-slab fast path must agree with the
per-cell LabTables reference on every face ghost, across BCs, widths,
scalar/vector, and mixed-level topologies — and the hot operators built on
it (Laplacian, Poisson solve) must match."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cup3d_tpu.grid.blocks import BlockGrid
from cup3d_tpu.grid.flux import build_flux_tables
from cup3d_tpu.grid.octree import Octree, TreeConfig
from cup3d_tpu.grid.uniform import BC
from cup3d_tpu.ops import amr_ops
from tests._grids import BS, THREE_LEVEL, assert_dots_highest


def _grid(levels=2, bc=(BC.periodic,) * 3, refine=((0, 0, 0, 0),),
          bpd=(2, 2, 2)):
    periodic = tuple(b == BC.periodic for b in bc)
    t = Octree(TreeConfig(bpd, levels, periodic), 0)
    for key in refine:
        t.refine(key)
    t.assert_balanced()
    return BlockGrid(t, (float(bpd[0]),) * 3, bc, bs=BS)


def _face_region_mask(L, w, bs):
    """Bool (L,L,L): the 6 face slabs (excluding edges/corners)."""
    m = np.zeros((L,) * 3, bool)
    inner = slice(w, w + bs)
    for a in range(3):
        for hi in (0, 1):
            idx = [inner] * 3
            idx[a] = slice(w + bs, L) if hi else slice(0, w)
            m[tuple(idx)] = True
    return m


def _check_scalar(g, w, atol=3e-6):
    rng = np.random.default_rng(0)
    f = jnp.asarray(rng.standard_normal((g.nb, BS, BS, BS)).astype(np.float32))
    ref = np.asarray(g.lab_tables(w).assemble_scalar(f, BS))
    new = np.asarray(g.face_tables(w).assemble_scalar(f, BS))
    L = BS + 2 * w
    m = _face_region_mask(L, w, BS)
    np.testing.assert_allclose(new[:, m], ref[:, m], rtol=0, atol=atol)
    # interior identical
    np.testing.assert_array_equal(
        new[:, w:w + BS, w:w + BS, w:w + BS],
        ref[:, w:w + BS, w:w + BS, w:w + BS],
    )


def _check_vector(g, w, atol=3e-6):
    rng = np.random.default_rng(1)
    f = jnp.asarray(
        rng.standard_normal((g.nb, BS, BS, BS, 3)).astype(np.float32)
    )
    ref = np.asarray(g.lab_tables(w).assemble_vector(f, BS))
    new = np.asarray(g.face_tables(w).assemble_vector(f, BS))
    L = BS + 2 * w
    m = _face_region_mask(L, w, BS)
    np.testing.assert_allclose(new[:, m], ref[:, m], rtol=0, atol=atol)


@pytest.mark.parametrize("w", [1, 3])
def test_uniform_periodic(w):
    _check_scalar(_grid(levels=1, refine=()), w)


@pytest.mark.parametrize("w", [1, 3])
def test_two_level_periodic(w):
    _check_scalar(_grid(), w)
    _check_vector(_grid(), w)


@pytest.mark.parametrize("w", [1, 3])
def test_three_level_periodic(w):
    g = _grid(levels=3, refine=THREE_LEVEL)
    _check_scalar(g, w)
    _check_vector(g, w)


@pytest.mark.parametrize(
    "bc",
    [
        (BC.wall, BC.wall, BC.wall),
        (BC.freespace, BC.freespace, BC.freespace),
        (BC.periodic, BC.wall, BC.freespace),
    ],
)
def test_closed_bc_vector_signs(bc):
    g = _grid(levels=1, bc=bc, refine=())
    _check_scalar(g, 1)
    _check_vector(g, 1)
    _check_vector(g, 3)


def test_closed_bc_mixed_levels_fallback():
    """Coarse faces near closed boundaries take the degenerate per-cell
    fallback — values must STILL match LabTables everywhere."""
    bc = (BC.wall,) * 3
    g = _grid(levels=2, bc=bc, refine=((0, 0, 0, 0),))
    assert g.face_tables(1).fb_rows is not None
    _check_scalar(g, 1)
    _check_vector(g, 1)
    _check_scalar(g, 3)
    _check_vector(g, 3)


def test_single_block_periodic_wrap():
    """bpd=1: every neighbor lookup wraps to the block itself."""
    g = _grid(levels=1, refine=(), bpd=(1, 1, 1))
    _check_scalar(g, 1)
    _check_scalar(g, 3)


def test_two_fish_style_tree():
    """bpd=1, deep refinement around the center (the run.sh topology)."""
    t = Octree(TreeConfig((1, 1, 1), 3, (False,) * 3), 0)
    t.refine((0, 0, 0, 0))
    t.refine((1, 1, 1, 1))
    t.assert_balanced()
    g = BlockGrid(t, (1.0,) * 3, (BC.freespace,) * 3, bs=BS)
    _check_scalar(g, 1)
    _check_vector(g, 3)


def test_laplacian_parity():
    g = _grid(levels=3, refine=THREE_LEVEL)
    rng = np.random.default_rng(2)
    f = jnp.asarray(rng.standard_normal((g.nb, BS, BS, BS)).astype(np.float32))
    ft = build_flux_tables(g)
    ref = np.asarray(amr_ops.laplacian_blocks(g, f, g.lab_tables(1), ft))
    new = np.asarray(amr_ops.laplacian_blocks(g, f, g.face_tables(1), ft))
    h2 = (g.h**2).reshape(g.nb, 1, 1, 1)
    np.testing.assert_allclose(new * h2, ref * h2, rtol=0, atol=5e-5)


def test_poisson_solver_with_face_tables():
    """The AMR BiCGSTAB front-end runs unchanged on FaceTables and reaches
    the same tolerance."""
    g = _grid(levels=2, refine=((0, 0, 0, 0),))
    rng = np.random.default_rng(3)
    rhs = rng.standard_normal((g.nb, BS, BS, BS)).astype(np.float32)
    vol = (g.h**3).reshape(g.nb, 1, 1, 1)
    rhs -= (rhs * vol).sum() / (vol.sum() * BS**3)
    rhs_j = jnp.asarray(rhs)
    solver = amr_ops.build_amr_poisson_solver(
        g, tab=g.face_tables(1), flux_tab=build_flux_tables(g),
        tol_abs=1e-6, tol_rel=1e-4,
    )
    x = solver(rhs_j)
    r = np.asarray(
        amr_ops.laplacian_blocks(g, x, g.face_tables(1), build_flux_tables(g))
    ) - rhs
    rn = np.sqrt((r**2).sum())
    b0 = np.sqrt((rhs**2).sum())
    assert rn <= max(1e-5, 2e-4 * b0), (rn, b0)


def test_rk3_advection_parity():
    """The RK3 advection step (w=3 vector labs) matches on both table
    kinds."""
    g = _grid(levels=2, refine=((0, 0, 0, 0),))
    rng = np.random.default_rng(4)
    vel = jnp.asarray(
        0.1 * rng.standard_normal((g.nb, BS, BS, BS, 3)).astype(np.float32)
    )
    ft = build_flux_tables(g)
    uinf = jnp.zeros(3, jnp.float32)
    ref = np.asarray(
        amr_ops.rk3_step_blocks(g, vel, 1e-3, 1e-3, uinf, g.lab_tables(3), ft)
    )
    new = np.asarray(
        amr_ops.rk3_step_blocks(g, vel, 1e-3, 1e-3, uinf, g.face_tables(3), ft)
    )
    np.testing.assert_allclose(new, ref, rtol=0, atol=2e-6)


# -- the coarse-fine interpolation is exact in float32 on every backend ----


@pytest.mark.parametrize("kind,ncomp", [("scalar", 0), ("vector", 3)])
@pytest.mark.parametrize("w", [1, 3])
def test_coarse_halo_dots_carry_highest_precision(w, kind, ncomp):
    """A CPU run cannot see operands rounded to bfloat16, which is what
    the TPU makes of a float32 dot at the default precision: the guard is
    the program itself.  Every dot_general of the halo assembly, the three
    of _coarse_halo per face among them, asks for Precision.HIGHEST."""
    g = _grid()
    tab = g.face_tables(w)
    f = jnp.zeros((g.nb, BS, BS, BS) + ((ncomp,) if ncomp else ()),
                  jnp.float32)
    closed = jax.make_jaxpr(
        lambda a: getattr(tab, "assemble_" + kind)(a, BS))(f)
    assert_dots_highest(closed.jaxpr, at_least=3)


def _prolong_f64(a):
    """Dense periodic coarse array -> twice as fine, float64: per axis the
    parabola through a cell and its two neighbours, a quarter cell off the
    centre (weights 5/32, 15/16, -3/32; grid/faces.py's module text)."""
    for ax in range(3):
        lo, hi = np.roll(a, 1, axis=ax), np.roll(a, -1, axis=ax)
        even = 5 / 32 * lo + 15 / 16 * a - 3 / 32 * hi
        odd = -3 / 32 * lo + 15 / 16 * a + 5 / 32 * hi
        shape = list(a.shape)
        shape[ax] *= 2
        a = np.stack([even, odd], axis=ax + 1).reshape(shape)
    return a


@pytest.mark.parametrize("w", [1, 3])
def test_coarse_ghosts_equal_the_float64_parabola(w):
    """Two levels, periodic, a quadratic field: the ghosts a fine block
    takes from a coarser neighbour equal, to 1e-6, the separable parabola
    computed in float64 from the coarse level's composite array (its own
    leaves, and the 8-to-1 average under the fine ones).  With operands
    rounded to bfloat16 they are off by 4e-3."""
    g = _grid()
    nc = 2 * BS  # coarse cells per axis; the fine level has twice as many
    level = np.asarray(g.level)
    h, origin = np.asarray(g.h), np.asarray(g.origin)
    cells = np.arange(BS) + 0.5
    field = np.zeros((g.nb, BS, BS, BS))
    coarse = np.zeros((nc,) * 3)
    fine = np.zeros((2 * nc,) * 3)
    covered = np.zeros((2 * nc,) * 3, bool)
    for b in range(g.nb):
        x, y, z = np.meshgrid(*(origin[b, a] + cells * h[b] for a in range(3)),
                              indexing="ij")
        field[b] = (0.3 * x * x - 0.2 * y * y + 0.15 * z * z + 0.4 * x * y
                    - 0.25 * y * z + 0.1 * x + 0.05)
        i0 = np.rint(origin[b] / h[b]).astype(int)
        box = tuple(slice(i, i + BS) for i in i0)
        if level[b] == 0:
            coarse[box] = field[b]
        else:
            fine[box], covered[box] = field[b], True
    under = fine.reshape(nc, 2, nc, 2, nc, 2).mean(axis=(1, 3, 5))
    under_fine = covered.reshape(nc, 2, nc, 2, nc, 2).all(axis=(1, 3, 5))
    due = _prolong_f64(np.where(under_fine, under, coarse))

    lab = np.asarray(g.face_tables(w).assemble_scalar(
        jnp.asarray(field, jnp.float32), BS), np.float64)
    idx = np.argwhere(_face_region_mask(BS + 2 * w, w, BS))
    checked = 0
    for b in np.flatnonzero(level == 1):
        i0 = np.rint(origin[b] / h[b]).astype(int)
        gi = tuple(((i0 + idx - w) % (2 * nc)).T)
        ghost = ~covered[gi]  # the cell lies in a coarser leaf
        got = lab[b][tuple(idx[ghost].T)]
        np.testing.assert_allclose(
            got, due[tuple(a[ghost] for a in gi)], rtol=0, atol=1e-6)
        checked += int(ghost.sum())
    assert checked == 24 * BS * BS * w  # 8 fine blocks, 3 outer faces each
