"""AMR block grid: octree topology, Hilbert ordering, halo assembly
(reference Grid/BlockLab/SynchronizerMPI_AMR semantics)."""

import jax.numpy as jnp
import numpy as np
import pytest

from cup3d_tpu.grid.blocks import (
    BlockGrid,
    assemble_scalar_lab,
    assemble_vector_lab,
)
from cup3d_tpu.grid.octree import Octree, TreeConfig
from cup3d_tpu.grid.sfc import hilbert_index
from cup3d_tpu.grid.uniform import BC, UniformGrid
from tests._grids import two_level_grid

BS = 8


def _tree(bpd=(2, 2, 2), level_max=3, level_start=0, periodic=(True,) * 3):
    return Octree(TreeConfig(bpd, level_max, periodic), level_start)


def _grid(tree, bc=(BC.periodic,) * 3, extent=None):
    if extent is None:
        e = tree.cfg.bpd
        extent = (float(e[0]), float(e[1]), float(e[2]))
    return BlockGrid(tree, extent, bc, bs=BS)


def dense_from_blocks(g: BlockGrid, f: np.ndarray, level: int) -> np.ndarray:
    """Reassemble a single-level block field into a dense array."""
    n = [b * BS << level for b in g.tree.cfg.bpd]
    out = np.zeros(n, f.dtype)
    for s, (l, i, j, k) in enumerate(g.keys):
        assert l == level
        out[i * BS:(i + 1) * BS, j * BS:(j + 1) * BS, k * BS:(k + 1) * BS] = f[s]
    return out


def blocks_from_dense(g: BlockGrid, dense: np.ndarray, level: int) -> np.ndarray:
    out = np.zeros((g.nb, BS, BS, BS), dense.dtype)
    for s, (l, i, j, k) in enumerate(g.keys):
        out[s] = dense[i * BS:(i + 1) * BS, j * BS:(j + 1) * BS,
                       k * BS:(k + 1) * BS]
    return out


# -- octree ----------------------------------------------------------------


def test_octree_refine_compress_roundtrip():
    t = _tree()
    key = (0, 1, 1, 0)
    kids = t.refine(key)
    assert len(kids) == 8 and all(k in t.leaves for k in kids)
    assert key not in t.leaves
    t.assert_balanced()
    t.compress(kids[3])
    assert key in t.leaves and not any(k in t.leaves for k in kids)
    assert len(t.leaves) == 8


def test_octree_owner_lookup():
    t = _tree()
    t.refine((0, 0, 0, 0))
    assert t.owner_level(0, (0, 0, 0)) == 1  # covered by finer
    assert t.owner_level(1, (0, 0, 1)) == 1  # the child leaf itself
    assert t.owner_level(1, (2, 0, 0)) == 0  # covered by coarser leaf
    t.assert_balanced()


def test_owner_lookup_deep_refinement():
    """Covered-finer classification must be exact tree state, not a
    corner-child probe: a balanced tree whose corner child is itself
    refined used to raise KeyError from owner_level/assert_balanced
    (ADVICE round-1 repro)."""
    t = _tree(bpd=(2, 2, 2), level_max=4, periodic=(False,) * 3)
    for key in [(0, 0, 0, 0), (0, 1, 0, 0), (1, 0, 0, 0), (1, 1, 0, 0),
                (2, 2, 0, 0)]:
        t.refine(key)
    t.assert_balanced()  # balanced (non-periodic: deep leaves sit at a wall)
    # the level-0 position (0,0,0) is covered finer even though its corner
    # child (1,0,0,0) is internal, not a leaf
    assert t.owner_level(0, (0, 0, 0)) == 1
    assert t.covered_finer((0, 0, 0, 0))
    assert t.covered_finer((1, 0, 0, 0))
    assert not t.covered_finer((2, 4, 0, 0))
    # vectorized owner lookup + lab/flux table construction must succeed
    g = _grid(t, bc=(BC.wall,) * 3)
    for w in (1, 2):
        g.lab_tables(w)
    from cup3d_tpu.grid.flux import build_flux_tables

    build_flux_tables(g)
    # under periodic wrap the same refinement IS unbalanced: level-2 leaves
    # touch the level-0 column through the z-boundary
    tp = _tree(bpd=(2, 2, 2), level_max=4)
    for key in [(0, 0, 0, 0), (0, 1, 0, 0), (1, 0, 0, 0), (1, 1, 0, 0),
                (2, 2, 0, 0)]:
        tp.refine(key)
    with pytest.raises(AssertionError):
        tp.assert_balanced()


def test_assert_balanced_catches_violation():
    t = _tree(bpd=(2, 2, 2), level_max=3)
    t.refine((0, 0, 0, 0))
    t.refine((1, 0, 0, 0))  # level-2 leaves now touch level-0 neighbors
    with pytest.raises(AssertionError):
        t.assert_balanced()


def test_ordered_leaves_locality():
    t = _tree()
    t.refine((0, 0, 0, 0))
    keys = t.ordered_leaves()
    assert len(keys) == 15
    # children of the refined block appear contiguously
    child_pos = [n for n, k in enumerate(keys) if k[0] == 1]
    assert child_pos == list(range(child_pos[0], child_pos[0] + 8))


# -- single-level halo assembly vs dense padding ---------------------------


@pytest.mark.parametrize("bc", [BC.periodic, BC.wall, BC.freespace])
@pytest.mark.parametrize("width", [1, 2])
def test_uniform_topology_scalar_lab_matches_dense_pad(bc, width):
    t = _tree(level_max=1, periodic=(bc == BC.periodic,) * 3)
    g = _grid(t, bc=(bc,) * 3)
    rng = np.random.default_rng(0)
    dense = rng.standard_normal([2 * BS] * 3).astype(np.float32)
    f = jnp.asarray(blocks_from_dense(g, dense, 0))

    tab = g.lab_tables(width)
    labs = np.asarray(assemble_scalar_lab(f, tab, BS))

    ug = UniformGrid((2 * BS,) * 3, (2.0,) * 3, (bc,) * 3)
    padded = np.asarray(ug.pad_scalar(jnp.asarray(dense), width))
    for s, (l, i, j, k) in enumerate(g.keys):
        ref = padded[
            i * BS:i * BS + BS + 2 * width,
            j * BS:j * BS + BS + 2 * width,
            k * BS:k * BS + BS + 2 * width,
        ]
        np.testing.assert_allclose(labs[s], ref, rtol=0, atol=1e-6)


@pytest.mark.parametrize("bc", [BC.periodic, BC.wall, BC.freespace])
def test_uniform_topology_vector_lab_matches_dense_pad(bc):
    width = 2
    t = _tree(level_max=1, periodic=(bc == BC.periodic,) * 3)
    g = _grid(t, bc=(bc,) * 3)
    rng = np.random.default_rng(1)
    dense = rng.standard_normal([2 * BS] * 3 + [3]).astype(np.float32)
    f = np.zeros((g.nb, BS, BS, BS, 3), np.float32)
    for c in range(3):
        f[..., c] = blocks_from_dense(g, dense[..., c], 0)

    labs = np.asarray(assemble_vector_lab(jnp.asarray(f), g.lab_tables(width), BS))

    ug = UniformGrid((2 * BS,) * 3, (2.0,) * 3, (bc,) * 3)
    padded = np.asarray(ug.pad_vector(jnp.asarray(dense), width))
    for s, (l, i, j, k) in enumerate(g.keys):
        ref = padded[
            i * BS:i * BS + BS + 2 * width,
            j * BS:j * BS + BS + 2 * width,
            k * BS:k * BS + BS + 2 * width,
        ]
        np.testing.assert_allclose(labs[s], ref, rtol=0, atol=1e-6)


# -- two-level interpolation -----------------------------------------------


def _fill_quadratic(g: BlockGrid):
    """f(x) = a + bx + cy + dz + exy + ... full quadratic in cell centers."""
    xc = g.cell_centers(np.float64)
    x, y, z = xc[..., 0], xc[..., 1], xc[..., 2]
    f = (
        0.3
        + 1.2 * x
        - 0.7 * y
        + 0.5 * z
        + 0.25 * x * y
        - 0.1 * y * z
        + 0.35 * x * x
        - 0.2 * z * z
    )
    return f.astype(np.float32), lambda X, Y, Z: (
        0.3
        + 1.2 * X
        - 0.7 * Y
        + 0.5 * Z
        + 0.25 * X * Y
        - 0.1 * Y * Z
        + 0.35 * X * X
        - 0.2 * Z * Z
    )


def test_two_level_ghosts_exact_for_quadratics():
    """Quadratic Lagrange interpolation must reproduce quadratics exactly;
    fine->coarse averaging is exact for linears, 2nd-order for quadratics
    (cell average vs center value differs by h^2/24 * lap f)."""
    g = two_level_grid(2.0)
    f, fexact = _fill_quadratic(g)
    tab = g.lab_tables(1)
    labs = np.asarray(assemble_scalar_lab(jnp.asarray(f), tab, BS))

    gx, gy, gz = (np.asarray(a) for a in tab.ghost_xyz)
    lap_f = 2 * (0.35 - 0.2)  # laplacian of the quadratic
    for s, (l, i, j, k) in enumerate(g.keys):
        h = g.h[s]
        ox, oy, oz = g.origin[s]
        X = ox + (gx - tab.width + 0.5) * h
        Y = oy + (gy - tab.width + 0.5) * h
        Z = oz + (gz - tab.width + 0.5) * h
        expect = fexact(X, Y, Z)
        got = labs[s][gx, gy, gz]
        # the quadratic is not periodic: only check ghosts that stay inside
        # margin: 2 coarse cells from the seam, so the quadratic-interp
        # stencil of checked ghosts never wraps the (non-periodic) function
        m = 2 * g.h0
        ext = g.extent
        inside = (
            (X >= m) & (X <= ext[0] - m) & (Y >= m) & (Y <= ext[1] - m)
            & (Z >= m) & (Z <= ext[2] - m)
        )
        # tolerance: exact for the interpolation path; averaging path has
        # the h^2/24 cell-average offset
        hmax = g.h.max()
        tol = abs(lap_f) * hmax * hmax / 24 * 4 + 1e-5
        np.testing.assert_allclose(got[inside], expect[inside], rtol=0, atol=tol)


def test_two_level_ghosts_exact_for_linears():
    """Linear fields: every path (copy, 2:1 average, quadratic interp) is
    exact to roundoff."""
    g = two_level_grid(2.0)
    xc = g.cell_centers(np.float64)
    f = (0.5 + 2.0 * xc[..., 0] - 1.0 * xc[..., 1] + 0.25 * xc[..., 2]).astype(
        np.float32
    )
    tab = g.lab_tables(2)
    labs = np.asarray(assemble_scalar_lab(jnp.asarray(f), tab, BS))

    gx, gy, gz = (np.asarray(a) for a in tab.ghost_xyz)
    ok = True
    for s in range(g.nb):
        h = g.h[s]
        ox, oy, oz = g.origin[s]
        X = ox + (gx - tab.width + 0.5) * h
        Y = oy + (gy - tab.width + 0.5) * h
        Z = oz + (gz - tab.width + 0.5) * h
        # periodic wrap makes "linear" non-linear across the seam: restrict
        # the check to ghosts whose physical position stays inside the box
        # margin: 2 coarse cells from the seam, so the quadratic-interp
        # stencil of checked ghosts never wraps the (non-periodic) function
        m = 2 * g.h0
        ext = g.extent
        inside = (
            (X >= m) & (X <= ext[0] - m) & (Y >= m) & (Y <= ext[1] - m)
            & (Z >= m) & (Z <= ext[2] - m)
        )
        expect = 0.5 + 2.0 * X - 1.0 * Y + 0.25 * Z
        got = labs[s][gx, gy, gz]
        np.testing.assert_allclose(got[inside], expect[inside], rtol=0, atol=2e-5)
    assert ok


def test_lab_assembly_is_jittable_and_stable():
    import jax

    g = two_level_grid(2.0)
    f, _ = _fill_quadratic(g)
    tab = g.lab_tables(1)
    fn = jax.jit(lambda x: assemble_scalar_lab(x, tab, BS))
    a = np.asarray(fn(jnp.asarray(f)))
    b = np.asarray(assemble_scalar_lab(jnp.asarray(f), tab, BS))
    np.testing.assert_array_equal(a, b)
