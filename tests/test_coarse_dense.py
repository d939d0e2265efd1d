"""The coarse solve of the forest's two-level preconditioner as ONE dense
product (ops/krylov.py: ``BlockGraph.pinv``, ``coarse_correct_blocks``).

``block_graph_tables`` builds the pseudo-inverse of the block-graph
Laplacian on the host, in float64, for forests of at most
``krylov.DENSE_COARSE_MAX`` blocks; ``coarse_correct_blocks`` multiplies
by it at ``Precision.HIGHEST`` where it is there and runs the 32-sweep CG
over the gathered graph where it is not.  Held here: the matrix is the
pseudo-inverse and keeps padding rows exactly 0; both arms are the same
operator to the CG's tolerance; the outer BiCGSTAB does not notice; the
size of the forest alone chooses the arm; the dense arm traces to one
``dot_general`` and no ``gather`` or ``while``; the bucketed driver says
which arm it bound (gauge ``poisson.coarse_dense``) and carries a fresh
matrix through a regrid inside a bucket without retracing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cup3d_tpu.analysis.runtime import RecompileCounter
from cup3d_tpu.grid import bucket as bk
from cup3d_tpu.grid.blocks import BlockGrid
from cup3d_tpu.grid.flux import build_flux_tables
from cup3d_tpu.grid.octree import Octree, TreeConfig
from cup3d_tpu.grid.uniform import BC
from cup3d_tpu.obs import metrics as obs_metrics
from cup3d_tpu.ops import amr_ops, krylov
from cup3d_tpu.sim.amr import AMRSimulation
from tests._grids import assert_dots_highest, iter_eqns
from tests.test_bucketing import _cfg, _states, _step


def _forest():
    """test_two_level_cuts_amr_iterations' forest: 4^3 periodic base, the
    corner octant refined (56 + 64 = 120 blocks on two levels)."""
    tree = Octree(TreeConfig((4, 4, 4), 2, (True,) * 3), 0)
    for key in [k for k in list(tree.leaves) if max(k[1], k[2], k[3]) < 2]:
        tree.refine(key)
    return BlockGrid(tree, (1.0, 1.0, 1.0), (BC.periodic,) * 3, 8)


def _apply_laplacian(graph, z):
    """C z = deg z - W z as the CG loop applies it, gathers and all, in
    float64 on the host: the operator's definition, no matrix."""
    idx = np.asarray(graph.idx)
    w, deg = np.asarray(graph.w, np.float64), np.asarray(graph.deg, np.float64)
    return deg * z - (z[idx] * w).sum(axis=-1)


@pytest.fixture(scope="module")
def forest():
    g = _forest()
    cap = bk.capacity(g.nb)
    dense = krylov.block_graph_tables(g, cap=cap)
    xc = g.cell_centers(np.float64)
    rhs = (np.sin(2 * np.pi * xc[..., 0]) * np.cos(2 * np.pi * xc[..., 1])
           + 0.3 * np.sin(6 * np.pi * xc[..., 2]))
    return dict(g=g, cap=cap, dense=dense, loop=dense._replace(pinv=None),
                rhs=rhs.astype(np.float32))


def _padded_residual(forest):
    """A mean-free residual and the volume column, padded to the bucket."""
    g, cap = forest["g"], forest["cap"]
    vol = np.zeros((cap, 1, 1, 1), np.float32)
    vol[: g.nb, 0, 0, 0] = g.h**3
    r = np.zeros((cap,) + forest["rhs"].shape[1:], np.float32)
    r[: g.nb] = forest["rhs"]
    r -= (r * vol).sum() / (vol.sum() * g.bs**3) * (vol > 0)
    return jnp.asarray(r), jnp.asarray(vol)


def test_pinv_is_the_pseudo_inverse_and_keeps_padding_zero(forest):
    g, cap, graph = forest["g"], forest["cap"], forest["dense"]
    assert cap > g.nb
    pinv = np.asarray(graph.pinv)
    assert pinv.shape == (cap, cap) and pinv.dtype == np.float32
    np.testing.assert_array_equal(pinv, pinv.T)
    assert np.all(pinv[g.nb:] == 0.0) and np.all(pinv[:, g.nb:] == 0.0)
    v = np.zeros(cap)
    v[: g.nb] = np.random.default_rng(0).standard_normal(g.nb)
    v[: g.nb] -= v[: g.nb].mean()
    back = _apply_laplacian(graph, pinv.astype(np.float64) @ v)
    assert np.abs(back - v).max() <= 1e-5 * np.abs(v).max()
    # the constant is the null space of both
    assert np.abs(pinv[: g.nb, : g.nb].sum(axis=1)).max() < 1e-6


def test_dense_and_looped_coarse_solves_agree(forest):
    g = forest["g"]
    r, vol = _padded_residual(forest)
    z_dense = np.asarray(krylov.coarse_correct_blocks(r, vol, forest["dense"]))
    z_loop = np.asarray(krylov.coarse_correct_blocks(r, vol, forest["loop"]))
    assert np.linalg.norm(z_loop) > 0.0
    assert (np.linalg.norm(z_dense - z_loop)
            <= 1e-4 * np.linalg.norm(z_loop))
    assert np.all(z_dense[g.nb:] == 0.0) and np.all(z_loop[g.nb:] == 0.0)


def test_dense_coarse_solve_traces_to_one_dot_at_highest(forest):
    r, vol = _padded_residual(forest)
    jaxpr = jax.make_jaxpr(krylov.coarse_correct_blocks)(
        r, vol, forest["dense"])
    names = [eqn.primitive.name for eqn, _, _ in iter_eqns(jaxpr)]
    assert names.count("dot_general") == 1
    assert_dots_highest(jaxpr, at_least=1)
    assert not {"gather", "while", "scan"} & set(names), names
    # ... and the loop is what a graph without the matrix still traces to
    looped = [eqn.primitive.name for eqn, _, _ in iter_eqns(
        jax.make_jaxpr(krylov.coarse_correct_blocks)(r, vol, forest["loop"]))]
    # (a fori_loop of known trip count is a scan in the jaxpr)
    assert {"while", "scan"} & set(looped) and "gather" in looped
    assert "dot_general" not in looped


@pytest.fixture(scope="module")
def outer_solve(forest):
    """test_two_level_cuts_amr_iterations' outer BiCGSTAB on the unpadded
    forest, the graph a traced argument: (iterations, recursive residual,
    true residual, |b|) of one solve."""
    g = forest["g"]
    tab, ftab = g.lab_tables(1), build_flux_tables(g)
    vol = jnp.asarray((g.h**3).reshape(g.nb, 1, 1, 1), jnp.float32)
    rhs = jnp.asarray(forest["rhs"])
    b = rhs - jnp.sum(rhs * vol) / (jnp.sum(vol) * g.bs**3)
    h_col = jnp.asarray(g.h.reshape(g.nb, 1, 1, 1), jnp.float32)
    bnorm = float(jnp.sqrt(jnp.sum(b * b)))

    def A(x):
        return amr_ops.laplacian_blocks(g, x, tab, ftab)

    @jax.jit
    def solve(graph):
        def M(r):
            zc = krylov.coarse_correct_blocks(r, vol, graph)
            zf = jnp.broadcast_to(zc[:, None, None, None], r.shape)
            return krylov.getz_blocks(-h_col * h_col * (r - A(zf))) + zf

        x, rn, k = krylov.bicgstab(A, b, M=M, tol_abs=1e-7, tol_rel=1e-5,
                                   rnorm_ref=jnp.sqrt(jnp.sum(b * b)))
        res = A(x) - b
        return k, rn, jnp.sqrt(jnp.sum(res * res))

    def run(graph):
        k, rn, res = solve(graph)
        return int(k), float(rn), float(res), bnorm

    return run


def test_outer_bicgstab_does_not_notice(forest, outer_solve):
    g = forest["g"]
    k_d, rn_d, res_d, bnorm = outer_solve(krylov.block_graph_tables(g))
    k_l, rn_l, res_l, _ = outer_solve(
        krylov.block_graph_tables(g)._replace(pinv=None))
    assert k_d <= k_l, (k_d, k_l)
    assert rn_d <= 1e-5 * bnorm * 1.01 and rn_l <= 1e-5 * bnorm * 1.01
    # the recomputed TRUE residual, under test_bucketing's bar and the
    # same on both arms (7.1e-5 |b| on either in the issue's experiment)
    assert res_d < 5e-4 * bnorm and res_l < 5e-4 * bnorm
    assert res_d <= 1.25 * res_l, (res_d, res_l)


def test_a_forest_above_the_bound_keeps_the_loop(forest, outer_solve,
                                                 monkeypatch):
    """The size of the forest alone chooses: no argument, no switch."""
    g = forest["g"]
    monkeypatch.setattr(krylov, "DENSE_COARSE_MAX", g.nb - 1)
    assert krylov.block_graph_tables(g).pinv is None
    monkeypatch.setattr(krylov, "DENSE_COARSE_MAX", g.nb)
    assert krylov.block_graph_tables(g).pinv is not None
    # padded, the bucket's capacity is what the device holds: it decides
    assert krylov.block_graph_tables(g, cap=forest["cap"]).pinv is None
    monkeypatch.setattr(krylov, "DENSE_COARSE_MAX", 8)
    graph = krylov.block_graph_tables(g)
    assert graph.pinv is None
    k, rn, res, bnorm = outer_solve(graph)
    assert rn <= 1e-5 * bnorm * 1.01 and res < 5e-4 * bnorm and k < 20


def _probe_iterations(sim):
    """Iterations of the bound solver on a smooth right-hand side over the
    grid the driver holds now."""
    xc = sim._xc
    rhs = (jnp.sin(xc[..., 0]) * jnp.cos(xc[..., 1])
           + 0.3 * jnp.sin(3.0 * xc[..., 2])) * sim._real_mask
    x, stats = sim._solver(rhs, tab_arg=sim._tab1, flux_arg=sim._ftab,
                           with_stats=True)
    assert bool(jnp.all(jnp.isfinite(x)))
    return int(np.asarray(stats)[1])


def test_a_regrid_inside_a_bucket_brings_its_own_matrix(tmp_path):
    """Two topologies of one bucket (71 blocks, another block refined):
    the second binds the executables of the first (no retrace) with the
    pseudo-inverse of ITS graph, and its solve converges as the first's."""
    hits = obs_metrics.counter("bucket.exec_cache_hits")
    with RecompileCounter() as rc:  # counts the jits built inside it
        sim = AMRSimulation(_cfg(tmp_path, initCond="taylorGreen",
                                 extent=float(2 * np.pi)))
        sim.init()
        sim.adapt_enabled = False
        assert obs_metrics.gauge("poisson.coarse_dense").value == 1
        assert sim._apply_states(_states(sim, refine=(0, 0, 0, 0)))
        _step(sim)
        pinv_b1, k_b1 = np.asarray(sim._graph.pinv), _probe_iterations(sim)
        assert sim._apply_states(_states(sim, coarsen_parent=(0, 0, 0, 0)))
        hits0, compiled = hits.value, rc.total_compiles
        assert compiled > 0
        assert sim._apply_states(_states(sim, refine=(0, 2, 2, 2)))
        _step(sim)
        assert rc.total_compiles == compiled, rc.compiles
    assert hits.value == hits0 + 1
    assert obs_metrics.gauge("poisson.coarse_dense").value == 1
    pinv_b2 = np.asarray(sim._graph.pinv)
    assert pinv_b2.shape == pinv_b1.shape == (sim._cap, sim._cap)
    assert not np.array_equal(pinv_b2, pinv_b1)
    np.testing.assert_array_equal(
        pinv_b2,
        np.asarray(krylov.block_graph_tables(sim.grid, cap=sim._cap).pinv))
    assert abs(_probe_iterations(sim) - k_b1) <= 2
    for k, f in sim.state.items():
        assert float(jnp.max(jnp.abs(f[sim.grid.nb:]))) == 0.0, k
