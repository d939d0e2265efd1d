"""Capacity bucketing (grid/bucket.py + sim/amr.py compiled-step cache)
and the AMR two-level preconditioner (ops/krylov.py block graph).

The contract under test (VALIDATION.md "Capacity bucketing"):

- compiles are bounded by the number of DISTINCT buckets visited, not
  the number of regrids (RecompileCounter-verified);
- re-entering a bucket through the compiled-step cache computes
  bit-identically to the freshly-compiled first visit (stale topology
  baked into a reused executable would break this);
- padding blocks stay exactly zero through stepping;
- padding is neutral: one rung more of it changes reduction-free
  kernels at the last ulp and full trajectories at f32 round-off (the
  Krylov global dots reduce over differently-shaped padded arrays whose
  XLA reduction trees round differently at the ulp, which legitimately
  perturbs the iteration path);
- the block-graph coarse level cuts AMR BiCGSTAB outer iterations vs
  tile-only getZ at equal solution quality.
"""

import jax.numpy as jnp
import numpy as np

from cup3d_tpu.analysis.runtime import RecompileCounter
from cup3d_tpu.config import SimulationConfig
from cup3d_tpu.grid import bucket as bk
from cup3d_tpu.sim.amr import AMRSimulation


def _cfg(tmp_path, **kw):
    base = dict(
        bpdx=4, bpdy=4, bpdz=4, levelMax=2, levelStart=0, extent=1.0,
        nu=1e-3, nsteps=2, rampup=0, dt=1e-3, tend=-1.0,
        Rtol=1e9, Ctol=-1.0,  # no natural tagging: tests force regrids
        step_2nd_start=0,  # one projection variant -> clean compile math
        verbose=False, path4serialization=str(tmp_path),
    )
    base.update(kw)
    return SimulationConfig(**base)


def _states(sim, refine=None, coarsen_parent=None):
    """Hand-built tag states: refine one leaf / coarsen one octet."""
    st = {k: "L" for k in sim.grid.keys}
    if refine is not None:
        st[refine] = "R"
    if coarsen_parent is not None:
        l, i, j, k = coarsen_parent
        for di in (0, 1):
            for dj in (0, 1):
                for dk in (0, 1):
                    st[(l + 1, 2 * i + di, 2 * j + dj, 2 * k + dk)] = "C"
    return st


def _step(sim, n=1):
    for _ in range(n):
        sim.advance(sim.calc_max_timestep())


def test_capacity_ladder():
    # strict for the block axis: >= 1 padding block always exists
    assert bk.capacity(0) == 8
    assert bk.capacity(8) > 8
    for n in (1, 7, 8, 63, 64, 500):
        c = bk.capacity(n)
        assert c > n
        assert c <= max(8, int(np.ceil(1.25 * n)) + 1)
    # count ladder: 0 stays 0, rung >= n otherwise
    assert bk.count_capacity(0) == 0
    assert bk.count_capacity(5) >= 5
    assert bk.count_capacity(5) == bk.count_capacity(
        bk.count_capacity(5)
    )


def test_compiles_bounded_by_buckets_not_regrids(tmp_path):
    """The ISSUE acceptance test: a forced refine -> coarsen -> refine
    cycle compiles only when it enters a NEW bucket; revisiting a bucket
    — even via a different same-signature topology — adds zero."""
    with RecompileCounter() as rc:
        sim = AMRSimulation(_cfg(tmp_path))
        sim.init()
        sim.adapt_enabled = False
        _step(sim, 2)
        base = rc.total_compiles
        assert base > 0  # the counter saw the bucket-A executables

        # bucket B: refine the corner block (64 -> 71 blocks)
        assert sim._apply_states(_states(sim, refine=(0, 0, 0, 0)))
        _step(sim, 2)
        after_b = rc.total_compiles
        assert after_b > base  # a genuinely new bucket compiles

        # back to bucket A: ZERO new compiles
        assert sim._apply_states(
            _states(sim, coarsen_parent=(0, 0, 0, 0))
        )
        _step(sim, 2)
        assert rc.total_compiles == after_b, rc.compiles

        # a DIFFERENT topology with the same bucket signature (refine a
        # far block): still ZERO new compiles — the compiled-step cache
        # is keyed on shapes, not on the particular leaf set
        assert sim._apply_states(_states(sim, refine=(0, 2, 2, 2)))
        _step(sim, 2)
        assert rc.total_compiles == after_b, rc.compiles
    assert len(sim._exec_cache) == 2  # exactly the two buckets


def test_bucket_reuse_is_bitwise(tmp_path):
    """Re-entering a bucket through the compiled-step cache computes
    bit-identically to the freshly-compiled first visit: any stale
    topology (h, tables, volumes) baked into a reused executable would
    show up here."""
    cfg = _cfg(tmp_path, initCond="taylorGreen", extent=float(2 * np.pi))
    sim = AMRSimulation(cfg)
    sim.init()
    sim.adapt_enabled = False

    def run_in_b():
        sim._ic()  # identical IC on the (current) B topology
        for k in ("chi", "udef"):
            sim.state[k] = sim._pad(jnp.zeros_like(
                sim._unpad(sim.state[k])))
        _step(sim, 3)
        return (np.asarray(sim._unpad(sim.state["vel"])),
                np.asarray(sim._unpad(sim.state["p"])))

    # first visit to bucket B: compiles fresh
    assert sim._apply_states(_states(sim, refine=(0, 0, 0, 0)))
    v1, p1 = run_in_b()
    # leave and re-enter the SAME topology: cache hit on every executable
    assert sim._apply_states(_states(sim, coarsen_parent=(0, 0, 0, 0)))
    assert sim._apply_states(_states(sim, refine=(0, 0, 0, 0)))
    v2, p2 = run_in_b()
    np.testing.assert_array_equal(v1, v2)
    np.testing.assert_array_equal(p1, p2)


def test_padding_rows_stay_zero(tmp_path):
    cfg = _cfg(tmp_path, bpdx=2, bpdy=2, bpdz=2, nsteps=3,
               initCond="taylorGreen", extent=float(2 * np.pi),
               Rtol=0.5, Ctol=0.01, dt=-1.0, tend=0.0, CFL=0.3, nu=0.02)
    sim = AMRSimulation(cfg)
    sim.init()
    sim.simulate()
    nb, cap = sim.grid.nb, sim._cap
    assert cap > nb  # strict ladder: the dump block exists
    for k, f in sim.state.items():
        assert float(jnp.max(jnp.abs(f[nb:]))) == 0.0, k


def test_table_memo_hits_on_pingpong(tmp_path):
    """A -> B -> A reuses the memoized padded tables (same objects), so
    ping-pong regrids skip the host gather-table rebuild entirely."""
    sim = AMRSimulation(_cfg(tmp_path))
    sim.init()
    tab_a = sim._tab1
    assert sim._apply_states(_states(sim, refine=(0, 0, 0, 0)))
    assert sim._tab1 is not tab_a
    assert sim._apply_states(_states(sim, coarsen_parent=(0, 0, 0, 0)))
    assert sim._tab1 is tab_a  # memo hit, not a rebuild
    assert len(sim._table_memo) == 2


def test_padding_is_neutral(tmp_path, monkeypatch):
    """Padding rows change nothing beyond round-off: the same adapting
    TGV run at the ladder's own capacity and again one rung up.
    Trajectories agree to f32 round-off; exact bitwise equality is NOT
    expected through the Krylov solve (module docstring: padded-shape
    reductions round differently at the ulp and perturb the iteration
    path)."""
    ladder = bk.capacity

    def run(name, capacity):
        with monkeypatch.context() as m:
            m.setattr(bk, "capacity", capacity)
            cfg = SimulationConfig(
                bpdx=2, bpdy=2, bpdz=2, levelMax=2, levelStart=0,
                extent=float(2 * np.pi), CFL=0.3, nu=0.02, nsteps=4,
                rampup=0, Rtol=0.5, Ctol=0.01, initCond="taylorGreen",
                poissonTol=1e-6, poissonTolRel=1e-5, verbose=False,
                path4serialization=str(tmp_path / name),
            )
            s = AMRSimulation(cfg)
            s.init()
            s.simulate()
            return s

    sb = run("own", ladder)
    sw = run("next", lambda n: ladder(ladder(n)))
    assert sb.grid.keys == sw.grid.keys
    assert sb._cap == ladder(sb.grid.nb)
    assert sw._cap == ladder(ladder(sw.grid.nb)) > sb._cap
    vb = np.asarray(sb._unpad(sb.state["vel"]))
    vw = np.asarray(sw._unpad(sw.state["vel"]))
    # measured: ulp-level; the 1e-5 gate leaves room for platform fusion
    # differences without letting a real divergence (1e-4+) through
    np.testing.assert_allclose(vb, vw, atol=1e-5)
    assert float(jnp.max(jnp.abs(sw.state["vel"][sw.grid.nb:]))) == 0.0
    # one advdiff application on the shared state: reduction-free, so
    # the two capacities agree to the last ulp of XLA's shape-dependent
    # fusion (FMA contraction differs across padded shapes — true
    # bitwise across SHAPES is not promised; the bitwise contract lives
    # in test_bucket_reuse_is_bitwise, where shapes match)
    dt = jnp.asarray(1e-3, jnp.float32)
    uinf = jnp.zeros(3, jnp.float32)
    shared = jnp.asarray(vb)
    a_b = np.asarray(sb._unpad(sb._advdiff(sb._pad(shared), dt, uinf)))
    a_w = np.asarray(sw._unpad(sw._advdiff(sw._pad(shared), dt, uinf)))
    np.testing.assert_allclose(a_b, a_w, atol=1e-6)


def test_two_level_cuts_amr_iterations():
    """The AMR two-level preconditioner (tile getZ + block-graph coarse)
    needs fewer outer BiCGSTAB iterations than tile-only getZ on a
    mixed-level forest, at equal solution quality."""
    from cup3d_tpu.grid.blocks import BlockGrid
    from cup3d_tpu.grid.flux import build_flux_tables
    from cup3d_tpu.grid.octree import Octree, TreeConfig
    from cup3d_tpu.grid.uniform import BC
    from cup3d_tpu.ops import amr_ops, krylov

    # 4^3 base + a refined corner octant (120 blocks): large enough that
    # block-Jacobi's iteration growth shows (measured 28 tile-only vs 14
    # two-level here; 41 vs 15 at 6^3 — the same resolution-independence
    # the uniform path's coarse level bought, VALIDATION.md round 8)
    tree = Octree(TreeConfig((4, 4, 4), 2, (True,) * 3), 0)
    for key in [k for k in list(tree.leaves)
                if max(k[1], k[2], k[3]) < 2]:
        tree.refine(key)
    g = BlockGrid(tree, (1.0, 1.0, 1.0), (BC.periodic,) * 3, 8)
    xc = g.cell_centers(np.float64)
    rhs = (np.sin(2 * np.pi * xc[..., 0]) * np.cos(2 * np.pi * xc[..., 1])
           + 0.3 * np.sin(6 * np.pi * xc[..., 2]))
    rhs = jnp.asarray(rhs.astype(np.float32))
    tab = g.lab_tables(1)
    ftab = build_flux_tables(g)
    vol = jnp.asarray((g.h**3).reshape(g.nb, 1, 1, 1), jnp.float32)
    b = rhs - jnp.sum(rhs * vol) / (jnp.sum(vol) * g.bs**3)
    h_col = jnp.asarray(g.h.reshape(g.nb, 1, 1, 1), jnp.float32)
    h2 = h_col * h_col
    graph = krylov.block_graph_tables(g)
    # symmetric with constant nullspace: row sums of (deg - W) vanish
    np.testing.assert_allclose(
        np.asarray(graph.deg),
        np.asarray(jnp.sum(graph.w, axis=-1)), rtol=1e-6,
    )

    def A(x):
        return amr_ops.laplacian_blocks(g, x, tab, ftab)

    def M_tile(r):
        return krylov.getz_blocks(-h2 * r)

    def M_two(r):
        zc = krylov.coarse_correct_blocks(r, vol, graph)
        zf = jnp.broadcast_to(zc[:, None, None, None], r.shape)
        return krylov.getz_blocks(-h2 * (r - A(zf))) + zf

    def solve(M):
        return krylov.bicgstab(
            A, b, M=M, tol_abs=1e-7, tol_rel=1e-5,
            rnorm_ref=jnp.sqrt(jnp.sum(b * b)),
        )

    x_t, rn_t, k_tile = solve(M_tile)
    x_2, rn_2, k_two = solve(M_two)
    bnorm = float(jnp.sqrt(jnp.sum(b * b)))
    # both converged to the same quality bar
    assert float(rn_t) <= 1e-5 * bnorm * 1.01
    assert float(rn_2) <= 1e-5 * bnorm * 1.01
    # recomputed TRUE residual: looser than the recursive one — the f32
    # BiCGSTAB recurrence drifts from the true residual by a few 1e-4
    # relative over the solve (same class of gate as the 5e-4 in
    # test_parity_gaps.test_amr_mean_constraint_modes)
    res = A(x_2) - b
    assert float(jnp.sqrt(jnp.sum(res * res))) < 5e-4 * bnorm
    # ... and the coarse level carries the smooth modes: well under the
    # block-Jacobi count (measured 14 vs 28 on this forest)
    assert int(k_two) <= 0.7 * int(k_tile), (int(k_two), int(k_tile))
