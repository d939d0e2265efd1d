"""Iterative Poisson path: getZ-preconditioned BiCGSTAB (reference
PoissonSolverAMR main.cpp:14363-14616 + poisson_kernels 14617-14746)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cup3d_tpu.grid.uniform import BC, UniformGrid
from cup3d_tpu.ops import krylov
from cup3d_tpu.ops.poisson import build_spectral_solver
from tests._grids import unit_cube


def test_block_precond_reduces_residual():
    g = unit_cube(BC.periodic)
    A = krylov.make_laplacian(g)
    M = krylov.make_block_cg_preconditioner(bs=8, iters=12, h=g.h)
    key = jax.random.PRNGKey(0)
    r = jax.random.normal(key, g.shape, jnp.float32)
    r = r - jnp.mean(r)
    z = M(r)
    # z should be a decent block-local inverse: residual of A z vs r drops
    # compared to the trivial preconditioner z=r scaled optimally.
    res_M = jnp.linalg.norm((A(z) - r).ravel()) / jnp.linalg.norm(r.ravel())
    assert np.isfinite(float(res_M))
    # the block solve is exact in the tile interior; the mismatch is only the
    # zero-Dirichlet tile skin, so the relative residual must be well below 1
    assert float(res_M) < 0.9


@pytest.mark.parametrize("bc", [BC.periodic, BC.wall])
def test_bicgstab_solves_discrete_poisson(bc):
    g = unit_cube(bc)
    A = krylov.make_laplacian(g)
    x = np.asarray(g.cell_centers())
    # manufactured pressure compatible with both wrap and zero-gradient BCs
    p_true = (
        np.cos(2 * np.pi * x[..., 0])
        * np.cos(2 * np.pi * x[..., 1])
        * np.cos(4 * np.pi * x[..., 2])
    ).astype(np.float32)
    p_true -= p_true.mean()
    rhs = A(jnp.asarray(p_true))

    solve = krylov.build_iterative_solver(g, tol_abs=1e-6, tol_rel=1e-5)
    p = jax.jit(solve)(rhs)
    err = np.linalg.norm(np.asarray(p) - p_true) / np.linalg.norm(p_true)
    assert err < 2e-3, err


def test_bicgstab_matches_spectral_on_periodic():
    g = unit_cube(BC.periodic, n=16)
    A = krylov.make_laplacian(g)
    key = jax.random.PRNGKey(1)
    rhs = jax.random.normal(key, g.shape, jnp.float32)
    rhs = rhs - jnp.mean(rhs)

    p_it = krylov.build_iterative_solver(g, tol_abs=1e-7, tol_rel=1e-6)(rhs)
    p_sp = build_spectral_solver(g, operator="compact")(rhs)
    err = np.linalg.norm(np.asarray(p_it - p_sp)) / np.linalg.norm(np.asarray(p_sp))
    assert err < 1e-3, err


def test_bicgstab_reports_iterations_and_converges_fast():
    g = unit_cube(BC.periodic)
    A = krylov.make_laplacian(g)
    M = krylov.make_block_cg_preconditioner(8, 12, h=g.h)
    key = jax.random.PRNGKey(2)
    b = jax.random.normal(key, g.shape, jnp.float32)
    b = b - jnp.mean(b)
    x, rnorm, k = krylov.bicgstab(A, b, M=M, tol_abs=1e-6, tol_rel=1e-5)
    b_norm = float(jnp.linalg.norm(b.ravel()))
    assert float(rnorm) <= max(1e-6, 1e-5 * b_norm) * 1.01
    # getZ preconditioning should converge far faster than the 1000-it cap
    assert int(k) < 100


def test_simulation_with_iterative_solver(tmp_path):
    """End-to-end driver run on the Krylov path (poissonSolver=iterative)."""
    from cup3d_tpu.config import SimulationConfig
    from cup3d_tpu.sim.simulation import Simulation

    cfg = SimulationConfig(
        bpdx=4, bpdy=4, bpdz=4, levelMax=1, levelStart=0,
        extent=2 * np.pi, CFL=0.3, nu=0.02, nsteps=3, rampup=0,
        initCond="taylorGreen", poissonSolver="iterative", freqDiagnostics=1,
        verbose=False, path4serialization=str(tmp_path),
    )
    s = Simulation(cfg)
    s.init()
    s.simulate()
    div_last = [
        float(v)
        for v in (tmp_path / "div.txt").read_text().splitlines()[-1].split()
    ]
    assert div_last[3] < 5e-3  # max|div u| after iterative projection


# -- lane-resident layout (to_lanes / make_laplacian_lanes) ------------------


def test_lanes_roundtrip():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((32, 16, 24)).astype(np.float32))
    t = krylov.to_lanes(x)
    assert t.shape == (8, 8, 8, (32 // 8) * (16 // 8) * (24 // 8))
    np.testing.assert_array_equal(np.asarray(krylov.from_lanes(t, x.shape)),
                                  np.asarray(x))


@pytest.mark.parametrize("bc", [BC.periodic, BC.wall, BC.freespace])
def test_lanes_laplacian_matches_dense(bc):
    g = UniformGrid((32, 16, 24), (1.0, 0.5, 0.75), (bc,) * 3)
    A = krylov.make_laplacian(g)
    At = krylov.make_laplacian_lanes(g)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal(g.shape).astype(np.float32))
    want = np.asarray(A(x))
    got = np.asarray(krylov.from_lanes(At(krylov.to_lanes(x)), g.shape))
    # f32 summation-order noise scales with inv_h^2 * |x|
    np.testing.assert_allclose(got, want, atol=3e-6 * np.abs(want).max())


def test_lanes_laplacian_mixed_bcs():
    g = UniformGrid((16, 24, 32), (0.5, 0.75, 1.0),
                    (BC.periodic, BC.wall, BC.periodic))
    A = krylov.make_laplacian(g)
    At = krylov.make_laplacian_lanes(g)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal(g.shape).astype(np.float32))
    want = np.asarray(A(x))
    got = np.asarray(krylov.from_lanes(At(krylov.to_lanes(x)), g.shape))
    np.testing.assert_allclose(got, want, atol=3e-6 * np.abs(want).max())


def test_lanes_solver_matches_dense_path():
    g = unit_cube(BC.periodic, n=32)
    rng = np.random.default_rng(3)
    rhs = jnp.asarray(rng.standard_normal(g.shape).astype(np.float32))
    rhs = rhs - jnp.mean(rhs)
    p_lanes = krylov.build_iterative_solver(g, tol_abs=1e-7, tol_rel=1e-6)(rhs)
    p_dense = krylov._build_iterative_solver_dense(
        g, tol_abs=1e-7, tol_rel=1e-6)(rhs)
    scale = float(jnp.max(jnp.abs(p_dense))) + 1e-30
    np.testing.assert_allclose(
        np.asarray(p_lanes) / scale, np.asarray(p_dense) / scale, atol=2e-5
    )


@pytest.mark.parametrize("bc", [BC.periodic, BC.wall])
def test_tileconst_laplacian_matches_full_operator(bc):
    """The analytic tile-face form of A@(P zc) used by the two-level
    preconditioner must equal the full lane Laplacian on the broadcast
    coarse field, for both BC families."""
    g = unit_cube(bc, n=32)
    A = krylov.make_laplacian_lanes(g)
    M = krylov.make_twolevel_preconditioner_lanes(g, g.h * g.h)
    key = jax.random.PRNGKey(1)
    r = jax.random.normal(key, (8, 8, 8, 64), jnp.float32)
    # reach inside: the closure's lap_tileconst is exercised via M, so
    # instead verify the identity M encodes: A(M(r)) ~ r up to the tile
    # skin.  A stronger direct check: build zc via the additive corrector
    # (broadcast form) and compare A(zc) with the analytic assembly.
    corr = krylov.make_coarse_correction_lanes(g)
    zc_b = corr(r)                     # broadcast tile-constant field
    zc_vec = zc_b[0, 0, 0, :]
    full = A(zc_b)
    solve_vec = krylov._make_coarse_solve_vec(g)
    assert np.allclose(np.asarray(solve_vec(r)), np.asarray(zc_vec),
                       atol=1e-5)
    # analytic: reconstruct through the public M by linearity:
    # M(r) = zc + getZ(-h2 (r - A zc))  =>  getZ term = M(r) - zc
    from cup3d_tpu.ops import tilesolve
    got = M(r) - zc_b
    want = tilesolve.tile_solve_lanes(-g.h * g.h * (r - full))
    assert np.allclose(np.asarray(got), np.asarray(want), atol=2e-4)


@pytest.mark.parametrize("bc", [BC.periodic, BC.wall])
def test_twolevel_cuts_iterations(bc):
    """Two-level preconditioner: resolution-independent iteration count,
    well below tile-only (measured 12 vs 51 at 128^3; here 48^3 keeps the
    test fast)."""
    g = unit_cube(bc, n=48)
    A = krylov.make_laplacian_lanes(g)
    h2 = g.h * g.h
    rng = np.random.default_rng(3)
    rhs = jnp.asarray(rng.standard_normal(g.shape).astype(np.float32))
    rhs = rhs - jnp.mean(rhs)
    bt = krylov.to_lanes(rhs)
    ref = jnp.sqrt(jnp.sum(bt * bt, dtype=jnp.float32))
    M1 = lambda r: krylov.getz_lanes(-h2 * r)
    M2 = krylov.make_twolevel_preconditioner_lanes(g, h2)
    _, rn1, k1 = krylov.bicgstab(A, bt, M=M1, tol_abs=1e-6, tol_rel=1e-4,
                                 rnorm_ref=ref)
    x2, rn2, k2 = krylov.bicgstab(A, bt, M=M2, tol_abs=1e-6, tol_rel=1e-4,
                                  rnorm_ref=ref)
    assert int(k2) <= 16
    assert int(k2) < int(k1)
    # converged solution really solves the system
    res = A(x2) - (bt - jnp.mean(bt))
    assert float(rn2) <= max(1e-6, 1e-4 * float(ref)) * 1.01


@pytest.mark.parametrize("bc", [BC.periodic, BC.wall])
def test_coarse_solve_degenerate_axis_matches_galerkin(bc):
    """An axis with a single tile must contribute a 1x1 coarse Laplacian
    of 0 (isolated node) for both BC families, so the coarse solve equals
    the pseudo-inverse of the exact Galerkin P^T A P and the constant
    null mode is projected out (ADVICE r5: the wall branch used to pin
    the lone diagonal to 1)."""
    bs = 8
    g = UniformGrid((8, 16, 16), (0.5, 1.0, 1.0), (bc,) * 3)
    nb = (1, 2, 2)
    solve_vec = krylov._make_coarse_solve_vec(g, bs=bs)

    # explicit exact Galerkin coarse operator: A_c = -(bs^2/h^2)(Lx+Ly+Lz)
    def lap1d(n):
        if n == 1:
            return np.zeros((1, 1))
        L = 2.0 * np.eye(n) - np.diag(np.ones(n - 1), 1) \
            - np.diag(np.ones(n - 1), -1)
        if bc == BC.periodic:
            L[0, -1] -= 1.0
            L[-1, 0] -= 1.0
        else:
            L[0, 0] = 1.0
            L[-1, -1] = 1.0
        return L

    eye = [np.eye(n) for n in nb]
    Lsum = (
        np.kron(np.kron(lap1d(nb[0]), eye[1]), eye[2])
        + np.kron(np.kron(eye[0], lap1d(nb[1])), eye[2])
        + np.kron(np.kron(eye[0], eye[1]), lap1d(nb[2]))
    )
    A_c = -(bs * bs / (g.h * g.h)) * Lsum

    rng = np.random.default_rng(7)
    rt = jnp.asarray(
        rng.standard_normal((bs, bs, bs, int(np.prod(nb)))), jnp.float32
    )
    rc = np.asarray(jnp.sum(rt, axis=(0, 1, 2)))  # P^T r, lane order
    want = np.linalg.pinv(A_c) @ rc
    got = np.asarray(solve_vec(rt))
    np.testing.assert_allclose(got, want, atol=2e-4 * max(1.0, np.abs(want).max()))
    # the global-constant null mode is projected out exactly: a constant
    # residual produces zero coarse correction
    const = jnp.ones((bs, bs, bs, int(np.prod(nb))), jnp.float32)
    zc = np.asarray(solve_vec(const))
    assert np.abs(zc).max() < 1e-5


@pytest.mark.parametrize("mc", [1, 3])
def test_mean_constraint_pinned_paths(mc, monkeypatch):
    """mean_constraint 1 (mean row) and 3 (Dirichlet pin) replace one
    equation row, making A nonsingular — but the two-level M's exact
    Galerkin coarse solve is built from the UNMODIFIED singular
    Laplacian, whose pseudo-inverse projects the constant mode back out
    (ADVICE r5).  These paths must use the tile-only preconditioner, and
    the replaced row must be rescaled to the Laplacian's O(1/h^2) row
    magnitude: unscaled, float32 BiCGSTAB stalls (1000 iterations, NaN
    breakdowns) on what should be a ~30-iteration solve."""
    monkeypatch.setenv("CUP3D_COARSE", "1")  # exercise the mc-1/3 fallback
    g = unit_cube(BC.periodic)
    A = krylov.make_laplacian(g)
    x = np.asarray(g.cell_centers())
    p_true = (
        np.cos(2 * np.pi * x[..., 0])
        * np.cos(2 * np.pi * x[..., 1])
        * np.cos(4 * np.pi * x[..., 2])
    ).astype(np.float32)
    p_true -= p_true.mean()
    rhs = A(jnp.asarray(p_true))

    solve = krylov.build_iterative_solver(
        g, tol_abs=1e-6, tol_rel=1e-5, mean_constraint=mc
    )
    p = np.asarray(jax.jit(solve)(rhs))
    # mc=1 pins the volume mean to 0 (p_true is mean-zero); mc=3 pins
    # cell (0,0,0) to 0 — the same solution up to the constant shift
    want = p_true - p_true[0, 0, 0] if mc == 3 else p_true
    err = np.linalg.norm(p - want) / np.linalg.norm(p_true)
    assert err < 2e-2, err
    # the pinned cell really honors its constraint
    if mc == 3:
        assert abs(float(p[0, 0, 0])) < 1e-4
    else:
        assert abs(float(p.mean())) < 1e-4


# -- the solve's entry: increment form on the natural grid --------------------

ENTRY_GRIDS = {
    "periodic32": UniformGrid((32, 32, 32), (1.0, 1.0, 1.0)),
    # walls in y, as the channel has them
    "wall_y48x16x24": UniformGrid((48, 16, 24), (3.0, 1.0, 1.5),
                                  (BC.periodic, BC.wall, BC.periodic)),
}


def _composed_solve(g, rhs, x0):
    """The lanes entry as it was composed before the increment form: b
    and x0 transposed, the norm of b and r0 = b - A x0 taken in the
    lanes layout, the iterate transposed back."""
    A = krylov.make_laplacian_lanes(g)
    M = krylov.make_twolevel_preconditioner_lanes(g, g.h * g.h)
    bt = krylov.to_lanes(rhs - jnp.mean(rhs))
    x0t = None if x0 is None else krylov.to_lanes(x0)
    xt, rnorm, k = krylov.bicgstab(A, bt, M=M, x0=x0t,
                                   rnorm_ref=jnp.sqrt(krylov._dot(bt, bt)))
    x = krylov.from_lanes(xt, rhs.shape)
    return x - jnp.mean(x), krylov.solver_stats(rnorm, k)


def _entry_problem(g):
    """A mean-free pressure, its right-hand side, and a warm start off it
    by a twentieth of its size."""
    rng = np.random.default_rng(42)
    p = jnp.asarray(rng.standard_normal(g.shape), jnp.float32)
    p = p - jnp.mean(p)
    warm = p + 0.05 * jnp.asarray(rng.standard_normal(g.shape), jnp.float32)
    return p, krylov.make_laplacian(g)(p), warm


@pytest.mark.parametrize("start", ["cold", "warm", "exact"])
@pytest.mark.parametrize("name", sorted(ENTRY_GRIDS))
def test_the_increment_entry_is_the_composed_solve(name, start):
    """The same Krylov iteration in exact arithmetic: the solution to
    float32 rounding, the iteration count within one, the final residual
    within the target; from the exact solution, no iteration and x0
    back."""
    g = ENTRY_GRIDS[name]
    p, rhs, warm = _entry_problem(g)
    x0 = {"cold": None, "warm": warm, "exact": p}[start]
    solve = krylov.build_iterative_solver(g, two_level=True)
    assert solve.entry == "increment"
    x, stats = jax.jit(lambda r, s: solve(r, s, with_stats=True))(rhs, x0)
    want, ref = jax.jit(lambda r, s: _composed_solve(g, r, s))(rhs, x0)
    x, want = np.asarray(x), np.asarray(want)
    (rn, k), (rn_ref, k_ref) = np.asarray(stats), np.asarray(ref)
    scale = np.abs(want).max()
    target = max(1e-6, 1e-4 * float(jnp.linalg.norm(rhs - jnp.mean(rhs))))
    assert abs(k - k_ref) <= 1 and rn <= target and rn_ref <= target
    if start == "exact":
        assert k == k_ref == 0
        np.testing.assert_allclose(x, np.asarray(p), atol=1e-6 * scale)
    else:
        assert k > 0
        np.testing.assert_allclose(x, want, atol=1e-5 * scale)
        assert abs(rn - rn_ref) <= 1e-3 * rn_ref
    # both reach the pressure itself to the tolerance's accuracy
    assert np.abs(x - np.asarray(p)).max() < 1e-2 * scale


def _outside_the_loop(jaxpr):
    """Every equation of ``jaxpr`` and of its sub-programs, except what
    runs inside a ``while``."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "while":
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _outside_the_loop(sub)


@pytest.mark.parametrize("name", sorted(ENTRY_GRIDS))
def test_the_set_up_transposes_once_each_way(name):
    """Outside the BiCGSTAB loop the solve transposes r0 in and the
    increment out (the composed entry also transposed x0), and no
    reduction there reads an array in the lanes layout (the composed
    entry took the norms of b and r0 there)."""
    g = ENTRY_GRIDS[name]
    p, rhs, _ = _entry_problem(g)
    lanes = (8, 8, 8, int(np.prod(g.shape)) // 512)
    solve = krylov.build_iterative_solver(g, two_level=True)

    def counts(fn):
        eqns = list(_outside_the_loop(jax.make_jaxpr(fn)(rhs, p).jaxpr))
        return (sum(e.primitive.name == "transpose" for e in eqns),
                sum(e.primitive.name == "reduce_sum"
                    and tuple(e.invars[0].aval.shape) == lanes
                    for e in eqns))

    assert counts(lambda r, s: solve(r, s, with_stats=True)) == (2, 0)
    assert counts(lambda r, s: _composed_solve(g, r, s)) == (3, 2)


def test_the_entry_follows_the_constraint(monkeypatch):
    """The increment form where the operator is the natural stencil
    (constraints 0 and 2), the composed one where a row is pinned (1, 3),
    for the dense fallback and for the fused front end; the spectral
    solve names none."""
    g = unit_cube(BC.periodic, n=16)
    entries = [krylov.build_iterative_solver(g, mean_constraint=mc).entry
               for mc in range(4)]
    assert entries == ["increment", "composed", "increment", "composed"]
    odd = UniformGrid((12, 12, 12), (1.0, 1.0, 1.0))
    assert krylov.build_iterative_solver(odd).entry == "composed"
    assert not hasattr(build_spectral_solver(g), "entry")
    monkeypatch.setenv("CUP3D_FUSED", "1")
    assert krylov.build_iterative_solver(g).entry == "composed"
