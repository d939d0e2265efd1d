"""Fused Pallas BiCGSTAB driver (ops/fused_bicgstab.py, round 12).

Every stage kernel runs in Pallas interpreter mode against its pure-jnp
twin (the ``block_cg_tiles_fast`` pattern), then the whole solve: the
interpret driver must match the twin driver, the fused driver must match
the legacy ``krylov.bicgstab`` composition at matched residual quality,
and the mixed-precision policy (ops/precision.py) must hold — bf16
storage still meets the solver's own stopping target, the default f32
config dispatches through the unchanged legacy path, and the
``build_iterative_solver`` contract (with_stats, maxiter, steady-state
retrace budget) survives the CUP3D_FUSED / CUP3D_KRYLOV_DTYPE knobs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cup3d_tpu.grid.uniform import BC, UniformGrid
from cup3d_tpu.ops import fused_bicgstab as fb
from cup3d_tpu.ops import krylov, precision, tilesolve
from tests._grids import BS, randn, unit_cube


def _stages(T, store=jnp.float32, kernels=False, h=0.25):
    h2 = h * h
    C = min(fb.TILE_T, T)
    return fb._Stages(bs=BS, Tpad=T, C=C, store=store, h2=h2,
                      inv_h2=1.0 / h2, kernels=kernels, interpret=kernels)


# -- per-stage interpret-mode kernel parity vs the jnp twins -----------------
# T=512 with TILE_T=256 exercises the chunked (grid=(2,)) kernel path;
# per-lane partials are chunk-invariant, so parity is tight.


def _stage_pair(T=512, store=jnp.float32):
    return (_stages(T, store, kernels=False),
            _stages(T, store, kernels=True))


def test_update_stage_interpret_parity():
    tw, kn = _stage_pair()
    rng = np.random.default_rng(0)
    r, p, v, rhat = (randn(rng, BS, BS, BS, 512) for _ in range(4))
    scal = fb._scalars(0.7, 1.3, 0.0)
    for a, b in zip(tw.update(r, p, v, rhat, scal),
                    kn.update(r, p, v, rhat, scal)):
        # chunked-vs-whole reduction order costs a few ulps on the
        # per-lane partials (still f32-accumulated)
        sc = max(float(jnp.max(jnp.abs(a))), 1.0)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=2e-6 * sc)
    # the breakdown branch (broke=1): p/v zeroed, rhat re-seeded to r
    scal_b = fb._scalars(0.0, 1.3, 1.0)
    p_n, rh_n, _ = tw.update(r, p, v, rhat, scal_b)
    np.testing.assert_allclose(np.asarray(p_n), np.asarray(r), atol=1e-6)
    np.testing.assert_allclose(np.asarray(rh_n), np.asarray(r), atol=1e-6)


@pytest.mark.parametrize("two_level", [True, False])
def test_getz_stage_interpret_parity(two_level):
    tw, kn = _stage_pair()
    rng = np.random.default_rng(1)
    w = randn(rng, BS, BS, BS, 512)
    aux = randn(rng, 8, 512) if two_level else None
    S3, lam3, _ = tilesolve._basis(BS, "float32")
    lam = lam3.reshape(BS ** 3, 1)
    a = tw.getz(w, aux, S3, lam)
    b = kn.getz(w, aux, S3, lam)
    scale = float(jnp.max(jnp.abs(a)))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=1e-6 * scale)


def test_getz_stage_matches_tilesolve():
    """Tile-only getz IS the exact DST tile solve of -h2*w."""
    tw = _stages(128)
    rng = np.random.default_rng(2)
    w = randn(rng, BS, BS, BS, 128)
    S3, lam3, _ = tilesolve._basis(BS, "float32")
    y = tw.getz(w, None, S3, lam3.reshape(BS ** 3, 1))
    want = tilesolve.tile_solve_lanes(-tw.h2 * w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))


def test_lap_axpy_finish_stage_interpret_parity():
    tw, kn = _stage_pair()
    rng = np.random.default_rng(3)
    w, a, r, v, y, z, s, t, rhat = (
        randn(rng, BS, BS, BS, 512) for _ in range(9))
    x = randn(rng, BS, BS, BS, 512)
    planes = randn(rng, 6, BS, BS, 512)
    for got, want in zip(kn.lap(w, planes, a), tw.lap(w, planes, a)):
        sc = max(float(jnp.max(jnp.abs(want))), 1.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6 * sc)
    sc_a = fb._scalars(0.37)
    for got, want in zip(kn.axpy(r, v, sc_a), tw.axpy(r, v, sc_a)):
        sc = max(float(jnp.max(jnp.abs(want))), 1.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6 * sc)
    sc_f = fb._scalars(0.37, 1.21)
    for got, want in zip(kn.finish(x, y, z, s, t, rhat, sc_f),
                         tw.finish(x, y, z, s, t, rhat, sc_f)):
        sc = max(float(jnp.max(jnp.abs(want))), 1.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6 * sc)


# -- the fused glue vs the legacy operators ----------------------------------


@pytest.mark.parametrize("bc", [BC.periodic, BC.wall, BC.freespace])
def test_lane_planes_laplacian_matches_legacy(bc):
    """laplacian_lanes_chunk over make_lane_planes == the legacy
    cross-tile make_laplacian_lanes, per BC family, non-cubic grid."""
    from cup3d_tpu.ops.stencils import laplacian_lanes_chunk

    g = UniformGrid((32, 16, 24), (1.0, 0.5, 0.75), (bc,) * 3)
    A = krylov.make_laplacian_lanes(g)
    planes_fn = krylov.make_lane_planes(g)
    rng = np.random.default_rng(4)
    t = jnp.asarray(rng.standard_normal((BS, BS, BS, 4 * 2 * 3)),
                    jnp.float32)
    want = np.asarray(A(t))
    got = np.asarray(
        laplacian_lanes_chunk(t, planes_fn(t), 1.0 / (g.h * g.h)))
    np.testing.assert_allclose(got, want, atol=3e-6 * np.abs(want).max())


@pytest.mark.parametrize("bc", [BC.periodic, BC.wall])
def test_face_deltas_reconstruct_tileconst_laplacian(bc):
    """aux rows (make_face_deltas + zc) -> _azc_from_aux must equal the
    full Laplacian of the broadcast tile-constant coarse field."""
    g = unit_cube(bc)
    A = krylov.make_laplacian_lanes(g)
    deltas_fn = krylov.make_face_deltas(g)
    T = 64
    rng = np.random.default_rng(5)
    zc = jnp.asarray(rng.standard_normal(T), jnp.float32)
    zc_b = jnp.broadcast_to(zc, (BS, BS, BS, T))
    aux = jnp.concatenate(
        [deltas_fn(zc), zc[None, :], jnp.zeros((1, T), jnp.float32)], axis=0
    )
    got = np.asarray(fb._azc_from_aux(aux, BS))
    want = np.asarray(A(zc_b))
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())


# -- whole-solve parity and equivalence --------------------------------------


def test_fused_interpret_matches_twin_mixed_bcs():
    g = UniformGrid((16, 16, 16), (1.0, 1.0, 1.0),
                    (BC.wall, BC.periodic, BC.freespace))
    rng = np.random.default_rng(6)
    rhs = jnp.asarray(rng.standard_normal(g.shape), jnp.float32)
    bt = krylov.to_lanes(rhs - jnp.mean(rhs))
    kw = dict(tol_abs=1e-6, tol_rel=1e-5, maxiter=40,
              store_dtype=jnp.float32)
    x_tw, rn_tw, k_tw = fb.fused_bicgstab(g, bt, kernels=False, **kw)
    x_kn, rn_kn, k_kn = fb.fused_bicgstab(g, bt, interpret=True, **kw)
    assert int(k_tw) == int(k_kn)
    scale = float(jnp.max(jnp.abs(x_tw))) or 1.0
    assert float(jnp.max(jnp.abs(x_tw - x_kn))) / scale < 1e-5


@pytest.mark.parametrize("two_level", [True, False])
def test_fused_matches_legacy_bicgstab_f32(two_level):
    """Fused f32 vs the legacy composition on the identical system:
    same residual quality, equivalent solution (the documented fused-vs-
    unfused equivalence bound, VALIDATION.md round 12)."""
    g = unit_cube(BC.periodic)
    A = krylov.make_laplacian_lanes(g)
    h2 = g.h * g.h
    if two_level:
        M = krylov.make_twolevel_preconditioner_lanes(g, h2)
    else:
        M = lambda r: krylov.getz_lanes(-h2 * r)
    rng = np.random.default_rng(7)
    rhs = jnp.asarray(rng.standard_normal(g.shape), jnp.float32)
    bt = krylov.to_lanes(rhs - jnp.mean(rhs))
    ref = jnp.sqrt(jnp.sum(bt * bt, dtype=jnp.float32))
    x_leg, rn_leg, k_leg = krylov.bicgstab(
        A, bt, M=M, tol_abs=1e-6, tol_rel=1e-4, rnorm_ref=ref)
    x_fus, rn_fus, k_fus = fb.fused_bicgstab(
        g, bt, tol_abs=1e-6, tol_rel=1e-4, rnorm_ref=ref,
        two_level=two_level, store_dtype=jnp.float32)
    target = max(1e-6, 1e-4 * float(ref))
    # both converged to the solver's own target
    assert float(rn_leg) <= target * 1.01
    assert float(rn_fus) <= target * 1.01
    # iteration counts agree up to reduction-order noise in the scalars
    assert abs(int(k_fus) - int(k_leg)) <= 3
    # equivalence bound on the solutions (VALIDATION.md round 12): two
    # converged iterates can differ by O(target/||A||); the weaker
    # tile-only preconditioner takes ~17 vs ~12 iterations so the
    # reduction-order noise compounds further
    bound = 1e-4 if two_level else 1e-3
    scale = float(jnp.max(jnp.abs(x_leg))) or 1.0
    assert float(jnp.max(jnp.abs(x_fus - x_leg))) / scale < bound


def test_fused_bf16_storage_meets_residual_quality():
    """bf16 Krylov storage with f32 accumulation still reaches the f32
    stopping target on the production tolerances, and the solution stays
    within the mixed-precision ladder's bound of the f32 solve."""
    g = unit_cube(BC.periodic)
    rng = np.random.default_rng(8)
    rhs = jnp.asarray(rng.standard_normal(g.shape), jnp.float32)
    bt = krylov.to_lanes(rhs - jnp.mean(rhs))
    ref = jnp.sqrt(jnp.sum(bt * bt, dtype=jnp.float32))
    kw = dict(tol_abs=1e-6, tol_rel=1e-4, rnorm_ref=ref, maxiter=100)
    x32, rn32, k32 = fb.fused_bicgstab(g, bt, store_dtype=jnp.float32, **kw)
    xbf, rnbf, kbf = fb.fused_bicgstab(g, bt, store_dtype=jnp.bfloat16, **kw)
    target = max(1e-6, 1e-4 * float(ref))
    assert float(rnbf) <= target * 1.01          # residual-quality gate
    assert int(kbf) <= int(k32) + 10             # no convergence stall
    assert xbf.dtype == jnp.float32              # x stays the f32 accumulator
    scale = float(jnp.max(jnp.abs(x32))) or 1.0
    assert float(jnp.max(jnp.abs(xbf - x32))) / scale < 1e-2


def test_fused_warm_start_and_maxiter_escalation():
    """x0 warm starts work and the maxiter knob (the recovery ladder's
    escalation parameter) caps the iteration count exactly."""
    g = unit_cube(BC.periodic, n=16)
    rng = np.random.default_rng(9)
    rhs = jnp.asarray(rng.standard_normal(g.shape), jnp.float32)
    bt = krylov.to_lanes(rhs - jnp.mean(rhs))
    # rnorm_ref pinned to |b| like the production front-end — a warm
    # start must not re-target against its own (tiny) initial residual
    ref = jnp.sqrt(jnp.sum(bt * bt, dtype=jnp.float32))
    x1, rn1, k1 = fb.fused_bicgstab(g, bt, tol_abs=1e-6, tol_rel=1e-5,
                                    rnorm_ref=ref)
    # warm start from the converged solution: 0 or 1 extra iterations
    _, rn2, k2 = fb.fused_bicgstab(g, bt, x0=x1, tol_abs=1e-6,
                                   tol_rel=1e-5, rnorm_ref=ref)
    assert int(k2) <= 1
    # a maxiter cap binds
    _, _, k3 = fb.fused_bicgstab(g, bt, tol_abs=0.0, tol_rel=0.0, maxiter=3)
    assert int(k3) == 3


# -- build_iterative_solver dispatch + the precision policy ------------------


def _manufactured(g):
    A = krylov.make_laplacian(g)
    x = np.asarray(g.cell_centers())
    p_true = (
        np.cos(2 * np.pi * x[..., 0])
        * np.cos(2 * np.pi * x[..., 1])
        * np.cos(4 * np.pi * x[..., 2])
    ).astype(np.float32)
    p_true -= p_true.mean()
    return jnp.asarray(p_true), A(jnp.asarray(p_true))


def test_solver_dispatch_fused_and_stats(monkeypatch):
    """CUP3D_FUSED=1 routes build_iterative_solver through the fused
    driver with the with_stats/maxiter contract intact, and the result
    matches the legacy solver."""
    g = unit_cube(BC.periodic)
    p_true, rhs = _manufactured(g)
    legacy = krylov.build_iterative_solver(g, tol_abs=1e-6, tol_rel=1e-5)
    p_leg = legacy(rhs)

    monkeypatch.setenv("CUP3D_FUSED", "1")
    fused = krylov.build_iterative_solver(g, tol_abs=1e-6, tol_rel=1e-5,
                                          maxiter=77)
    assert fused.supports_stats and fused.maxiter == 77
    p_fus, stats = jax.jit(
        lambda b: fused(b, with_stats=True))(rhs)
    assert stats.shape == (2,) and stats.dtype == jnp.float32
    assert int(stats[1]) > 0
    scale = float(jnp.max(jnp.abs(p_leg))) or 1.0
    assert float(jnp.max(jnp.abs(p_fus - p_leg))) / scale < 1e-4
    err = np.linalg.norm(np.asarray(p_fus) - np.asarray(p_true))
    assert err / np.linalg.norm(np.asarray(p_true)) < 2e-3


def test_solver_dispatch_bf16_solves_and_policy_raises(monkeypatch):
    g = unit_cube(BC.periodic)
    p_true, rhs = _manufactured(g)
    # bf16 + default CUP3D_FUSED (auto) -> fused driver, converged solve
    monkeypatch.setenv("CUP3D_KRYLOV_DTYPE", "bf16")
    monkeypatch.delenv("CUP3D_FUSED", raising=False)
    assert precision.use_fused()
    solve = krylov.build_iterative_solver(g, tol_abs=1e-6, tol_rel=1e-5)
    p = solve(rhs)
    err = np.linalg.norm(np.asarray(p) - np.asarray(p_true))
    assert err / np.linalg.norm(np.asarray(p_true)) < 5e-3
    # bf16 with the fused driver explicitly disabled is a config error,
    # not a silent fall-through to an unaudited bf16 legacy solve
    monkeypatch.setenv("CUP3D_FUSED", "0")
    with pytest.raises(ValueError):
        krylov.build_iterative_solver(g)


def test_default_f32_config_uses_legacy_path(monkeypatch):
    """With the knobs at their defaults the factory must return the
    LEGACY solver (the f32 bitwise-baseline guarantee is dispatch-level:
    the pre-PR code path runs, not a numerically-close twin)."""
    monkeypatch.delenv("CUP3D_KRYLOV_DTYPE", raising=False)
    monkeypatch.delenv("CUP3D_FUSED", raising=False)
    assert precision.krylov_dtype() == jnp.float32
    assert not precision.use_fused()
    g = unit_cube(BC.periodic, n=16)
    import inspect

    solve = krylov.build_iterative_solver(g)
    # the fused front-end's closure mentions fused_bicgstab; the legacy
    # one calls bicgstab with the M it built
    src = inspect.getsource(solve)
    assert "fused" not in src and "bicgstab(" in src


def test_fused_solver_steady_state_retrace_budget(monkeypatch):
    """One trace serves the steady state: repeated calls with fresh rhs
    values never retrace (RecompileCounter budget 1)."""
    from cup3d_tpu.analysis.runtime import RecompileCounter

    monkeypatch.setenv("CUP3D_FUSED", "1")
    g = unit_cube(BC.periodic, n=16)
    rng = np.random.default_rng(10)
    with RecompileCounter() as rc:
        solve = jax.jit(krylov.build_iterative_solver(
            g, tol_abs=1e-6, tol_rel=1e-5))
        for _ in range(3):
            rhs = jnp.asarray(rng.standard_normal(g.shape), jnp.float32)
            solve(rhs).block_until_ready()
    rc.assert_steady_state(budget=1)


# -- analytic traffic model --------------------------------------------------


def test_bytes_model_shape_and_bf16_savings():
    f32 = fb.bytes_model(jnp.float32)
    bf16 = fb.bytes_model(jnp.bfloat16)
    for per in (f32, bf16):
        for key in ("update", "getz", "planes", "lap", "axpy", "finish",
                    "best_x", "total"):
            assert key in per
        assert per["total"] == pytest.approx(
            sum(v for k, v in per.items() if k != "total"))
    # bf16 storage roughly halves the storage-dtype traffic; the f32
    # x accumulator keeps it from being a full 2x
    assert bf16["total"] < 0.65 * f32["total"]
    assert fb.legacy_bytes_model() > 0
