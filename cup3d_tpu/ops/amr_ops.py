"""Differential operators on block-structured AMR fields.

Mirrors the uniform-grid kernels (cup3d_tpu.ops.stencils) on
``(nb, bs, bs, bs[, 3])`` block batches: halo'd labs come from the gather
tables (grid/blocks.py), spatial derivatives are batch slices, and each
block scales by its own spacing ``h``.  Conservative operators emit
outward per-unit-area face fluxes for coarse-fine refluxing (grid/flux.py).

Reference counterparts: KernelLHSPoisson (main.cpp:9197-9269),
KernelAdvectDiffuse (9461-9639), KernelPressureRHS (14761-14948),
KernelGradP (14957-15056), ComputeVorticity (8624-8745).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cup3d_tpu.grid.blocks import BlockGrid, LabTables
from cup3d_tpu.grid.flux import FluxTables


def _sh(lab: jnp.ndarray, w: int, bs: int, ox=0, oy=0, oz=0) -> jnp.ndarray:
    """Interior view of a (nb, L,L,L, ...) lab shifted by (ox,oy,oz)."""
    return lab[
        :,
        w + ox : w + ox + bs,
        w + oy : w + oy + bs,
        w + oz : w + oz + bs,
    ]


def _off(axis, k):
    o = [0, 0, 0]
    o[axis] = k
    return tuple(o)


def _hcol(grid: BlockGrid, dtype=jnp.float32, extra: int = 0) -> jnp.ndarray:
    """(nb, 1, 1, 1[, 1]) per-block spacing."""
    shape = (grid.nb, 1, 1, 1) + (1,) * extra
    return jnp.asarray(grid.h.reshape(shape), dtype)


@jax.named_scope("FluxCorrection")
def face_fluxes(lab: jnp.ndarray, w: int, bs: int, inv_h: jnp.ndarray):
    """Outward per-unit-area gradient fluxes (lab_nb - c)/h on the 6 faces:
    (nb, 6, bs, bs) in the grid/flux.py convention."""
    c = _sh(lab, w, bs)
    ih = inv_h[:, 0, 0, 0][:, None, None]  # (nb,1,1)
    fl = []
    for ax in range(3):
        lo = _sh(lab, w, bs, *_off(ax, -1))
        hi = _sh(lab, w, bs, *_off(ax, 1))
        sel_lo = [slice(None)] * 4
        sel_lo[ax + 1] = 0
        sel_hi = [slice(None)] * 4
        sel_hi[ax + 1] = bs - 1
        fl.append((lo - c)[tuple(sel_lo)] * ih)
        fl.append((hi - c)[tuple(sel_hi)] * ih)
    return jnp.stack(fl, axis=1)


def laplacian_blocks(
    grid: BlockGrid,
    field: jnp.ndarray,
    tab: LabTables,
    flux_tab: Optional[FluxTables] = None,
) -> jnp.ndarray:
    """Refluxed 7-point Laplacian (the AMR ComputeLHS, main.cpp:9196-9328,
    in physical 1/h^2 units)."""
    bs = grid.bs
    w = tab.width
    lab = tab.assemble_scalar(field, bs)
    c = _sh(lab, w, bs)
    s = -6.0 * c
    for ax in range(3):
        s = s + _sh(lab, w, bs, *_off(ax, 1)) + _sh(lab, w, bs, *_off(ax, -1))
    inv_h = 1.0 / _hcol(grid, field.dtype)
    out = s * inv_h * inv_h
    if flux_tab is not None and flux_tab.ncorr:
        fluxes = face_fluxes(lab, w, bs, inv_h)
        out = flux_tab.apply(out, fluxes)
    return out


def grad_blocks(grid: BlockGrid, lab: jnp.ndarray, w: int) -> jnp.ndarray:
    """(nb,bs,bs,bs,3) centered gradient from a scalar lab."""
    bs = grid.bs
    inv2h = 0.5 / _hcol(grid, lab.dtype)
    return jnp.stack(
        [
            (_sh(lab, w, bs, *_off(a, 1)) - _sh(lab, w, bs, *_off(a, -1))) * inv2h
            for a in range(3)
        ],
        axis=-1,
    )


def div_blocks(grid: BlockGrid, vlab: jnp.ndarray, w: int) -> jnp.ndarray:
    """Centered divergence from a vector lab (nb, L,L,L, 3)."""
    bs = grid.bs
    inv2h = 0.5 / _hcol(grid, vlab.dtype)
    out = 0.0
    for a in range(3):
        out = out + (
            _sh(vlab[..., a], w, bs, *_off(a, 1))
            - _sh(vlab[..., a], w, bs, *_off(a, -1))
        )
    return out * inv2h


def curl_blocks(grid: BlockGrid, vlab: jnp.ndarray, w: int) -> jnp.ndarray:
    bs = grid.bs
    inv2h = 0.5 / _hcol(grid, vlab.dtype)

    def d(c, a):
        return (
            _sh(vlab[..., c], w, bs, *_off(a, 1))
            - _sh(vlab[..., c], w, bs, *_off(a, -1))
        ) * inv2h

    return jnp.stack(
        [d(2, 1) - d(1, 2), d(0, 2) - d(2, 0), d(1, 0) - d(0, 1)], axis=-1
    )


# ---------------------------------------------------------------------------
# advection-diffusion (explicit RK3) on blocks
# ---------------------------------------------------------------------------

_UP_W = 3  # 6-point biased upwind needs 3 ghosts


def _upwind_d1(lab_c: jnp.ndarray, w: int, bs: int, axis: int, vel, inv_h):
    """5th-order biased upwind derivative (KernelAdvectDiffuse,
    main.cpp:9474-9483) on a batched lab component."""
    q = [_sh(lab_c, w, bs, *_off(axis, k)) for k in range(-3, 4)]
    inv60h = inv_h / 60.0
    dplus = (
        -2.0 * q[0] + 15.0 * q[1] - 60.0 * q[2] + 20.0 * q[3] + 30.0 * q[4]
        - 3.0 * q[5]
    ) * inv60h
    dminus = (
        2.0 * q[6] - 15.0 * q[5] + 60.0 * q[4] - 20.0 * q[3] - 30.0 * q[2]
        + 3.0 * q[1]
    ) * inv60h
    return jnp.where(vel > 0, dplus, dminus)


def advdiff_rhs_blocks(
    grid: BlockGrid,
    vel: jnp.ndarray,
    tab: LabTables,
    nu: float,
    uinf: jnp.ndarray,
    flux_tab: Optional[FluxTables] = None,
) -> jnp.ndarray:
    """du/dt = -(u+uinf).grad(u) + nu lap(u), refluxing diffusive fluxes
    (reference AdvectionDiffusion, main.cpp:9640-9728)."""
    bs = grid.bs
    w = tab.width
    vlab = tab.assemble_vector(vel, bs)
    inv_h = 1.0 / _hcol(grid, vel.dtype)
    adv_u = _sh(vlab, w, bs) + uinf  # (nb,bs,bs,bs,3)

    rhs = []
    for c in range(3):
        lab_c = vlab[..., c]
        conv = 0.0
        for a in range(3):
            conv = conv + adv_u[..., a] * _upwind_d1(
                lab_c, w, bs, a, adv_u[..., a], inv_h
            )
        s = -6.0 * _sh(lab_c, w, bs)
        for a in range(3):
            s = s + _sh(lab_c, w, bs, *_off(a, 1)) + _sh(lab_c, w, bs, *_off(a, -1))
        diff = nu * s * inv_h * inv_h
        out_c = diff - conv
        if flux_tab is not None and flux_tab.ncorr:
            fluxes = nu * face_fluxes(lab_c, w, bs, inv_h)
            out_c = flux_tab.apply(out_c, fluxes)
        rhs.append(out_c)
    return jnp.stack(rhs, axis=-1)


@jax.named_scope("AdvectionDiffusion")
def rk3_step_blocks(
    grid: BlockGrid,
    vel: jnp.ndarray,
    dt,
    nu: float,
    uinf: jnp.ndarray,
    tab: LabTables,
    flux_tab: Optional[FluxTables] = None,
) -> jnp.ndarray:
    """Low-storage RK3 (Williamson; the reference's AdvectionDiffusion
    coefficients, main.cpp:9640-9655) — identical staging to the uniform
    path (cup3d_tpu.ops.advection.rk3_step)."""
    from cup3d_tpu.ops.advection import RK3_A, RK3_B

    k = jnp.zeros_like(vel)
    u = vel
    for a, b in zip(RK3_A, RK3_B):
        k = a * k + dt * advdiff_rhs_blocks(grid, u, tab, nu, uinf, flux_tab)
        u = u + b * k
    return u


# ---------------------------------------------------------------------------
# AMR Poisson front-end
# ---------------------------------------------------------------------------


def build_amr_poisson_solver(
    grid: BlockGrid,
    tol_abs: float = 1e-6,
    tol_rel: float = 1e-4,
    maxiter: int = 1000,
    precond_iters: int = 24,
    tab: Optional[LabTables] = None,
    flux_tab: Optional[FluxTables] = None,
    vol: Optional[jnp.ndarray] = None,
    pmask: Optional[jnp.ndarray] = None,
    mean_constraint: int = 2,
    two_level: Optional[bool] = None,
):
    """getZ-preconditioned BiCGSTAB on the AMR forest: the direct TPU
    analogue of PoissonSolverAMR (main.cpp:14363-14616).
    ``two_level`` overrides the CUP3D_COARSE env default (None =
    ``krylov.use_coarse_correction``) — the resilience escalation ladder
    drops to tile-only getZ per driver, not per process.

    This STATIC front-end runs the unfused composition regardless of
    CUP3D_FUSED (it exists for direct/legacy use on unpadded forests);
    the bucketed production path goes through
    ``build_amr_poisson_solver_dynamic``, which dispatches the fused
    Pallas iteration (ops/fused_amr_bicgstab.py) under CUP3D_FUSED.
    It still inherits the round-12 precision hygiene — getZ
    tile solves accumulate in >= f32 for any storage dtype
    (ops/tilesolve.py, ops/precision.py) and the bicgstab breakdown
    threshold lives in the accumulation dtype.

    ``mean_constraint`` mirrors the reference's bMeanConstraint
    (ComputeLHS, main.cpp:9273-9327):

    - 0: no nullspace handling (caller guarantees compatibility);
    - 1: the equation row of cell (0,0,0) of the corner block is
      replaced by the volume-weighted mean of the unknown;
    - 2 (default): mean removal — the projection formulation of the
      reference's rank-one "LHS += avg * h^3" shift;
    - 3 (reference: any value > 2): Dirichlet-pin — the corner row is
      replaced by the identity, fixing p at that cell.

    ``tab``/``flux_tab`` may be pre-built (or the sharded forest's
    duck-typed equivalents); ``vol`` overrides the per-block cell volume
    (the forest passes zeros on padding blocks) and ``pmask`` zeroes
    padding blocks after the mean shifts so they never re-enter the
    Krylov iteration."""
    from cup3d_tpu.grid.flux import build_flux_tables
    from cup3d_tpu.ops import krylov

    if tab is None:
        tab = grid.lab_tables(1)
    if flux_tab is None:
        flux_tab = build_flux_tables(grid)
    if vol is None:
        vol = jnp.asarray(
            (grid.h**3).reshape(grid.nb, 1, 1, 1), jnp.float32
        )
    vol_total = jnp.sum(vol) * grid.bs**3
    # square in f32 AFTER the dtype cast: bit-identical to the dynamic
    # builder's h_col * h_col
    h_col = jnp.asarray(grid.h.reshape(grid.nb, 1, 1, 1), jnp.float32)
    h2 = h_col * h_col
    # corner block: the reference pins block .index == (0,0,0); in the
    # Hilbert-ordered forest that is the leaf covering the domain corner
    slot0 = int(
        np.lexsort(
            (grid.ijk[:, 2], grid.ijk[:, 1], grid.ijk[:, 0])
        )[0]
    ) if mean_constraint in (1, 3) else 0

    # AMR two-level preconditioner (the round-5 uniform win extended to
    # the forest): tile getZ at the block's own h plus a coarse
    # correction over the block face graph (krylov.BlockGraph).  Gated
    # exactly like the uniform path: pinned-row modes 1/3 would have
    # their removed nullspace reintroduced by the singular coarse solve
    # (ADVICE r5), and the sharded forest's _PaddedGeom carries no tree
    # (distributed coarse solve is future work — VALIDATION.md).
    use_two = (krylov.use_coarse_correction() if two_level is None
               else bool(two_level))
    graph = None
    if (use_two and mean_constraint not in (1, 3)
            and hasattr(grid, "tree")):
        graph = krylov.block_graph_tables(grid)

    def wmean(x):
        return jnp.sum(x * vol) / vol_total

    def M_of(t, ft):
        if graph is None:
            # per-block getZ with the block's own h^2 (poisson_kernels
            # getZ, main.cpp:14617-14746); blocks are already bs^3 tiles
            return lambda r: krylov.getz_blocks(-h2 * r,
                                                cg_iters=precond_iters)

        def M(r):
            # multiplicative two-level: coarse first, then the tile
            # solve on the coarse-corrected residual (the lanes-layout
            # scheme of make_twolevel_preconditioner_lanes, with the
            # analytic tile-face A zc replaced by one full refluxed
            # Laplacian — correct on any forest topology)
            zc = krylov.coarse_correct_blocks(r, vol, graph)
            zf = jnp.broadcast_to(
                zc[:, None, None, None], r.shape
            ).astype(r.dtype)
            r2 = r - laplacian_blocks(grid, zf, t, ft)
            return krylov.getz_blocks(-h2 * r2,
                                      cg_iters=precond_iters) + zf

        return M

    def A_of(t, ft):
        if mean_constraint == 1:
            return lambda x_: laplacian_blocks(grid, x_, t, ft).at[
                slot0, 0, 0, 0
            ].set(wmean(x_) * vol_total)
        if mean_constraint == 3:
            return lambda x_: laplacian_blocks(grid, x_, t, ft).at[
                slot0, 0, 0, 0
            ].set(x_[slot0, 0, 0, 0])
        return lambda x_: laplacian_blocks(grid, x_, t, ft)

    @jax.named_scope("PoissonSolve")
    def solve(rhs, x0=None, tab_arg=None, flux_arg=None, rnorm_ref=None,
              with_stats=False):
        # callers under jit pass the tables as traced ARGUMENTS so they
        # are runtime buffers, not constants embedded in the lowered HLO
        # (see grid/blocks.py pytree registration); the builder's own
        # tables are the fallback for direct use
        t = tab if tab_arg is None else tab_arg
        ft = flux_tab if flux_arg is None else flux_arg
        if mean_constraint == 2:
            b = rhs - wmean(rhs)
        elif mean_constraint in (1, 3):
            # pinned row: its RHS is the pin target (0 = zero mean / p=0)
            b = rhs.at[slot0, 0, 0, 0].set(0.0)
        else:
            b = rhs
        if pmask is not None:
            b = b * pmask
        if rnorm_ref is None:
            # rel tolerance references the system's own RHS; warm-started
            # callers pass the cold RHS norm (see krylov.bicgstab)
            rnorm_ref = jnp.sqrt(jnp.sum(b * b, dtype=jnp.float32))
        x, rnorm, k = krylov.bicgstab(
            A_of(t, ft), b, M=M_of(t, ft), x0=x0,
            tol_abs=tol_abs, tol_rel=tol_rel, maxiter=maxiter,
            rnorm_ref=rnorm_ref,
        )
        if mean_constraint == 2:
            x = x - wmean(x)
        x = x * pmask if pmask is not None else x
        if with_stats:
            return x, krylov.solver_stats(rnorm, k)
        return x

    solve.supports_stats = True
    solve.maxiter = maxiter
    return solve


def build_amr_poisson_solver_dynamic(
    bs: int,
    tol_abs: float = 1e-6,
    tol_rel: float = 1e-4,
    maxiter: int = 1000,
    precond_iters: int = 24,
    mean_constraint: int = 2,
):
    """The bucket-stable variant of build_amr_poisson_solver: EVERY
    topology-dependent quantity travels as a call argument, so one built
    solve function serves every regrid of a capacity bucket without
    retracing (sim/amr.py compiled-step cache).

    Per-call arguments: ``geom`` (a duck-typed grid whose ``h`` is a
    traced (nb,) array — sim/amr._ArgGeom), ``vol``/``pmask`` (padded
    (nb,1,1,1) cell volume / real-block mask, 0 on padding), optional
    ``graph`` (krylov.BlockGraph: enables the two-level preconditioner),
    and ``slot0`` (traced corner-block slot for the pinned-row modes —
    a dynamic index, so pin relocation across regrids never retraces).
    The math is identical to the static builder's.

    Under ``CUP3D_FUSED`` (precision.use_fused) the production pressure
    configuration — mean removal (mode 2) with the exact getZ — routes
    the iteration through the fused Pallas driver
    (ops/fused_amr_bicgstab.py): same A/M composition, intermediates
    fused into per-stage kernels with in-kernel dot partials, Krylov
    storage in ``precision.krylov_dtype()``.  Equivalence to the legacy
    composition is at matched residual targets, not bitwise (the
    reduction trees differ) — tests/test_fused_amr.py pins the bound.
    Pinned-row modes and the CUP3D_GETZ=cg ladder keep the legacy loop.
    """
    from cup3d_tpu.ops import krylov
    from cup3d_tpu.ops import precision as _precision

    # read the env knobs at build time, like build_iterative_solver:
    # tests rebuild the solver to flip paths, production builds once
    fused_on = (_precision.use_fused() and mean_constraint == 2
                and krylov.use_exact_getz())
    # a knob combination this build cannot honor raises here (bf16
    # without the fused driver; the fused driver where its kernels
    # would compile natively) — never a quiet downgrade
    _precision.check_policy(mean_constraint, forest_fused=fused_on)

    @jax.named_scope("PoissonSolve")
    def solve(rhs, x0=None, tab_arg=None, flux_arg=None, rnorm_ref=None,
              geom=None, vol=None, pmask=None, graph=None, slot0=None,
              with_stats=False):
        t, ft = tab_arg, flux_arg
        h_col = jnp.reshape(
            jnp.asarray(geom.h, rhs.dtype), (geom.nb, 1, 1, 1)
        )
        h2 = h_col * h_col
        vol_total = jnp.sum(vol) * bs**3

        def wmean(x):
            return jnp.sum(x * vol) / vol_total

        if slot0 is None:
            slot0 = 0

        def A(x_):
            out = laplacian_blocks(geom, x_, t, ft)
            if mean_constraint == 1:
                out = out.at[slot0, 0, 0, 0].set(wmean(x_) * vol_total)
            elif mean_constraint == 3:
                out = out.at[slot0, 0, 0, 0].set(x_[slot0, 0, 0, 0])
            return out

        if graph is not None and mean_constraint not in (1, 3):
            def M(r):
                zc = krylov.coarse_correct_blocks(r, vol, graph)
                zf = jnp.broadcast_to(
                    zc[:, None, None, None], r.shape
                ).astype(r.dtype)
                r2 = r - laplacian_blocks(geom, zf, t, ft)
                return krylov.getz_blocks(-h2 * r2,
                                          cg_iters=precond_iters) + zf
        else:
            def M(r):
                return krylov.getz_blocks(-h2 * r,
                                          cg_iters=precond_iters)

        if mean_constraint == 2:
            b = rhs - wmean(rhs)
        elif mean_constraint in (1, 3):
            b = rhs.at[slot0, 0, 0, 0].set(0.0)
        else:
            b = rhs
        b = b * pmask if pmask is not None else b
        if rnorm_ref is None:
            rnorm_ref = jnp.sqrt(jnp.sum(b * b, dtype=jnp.float32))
        if fused_on:
            from cup3d_tpu.ops import fused_amr_bicgstab as _fused

            x, rnorm, k = _fused.fused_amr_bicgstab(
                geom, b, tab=t, ftab=ft, vol=vol, graph=graph,
                tol_abs=tol_abs, tol_rel=tol_rel, maxiter=maxiter,
                rnorm_ref=rnorm_ref, x0=x0,
                store_dtype=_precision.krylov_dtype(),
            )
        else:
            x, rnorm, k = krylov.bicgstab(
                A, b, M=M, x0=x0, tol_abs=tol_abs, tol_rel=tol_rel,
                maxiter=maxiter, rnorm_ref=rnorm_ref,
            )
        if mean_constraint == 2:
            x = x - wmean(x)
        x = x * pmask if pmask is not None else x
        if with_stats:
            return x, krylov.solver_stats(rnorm, k)
        return x

    solve.supports_stats = True
    solve.maxiter = maxiter
    return solve


# ---------------------------------------------------------------------------
# pressure projection on blocks (reference PressureProjection,
# main.cpp:15061-15160, kernels 14761-15056)
# ---------------------------------------------------------------------------


@jax.named_scope("FluxCorrection")
def div_fluxes(vlab: jnp.ndarray, w: int, bs: int) -> jnp.ndarray:
    """Outward per-unit-area *velocity* fluxes of the centered divergence:
    F(+a) = +(u_c + u_hi)/2 . e_a, F(-a) = -(u_c + u_lo)/2 . e_a, so that
    div = (1/h) sum_f F — the flux form the reflux tables expect."""
    fl = []
    for ax in range(3):
        u = vlab[..., ax]
        c = _sh(u, w, bs)
        lo = _sh(u, w, bs, *_off(ax, -1))
        hi = _sh(u, w, bs, *_off(ax, 1))
        sel_lo = [slice(None)] * 4
        sel_lo[ax + 1] = 0
        sel_hi = [slice(None)] * 4
        sel_hi[ax + 1] = bs - 1
        fl.append((-0.5 * (c + lo))[tuple(sel_lo)])
        fl.append((0.5 * (c + hi))[tuple(sel_hi)])
    return jnp.stack(fl, axis=1)


@jax.named_scope("PoissonRHS")
def pressure_rhs_blocks(
    grid: BlockGrid,
    vel: jnp.ndarray,
    dt,
    tab: LabTables,
    flux_tab: Optional[FluxTables] = None,
    chi: Optional[jnp.ndarray] = None,
    udef: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """rhs = div(u)/dt - chi div(u_def)/dt with conservative refluxing of
    the velocity fluxes (KernelPressureRHS, main.cpp:14761-14948)."""
    bs = grid.bs
    w = tab.width
    vlab = tab.assemble_vector(vel, bs)
    rhs = div_blocks(grid, vlab, w)
    if flux_tab is not None and flux_tab.ncorr:
        rhs = flux_tab.apply(rhs, div_fluxes(vlab, w, bs))
    if chi is not None and udef is not None:
        dlab = tab.assemble_vector(udef, bs)
        rhs = rhs - chi * div_blocks(grid, dlab, w)
    return rhs / dt


def solver_supports_stats(solver) -> bool:
    """True when ``solver`` (or the function under a ``partial``
    binding) advertises the ``with_stats`` return — the AMR front-ends
    built in this module do, the sharded forest's does not yet."""
    if getattr(solver, "supports_stats", False):
        return True
    return bool(getattr(getattr(solver, "func", None),
                        "supports_stats", False))


@jax.named_scope("PressureProjection")
def project_blocks(
    grid: BlockGrid,
    vel: jnp.ndarray,
    dt,
    solver,
    tab: LabTables,
    flux_tab: Optional[FluxTables] = None,
    chi: Optional[jnp.ndarray] = None,
    udef: Optional[jnp.ndarray] = None,
    p_init: Optional[jnp.ndarray] = None,
    second_order: bool = False,
    with_stats: bool = False,
):
    """Solve lap p = rhs and correct u -= dt grad p.  Returns (u, p).

    ``p_init`` warm-starts the Krylov solve from the previous step's
    pressure.  With ``second_order`` the reference's 2nd-order-in-time form
    (main.cpp:15087-15100) is used instead: subtract lap(p_old) from the
    RHS, solve for the *increment*, and add p_old back — algebraically the
    same warm start, but matching the reference's residual bookkeeping.

    ``with_stats`` returns (u, p, stats) with stats the solver's (2,)
    [residual, iterations] vector (zeros when the solver cannot report —
    the forest path), so driver call signatures stay uniform.
    """
    bs = grid.bs
    rhs = pressure_rhs_blocks(grid, vel, dt, tab, flux_tab, chi, udef)
    # the warm/increment solves stop relative to the COLD system's RHS
    # norm, so a good start can only cut iterations (krylov.bicgstab)
    ref = jnp.sqrt(jnp.sum(rhs * rhs, dtype=jnp.float32))
    stats_kw = (
        {"with_stats": True}
        if with_stats and solver_supports_stats(solver) else {}
    )
    if second_order and p_init is not None:
        rhs = rhs - laplacian_blocks(grid, p_init, tab, flux_tab)
        out = solver(rhs, None, tab_arg=tab, flux_arg=flux_tab,
                     rnorm_ref=ref, **stats_kw)
        p, stats = out if stats_kw else (out, None)
        p = p_init + p
    else:
        out = solver(rhs, p_init, tab_arg=tab, flux_arg=flux_tab,
                     rnorm_ref=ref, **stats_kw)
        p, stats = out if stats_kw else (out, None)
    with jax.named_scope("Gradient"):
        plab = tab.assemble_scalar(p, bs)
        gp = grad_blocks(grid, plab, tab.width)
    if with_stats:
        if stats is None:
            stats = jnp.zeros(2, jnp.float32)
        return vel - dt * gp, p, stats
    return vel - dt * gp, p


# ---------------------------------------------------------------------------
# refinement scores (ComputeVorticity + GradChiOnTmp tagging,
# main.cpp:8624-8745, 8540-8602)
# ---------------------------------------------------------------------------


@jax.named_scope("AdaptMesh")
def vorticity_score(grid: BlockGrid, vel: jnp.ndarray, tab: LabTables):
    """(nb,) max |curl u| per block — the reference's tag magnitude."""
    vlab = tab.assemble_vector(vel, grid.bs)
    om = curl_blocks(grid, vlab, tab.width)
    mag = jnp.sqrt(jnp.sum(om * om, axis=-1))
    return jnp.max(mag.reshape(grid.nb, -1), axis=-1)


@jax.named_scope("AdaptMesh")
def gradchi_mask(grid: BlockGrid, chi: jnp.ndarray, tab: LabTables):
    """(nb,) bool: block touches the body interface (0 < chi < 1 anywhere
    or grad chi != 0) -> force max refinement (GradChiOnTmp)."""
    clab = tab.assemble_scalar(chi, grid.bs)
    g = grad_blocks(grid, clab, tab.width)
    has_grad = jnp.max(jnp.sum(g * g, axis=-1).reshape(grid.nb, -1), axis=-1) > 0
    return has_grad


# ---------------------------------------------------------------------------
# forces + diagnostics on blocks (ComputeForces main.cpp:12250-12503,
# ComputeDissipation 10347-10447, ComputeDivergence 8789-8919)
# ---------------------------------------------------------------------------


def _vel_gradients(grid: BlockGrid, vlab: jnp.ndarray, w: int):
    """g[c][a] = d u_c / d x_a as (nb,bs,bs,bs) arrays."""
    bs = grid.bs
    inv2h = 0.5 / _hcol(grid, vlab.dtype)
    return [
        [
            (
                _sh(vlab[..., c], w, bs, *_off(a, 1))
                - _sh(vlab[..., c], w, bs, *_off(a, -1))
            )
            * inv2h
            for a in range(3)
        ]
        for c in range(3)
    ]


def force_integrals_blocks(
    grid: BlockGrid,
    tab: LabTables,
    xc: jnp.ndarray,
    chi: jnp.ndarray,
    p: jnp.ndarray,
    vel: jnp.ndarray,
    nu: float,
    cm: jnp.ndarray,
    ubody: jnp.ndarray,
    udef: Optional[jnp.ndarray] = None,
    vel_unit: Optional[jnp.ndarray] = None,
):
    """Surface tractions via the chi-gradient surface measure, per-block h.

    The block-forest counterpart of models.base.force_integrals: with n_hat
    the outward normal, grad(chi) = -n_hat * delta, so pressure and viscous
    tractions become volume reductions against grad(chi) (the dense-band
    formulation replacing the reference's 5h surface probing,
    main.cpp:12250-12494).  xc: (nb,bs,bs,bs,3) cell centers.
    """
    bs = grid.bs
    w = tab.width
    vol = _hcol(grid, vel.dtype) ** 3
    clab = tab.assemble_scalar(chi, bs)
    gchi = grad_blocks(grid, clab, w)  # points into the body
    vlab = tab.assemble_vector(vel, bs)
    g = _vel_gradients(grid, vlab, w)
    fpres = jnp.stack([jnp.sum(p * gchi[..., a] * vol) for a in range(3)])
    visc_tr = jnp.stack(
        [
            sum((g[c][a] + g[a][c]) * gchi[..., c] for c in range(3))
            for a in range(3)
        ],
        axis=-1,
    )
    fvisc = -nu * jnp.stack([jnp.sum(visc_tr[..., a] * vol) for a in range(3)])
    traction = p[..., None] * gchi - nu * visc_tr
    r = xc - cm
    torque = jnp.sum(jnp.cross(r, traction) * vol[..., None], axis=(0, 1, 2, 3))
    power = jnp.sum(traction * ubody * vol[..., None])
    from cup3d_tpu.ops.diagnostics import swim_split

    return {"pres_force": fpres, "visc_force": fvisc, "torque": torque,
            "power": power,
            **swim_split(traction, vol, udef, vel_unit)}


def divergence_norms_blocks(grid: BlockGrid, vel: jnp.ndarray, tab: LabTables):
    """(sum |div u| h^3, max |div u|) over the forest."""
    vlab = tab.assemble_vector(vel, grid.bs)
    d = div_blocks(grid, vlab, tab.width)
    vol = _hcol(grid, vel.dtype) ** 3
    return jnp.sum(jnp.abs(d) * vol), jnp.max(jnp.abs(d))


def dissipation_blocks(grid: BlockGrid, vel: jnp.ndarray, nu: float,
                       tab: LabTables):
    """Energy-budget integrals with per-block cell volume (KernelDissipation
    semantics, main.cpp:10347-10435)."""
    bs = grid.bs
    w = tab.width
    vol = _hcol(grid, vel.dtype) ** 3
    vlab = tab.assemble_vector(vel, bs)
    g = _vel_gradients(grid, vlab, w)
    ss = 0.0
    for c in range(3):
        for a in range(3):
            s = 0.5 * (g[c][a] + g[a][c])
            ss = ss + s * s
    om = curl_blocks(grid, vlab, w)
    return {
        "kinetic_energy": 0.5 * jnp.sum(jnp.sum(vel * vel, axis=-1) * vol),
        "enstrophy": 0.5 * jnp.sum(jnp.sum(om * om, axis=-1) * vol),
        "dissipation_rate": 2.0 * nu * jnp.sum(ss * vol),
    }
