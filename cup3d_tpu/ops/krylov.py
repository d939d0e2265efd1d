"""Matrix-free Krylov machinery: the TPU analogue of the reference's
``PoissonSolverAMR`` (pipelined BiCGSTAB, main.cpp:14363-14616) and its
per-block CG "getZ" preconditioner (poisson_kernels, main.cpp:14617-14746).

Design notes (TPU-first, not a port):

- The reference overlaps ``MPI_Iallreduce`` with preconditioner work to hide
  reduction latency across ranks.  Under ``jit`` + SPMD sharding, XLA already
  schedules the ``psum`` behind independent compute, so we use the *plain*
  preconditioned BiCGSTAB recurrence — fewer fused reductions beat manual
  pipelining on ICI (SURVEY.md section 7, hard part (c)).
- The getZ preconditioner is kept, because its structure is ideal for TPU:
  an independent fixed-iteration CG on every 8^3 tile, batched over the tile
  axis — a dense, static-shape, embarrassingly parallel kernel.  The
  reference iterates each block CG to a tolerance (<=100 its,
  main.cpp:14739); we use a *fixed* iteration count so the compiled graph is
  static and every tile takes the same time (no block-imbalance).  The
  default is 24 inner iterations: measured on a 128^3 TGV pressure system
  in float32, 12 inner iterations let the outer BiCGSTAB stagnate just
  above the 1e-4 relative target and burn the full 1000-iteration cap,
  while 24 converges in ~50 outer iterations (12x wall-clock) — with the
  VMEM-resident Pallas kernel (ops/getz_pallas.py) the extra inner
  iterations are nearly free.
- Breakdown handling: the reference restarts up to 100 times and keeps the
  best-residual ``x_opt`` (main.cpp:14374, 14452).  We do the same inside
  one ``lax.while_loop``: on rho/omega breakdown the recurrence re-seeds
  ``rhat = r, p = v = 0``, and a running best-x is carried in the state.

All reductions are ``jnp`` dots: under ``pjit`` they lower to ``psum`` over
the device mesh, which is the ICI-native replacement for the reference's
``MPI_Iallreduce``.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from cup3d_tpu.grid.uniform import UniformGrid

_HI = jax.lax.Precision.HIGHEST


def _dot(a, b):
    # accumulate in at least f32 (precision.accum_dtype): bf16-stored
    # Krylov vectors still reduce in f32, and f64 solves stay f64.  The
    # promote_types form cannot silently produce f64 from f32/bf16
    # inputs (JX005 audit, round 12).
    acc = jnp.promote_types(a.dtype, jnp.float32)
    return jnp.sum(a * b, dtype=acc)


def make_laplacian(grid: UniformGrid) -> Callable:
    """Matrix-free 7-point Laplacian  (lap x)_i = (sum_nb x - 6 x_i)/h^2
    with the grid's scalar BCs (periodic wrap / zero-gradient), the same
    operator ``ComputeLHS`` applies (main.cpp:9197-9269, without the h^3
    scaling — we keep physical 1/h^2 units so rhs is the physical rhs).
    """
    inv_h2 = 1.0 / (grid.h * grid.h)

    def apply(x: jnp.ndarray) -> jnp.ndarray:
        xp = grid.pad_scalar(x, 1)
        c = xp[1:-1, 1:-1, 1:-1]
        out = (
            xp[2:, 1:-1, 1:-1]
            + xp[:-2, 1:-1, 1:-1]
            + xp[1:-1, 2:, 1:-1]
            + xp[1:-1, :-2, 1:-1]
            + xp[1:-1, 1:-1, 2:]
            + xp[1:-1, 1:-1, :-2]
            - 6.0 * c
        )
        return out * inv_h2

    return apply


# ---------------------------------------------------------------------------
# lane-resident layout: (bs, bs, bs, T) with the tile batch on the 128-wide
# lane axis.  The whole Krylov solve runs in this layout (one transpose in,
# one out) because per-iteration tile/untile transposes around the Pallas
# getZ kernel measured ~55% of the BiCGSTAB iteration on a v5e.
# ---------------------------------------------------------------------------


def to_lanes(x: jnp.ndarray, bs: int = 8) -> jnp.ndarray:
    """(nx,ny,nz) -> (bs,bs,bs,T), T = (nx/bs)(ny/bs)(nz/bs), lane index
    t = (tx*NBy + ty)*NBz + tz."""
    nx, ny, nz = x.shape
    t = x.reshape(nx // bs, bs, ny // bs, bs, nz // bs, bs)
    return t.transpose(1, 3, 5, 0, 2, 4).reshape(bs, bs, bs, -1)


def from_lanes(t: jnp.ndarray, shape) -> jnp.ndarray:
    bs = t.shape[0]
    nbx, nby, nbz = (s // bs for s in shape)
    t = t.reshape(bs, bs, bs, nbx, nby, nbz)
    return t.transpose(3, 0, 4, 1, 5, 2).reshape(shape)


def make_laplacian_lanes(grid: UniformGrid, bs: int = 8) -> Callable:
    """The same operator as make_laplacian, acting on the lane-resident
    layout.  Intra-tile neighbors are sublane shifts; cross-tile neighbor
    planes are lane-axis rolls by the tile stride (periodic wrap is exactly
    the roll; zero-gradient clamps the domain-edge plane to itself)."""
    from cup3d_tpu.grid.uniform import BC

    nb = tuple(s // bs for s in grid.shape)
    strides = (nb[1] * nb[2], nb[2], 1)
    T = nb[0] * nb[1] * nb[2]
    lanes = np.arange(T)
    tco = (lanes // strides[0] % nb[0],
           lanes // strides[1] % nb[1],
           lanes % nb[2])
    inv_h2 = 1.0 / (grid.h * grid.h)

    def edge_src(t, axis, idx):
        return jax.lax.slice_in_dim(t, idx, idx + 1, axis=axis)

    def neighbor(t, axis, sign):
        """Value of each cell's +/-1 neighbor along ``axis``.

        A lane roll by the tile stride reaches the next tile along the
        axis — except for domain-edge tiles on non-outermost axes, where
        the flat roll crosses into the adjacent outer tile, so edge lanes
        get an explicit wrap roll (periodic) or a zero-gradient clamp."""
        periodic = grid.bc[axis] == BC.periodic
        n = t.shape[axis]
        st, nba = strides[axis], nb[axis]
        if sign > 0:
            inner = jax.lax.slice_in_dim(t, 1, n, axis=axis)
            edge = jax.lax.slice_in_dim(t, n - 1, n, axis=axis)
            src = edge_src(t, axis, 0)  # next tile's low plane
            plane = jnp.roll(src, -st, axis=-1)
            mask = jnp.asarray(tco[axis] == nba - 1)
            wrap = jnp.roll(src, (nba - 1) * st, axis=-1)
        else:
            inner = jax.lax.slice_in_dim(t, 0, n - 1, axis=axis)
            edge = jax.lax.slice_in_dim(t, 0, 1, axis=axis)
            src = edge_src(t, axis, n - 1)  # previous tile's high plane
            plane = jnp.roll(src, st, axis=-1)
            mask = jnp.asarray(tco[axis] == 0)
            wrap = jnp.roll(src, -(nba - 1) * st, axis=-1)
        plane = jnp.where(mask, wrap if periodic else edge, plane)
        parts = (inner, plane) if sign > 0 else (plane, inner)
        return jnp.concatenate(parts, axis=axis)

    def apply(t: jnp.ndarray) -> jnp.ndarray:
        out = -6.0 * t
        for ax in range(3):
            out = out + neighbor(t, ax, +1) + neighbor(t, ax, -1)
        return out * inv_h2

    return apply


def make_lane_planes(grid: UniformGrid, bs: int = 8) -> Callable:
    """w (bs,bs,bs,T) -> (6,bs,bs,T) cross-tile neighbor face planes,
    rows [lo0, hi0, lo1, hi1, lo2, hi2]: row 2*ax+1 holds the +1
    neighbor of each tile's cells at local index bs-1 along ``ax``, row
    2*ax the -1 neighbor of the cells at index 0 — exactly the boundary
    planes make_laplacian_lanes's ``neighbor()`` concatenates in, with
    the same lane-roll / periodic-wrap / zero-gradient-clamp selection.

    Factored out so the fused iteration (ops/fused_bicgstab.py) can
    pass the planes as a kernel input and keep the Laplacian apply
    itself pure intra-chunk slicing — this boundary fetch touches
    6*bs^2/bs^3 = 3/4 of a plane's bytes per tile and is the only part
    of the apply with cross-lane data flow (on the sharded path it is
    also the natural seam for the ring-DMA halo, parallel/ring.py)."""
    from cup3d_tpu.grid.uniform import BC

    nb = tuple(s // bs for s in grid.shape)
    strides = (nb[1] * nb[2], nb[2], 1)
    T = nb[0] * nb[1] * nb[2]
    lanes = np.arange(T)
    tco = (lanes // strides[0] % nb[0],
           lanes // strides[1] % nb[1],
           lanes % nb[2])

    def planes(t: jnp.ndarray) -> jnp.ndarray:
        rows = []
        for ax in range(3):
            periodic = grid.bc[ax] == BC.periodic
            st, nba = strides[ax], nb[ax]
            p0 = jax.lax.slice_in_dim(t, 0, 1, axis=ax)       # own low plane
            p1 = jax.lax.slice_in_dim(t, bs - 1, bs, axis=ax)  # own high
            hi = jnp.roll(p0, -st, axis=-1)  # next tile's low plane
            hi = jnp.where(jnp.asarray(tco[ax] == nba - 1),
                           jnp.roll(p0, (nba - 1) * st, axis=-1)
                           if periodic else p1, hi)
            lo = jnp.roll(p1, st, axis=-1)   # previous tile's high plane
            lo = jnp.where(jnp.asarray(tco[ax] == 0),
                           jnp.roll(p1, -(nba - 1) * st, axis=-1)
                           if periodic else p0, lo)
            rows.append(jnp.squeeze(lo, axis=ax))
            rows.append(jnp.squeeze(hi, axis=ax))
        return jnp.stack(rows, axis=0)

    return planes


# ---------------------------------------------------------------------------
# getZ block preconditioner: fixed-iteration CG on every bs^3 tile
# ---------------------------------------------------------------------------


def _tile(x: jnp.ndarray, bs: int) -> jnp.ndarray:
    """(nx,ny,nz) -> (NBx,NBy,NBz,bs,bs,bs) tile view."""
    nx, ny, nz = x.shape
    x = x.reshape(nx // bs, bs, ny // bs, bs, nz // bs, bs)
    return x.transpose(0, 2, 4, 1, 3, 5)


def _untile(t: jnp.ndarray) -> jnp.ndarray:
    nbx, nby, nbz, bs, _, _ = t.shape
    return t.transpose(0, 3, 1, 4, 2, 5).reshape(nbx * bs, nby * bs, nbz * bs)


def _block_lap(t: jnp.ndarray) -> jnp.ndarray:
    """Per-tile 7-pt Laplacian (h^2-scaled out) with implicit zero-Dirichlet
    halo — exactly the preconditioner operator of kernelPoissonGetZInner
    (main.cpp:14651-14702)."""
    z = jnp.pad(t, [(0, 0)] * (t.ndim - 3) + [(1, 1)] * 3)
    c = z[..., 1:-1, 1:-1, 1:-1]
    return (
        z[..., 2:, 1:-1, 1:-1]
        + z[..., :-2, 1:-1, 1:-1]
        + z[..., 1:-1, 2:, 1:-1]
        + z[..., 1:-1, :-2, 1:-1]
        + z[..., 1:-1, 1:-1, 2:]
        + z[..., 1:-1, 1:-1, :-2]
        - 6.0 * c
    )


def use_exact_getz() -> bool:
    """Round-4 default: the exact fast-diagonalization tile solve
    (ops/tilesolve.py) replaces the fixed-sweep CG getZ.  CUP3D_GETZ=cg
    restores the round-3 Pallas/jnp CG path."""
    import os

    return os.environ.get("CUP3D_GETZ", "") != "cg"


@jax.named_scope("TileSolve")
def getz_blocks(b_scaled: jnp.ndarray, shift=None,
                cg_iters: int = 24) -> jnp.ndarray:
    """getZ preconditioner application in the (..., bs, bs, bs) blocks
    layout: solve (-lap_tile + shift) z = b_scaled per tile.  Dispatches to
    the exact tile solve (default) or the legacy fixed-iteration CG."""
    from cup3d_tpu.ops import tilesolve

    if use_exact_getz():
        return tilesolve.tile_solve_blocks(b_scaled, shift)
    return block_cg_tiles(b_scaled, cg_iters,
                          shift=0.0 if shift is None else shift)


@jax.named_scope("TileSolve")
def getz_lanes(bt_scaled: jnp.ndarray, shift=None,
               cg_iters: int = 24) -> jnp.ndarray:
    """getZ in the lane-resident (bs, bs, bs, T) layout (see getz_blocks)."""
    from cup3d_tpu.ops import getz_pallas, tilesolve

    if use_exact_getz():
        return tilesolve.tile_solve_lanes(bt_scaled, shift)
    return getz_pallas.cg_tiles_lanes(bt_scaled, cg_iters,
                                      shift=0.0 if shift is None else shift)


def block_cg_tiles(b: jnp.ndarray, iters: int, shift=0.0) -> jnp.ndarray:
    """Solve (-block_lap + shift*I) z = b independently on every
    trailing-bs^3 tile of ``b`` (shape (..., bs, bs, bs)) with `iters` CG
    steps — the batched getZ kernel (kernelPoissonGetZInner,
    main.cpp:14651-14702; the shifted variant is the diffusion getZ with
    coefficient -6 - h^2/(nu dt), main.cpp:10571).

    On TPU this dispatches to the VMEM-resident Pallas kernel
    (ops/getz_pallas.py, ~3x per application); elsewhere (and in tests)
    it runs the jnp reference below."""
    from cup3d_tpu.ops import getz_pallas

    if getz_pallas.use_pallas():
        return getz_pallas.block_cg_tiles_pallas(b, iters, shift)
    return block_cg_tiles_reference(b, iters, shift)


def block_cg_tiles_reference(b: jnp.ndarray, iters: int, shift=0.0) -> jnp.ndarray:
    """Pure-jnp getZ (the ground truth the Pallas kernel is tested
    against — the reference's own optimized-vs-reference kernel pattern,
    main.cpp:9186-9190).  The tile operator with its implicit
    zero-Dirichlet halo is SPD for shift >= 0, so plain CG applies; the
    fixed iteration count keeps the graph static and every tile equally
    expensive (no block imbalance).  ``shift`` may be a traced scalar or
    an array broadcastable to ``b`` (per-block h^2)."""
    acc = jnp.promote_types(b.dtype, jnp.float32)
    bdot = lambda a, c: jnp.sum(
        a * c, axis=(-1, -2, -3), keepdims=True, dtype=acc
    )

    z0 = jnp.zeros_like(b)
    rs0 = bdot(b, b)

    def body(_, carry):
        z, res, p, rs = carry
        ap = -_block_lap(p) + shift * p
        denom = bdot(p, ap)
        alpha = rs / jnp.where(jnp.abs(denom) > 1e-30, denom, 1.0)
        alpha = jnp.where(jnp.abs(denom) > 1e-30, alpha, 0.0)
        z = z + alpha * p
        res = res - alpha * ap
        rs_new = bdot(res, res)
        beta = rs_new / jnp.where(rs > 1e-30, rs, 1.0)
        beta = jnp.where(rs > 1e-30, beta, 0.0)
        p = res + beta * p
        return z, res, p, rs_new

    z, _, _, _ = jax.lax.fori_loop(0, iters, body, (z0, b, b, rs0))
    return z


def make_block_cg_preconditioner(bs: int = 8, iters: int = 24,
                                 h: float = 1.0) -> Callable:
    """z ~ A^{-1} r block-locally for A = lap/h^2 on a *dense* grid:
    tile the grid into bs^3 blocks and run block_cg_tiles.  The h^2 scaling
    of A is folded into the per-tile rhs so M is a genuine approximate
    inverse of A (not just a Krylov-equivalent rescaling)."""
    h2 = h * h

    def precond(r: jnp.ndarray) -> jnp.ndarray:
        z = getz_blocks(-h2 * _tile(r, bs), cg_iters=iters)
        return _untile(z)

    return precond


# ---------------------------------------------------------------------------
# coarse-grid correction: the round-5 second preconditioner level
# ---------------------------------------------------------------------------


def make_coarse_correction_lanes(grid: UniformGrid, bs: int = 8) -> Callable:
    """Galerkin coarse correction T = P (P^T A P)^{-1} P^T on the tile-mean
    grid, for A = the 7-point Laplacian/h^2 with the grid's BCs.

    P is piecewise-constant prolongation over each bs^3 tile.  A is
    separable, so the coarse operator is exactly
    P^T A P = (bs^2/h^2) (L_x (+) L_y (+) L_z) with L_* the 1D coarse
    graph Laplacians (periodic wrap or Neumann path per BC) — solved
    EXACTLY by per-axis eigendecomposition: three (NB,NB) matmuls on an
    (NBx,NBy,NBz) array, negligible next to the fine-grid work.

    Why: the exact tile solve (ops/tilesolve.py) is block-Jacobi — no
    global coupling — so outer BiCGSTAB iterations grow with resolution
    (48 at 128^3, more at 256^3; BENCH_r04).  Adding this coarse level
    (additive two-level Schwarz) carries the smooth modes globally and
    makes the iteration count roughly resolution-independent.  The
    reference has no counterpart (its getZ is block-local too,
    main.cpp:14617-14746) — this is a TPU-side algorithmic win, not a
    port.
    """
    solve_vec = _make_coarse_solve_vec(grid, bs)

    def correct(rt: jnp.ndarray) -> jnp.ndarray:
        """rt: residual in lanes layout (bs,bs,bs,T) -> coarse correction
        in the same layout (constant per tile)."""
        zc = solve_vec(rt)
        return jnp.broadcast_to(zc[None, None, None, :], rt.shape)

    return correct


def make_twolevel_preconditioner_lanes(grid: UniformGrid, h2: float,
                                       bs: int = 8,
                                       precond_iters: int = 24) -> Callable:
    """Multiplicative two-level preconditioner in the lanes layout:

        zc = P (P^T A P)^{-1} P^T r        (exact Galerkin coarse solve)
        z  = zc + getZ(r - A zc)           (exact tile solve on the rest)

    Measured on the 128^3 pressure system this converges in 12 outer
    BiCGSTAB iterations vs 51 for the tile solve alone, and the count is
    resolution-independent (11-12 at 64^3/128^3/256^3; on one v5e the
    benchmark's harness reads 11.3 a solve on the 128^3 fish and
    10.4-10.6 on the 256^3 fish, warm-started, 11-12 in the solve probe:
    PERF.md, PR 36) — the coarse
    level carries the smooth modes the block-local getZ cannot see.

    Coarse-first ordering makes the multiplicative coupling nearly free:
    zc is CONSTANT per tile, so A zc is nonzero only on the 6 tile-face
    sublane planes and is assembled analytically from coarse neighbor
    differences — no fine-grid stencil application.
    """
    coarse_vec = _make_coarse_solve_vec(grid, bs)
    nb = tuple(s // bs for s in grid.shape)
    T = nb[0] * nb[1] * nb[2]
    deltas_fn = make_face_deltas(grid, bs)

    def lap_tileconst(zc: jnp.ndarray) -> jnp.ndarray:
        """(T,) coarse values -> A zc in lanes layout (bs,bs,bs,T)."""
        d = deltas_fn(zc)
        out = jnp.zeros((bs, bs, bs, T), zc.dtype)
        for ax in range(3):
            idx_hi = [slice(None)] * 4
            idx_hi[ax] = bs - 1
            idx_lo = [slice(None)] * 4
            idx_lo[ax] = 0
            out = out.at[tuple(idx_hi)].add(d[2 * ax + 1])
            out = out.at[tuple(idx_lo)].add(d[2 * ax])
        return out

    def M(r: jnp.ndarray) -> jnp.ndarray:
        zc = coarse_vec(r)
        z = getz_lanes(-h2 * (r - lap_tileconst(zc)),
                       cg_iters=precond_iters)
        return z + zc[None, None, None, :]

    return M


def make_face_deltas(grid: UniformGrid, bs: int = 8) -> Callable:
    """zc (T,) coarse tile values -> (6, T) face deltas of A zc, rows
    [lo0, hi0, lo1, hi1, lo2, hi2].

    For tile-constant zc, A zc is nonzero only on the 6 tile-face
    planes: row 2*ax+1 is the value added on the face at local index
    bs-1 along ``ax`` ((next - self)/h^2 with the BC's wrap/clamp), row
    2*ax the face at index 0.  make_twolevel_preconditioner_lanes
    scatters these into the lanes layout; the fused iteration
    (ops/fused_bicgstab.py) ships them to its getZ kernel as coarse aux
    rows and reconstructs A zc in-kernel by face concatenation."""
    from cup3d_tpu.grid.uniform import BC

    nb = tuple(s // bs for s in grid.shape)
    strides = (nb[1] * nb[2], nb[2], 1)
    T = nb[0] * nb[1] * nb[2]
    lanes = np.arange(T)
    tco = (lanes // strides[0] % nb[0],
           lanes // strides[1] % nb[1],
           lanes % nb[2])
    inv_h2 = 1.0 / (grid.h * grid.h)
    periodic = [grid.bc[ax] == BC.periodic for ax in range(3)]
    masks_hi = [jnp.asarray(tco[ax] == nb[ax] - 1) for ax in range(3)]
    masks_lo = [jnp.asarray(tco[ax] == 0) for ax in range(3)]

    def deltas(zc: jnp.ndarray) -> jnp.ndarray:
        rows = []
        for ax in range(3):
            st, nba = strides[ax], nb[ax]
            nxt = jnp.roll(zc, -st)
            wrap_hi = jnp.roll(zc, (nba - 1) * st)
            # Neumann wall: neighbor = self -> zero face difference
            nxt = jnp.where(masks_hi[ax],
                            wrap_hi if periodic[ax] else zc, nxt)
            prv = jnp.roll(zc, st)
            wrap_lo = jnp.roll(zc, -(nba - 1) * st)
            prv = jnp.where(masks_lo[ax],
                            wrap_lo if periodic[ax] else zc, prv)
            rows.append((prv - zc) * inv_h2)
            rows.append((nxt - zc) * inv_h2)
        return jnp.stack(rows, axis=0)

    return deltas


def _make_coarse_solve_vec(grid: UniformGrid, bs: int = 8) -> Callable:
    """(bs,bs,bs,T) residual -> (T,) coarse correction values (the shared
    core of make_coarse_correction_lanes / make_twolevel_preconditioner)."""
    core = _make_coarse_core(grid, bs)

    @jax.named_scope("CoarseSolve")
    def solve_vec(rt: jnp.ndarray) -> jnp.ndarray:
        return core(jnp.sum(rt, axis=(0, 1, 2)).reshape(-1))

    return solve_vec


def _make_coarse_core(grid: UniformGrid, bs: int = 8) -> Callable:
    """(T,) tile sums (R = P^T r) -> (T,) coarse correction values: the
    eigendecomposition einsum core of _make_coarse_solve_vec, split out
    so the fused iteration can feed it the per-tile partial sums its
    kernels already emit instead of re-reducing the fine grid."""
    from cup3d_tpu.grid.uniform import BC

    nb = tuple(s // bs for s in grid.shape)
    Vs, lams = [], []
    for ax in range(3):
        n = nb[ax]
        if n == 1:
            # degenerate axis: a single tile has no coarse neighbor in
            # either BC family (the periodic wrap is itself, the Neumann
            # wall is zero-gradient), so the exact Galerkin P^T A P row is
            # 0 — an isolated node whose constant mode the pseudo-inverse
            # below projects out (ADVICE r5: the wall branch's diagonal 1
            # added a spurious bs^2/h^2 eigenvalue shift here)
            L = np.zeros((1, 1))
        else:
            L = 2.0 * np.eye(n) - np.diag(np.ones(n - 1), 1) - np.diag(
                np.ones(n - 1), -1
            )
            if grid.bc[ax] == BC.periodic:
                L[0, -1] -= 1.0
                L[-1, 0] -= 1.0
            else:  # zero-gradient: no coupling through the wall
                L[0, 0] = 1.0
                L[-1, -1] = 1.0
        w, V = np.linalg.eigh(L)
        Vs.append(V)
        lams.append(w)
    scale = bs * bs / (grid.h * grid.h)
    lam3 = scale * (
        lams[0][:, None, None] + lams[1][None, :, None]
        + lams[2][None, None, :]
    )
    inv3 = np.where(lam3 > 1e-8 * scale, 1.0 / np.maximum(lam3, 1e-300), 0.0)
    dt = np.float32
    Vx, Vy, Vz = (jnp.asarray(V.astype(dt)) for V in Vs)
    inv3 = jnp.asarray(inv3.astype(dt))
    T = nb[0] * nb[1] * nb[2]

    def core(rc_flat: jnp.ndarray) -> jnp.ndarray:
        rc = rc_flat.reshape(nb)
        t = jnp.einsum("ia,abc->ibc", Vx.T, rc, precision=_HI)
        t = jnp.einsum("jb,ibc->ijc", Vy.T, t, precision=_HI)
        t = jnp.einsum("kc,ijc->ijk", Vz.T, t, precision=_HI)
        t = -t * inv3  # A is the negative of the positive graph form
        t = jnp.einsum("ai,ijk->ajk", Vx, t, precision=_HI)
        t = jnp.einsum("bj,ajk->abk", Vy, t, precision=_HI)
        zc = jnp.einsum("ck,abk->abc", Vz, t, precision=_HI)
        return zc.reshape(T)

    return core


def use_coarse_correction() -> bool:
    """Round-5 default: two-level (tile + coarse) preconditioner.
    CUP3D_COARSE=0 restores the pure block-Jacobi tile solve."""
    import os

    return os.environ.get("CUP3D_COARSE", "1") != "0"


# ---------------------------------------------------------------------------
# AMR coarse level: one DOF per block over the forest's face graph
# ---------------------------------------------------------------------------

#: max face-neighbor entries per block under 26-neighbor 2:1 balance:
#: 6 faces x up to 4 finer blocks per face
GRAPH_K = 24


#: largest block count (the bucket's capacity where the graph is padded)
#: for which block_graph_tables also builds the dense pseudo-inverse: the
#: host inverts one (nb, nb) float64 matrix per new octree signature and
#: the device holds cap^2 values.  Inversions measured on the chip
#: machine's host (PERF.md section 6, PR 33): 215 rows 4 ms (185 KB on
#: the device), 1613 rows 0.11 s (10 MB), 2048 rows 0.22 s (17 MB), 4096
#: rows 1.6 s (67 MB); 8192 rows would take ten seconds and 268 MB, too
#: much at every regrid.
DENSE_COARSE_MAX = 2048


class BlockGraph(NamedTuple):
    """Face-adjacency graph of one forest topology, the coarse space of
    the AMR two-level preconditioner (the multi-level counterpart of
    make_coarse_correction_lanes' tile-mean grid).

    ``idx``/``w``: (nb[, pad], K) neighbor slots and couplings (w = 0 on
    padding entries and padding blocks); ``deg``: (nb[, pad],) row sums.
    The coarse operator is the SPSD graph Laplacian C z = deg*z - W z,
    whose nullspace is the constant — consistent with the mean-removed
    pressure system, exactly like the uniform path's pseudo-inverse.

    ``pinv``: the dense pseudo-inverse C^+ of that operator, (nb[, pad],
    nb[, pad]) in the tables' dtype, rows and columns of padding blocks
    exactly 0; block_graph_tables builds it on the host in float64 from
    the idx/w/deg it has just built, for graphs of at most
    DENSE_COARSE_MAX rows, and leaves it ``None`` above (the inversion is
    O(nb^3) on the host at every new topology and the matrix cap^2 on the
    device).  With it coarse_correct_blocks is one matrix-vector product;
    without it, the CG loop over idx/w/deg.  The size of the forest alone
    decides: no switch selects either.

    NamedTuple => pytree: travels as a traced jit ARGUMENT, so bucketed
    drivers (sim/amr.py) reuse compiled executables across regrids; a
    ``None`` pinv is an empty subtree, one leaf fewer."""

    idx: jnp.ndarray
    w: jnp.ndarray
    deg: jnp.ndarray
    pinv: Optional[jnp.ndarray] = None


def _graph_pinv(idx: np.ndarray, w: np.ndarray, deg: np.ndarray) -> np.ndarray:
    """Float64 pseudo-inverse of the graph Laplacian over the real blocks.
    The face graph of a forest is connected, so the nullspace is the
    constant alone and C^+ = (C + 11^T/n)^-1 - 11^T/n: one symmetric
    positive definite inversion, no eigendecomposition."""
    n = len(deg)
    C = np.zeros((n, n), np.float64)
    # a neighbor can fill several slots of a row (periodic wrap on a
    # 1- or 2-block axis): add, never assign
    np.add.at(C, (np.repeat(np.arange(n), idx.shape[1]), idx.ravel()),
              -w.ravel())
    C[np.arange(n), np.arange(n)] += deg
    pinv = np.linalg.inv(C + 1.0 / n) - 1.0 / n
    return 0.5 * (pinv + pinv.T)


def block_graph_tables(grid, cap: Optional[int] = None,
                       dtype=jnp.float32) -> BlockGraph:
    """Host-build the face graph of ``grid`` (a BlockGrid).

    Couplings are the physical finite-volume face conductances A/d in
    the convention that makes the graph Laplacian the exact Galerkin
    P^T A P of the refluxed 7-pt Laplacian for SAME-LEVEL faces (the
    verified uniform limit: w = bs^2 h with volume-weighted restriction
    reproduces make_coarse_correction_lanes' bs^2/h^2 operator exactly).
    Coarse-fine faces use the same A/d rule — shared area (bs h_f)^2
    over the 1.5 h_f center distance — which is an APPROXIMATION of the
    interpolated-ghost Galerkin rows there; a preconditioner-grade one
    (symmetric, positive semidefinite, constant nullspace), documented
    in VALIDATION.md.  ``cap``: optional bucket capacity to pad to.
    Graphs of at most DENSE_COARSE_MAX rows (``cap``, else ``nb``) also
    carry the dense pseudo-inverse (BlockGraph.pinv)."""
    tree = grid.tree
    bs = grid.bs
    nb = grid.nb
    idx = np.zeros((nb, GRAPH_K), np.int64)
    w = np.zeros((nb, GRAPH_K), np.float64)
    fill = np.zeros(nb, np.int64)

    def add(i, j, wij):
        k = fill[i]
        idx[i, k] = j
        w[i, k] = wij
        fill[i] = k + 1

    offs2 = [(0, 0), (0, 1), (1, 0), (1, 1)]
    for s, (l, bi, bj, bk) in enumerate(grid.keys):
        h = float(grid.h[s])
        for ax in range(3):
            t1, t2 = [a for a in range(3) if a != ax]
            for side in (-1, 1):
                npos = [bi, bj, bk]
                npos[ax] += side
                wp = tree.wrap(l, npos)
                if wp is None:
                    continue  # closed face: no coupling (zero-gradient)
                own = tree.owner_level(l, wp)
                if own == l:
                    add(s, grid.slot[(l, *wp)], bs * bs * h)
                elif own == l - 1:
                    parent = (l - 1, wp[0] // 2, wp[1] // 2, wp[2] // 2)
                    # fine side of a coarse-fine face: A = (bs h)^2,
                    # d = (h + 2h)/2 -> w = bs^2 h / 1.5
                    add(s, grid.slot[parent], bs * bs * h / 1.5)
                else:  # own == l + 1: 4 finer blocks, h_f = h/2
                    hf = 0.5 * h
                    for o1, o2 in offs2:
                        fpos = [0, 0, 0]
                        fpos[ax] = 2 * wp[ax] + (1 if side < 0 else 0)
                        fpos[t1] = 2 * wp[t1] + o1
                        fpos[t2] = 2 * wp[t2] + o2
                        fslot = grid._slot_maps[l + 1][tuple(fpos)]
                        if fslot < 0:
                            raise KeyError("fine neighbor missing: "
                                           "unbalanced tree")
                        add(s, int(fslot), bs * bs * hf / 1.5)
    deg = w.sum(axis=1)
    rows = nb if cap is None else cap
    pinv = None
    if rows <= DENSE_COARSE_MAX:
        pinv = np.zeros((rows, rows), np.float64)
        pinv[:nb, :nb] = _graph_pinv(idx, w, deg)
        # cast on the host: jnp.asarray(float64, float32) is an upload
        # AND a convert program
        pinv = jnp.asarray(pinv.astype(np.dtype(dtype)))
    if cap is not None:
        from cup3d_tpu.grid import bucket as bk_

        idx = bk_.pad_rows(idx, cap)
        w = bk_.pad_rows(w, cap)
        deg = bk_.pad_rows(deg, cap)
    return BlockGraph(
        idx=jnp.asarray(idx, jnp.int32),
        w=jnp.asarray(w, dtype),
        deg=jnp.asarray(deg, dtype),
        pinv=pinv,
    )


def _cg_graph(Cfun: Callable, b: jnp.ndarray, iters: int,
              rtol: float = 1e-6) -> jnp.ndarray:
    """Fixed-iteration CG on the (tiny) coarse system — fixed so the
    preconditioner is a FIXED linear operator (BiCGSTAB requirement) and
    the graph stays static.

    Two gates make the fixed sweep safe in f32 on the SINGULAR
    (constant-nullspace) coarse system: updates freeze once the
    relative residual drops below ``rtol`` (CG iterating past
    convergence on roundoff noise diverges — measured NaN on a 22-node
    graph at 32 sweeps), and non-positive curvature directions (noise /
    nullspace: C is PSD) are skipped."""
    acc = jnp.promote_types(b.dtype, jnp.float32)
    dot = lambda a, c: jnp.sum(a * c, dtype=acc)
    rs0 = dot(b, b)

    def body(_, carry):
        z, r, p, rs = carry
        live = rs > (rtol * rtol) * rs0
        ap = Cfun(p)
        denom = dot(p, ap)
        ok = jnp.logical_and(live, denom > 0.0)
        alpha = jnp.where(ok, rs / jnp.where(ok, denom, 1.0), 0.0)
        z = z + alpha * p
        r = r - alpha * ap
        rs_new = dot(r, r)
        beta = jnp.where(ok, rs_new / jnp.where(rs > 0, rs, 1.0), 0.0)
        return z, r, r + beta * p, rs_new

    z0 = jnp.zeros_like(b)
    z, _, _, _ = jax.lax.fori_loop(0, iters, body, (z0, b, b, rs0))
    return z


@jax.named_scope("CoarseSolve")
def coarse_correct_blocks(r: jnp.ndarray, vol: jnp.ndarray,
                          graph: BlockGraph, iters: int = 32) -> jnp.ndarray:
    """Coarse correction over the block graph: volume-weighted restrict
    the residual to one value per block, solve the graph Laplacian,
    return the (nb,) per-block correction (prolonged by constant
    injection at the caller).

    The solve is ONE dense product with ``graph.pinv`` where the graph
    carries it (block_graph_tables: forests of at most DENSE_COARSE_MAX
    blocks), at Precision.HIGHEST (the default rounds float32 operands
    to bfloat16 on the TPU); above that size, ``iters`` sweeps of CG
    whose operator gathers cap x GRAPH_K single elements a sweep (on the
    v5e 44 us a sweep at cap 215, 30 of the 66 device ms of a
    twofish_l4 step before PR 33).  Both are the same fixed linear
    operator to the CG's own 1e-6 gate.

    ``vol`` is the per-cell volume column ((nb,1,1,1); 0 on padding
    blocks, which keeps their rows exactly 0 through either solve).  The
    restriction R r = h^3 sum_cells r makes the graph weights of
    block_graph_tables the exact uniform-limit Galerkin scaling (see
    there).  The singular-consistent system stays in range(C):
    conservation of the refluxed Laplacian puts zero volume-weighted
    mean on every Krylov residual of the mean-removed solve."""
    rc = jnp.sum(r * vol, axis=(1, 2, 3)).astype(graph.w.dtype)
    # project the constant nullspace out of the restricted residual (the
    # uniform path's pseudo-inverse does this spectrally): the outer
    # residual is mean-free only to f32 roundoff, and CG amplifies an
    # inconsistent nullspace component through near-zero curvature
    # directions (measured: NaN without this).  Real blocks carry
    # deg > 0; padding rows are isolated zero rows and stay untouched.
    m = (graph.deg > 0).astype(rc.dtype)
    nreal = jnp.maximum(jnp.sum(m), 1.0)

    def deflate(v):
        return (v - jnp.sum(v * m) / nreal) * m

    if graph.pinv is not None:
        zc = jnp.matmul(graph.pinv, deflate(rc), precision=_HI)
    else:
        def C(z):
            return graph.deg * z - jnp.sum(z[graph.idx] * graph.w, axis=-1)

        zc = _cg_graph(C, deflate(rc), iters)
    # the fine A is the NEGATIVE of the positive graph form (lap x =
    # sum(nb - c)/h^2), same sign flip as the uniform path's
    # `t = -t * inv3` (_make_coarse_solve_vec)
    return -deflate(zc).astype(r.dtype)


# ---------------------------------------------------------------------------
# restarted preconditioned BiCGSTAB
# ---------------------------------------------------------------------------


class _BiCGState(NamedTuple):
    k: jnp.ndarray
    x: jnp.ndarray
    r: jnp.ndarray
    rhat: jnp.ndarray
    p: jnp.ndarray
    v: jnp.ndarray
    rho: jnp.ndarray
    alpha: jnp.ndarray
    omega: jnp.ndarray
    rnorm: jnp.ndarray
    x_best: jnp.ndarray
    rnorm_best: jnp.ndarray


def bicgstab(
    apply_A: Callable,
    b: jnp.ndarray,
    M: Optional[Callable] = None,
    x0: Optional[jnp.ndarray] = None,
    tol_abs: float = 1e-6,
    tol_rel: float = 1e-4,
    maxiter: int = 1000,
    rnorm_ref=None,
    r0=None,
    rnorm0=None,
):
    """Preconditioned BiCGSTAB with breakdown re-seeding and best-x tracking
    (the reference's solve loop, main.cpp:14449-14604).  Returns
    (x_best, final residual norm, iterations used).

    Stopping matches the reference: ||r|| <= max(tol_abs, tol_rel*||r0||)
    (PoissonErrorTol/PoissonErrorTolRel, main.cpp:15364-15365).

    ``rnorm_ref`` overrides the relative-tolerance reference norm.  A warm
    start (x0 != 0, or the 2nd-order increment form) SHRINKS ||r0||, which
    would tighten the target exactly when the start is good and make warm
    solves cost MORE iterations than cold ones (measured 54 vs 44,
    VERDICT r2 item 4).  Callers with a warm start pass the cold system's
    RHS norm so the solve targets the same absolute quality as a cold
    solve and a good start can only reduce iterations.

    ``r0`` is the initial residual b - A x0 where the caller has formed
    it already, and ``rnorm0`` its norm: A is then applied, and the norm
    taken, only inside the loop (build_iterative_solver forms both on the
    natural grid and iterates on the increment from x0 = 0).
    """
    if M is None:
        M = lambda r: r
    if x0 is None:
        x0 = jnp.zeros_like(b)
    # the iteration's three kinds of work, by name in a device trace
    apply_A = jax.named_scope("Laplacian")(apply_A)
    M = jax.named_scope("Preconditioner")(M)
    dot = jax.named_scope("Dots")(_dot)

    # breakdown threshold in the ACCUMULATION dtype, not b.dtype: 1e-30
    # underflows to 0 in bf16/f16 storage, which would silently disable
    # the rho re-seed below (round-12 mixed-precision audit)
    eps = jnp.asarray(1e-30, jnp.promote_types(b.dtype, jnp.float32))

    if r0 is None:
        r0 = b - apply_A(x0)
    if rnorm0 is None:
        rnorm0 = jnp.sqrt(dot(r0, r0))
    ref = rnorm0 if rnorm_ref is None else rnorm_ref
    target = jnp.maximum(tol_abs, tol_rel * ref)
    one = jnp.asarray(1.0, b.dtype)

    init = _BiCGState(
        k=jnp.asarray(0, jnp.int32),
        x=x0,
        r=r0,
        rhat=r0,
        p=jnp.zeros_like(b),
        v=jnp.zeros_like(b),
        rho=one,
        alpha=one,
        omega=one,
        rnorm=rnorm0,
        x_best=x0,
        rnorm_best=rnorm0,
    )

    def cond(s: _BiCGState):
        return jnp.logical_and(s.k < maxiter, s.rnorm > target)

    def body(s: _BiCGState):
        rho_new = dot(s.rhat, s.r)
        # rho breakdown -> re-seed shadow residual (reference restart,
        # main.cpp:14452-14479)
        broke = jnp.abs(rho_new) < eps * jnp.maximum(s.rnorm * s.rnorm, 1.0)
        rhat = jnp.where(broke, s.r, s.rhat)
        rho_new = jnp.where(broke, s.rnorm * s.rnorm, rho_new)
        p_prev = jnp.where(broke, 0.0, s.p)
        v_prev = jnp.where(broke, 0.0, s.v)

        beta = (rho_new / _safe(s.rho)) * (s.alpha / _safe(s.omega))
        beta = jnp.where(broke, 0.0, beta)
        p = s.r + beta * (p_prev - s.omega * v_prev)
        y = M(p)
        v = apply_A(y)
        rhat_v = dot(rhat, v)
        alpha = rho_new / _safe(rhat_v)
        svec = s.r - alpha * v
        z = M(svec)
        t = apply_A(z)
        tt = dot(t, t)
        omega = dot(t, svec) / _safe(tt)
        x = s.x + alpha * y + omega * z
        r = svec - omega * t
        rnorm = jnp.sqrt(dot(r, r))

        better = rnorm < s.rnorm_best
        return _BiCGState(
            k=s.k + 1,
            x=x,
            r=r,
            rhat=rhat,
            p=p,
            v=v,
            rho=rho_new,
            alpha=alpha,
            omega=omega,
            rnorm=rnorm,
            x_best=jnp.where(better, x, s.x_best),
            rnorm_best=jnp.minimum(rnorm, s.rnorm_best),
        )

    out = jax.lax.while_loop(cond, body, init)
    return out.x_best, out.rnorm_best, out.k


def _safe(d):
    # ``d`` is always an accumulated scalar (f32+, never bf16 — see
    # _dot), so the 1e-30 floor is representable; the dtype-matched
    # asarray cannot promote an f32 pipeline to f64 (JX005 audit).
    return jnp.where(jnp.abs(d) > 1e-30, d, jnp.asarray(1e-30, d.dtype))


# ---------------------------------------------------------------------------
# Poisson front-end (iterative; see poisson.build_spectral_solver for the
# uniform-grid spectral fast path)
# ---------------------------------------------------------------------------


def build_iterative_solver(
    grid: UniformGrid,
    tol_abs: float = 1e-6,
    tol_rel: float = 1e-4,
    maxiter: int = 1000,
    precond_bs: int = 8,
    precond_iters: int = 24,
    mean_constraint: int = 2,
    two_level: Optional[bool] = None,
) -> Callable:
    """solve(rhs) -> p via getZ-preconditioned BiCGSTAB.

    ``mean_constraint`` mirrors the reference's bMeanConstraint
    (ComputeLHS, main.cpp:9273-9327): 0 = none, 1 = the equation row of
    cell (0,0,0) becomes the volume-weighted mean of the unknown, 2 =
    nullspace projection (mean removal; default), 3 = Dirichlet-pin of
    cell (0,0,0).  The pinned-row RHS is zeroed like the reference's
    solve loop (main.cpp:14404-14407).

    The iteration runs in the lane-resident tile layout (to_lanes /
    make_laplacian_lanes): one transpose in, one out, none per iteration.
    Without a pinned row (``mean_constraint`` 0 and 2) the entry is the
    increment form: b, its norm and r0 = b - A x0 are formed on the
    natural grid (make_laplacian, the same operator), r0 goes into the
    lanes layout, BiCGSTAB iterates on the increment d from d = 0, and
    x = x0 + d comes back on the natural grid.  In the lanes layout the
    compiler fused the set-up's norm into the transposes of b and x0 and
    gave its stencil the transposed tile layout, whose minor dimension of
    8 pads to the 128-wide lanes: on one v5e at 256^3 that norm alone
    took 14.2 ms a step, ~170x its bytes (PERF.md section 6).
    The pinned rows of 1 and 3 exist only on the lanes operator: those
    modes keep the composed entry (b and x0 transposed, r0 formed in
    the loop's layout).  ``solve.entry`` names which ("increment",
    "composed"); the drivers count solves by it (sim/operators.py).

    ``two_level`` overrides the CUP3D_COARSE env default for the
    preconditioner choice (None = :func:`use_coarse_correction`): the
    resilience escalation ladder drops to the tile-only getZ without
    touching process-global state (resilience/recovery.py).
    """
    if any(s % precond_bs for s in grid.shape):
        return _build_iterative_solver_dense(
            grid, tol_abs, tol_rel, maxiter, precond_bs, precond_iters,
            mean_constraint,
        )
    A0 = make_laplacian_lanes(grid, precond_bs)
    h2 = grid.h * grid.h
    h3 = grid.h ** 3

    # lanes layout: dense cell (0,0,0) lives at [0,0,0, lane 0].
    # The replaced row is rescaled to the Laplacian's diagonal magnitude
    # (6/h^2): its RHS entry is zeroed below, so row scaling leaves the
    # solution unchanged, but an O(1) (pin) or O(h^3) (mean) row next to
    # O(1/h^2) rows wrecks the conditioning and stalls float32 BiCGSTAB
    # (ADVICE r5 regression test: test_mean_constraint_pinned_paths)
    pin = 6.0 / h2
    if mean_constraint == 1:
        A = lambda t: A0(t).at[0, 0, 0, 0].set(jnp.sum(t) * h3 * pin)
    elif mean_constraint == 3:
        A = lambda t: A0(t).at[0, 0, 0, 0].set(t[0, 0, 0, 0] * pin)
    else:
        A = A0

    use_two = (use_coarse_correction() if two_level is None
               else bool(two_level))
    if use_two and mean_constraint not in (1, 3):
        # multiplicative two-level: 12 outer iterations vs 51 tile-only at
        # 128^3, resolution-independent (make_twolevel_preconditioner_lanes)
        M = make_twolevel_preconditioner_lanes(grid, h2, precond_bs,
                                               precond_iters)
    else:
        # mean_constraint 1/3 pin one equation row, making A nonsingular —
        # but the two-level M's exact Galerkin coarse solve is built from
        # the UNMODIFIED singular Laplacian, so its pseudo-inverse projects
        # the constant mode back out and the preconditioned operator
        # reintroduces the nullspace the pin removed (ADVICE r5).  The
        # tile-local getZ has no global coupling, so it is unaffected by
        # the single-row modification.

        def M(r):
            return getz_lanes(-h2 * r, cg_iters=precond_iters)

    from cup3d_tpu.ops import precision as _precision

    # round 12: loud build-time error for knob combinations that cannot
    # honor a bf16 request (no silent downgrade)
    _precision.check_policy(mean_constraint)
    # The fused per-iteration driver covers the production hot path
    # only: mean-removal constraint + exact getZ.  The pinned-row modes
    # (1/3) and the legacy CG getZ keep the unfused composition at f32
    # storage — they are off the hot path and the single-row A
    # modification doesn't fit the fused stencil kernel.
    if (_precision.use_fused() and mean_constraint == 2
            and use_exact_getz()):
        from cup3d_tpu.ops import fused_bicgstab as _fused

        store = _precision.krylov_dtype()

        @jax.named_scope("PoissonSolve")
        def solve(rhs: jnp.ndarray, x0: Optional[jnp.ndarray] = None,
                  with_stats: bool = False):
            b = rhs - jnp.mean(rhs)
            bt = to_lanes(b, precond_bs)
            x0t = None if x0 is None else to_lanes(x0, precond_bs)
            xt, rnorm, k = _fused.fused_bicgstab(
                grid, bt, tol_abs=tol_abs, tol_rel=tol_rel,
                maxiter=maxiter, rnorm_ref=jnp.sqrt(_dot(bt, bt)),
                x0=x0t, bs=precond_bs, two_level=use_two,
                store_dtype=store,
            )
            x = from_lanes(xt, rhs.shape)
            x = x - jnp.mean(x)
            if with_stats:
                return x, solver_stats(rnorm, k)
            return x

        solve.supports_stats = True
        solve.maxiter = maxiter
        solve.entry = "composed"
        return solve

    if mean_constraint in (1, 3):
        @jax.named_scope("PoissonSolve")
        def solve(rhs: jnp.ndarray, x0: Optional[jnp.ndarray] = None,
                  with_stats: bool = False):
            bt = to_lanes(rhs, precond_bs).at[0, 0, 0, 0].set(0.0)
            x0t = None if x0 is None else to_lanes(x0, precond_bs)
            # rel tolerance always references the cold system's RHS norm
            # so a warm start can only reduce iterations (see bicgstab)
            xt, rnorm, k = bicgstab(
                A, bt, M=M, x0=x0t, tol_abs=tol_abs, tol_rel=tol_rel,
                maxiter=maxiter, rnorm_ref=jnp.sqrt(_dot(bt, bt)),
            )
            x = from_lanes(xt, rhs.shape)
            if with_stats:
                return x, solver_stats(rnorm, k)
            return x

        solve.entry = "composed"
    else:
        lap = make_laplacian(grid)

        @jax.named_scope("PoissonSolve")
        def solve(rhs: jnp.ndarray, x0: Optional[jnp.ndarray] = None,
                  with_stats: bool = False):
            b = rhs - jnp.mean(rhs) if mean_constraint == 2 else rhs
            # rel tolerance always references the cold system's RHS norm
            # so a warm start can only reduce iterations (see bicgstab)
            rnorm_ref = jnp.sqrt(_dot(b, b))
            if x0 is None:
                r0, rnorm0 = b, rnorm_ref
            else:
                r0 = b - lap(x0)
                rnorm0 = jnp.sqrt(_dot(r0, r0))
            rt = to_lanes(r0, precond_bs)
            d, rnorm, k = bicgstab(
                A, rt, M=M, tol_abs=tol_abs, tol_rel=tol_rel,
                maxiter=maxiter, rnorm_ref=rnorm_ref, r0=rt, rnorm0=rnorm0,
            )
            x = from_lanes(d, rhs.shape)
            x = x if x0 is None else x0 + x
            x = x - jnp.mean(x) if mean_constraint == 2 else x
            if with_stats:
                # (final residual norm, iterations) as one device vector —
                # drivers pack it onto the async QoI read so per-step
                # solver telemetry costs ZERO extra syncs (obs/trace.py)
                return x, solver_stats(rnorm, k)
            return x

        solve.entry = "increment"

    solve.supports_stats = True
    solve.maxiter = maxiter
    return solve


def solver_stats(rnorm, k) -> jnp.ndarray:
    """(2,) f32 device vector [residual norm, iterations] — the packed
    per-solve telemetry the obs layer consumes (shared by the uniform
    and AMR solver front-ends)."""
    return jnp.stack([jnp.asarray(rnorm, jnp.float32),
                      jnp.asarray(k, jnp.float32)])


def _build_iterative_solver_dense(
    grid: UniformGrid,
    tol_abs: float = 1e-6,
    tol_rel: float = 1e-4,
    maxiter: int = 1000,
    precond_bs: int = 8,
    precond_iters: int = 24,
    mean_constraint: int = 2,
) -> Callable:
    """Dense-layout fallback (grids not divisible by the tile size)."""
    A0 = make_laplacian(grid)
    M = make_block_cg_preconditioner(precond_bs, precond_iters, h=grid.h)
    h3 = grid.h ** 3
    # pin-row rescale: same conditioning fix as the lanes path above
    pin = 6.0 / (grid.h * grid.h)
    if mean_constraint == 1:
        A = lambda x: A0(x).at[0, 0, 0].set(jnp.sum(x) * h3 * pin)
    elif mean_constraint == 3:
        A = lambda x: A0(x).at[0, 0, 0].set(x[0, 0, 0] * pin)
    else:
        A = A0

    @jax.named_scope("PoissonSolve")
    def solve(rhs: jnp.ndarray, x0: Optional[jnp.ndarray] = None,
              with_stats: bool = False):
        b = rhs - jnp.mean(rhs) if mean_constraint == 2 else rhs
        if mean_constraint in (1, 3):
            b = b.at[0, 0, 0].set(0.0)
        x, rnorm, k = bicgstab(
            A, b, M=M, x0=x0, tol_abs=tol_abs, tol_rel=tol_rel,
            maxiter=maxiter, rnorm_ref=jnp.sqrt(_dot(b, b)),
        )
        x = x - jnp.mean(x) if mean_constraint == 2 else x
        if with_stats:
            return x, solver_stats(rnorm, k)
        return x

    solve.supports_stats = True
    solve.maxiter = maxiter
    solve.entry = "composed"
    return solve
