"""Exact 8^3-tile Poisson/Helmholtz solve by fast diagonalization — the
round-4 getZ preconditioner.

The reference's getZ preconditioner (poisson_kernels, main.cpp:14617-14746)
approximately solves (-lap_tile + shift) z = b on every 8^3 block with the
tile's implicit zero-Dirichlet halo, via CG iterated to a tolerance.  Round
2/3 ran a fixed-24-sweep CG in a Pallas VMEM kernel (ops/getz_pallas.py),
~0.96 ms per application at 128^3 on a v5e — all VPU work.

TPU-first observation: the zero-Dirichlet 7-point Laplacian on a fixed 8^3
tile is diagonalized by the 8-point discrete sine transform (DST-I), so the
EXACT tile inverse is the fixed 512x512 matrix

    W = S3 diag(1/lam) S3^T,   S3 = S (x) S (x) S,
    S[k,i] = sqrt(2/9) sin(pi (i+1)(k+1)/9),
    lam[i,j,k] = 4 [sin^2(pi(i+1)/18) + sin^2(pi(j+1)/18) + sin^2(pi(k+1)/18)]

and one application is ONE (512,512)@(512,T) matmul — MXU work in any
layout, ~7x the Pallas CG kernel at 128^3 and exact (= infinitely many CG
sweeps, so the outer Krylov solve sees a strictly stronger preconditioner).
The shifted variant (diffusion getZ, coefficient -6 - h^2/(nu dt),
main.cpp:10571) keeps the split form S3 [ (S3^T b) / (lam + shift) ] so a
traced, per-block shift stays a cheap row-wise divide between the two
matmuls.

Matmul precision is HIGHEST (3-pass bf16 ~ f32): measured at 128^3, a
DEFAULT-precision (single-pass bf16) preconditioner makes the outer
BiCGSTAB stagnate (133+ iterations vs 50) — the ~4e-3 rounding noise acts
as a nonlinear perturbation the short recurrence cannot absorb.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


@lru_cache(maxsize=None)
def _basis_np(bs: int, np_dtype: str):
    """(S3, lam512, W) for the bs^3 zero-Dirichlet tile, built in f64.
    Cached as NUMPY arrays — jnp conversion happens at each call site so a
    trace-time first call cannot leak tracers into the cache."""
    i = np.arange(1, bs + 1)
    S1 = np.sqrt(2.0 / (bs + 1)) * np.sin(np.pi * np.outer(i, i) / (bs + 1))
    lam1 = 4.0 * np.sin(np.pi * i / (2 * (bs + 1))) ** 2  # eig of -[1,-2,1]
    lam3 = (lam1[:, None, None] + lam1[None, :, None]
            + lam1[None, None, :]).reshape(bs ** 3)
    S3 = np.einsum("ai,bj,ck->abcijk", S1, S1, S1).reshape(bs ** 3, bs ** 3)
    W = (S3 * (1.0 / lam3)) @ S3.T
    dt = np.dtype(np_dtype)
    return (S3.astype(dt), lam3.astype(dt), W.astype(dt))


def _basis(bs: int, np_dtype: str):
    S3, lam3, W = _basis_np(bs, np_dtype)
    return jnp.asarray(S3), jnp.asarray(lam3), jnp.asarray(W)


def tile_solve_blocks(b: jnp.ndarray, shift=None) -> jnp.ndarray:
    """Solve (-lap_tile + shift) z = b on every trailing-bs^3 tile of ``b``
    (shape (..., bs, bs, bs)), exactly.

    ``shift`` may be None (pure Poisson getZ), a scalar, or an array
    broadcastable over the leading dims (e.g. the per-block h^2/(nu dt) of
    the AMR diffusion getZ) — traced values are fine.
    """
    bs = b.shape[-1]
    lead = b.shape[:-3]
    n = int(np.prod(lead)) if lead else 1
    # basis + matmuls in the ACCUMULATION dtype (>= f32): a bf16 basis
    # degrades the preconditioner enough to stall the outer BiCGSTAB
    # (see module docstring) — sub-f32 inputs are solved in f32 and
    # rounded on the way out (ops/precision.py policy, round 12)
    acc = jnp.promote_types(b.dtype, jnp.float32)
    S3, lam3, W = _basis(bs, jnp.dtype(acc).name)
    b2 = b.reshape(n, bs ** 3).astype(acc)
    # always the split form: measured in-loop on a v5e (round-5 chip
    # run, record removed), ONE (n,512)x(512,512) HIGHEST matmul cost
    # ~320us while the TWO split matmuls cost ~23us total — the
    # single-pass W form is never worth it
    if shift is None:
        sh = jnp.zeros((n, 1), acc)
    else:
        sh = jnp.broadcast_to(jnp.asarray(shift, acc),
                              lead + (1, 1, 1)).reshape(n, 1)
    t = jax.lax.dot(b2, S3, precision=_HI)  # S3 symmetric: rows @ S3
    t = t / (lam3[None, :] + sh)
    z = jax.lax.dot(t, S3, precision=_HI)
    return z.reshape(b.shape).astype(b.dtype)


def tile_solve_lanes(bt: jnp.ndarray, shift=None) -> jnp.ndarray:
    """Same solve in the lane-resident (bs, bs, bs, T) layout the uniform
    Krylov path keeps every field in (krylov.make_laplacian_lanes).

    ``shift``: None, scalar, or a (T,)-broadcastable lane vector.
    """
    bs = bt.shape[0]
    T = bt.shape[-1]
    # accumulate in >= f32 regardless of storage dtype (see
    # tile_solve_blocks / ops/precision.py)
    acc = jnp.promote_types(bt.dtype, jnp.float32)
    S3, lam3, W = _basis(bs, jnp.dtype(acc).name)
    b2 = bt.reshape(bs ** 3, T).astype(acc)
    # split form always — see tile_solve_blocks
    if shift is None:
        sh = jnp.zeros((1, T), acc)
    else:
        sh = jnp.broadcast_to(jnp.asarray(shift, acc), (1, T))
    t = jax.lax.dot(S3, b2, precision=_HI)
    t = t / (lam3[:, None] + sh)
    z = jax.lax.dot(S3, t, precision=_HI)
    return z.reshape(bt.shape).astype(bt.dtype)
