"""Signed-distance -> characteristic function chi and surface measure.

The reference converts each obstacle's SDF into a mollified Heaviside chi and
extracts surface points with gradients and delta weights
(KernelCharacteristicFunction, main.cpp:13291-13404, Towers-style).  The TPU
formulation works on dense fields: chi is a C^1 smoothed Heaviside of the SDF
over a 2h mollification band, and the surface delta is |grad chi| — every
surface integral becomes a fused masked reduction instead of ragged
per-block point lists.

Convention: sdf > 0 inside the body (matching the reference's rasterizer).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cup3d_tpu.grid.uniform import UniformGrid
from cup3d_tpu.ops import stencils as st


def heaviside(sdf: jnp.ndarray, h: float) -> jnp.ndarray:
    """C^1 mollified Heaviside over the band |sdf| <= 2h:
    chi = (1 + t + sin(pi t)/pi) / 2 with t = clip(sdf/2h, -1, 1).

    Fallback used where no SDF neighbor values are available (the
    sharded-forest create path); the production chi is towers_chi below
    — its band is half as wide (+-1h), which measurably shrinks the
    effective body radius bias in drag (VALIDATION.md)."""
    t = jnp.clip(sdf / (2.0 * h), -1.0, 1.0)
    return 0.5 * (1.0 + t + jnp.sin(jnp.pi * t) / jnp.pi)


@jax.named_scope("CreateObstacles")
def towers_chi(sdf_lab: jnp.ndarray, h) -> jnp.ndarray:
    """The reference's discrete Heaviside (Towers construction;
    KernelCharacteristicFunction, main.cpp:13312-13346): outside the
    +-1h band chi is the sharp indicator; inside it

        chi = (grad I+ . grad phi) / |grad phi|^2,   I+ = max(0, phi)

    with centered differences.  ``sdf_lab``: a 1-ghost halo'd SDF lab
    (..., n+2, n+2, n+2), phi > 0 inside; ``h`` broadcastable to the
    interior.  Undivided differences — the scaling cancels in the ratio.
    """
    c = sdf_lab[..., 1:-1, 1:-1, 1:-1]
    gU2 = 0.0
    num = 0.0
    for a in range(3):
        lo = [slice(1, -1)] * 3
        hi = [slice(1, -1)] * 3
        lo[a] = slice(0, -2)
        hi[a] = slice(2, None)
        p = sdf_lab[(Ellipsis,) + tuple(hi)]
        m = sdf_lab[(Ellipsis,) + tuple(lo)]
        gU = p - m
        gI = jnp.maximum(p, 0.0) - jnp.maximum(m, 0.0)
        gU2 = gU2 + gU * gU
        num = num + gI * gU
    band = num / (gU2 + 1e-30)
    return jnp.where(c > h, 1.0, jnp.where(c < -h, 0.0, band))


def surface_delta(grid: UniformGrid, chi: jnp.ndarray) -> jnp.ndarray:
    """|grad chi| — the surface delta-function weight per cell.

    grad chi points INTO the body (chi rises inward), i.e. -n_hat * delta
    with n_hat the outward normal.
    """
    g = st.grad(grid.pad_scalar(chi, 1), 1, grid.h)
    return jnp.sqrt(jnp.sum(g * g, axis=-1))


def grad_chi(grid: UniformGrid, chi: jnp.ndarray) -> jnp.ndarray:
    return st.grad(grid.pad_scalar(chi, 1), 1, grid.h)
