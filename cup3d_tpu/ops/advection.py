"""Explicit advection-diffusion: the reference's ``AdvectionDiffusion``
operator (main.cpp:9461-9728) rebuilt as fused dense stencils.

RHS(u) = -((u + uinf) . grad) u + nu lap(u), with the reference's 5th-order
6-point biased-upwind advective derivatives and a 2nd-order 7-point viscous
Laplacian, advanced by low-storage RK3 (main.cpp:9640-9728).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cup3d_tpu.grid.uniform import UniformGrid
from cup3d_tpu.ops import stencils as st

GHOSTS = 3  # 5th-order upwind needs 3 ghost cells

# Low-storage RK3 (Williamson) — same scheme as the reference's
# coefficients {1/3, 15/16, 8/15} / {0, -5/9, -153/128}.
RK3_A = (0.0, -5.0 / 9.0, -153.0 / 128.0)
RK3_B = (1.0 / 3.0, 15.0 / 16.0, 8.0 / 15.0)


def advection_diffusion_rhs(grid: UniformGrid, u: jnp.ndarray, nu: float,
                            uinf: jnp.ndarray, pad=None) -> jnp.ndarray:
    """du/dt from advection + diffusion on the uniform grid.

    u: (nx, ny, nz, 3) velocity in the body/lab frame.
    uinf: (3,) frame velocity added to the advecting field only.
    pad: optional ``(u, width) -> padded`` ghost supplier replacing
    ``grid.pad_vector`` — the x-slab decomposition injects the
    ring-halo pad (parallel/ring.pad_slab_vector) here so the stencil
    body itself stays layout-agnostic.
    """
    h = grid.h
    up = grid.pad_vector(u, GHOSTS) if pad is None else pad(u, GHOSTS)
    uadv = [u[..., c] + uinf[c] for c in range(3)]
    out = []
    for c in range(3):
        comp = up[..., c]
        adv = sum(
            uadv[a] * st.d1_upwind5(comp, GHOSTS, a, uadv[a], h) for a in range(3)
        )
        dif = st.laplacian(comp, GHOSTS, h) * nu
        out.append(dif - adv)
    return jnp.stack(out, axis=-1)


@jax.named_scope("AdvectionDiffusion")
def rk3_step(grid: UniformGrid, u: jnp.ndarray, dt, nu: float,
             uinf: jnp.ndarray, pad=None) -> jnp.ndarray:
    """One explicit low-storage RK3 advection-diffusion step."""
    k = jnp.zeros_like(u)
    for a, b in zip(RK3_A, RK3_B):
        k = a * k + dt * advection_diffusion_rhs(grid, u, nu, uinf,
                                                 pad=pad)
        u = u + b * k
    return u
