"""Diagnostic kernels: vorticity, Q-criterion, divergence, dissipation,
max-velocity — the reference's diagnostics operators (ComputeVorticity
main.cpp:8624-8745, ComputeQcriterion 8746-8788, ComputeDivergence
8789-8919, KernelDissipation 10347-10435, findMaxU 8603-8623) as fused
dense reductions.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from cup3d_tpu.grid.uniform import UniformGrid
from cup3d_tpu.ops import stencils as st


def vorticity(grid: UniformGrid, u: jnp.ndarray) -> jnp.ndarray:
    return st.curl(grid.pad_vector(u, 1), 1, grid.h)


def q_criterion(grid: UniformGrid, u: jnp.ndarray) -> jnp.ndarray:
    """Q = 0.5 (|Omega|^2 - |S|^2), positive in vortex cores."""
    up = grid.pad_vector(u, 1)
    h = grid.h
    g = [[st.d1_central(up[..., c], 1, a, h) for a in range(3)] for c in range(3)]
    omega2 = jnp.zeros_like(g[0][0])
    s2 = jnp.zeros_like(g[0][0])
    for c in range(3):
        for a in range(3):
            s = 0.5 * (g[c][a] + g[a][c])
            o = 0.5 * (g[c][a] - g[a][c])
            s2 = s2 + s * s
            omega2 = omega2 + o * o
    return 0.5 * (omega2 - s2)


def divergence_field(grid: UniformGrid, u: jnp.ndarray) -> jnp.ndarray:
    return st.divergence(grid.pad_vector(u, 1), 1, grid.h)


def divergence_norms(grid: UniformGrid, u: jnp.ndarray):
    """(sum |div u| h^3, max |div u|) — the reference appends the former to
    div.txt every call (main.cpp:8911-8917)."""
    d = divergence_field(grid, u)
    vol = grid.h ** 3
    return jnp.sum(jnp.abs(d)) * vol, jnp.max(jnp.abs(d))


def fluid_divergence_max(grid: UniformGrid, u: jnp.ndarray,
                         chi: jnp.ndarray, halo: int = 3) -> jnp.ndarray:
    """max |div u| over cells at least ``halo`` cells away from the
    mollified chi band.  Inside the band the Brinkman forcing is a
    momentum source, so the projected field is legitimately not
    divergence-free there (the reference behaves the same); this is the
    meaningful incompressibility gate for flows with immersed bodies.

    "Away" is Chebyshev distance: the mask is dilated per axis in sequence
    (box dilation), wrapping only across periodic boundaries."""
    from cup3d_tpu.grid.uniform import BC

    def shift(m, sh, ax):
        if grid.bc[ax] == BC.periodic:
            return jnp.roll(m, sh, axis=ax)
        z = jnp.zeros_like(m)
        if sh > 0:
            src = jax.lax.slice_in_dim(m, 0, m.shape[ax] - sh, axis=ax)
            return jax.lax.dynamic_update_slice_in_dim(z, src, sh, axis=ax)
        src = jax.lax.slice_in_dim(m, -sh, m.shape[ax], axis=ax)
        return jax.lax.dynamic_update_slice_in_dim(z, src, 0, axis=ax)

    grow = chi > 1e-6
    for ax in range(3):  # sequential per-axis dilation = full box dilation
        g = grow
        for sh in range(1, halo + 1):
            g = g | shift(grow, sh, ax) | shift(grow, -sh, ax)
        grow = g
    d = divergence_field(grid, u)
    return jnp.max(jnp.abs(jnp.where(grow, 0.0, d)))


def fluid_divergence_max_blocks(grid, vel, chi, tab):
    """Block-forest twin of fluid_divergence_max: max |div u| over blocks
    whose chi halo'd lab vanishes everywhere — block granularity plus the
    ghost halo gives at least a stencil-width separation from the band."""
    from cup3d_tpu.ops import amr_ops

    vlab = tab.assemble_vector(vel, grid.bs)
    d = amr_ops.div_blocks(grid, vlab, tab.width)
    clab = tab.assemble_scalar(chi, grid.bs)
    fluid = jnp.max(clab.reshape(grid.nb, -1), axis=1) < 1e-6
    return jnp.max(
        jnp.where(fluid[:, None, None, None], jnp.abs(d), 0.0)
    )


@jax.named_scope("DtPolicy")
def max_velocity(u: jnp.ndarray, uinf: jnp.ndarray) -> jnp.ndarray:
    """max over cells of max-norm of lab-frame velocity (findMaxU)."""
    return jnp.max(jnp.abs(u + uinf))


def dissipation(grid: UniformGrid, u: jnp.ndarray, nu: float) -> Dict[str, jnp.ndarray]:
    """Energy-budget integrals (KernelDissipation semantics):

    kinetic energy  0.5 |u|^2, enstrophy 0.5 |omega|^2, viscous dissipation
    rate 2 nu S:S — each integrated over the domain with cell volume h^3.
    """
    up = grid.pad_vector(u, 1)
    h = grid.h
    g = [[st.d1_central(up[..., c], 1, a, h) for a in range(3)] for c in range(3)]
    ss = jnp.zeros_like(g[0][0])
    for c in range(3):
        for a in range(3):
            s = 0.5 * (g[c][a] + g[a][c])
            ss = ss + s * s
    w = st.curl(up, 1, h)
    vol = h ** 3
    return {
        "kinetic_energy": 0.5 * jnp.sum(jnp.sum(u * u, axis=-1)) * vol,
        "enstrophy": 0.5 * jnp.sum(jnp.sum(w * w, axis=-1)) * vol,
        "dissipation_rate": 2.0 * nu * jnp.sum(ss) * vol,
    }


def swim_split(traction, vol, udef, vel_unit):
    """thrust/drag/def_power from a per-cell traction band (reference
    per-surface-point split, main.cpp:12476-12485): forcePar is the
    traction component along the swimming direction; thrust sums its
    positive part, drag its negative part, def_power is traction . u_def.
    Layout-agnostic (dense uniform or block batch); vol broadcasts."""
    if vel_unit is None:
        z = jnp.zeros((), traction.dtype)
        return {"thrust": z, "drag": z, "def_power": z}
    force_par = jnp.einsum("...c,c->...", traction, vel_unit)
    thrust = jnp.sum(jnp.maximum(force_par, 0.0) * vol)
    drag = -jnp.sum(jnp.minimum(force_par, 0.0) * vol)
    if udef is None:
        def_power = jnp.zeros((), traction.dtype)
    else:
        def_power = jnp.sum(jnp.sum(traction * udef, axis=-1) * vol)
    return {"thrust": thrust, "drag": drag, "def_power": def_power}
