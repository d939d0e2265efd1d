"""Brinkman penalization (reference Penalization kernel,
main.cpp:13841-13912).

Implicit form: u^{n+1} = u + (lambda chi dt / (1 + lambda chi dt)) (u_body - u),
where u_body = u_trans + omega x r + u_def is the obstacle's local solid-body
+ deformation velocity.  Operating on the dense chi/ubody fields makes this a
single fused elementwise kernel over the whole domain.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("Penalization")
def penalize(vel: jnp.ndarray, chi: jnp.ndarray, ubody: jnp.ndarray,
             lam, dt) -> jnp.ndarray:
    """vel, ubody: (...,3); chi in [0,1]; lam, dt scalars."""
    x = lam * dt * chi
    fac = (x / (1.0 + x))[..., None]
    return vel + fac * (ubody - vel)


def penalization_force(vel_new: jnp.ndarray, vel_old: jnp.ndarray, dt,
                       h: float) -> jnp.ndarray:
    """Instantaneous penalization force density integrand
    F = (u^{n+1} - u^n)/dt * h^3 (reference force reduction, main.cpp:13913-13938)."""
    return (vel_new - vel_old) * (h ** 3 / dt)


@jax.named_scope("Penalization")
def per_obstacle_penalization_force(
    vel_new: jnp.ndarray,
    vel_old: jnp.ndarray,
    chis,
    dt,
    vol: jnp.ndarray,
    xc: jnp.ndarray,
    cms: jnp.ndarray,
    eps: float = 1e-6,
) -> jnp.ndarray:
    """Per-obstacle momentum-balance force/torque from the penalization
    update (the reference's kernelFinalizePenalizationForce,
    main.cpp:13913-13938: obst->force/torque come from the per-cell
    (u^{n+1}-u^n)/dt sums inside each obstacle's blocks).

    chis: tuple of per-obstacle chi fields; overlap cells are attributed
    by chi fraction.  vol broadcasts per cell ((nb,1,1,1) or scalar h^3).
    Returns a stacked (n_obs, 6) array [force(3), torque(3)] — one host
    read for all obstacles."""
    df = (vel_new - vel_old) / dt  # force density / cell volume
    chi_sum = sum(chis)
    den = jnp.maximum(chi_sum, eps)
    out = []
    for i, chi in enumerate(chis):
        w = chi / den  # overlap-fractional weight
        wv = (w * vol)[..., None]
        f = jnp.sum(df * wv, axis=tuple(range(df.ndim - 1)))
        r = xc - cms[i]
        t = jnp.sum(jnp.cross(r, df) * wv, axis=tuple(range(df.ndim - 1)))
        out.append(jnp.concatenate([f, t]))
    return jnp.stack(out)
