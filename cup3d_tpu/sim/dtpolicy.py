"""Timestep policy shared by the uniform and AMR drivers.

Matches the reference's calcMaxTimestep (main.cpp:15268-15292) exactly:

  dtDiffusion = (implicitDiffusion && step > 10) ? 0.1
              : (1/6) h^2 / (nu + (1/6) h uMax)
  dtAdvection = h / (uMax + 1e-8)
  CFL_eff     = exp(log(1e-3)(1-x) + log(CFL) x),  x = step/rampup  (ramp)
  dt          = min(dtDiffusion, CFL_eff * dtAdvection)

The diffusive cap is NOT the pure-diffusion von-Neumann limit: the
(1/6) h uMax term in the denominator is the upwind-3 advective
dissipation, so the cap is the COMBINED advection-diffusion stability
boundary of the explicit RK3/upwind3 update.  This is what the round-4
0.25 h^2/nu cap missed — at 256^3 with the sharp Towers chi the
combined limit binds BELOW the advective CFL dt, the explicit update
is linearly unstable at the chi interface, and the run blows up
(BENCH_r04 fish256 max|u|=2.1e5).  With this cap the same config is
stable (VALIDATION.md round 5).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["ramped_cfl", "diffusion_cap", "dt_host", "dt_device",
           "dt_device_implicit", "dt_scan"]


def ramped_cfl(cfl: float, step: int, rampup: int) -> float:
    """Log-space CFL ramp from an absolute 1e-3 (main.cpp:15275-15279)."""
    if rampup > 0 and step < rampup:
        x = step / rampup
        return math.exp(math.log(1e-3) * (1.0 - x) + math.log(cfl) * x)
    return cfl


def diffusion_cap(h: float, nu: float, umax: float,
                  implicit: bool, step: int) -> float:
    """Combined advection-diffusion stability cap (main.cpp:15269-15273)."""
    if implicit and step > 10:
        return 0.1
    return (h * h / 6.0) / (nu + (h / 6.0) * umax)


def dt_host(h: float, nu: float, umax: float, cfl: float, step: int,
            rampup: int, implicit: bool) -> float:
    """Full host-side dt = min(dtDiffusion, CFL_eff * dtAdvection)."""
    cfl_eff = ramped_cfl(cfl, step, rampup)
    dt_adv = h / (umax + 1e-8)
    return float(min(diffusion_cap(h, nu, umax, implicit, step),
                     cfl_eff * dt_adv))


@jax.named_scope("DtPolicy")
def dt_scan(cfl_eff, h: float, nu: float, umax, dtprev):
    """The scan bodies' dt, traced: from the CARRIED umax (one step
    stale, like the host chain's freshly consumed pack), capped by the
    combined bound above and by the 1.03x growth limiter against the
    previous step's dt."""
    cap = (h * h / 6.0) / (nu + (h / 6.0) * umax)
    dt = jnp.minimum(cfl_eff * h / (umax + 1e-8), cap)
    return jnp.where(dtprev > 0, jnp.minimum(dt, 1.03 * dtprev), dt)


@jax.jit
@jax.named_scope("DtPolicy")
def dt_device(umax, cfl_eff, hmin, nu):
    """Device-resident dt (explicit diffusion): same formula, umax stays
    on device so the pipelined driver never blocks on it."""
    cap = (hmin * hmin / 6.0) / (nu + (hmin / 6.0) * umax)
    return jnp.minimum(cfl_eff * hmin / (umax + 1e-8), cap)


@jax.jit
@jax.named_scope("DtPolicy")
def dt_device_implicit(umax, cfl_eff, hmin, nu, past_warmup):
    """Device-resident dt, implicit diffusion: absolute 0.1 cap once
    step > 10 (main.cpp:15270-15271), combined cap before that."""
    cap = jnp.where(
        past_warmup,
        jnp.asarray(0.1, umax.dtype),
        (hmin * hmin / 6.0) / (nu + (hmin / 6.0) * umax),
    )
    return jnp.minimum(cfl_eff * hmin / (umax + 1e-8), cap)
