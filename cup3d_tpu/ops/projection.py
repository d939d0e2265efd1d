"""Pressure projection: the reference's ``PressureProjection`` operator
(main.cpp:15061-15160) on the uniform dense grid.

rhs = (div u - chi * div u_def) / dt            (KernelPressureRHS semantics)
solve lap p = rhs
u  -= dt * grad p                                (KernelGradP semantics)

The obstacle term subtracts the deformation-velocity divergence inside the
body so that the penalized region does not source pressure.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from cup3d_tpu.grid.uniform import UniformGrid
from cup3d_tpu.ops import stencils as st


@jax.named_scope("PoissonRHS")
def pressure_rhs(grid: UniformGrid, u: jnp.ndarray, dt,
                 chi: Optional[jnp.ndarray] = None,
                 udef: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    w = 1
    div_u = st.divergence(grid.pad_vector(u, w), w, grid.h)
    if chi is not None and udef is not None:
        div_udef = st.divergence(grid.pad_vector(udef, w), w, grid.h)
        div_u = div_u - chi * div_udef
    return div_u / dt


@jax.named_scope("PressureProjection")
def project(grid: UniformGrid, u: jnp.ndarray, dt, solver: Callable,
            chi: Optional[jnp.ndarray] = None,
            udef: Optional[jnp.ndarray] = None,
            p_init: Optional[jnp.ndarray] = None,
            with_stats: bool = False):
    """Returns (projected velocity, pressure).  ``p_init`` warm-starts an
    iterative solver from the previous step's pressure (ignored by the
    exact spectral solver).

    ``with_stats`` (solvers advertising ``supports_stats``, i.e. the
    iterative front-ends) additionally returns the (2,) [residual,
    iterations] device vector — packed telemetry for the obs layer, no
    host sync here."""
    rhs = pressure_rhs(grid, u, dt, chi, udef)
    if with_stats and getattr(solver, "supports_stats", False):
        p, stats = solver(rhs, p_init, with_stats=True)
    else:
        p = solver(rhs, p_init)
        stats = None
    with jax.named_scope("Gradient"):
        gradp = st.grad(grid.pad_scalar(p, 1), 1, grid.h)
    if with_stats:
        return u - dt * gradp, p, stats
    return u - dt * gradp, p
