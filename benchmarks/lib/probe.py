"""The solve probe of a traced run: the driver's own Poisson solver,
jitted under a name the trace reduction can find and run twice (the
first time to compile) on the live pressure system of the step the
harness has just driven: the right-hand side ``(div u_pen - chi div
u_def) / dt`` with the penalised velocity recovered as ``u + dt grad p``,
from the pressure the driver held before that unit as the initial guess
(in a K-step scan that guess is K steps old, the program's own one step
old: more iterations, the same time for each).  Its device time per run
over its iterations is the time of one BiCGSTAB iteration; the solve's
fixed start-up cost is in it, so the roofline share built on it errs
low, never high."""

from __future__ import annotations

import functools

from . import drive

MODULE = "bench_solve_probe"


def _attr(obj, dotted):
    return functools.reduce(drive.need, dotted.split("."), obj)


def live_system(driver, p_before):
    """(rhs, x0) of the last step's pressure equation, on the device."""
    import jax.numpy as jnp

    d = driver.sim
    h, dt = float(d.grid.h), float(d.dt)
    vel, p, chi, udef = (drive.need(d.state, k)
                         for k in ("vel", "p", "chi", "udef"))

    def d1(a, axis):
        return (jnp.roll(a, -1, axis) - jnp.roll(a, 1, axis)) / (2.0 * h)

    div = lambda u: sum(d1(u[..., c], c) for c in range(3))
    u_pen = vel + dt * jnp.stack([d1(p, c) for c in range(3)], axis=-1)
    return (div(u_pen) - chi * div(udef)) / dt, p_before


def run(driver, spec: dict, p_before) -> dict:
    """``spec`` is the configuration's ``driver`` entry: ``solver`` names
    the solver on the driver."""
    import jax
    import numpy as np

    solver = _attr(driver, spec["solver"])

    def bench_solve_probe(rhs, x0):
        return solver(rhs, x0, with_stats=True)

    rhs, x0 = live_system(driver, p_before)
    fn = jax.jit(bench_solve_probe)
    for _ in range(2):
        out, stats = fn(rhs, x0)
        jax.block_until_ready(out)
    stats = np.asarray(stats, np.float64)
    return {"residual": float(stats[0]), "iterations": float(stats[1])}
