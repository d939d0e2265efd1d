"""The solve probe of a traced run: the driver's own Poisson solver,
jitted under a name the trace reduction can find and run twice (the
first time to compile) on the live pressure system of the step the
harness has just driven: the right-hand side ``(div u_pen - chi div
u_def) / dt`` with the penalised velocity recovered as ``u + dt grad p``
(built by the configuration's grid adapter, ``live_system``), from the
pressure the driver held before that unit as the initial guess (in a
K-step scan that guess is K steps old, the program's own one step old:
more iterations, the same time for each).  Its device time per run over
its iterations is the time of one BiCGSTAB iteration; the solve's fixed
start-up cost is in it, so the roofline share built on it errs low,
never high."""

from __future__ import annotations

import functools

from . import drive

MODULE = "bench_solve_probe"


def _attr(obj, dotted):
    return functools.reduce(drive.need, dotted.split("."), obj)


def run(driver, grid, spec: dict, p_before) -> dict:
    """``spec`` is the configuration's ``driver`` entry: ``solver`` names
    the solver on the driver; ``grid`` is the adapter of its kind of
    grid."""
    import jax
    import numpy as np

    solver = _attr(driver, spec["solver"])
    rhs, x0, kwargs = grid.live_system(driver, p_before)

    # the solver's further arguments (a forest's tables) are traced
    # arguments of the probe, as they are of the driver's own step
    def bench_solve_probe(rhs, x0, kwargs):
        return solver(rhs, x0, with_stats=True, **kwargs)

    fn = jax.jit(bench_solve_probe)
    for _ in range(2):
        out, stats = fn(rhs, x0, kwargs)
        jax.block_until_ready(out)
    stats = np.asarray(stats, np.float64)
    return {"residual": float(stats[0]), "iterations": float(stats[1])}
