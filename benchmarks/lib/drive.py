"""Drives the system under test: builds the driver the CLI would build,
puts the harness's spans around the calls into it, warms it up, runs the
measured window through ``simulate()`` and captures states for the
comparison.  What depends on the kind of grid is the adapter's
(``benchmarks/grids/<driver.kind>.py``, which ``run.py`` loads once and
hands down as ``grid``).

Everything the harness takes from the program is named here, and a name
that is gone raises (``need``): nothing falls back in silence.

- ``cup3d_tpu.__main__.build_driver(argv)``; on the driver ``cfg.nsteps``,
  ``init()``, ``simulate()`` and the methods a traffic file lists under
  ``spans`` (``calc_max_timestep``, ``advance``, ``advance_megaloop``,
  ``flush_packs``);
- ``driver.sim``: ``state`` (vel, p, chi, udef), ``grid``, ``obstacles``
  (``centerOfMass``, ``transVel``, ``angVel``, ``chi``, ``udef``),
  ``time``, ``dt``, ``step``, ``uinf``, ``profiler.totals``;
- of a uniform grid (``grids/uniform.py``): ``shape``, ``h``,
  ``cell_centers``;
- of a forest (``grids/forest.py``): ``grid.keys`` (one ``(level, i, j,
  k)`` per leaf, in the order of the fields' rows), ``grid.nb``,
  ``grid.bs``; the fields' rows past ``nb`` are the bucket's padding and
  are dropped.  For the solve probe: ``sim._solver`` (called as
  ``solver(rhs, x0, tab_arg=, flux_arg=, with_stats=True)``),
  ``sim._geom``, ``sim._tab1``, ``sim._ftab`` (the geometry and the
  width-1 halo and flux tables the driver's projection is bound to), and
  the program's own forest operators
  ``cup3d_tpu.ops.amr_ops.pressure_rhs_blocks`` and ``grad_blocks``;
- of a driver on the scan megaloop, ``_megaloop`` (the jitted scan and its
  row width) and ``_scan_carry`` (vel, p, chi, udef, rigid, dt, time; rigid
  laid out as ``models.base.RIGID_STATE``): ``checks/scan_chain.py``;
- the solver a configuration names under ``driver.solver``, called as
  ``solver(rhs, x0, with_stats=True)``: ``probe.py``;
- ``cup3d_tpu.obs.metrics`` (``snapshot``, ``delta``; the counters
  ``amr.regrids``, ``amr.regrid_noops``, ``poisson.iters_hist``,
  ``stream.stall_s``, ``resilience.rollbacks``) and
  ``cup3d_tpu.native.available``: ``run.py`` and the readers."""

from __future__ import annotations

import contextlib
import functools
import time

import numpy as np


def need(obj, name: str):
    """``obj.name``, or a refusal that says what the harness counted on."""
    try:
        return obj[name] if isinstance(obj, dict) else getattr(obj, name)
    except (AttributeError, KeyError):
        raise SystemExit(
            f"benchmark: the program no longer has {name!r} on "
            f"{type(obj).__name__} (benchmarks/lib/drive.py lists what the "
            f"harness takes from it)") from None


class Spans:
    """Host spans around the driver's methods, kept in memory."""

    def __init__(self):
        self.rows = []  # (name, t0, t1, steps, cells)
        self.annotate = False
        self.last_dt = None


def wrap_spans(driver, names, spans: Spans, cells):
    """Replace each named method of ``driver`` by a timed twin.  In a
    traced run the span is also written into the profiler's trace.
    ``cells`` counts the cells of the driver's grid (the adapter's)."""
    import jax

    def make(name, fn):
        def timed(*args, **kwargs):
            d = driver.sim
            step0 = d.step
            if name == "advance" and args:
                spans.last_dt = float(args[0])
            t0 = time.perf_counter()
            try:
                with (jax.profiler.TraceAnnotation("bench:" + name)
                      if spans.annotate else contextlib.nullcontext()):
                    return fn(*args, **kwargs)
            finally:
                t1 = time.perf_counter()
                steps = d.step - step0
                # an adaptation pass runs inside the call: the step was
                # taken on the grid the call leaves
                n_cells = cells(need(d, "grid"))
                spans.rows.append((name, t0, t1, steps, n_cells * steps))
        return timed

    for name in names:
        setattr(driver, name, make(name, need(driver, name)))


def run_steps(driver, steps: int) -> None:
    """Raise the step budget and let ``simulate()`` spend it."""
    driver.cfg.nsteps = int(driver.sim.step) + int(steps)
    driver.simulate()


def sync(driver) -> None:
    import jax

    jax.block_until_ready(driver.sim.state["vel"])


def window(driver, seconds: float, chunk_steps: int, spans: Spans,
           step_span: str) -> dict:
    """The measured window: whole chunks through ``simulate()`` until
    ``seconds`` are spent, closed on ``block_until_ready``.  Everything
    the window did is counted: no step is trimmed.  ``step_span`` names
    the driver method every step of this cell has to go through."""
    first = len(spans.rows)
    step0 = int(driver.sim.step)
    error = None
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        try:
            run_steps(driver, chunk_steps)
        except Exception as e:  # boundary: the run reports the failure
            error = repr(e)
            break
    sync(driver)
    t1 = time.perf_counter()
    rows = spans.rows[first:]
    return {"wall_s": t1 - t0,
            "steps": int(driver.sim.step) - step0,
            "cells": sum(r[4] for r in rows if r[0] == step_span),
            "steps_through_span": sum(r[3] for r in rows
                                      if r[0] == step_span),
            "rows": rows, "error": error}


def body_shapes(config: dict):
    """What the configuration says of each body's shape."""
    return [need(b, "shape") for b in config["bodies"]]


def fluid_state(driver, grid, config: dict) -> dict:
    """Host copy of the velocity and chi alone (what the divergence
    guarantee reads on the state the window opens on)."""
    state = need(driver.sim, "state")
    return {**grid.geometry(driver, config),
            "vel": grid.host(driver, need(state, "vel")),
            "chi": grid.host(driver, need(state, "chi"))}


def capture(driver, grid, config: dict) -> dict:
    """Host copy of what the comparison needs, from the driver's own
    state and the host mirrors of its bodies (current on a driver that
    reads its packs every step)."""
    host = functools.partial(grid.host, driver)
    d = driver.sim
    state = need(d, "state")
    obstacles = need(d, "obstacles")
    bodies = []
    for ob, shape in zip(obstacles, body_shapes(config)):
        # one body: the combined fields ARE its fields
        chi, udef = ((state["chi"], state["udef"]) if len(obstacles) == 1
                     else (need(ob, "chi"), need(ob, "udef")))
        bodies.append({
            **shape, "chi": host(chi), "udef": host(udef),
            **{k: np.array(need(ob, a), np.float64) for k, a in
               (("cm", "centerOfMass"), ("trans", "transVel"),
                ("ang", "angVel"))}})
    return {
        **grid.geometry(driver, config),
        **{k: host(need(state, k)) for k in ("vel", "p", "chi", "udef")},
        "time": float(d.time), "dt": float(d.dt), "step": int(d.step),
        "uinf": np.array(need(d, "uinf"), np.float64), "bodies": bodies,
    }
