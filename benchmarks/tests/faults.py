"""Faults planted under the timed path, for ``test_faults.py``: the
driver ``build_driver`` returns gets a step that is broken from a given
step on, in one of the ways a later change could break it.  And faults of
one kind of grid, planted in the reference that is put in the program's
place (``GRID_FAULTS``, ``faulty``): ``test_control.py``,
``seed_sweep.py``."""

import contextlib

from benchmarks.lib import reference_forest as rf

#: by kind of grid, the faults that only a reference of that kind can
#: see.  A forest's lie at its coarse-fine faces; each is a composite grid
#: built with the fault (and a solve loose enough to end on it)
GRID_FAULTS = {
    "forest": {
        # cells under a coarser leaf copied from it, not interpolated
        "ghost_inject": {"prolong": rf.prolong_inject,
                         "solve": (1e-6, 100, False)},
        # the coarse side of coarse-fine faces left uncorrected
        "no_reflux": {"reflux": False, "solve": (1e-6, 100, False)},
    },
}


def faulty(grid, kind: str, geom: dict, fault: str):
    """The adapter's reference on ``geom`` with ``fault`` planted."""
    return grid.Reference(geom, rf.Forest(
        geom["leaves"], geom["blocks0"], geom["bs"], geom["h0"],
        **GRID_FAULTS[kind][fault]))


def break_driver(driver, fault: str, from_step: int):
    import jax.numpy as jnp

    d = driver.sim

    def bump(steps, dt):
        if hasattr(driver, "step_idx"):
            driver.step_idx += steps
        else:
            d.step += steps
        d.time += dt

    def broken(orig, steps_of):
        def call(*args):
            if d.step < from_step:
                return orig(*args)
            if fault == "unchanged":  # the step hands its state back
                bump(steps_of(), args[0] if args else d.dt)
                return None
            before = jnp.copy(d.state["vel"])
            out = orig(*args)
            vel = d.state["vel"]
            if fault == "half":  # half of the domain is left out
                half = vel.shape[0] // 2
                vel = vel.at[:half].set(before[:half])
            elif fault == "altered":  # an answer altered where it is made
                vel = vel * (1.0 + 1e-3)
            else:
                raise ValueError(fault)
            d.state["vel"] = vel
            if getattr(driver, "_scan_carry", None) is not None:
                driver._scan_carry["vel"] = vel
            return out
        return call

    driver.advance = broken(driver.advance, lambda: 1)
    if hasattr(driver, "advance_megaloop"):
        driver.advance_megaloop = broken(driver.advance_megaloop,
                                         lambda: driver._scan_k)
    return driver


@contextlib.contextmanager
def planted(fault: str, from_step: int):
    import cup3d_tpu.__main__ as entry

    real = entry.build_driver
    entry.build_driver = lambda argv: break_driver(real(argv), fault,
                                                   from_step)
    try:
        yield
    finally:
        entry.build_driver = real
