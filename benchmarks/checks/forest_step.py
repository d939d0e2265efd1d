"""Check of a cell whose timed entry takes one step per call on a grid
that adapts: one more step through ``simulate()`` after the window,
captured on both sides, on a step at which the driver runs no adaptation
pass, so that both captures lie on one list of leaves.  Which steps
adapt is the traffic file's statement (``check.adapts``: every step below
``below`` and every ``every``-th); its step counts put the checked step
elsewhere, and where they do not, or the leaves changed all the same, the
run is refused: nothing is retaken.

The step's dt is the one the harness's span saw handed to ``advance``.
Its frame velocity is minus the mean velocity of the bodies that fix the
frame, as they stood before the step: upstream refreshes it from the
bodies before it advects."""

import numpy as np

from benchmarks.lib import drive


def links(driver, grid, traffic, config, spans, seed):
    adapts = traffic["check"]["adapts"]
    step = int(driver.sim.step)
    if step < int(adapts["below"]) or step % int(adapts["every"]) == 0:
        raise SystemExit(
            f"benchmark: the checked step {step} runs an adaptation pass; "
            f"the traffic file's step counts have to put it elsewhere")
    pre = drive.capture(driver, grid, config)
    spans.last_dt = None
    drive.run_steps(driver, 1)
    drive.sync(driver)
    post = drive.capture(driver, grid, config)
    if spans.last_dt is None:
        raise SystemExit("benchmark: the checked step did not go through "
                         "advance(dt)")
    if not np.array_equal(pre["leaves"], post["leaves"]):
        raise SystemExit("benchmark: the leaves changed in the checked "
                         "step")
    post["dt"] = spans.last_dt
    fixing = [b["trans"] for b in pre["bodies"] if b["fixes_frame"]]
    if fixing:
        post["uinf"] = -np.mean(fixing, axis=0)
    return [(pre, post)], {}
