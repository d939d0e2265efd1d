"""Check of a cell whose timed entry takes one step per call: one more
step through ``simulate()`` after the window, captured on both sides.
The step's dt is the one the harness's span saw handed to ``advance``;
its frame velocity is the one the driver holds after the step (upstream
refreshes it from the body before it advects)."""

from benchmarks.lib import drive


def links(driver, grid, traffic, config, spans, seed):
    pre = drive.capture(driver, grid, config)
    spans.last_dt = None
    drive.run_steps(driver, 1)
    drive.sync(driver)
    post = drive.capture(driver, grid, config)
    if spans.last_dt is None:
        raise SystemExit("benchmark: the checked step did not go through "
                         "advance(dt)")
    post["dt"] = spans.last_dt
    return [(pre, post)], {}
