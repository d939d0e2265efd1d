"""Device time of the pressure solve in one step of the window: the
window's BiCGSTAB iterations (the program's poisson.iters_hist, as
poisson.iters_per_solve reads it) over its steps, times the device time
of one iteration (poisson.iter_device_us's reading: the solve probe of
the traced run).  The share of a step that a faster iteration can move."""

META = {"name": "poisson.device_ms_per_step", "layer": "Poisson solve", "unit": "ms", "moves": "step_ms",
        "source": "device_trace", "better": "lower"}


def read(ctx):
    from benchmarks.lib import spec

    iter_us = spec.load_reader(spec.load_benchmark(),
                               "poisson.iter_device_us").read(ctx)
    steps = ctx["window"]["steps"]
    iterations = sum(v for k, v in ctx["obs"].items()
                     if k.startswith("poisson.iters_hist{")
                     and k.endswith(".sum"))
    if iter_us is None or not steps or not iterations:
        return None
    return 1e-3 * iter_us * iterations / steps
