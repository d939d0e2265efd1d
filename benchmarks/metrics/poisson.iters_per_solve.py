"""BiCGSTAB iterations per solve over the window, from the program's
poisson.iters_hist histogram (sum / count, whichever driver fed it)."""

META = {"name": "poisson.iters_per_solve", "layer": "Poisson solve", "unit": "count", "moves": "step_ms",
        "source": "program_counter", "better": "lower"}


def read(ctx):
    n = s = 0.0
    for k, v in ctx["obs"].items():
        if k.startswith("poisson.iters_hist{"):
            if k.endswith(".count"):
                n += v
            elif k.endswith(".sum"):
                s += v
    return s / n if n else None
