"""Device time of the solve probe (the driver's own solver, jitted under
a harness name, after the window) over its iterations."""

META = {"name": "poisson.iter_device_us", "layer": "Poisson solve", "unit": "us", "moves": "step_ms",
        "source": "device_trace", "better": "lower"}


def read(ctx):
    t = ctx["trace"]
    if not t or not t["probe"] or not t["probe"]["iterations"] > 0:
        return None
    runs = t["module_runs"].get("bench_solve_probe")
    dev = t["module_s"].get("bench_solve_probe", 0.0) / runs if runs else None
    return 1e6 * dev / t["probe"]["iterations"] if dev else None
