"""Least time the chip could take for one BiCGSTAB iteration (the larger
of bytes over HBM bandwidth and flops over peak; lib/counts.py, from the
grid's shapes alone, the count of the configuration's kind of grid) over
the measured time of one iteration."""

META = {"name": "poisson_iter_roofline", "layer": "kernels", "unit": "%", "moves": "step_ms",
        "source": "device_trace", "better": "higher"}


def read(ctx):
    from benchmarks.lib import counts

    t = ctx["trace"]
    work = ctx.get("iteration_work")
    if not t or not work or not t["probe"] \
            or not t["probe"]["iterations"] > 0:
        return None
    runs = t["module_runs"].get("bench_solve_probe")
    dev = t["module_s"].get("bench_solve_probe", 0.0) / runs if runs else None
    if not dev:
        return None
    least = counts.roofline_seconds(work, ctx["chip"])
    return 100.0 * least["seconds"] / (dev / t["probe"]["iterations"])
