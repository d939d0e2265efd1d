"""Un-synced host time inside the harness spans around the calls into the
driver (calc_max_timestep, advance, advance_megaloop), over steps."""

META = {"name": "driver.host_ms_per_step", "layer": "drivers", "unit": "ms", "moves": "step_ms",
        "source": "program_span", "better": "lower"}


STEP_CALLS = ("calc_max_timestep", "advance", "advance_megaloop")


def read(ctx):
    w = ctx["window"]
    if not w["steps"]:
        return None
    return 1e3 * sum(r[2] - r[1] for r in w["rows"]
                     if r[0] in STEP_CALLS) / w["steps"]
