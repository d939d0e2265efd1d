"""Process start (top of run.py) to the opening of the window: imports,
device init, init(), compile or cache load, crossing the CFL ramp."""

META = {"name": "setup_s", "layer": "end to end", "unit": "s", "moves": "setup_s",
        "source": "host_clock", "better": "lower"}


def read(ctx):
    return ctx["setup_s"]
