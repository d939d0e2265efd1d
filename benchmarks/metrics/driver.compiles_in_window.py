"""Programs JAX compiled or fetched from its cache inside the window
(JAX monitoring events): should be 0."""

META = {"name": "driver.compiles_in_window", "layer": "drivers", "unit": "count", "moves": "step_ms",
        "source": "program_counter", "better": "lower"}


def read(ctx):
    return float(ctx["compiles_in_window"])
