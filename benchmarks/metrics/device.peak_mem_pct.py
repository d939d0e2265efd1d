"""peak_bytes_in_use after the window over the HBM of the chip."""

META = {"name": "device.peak_mem_pct", "layer": "device", "unit": "%", "moves": "step_ms",
        "source": "program_counter", "better": "lower"}


def read(ctx):
    if not ctx["peak_bytes"] or not ctx["chip"]:
        return None
    return 100.0 * ctx["peak_bytes"] / ctx["chip"]["hbm_bytes"]
