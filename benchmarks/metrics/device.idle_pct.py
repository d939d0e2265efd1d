"""1 - union of device-operation intervals over the traced window."""

META = {"name": "device.idle_pct", "layer": "device", "unit": "%", "moves": "step_ms",
        "source": "device_trace", "better": "lower"}


def read(ctx):
    t = ctx["trace"]
    if not t or not t["busy_s"] > 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
