"""Time the step loop waited on the QoI stream (device behind host), from
the stream's stall_s counter, over steps."""

META = {"name": "stream.stall_ms_per_step", "layer": "host data plane", "unit": "ms", "moves": "step_ms",
        "source": "program_counter", "better": "lower"}


def read(ctx):
    w = ctx["window"]
    stall = sum(v for k, v in ctx["obs"].items()
                if k.startswith("stream.stall_s"))
    return 1e3 * stall / w["steps"] if w["steps"] else None
