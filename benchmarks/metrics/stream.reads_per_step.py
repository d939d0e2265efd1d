"""Blocking device-to-host reads a step: the window's visits of the
program's read sites (its counters transfers.sanctioned{site=...-read}:
the packed QoI read, the moments read, the dt read, the adaptation
pass's tags read, a grouped read of the stream) over its steps.  0.0
where the window made none."""

META = {"name": "stream.reads_per_step", "layer": "host data plane", "unit": "count", "moves": "step_ms",
        "source": "program_counter", "better": "lower"}


def read(ctx):
    w = ctx["window"]
    reads = sum(v for k, v in ctx["obs"].items()
                if k.startswith("transfers.sanctioned{site=")
                and k.endswith("-read}"))
    return reads / w["steps"] if w["steps"] else None
