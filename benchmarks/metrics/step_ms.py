"""Wall of the whole measured window, closed on block_until_ready of the
live state, over ALL steps completed in it.  No trimming, no median."""

META = {"name": "step_ms", "layer": "end to end", "unit": "ms", "moves": "step_ms",
        "source": "host_clock", "better": "lower"}


def read(ctx):
    w = ctx["window"]
    return 1e3 * w["wall_s"] / w["steps"] if w["steps"] else None
