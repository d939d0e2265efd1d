"""Leaf cells advanced, summed over the window's steps (n^3 per step on a
uniform grid, blocks * 8^3 per step on a forest, as the mesh adapts),
over the same wall as step_ms."""

META = {"name": "cells_per_s", "layer": "end to end", "unit": "Mcells/s", "moves": "cells_per_s",
        "source": "host_clock", "better": "higher"}


def read(ctx):
    w = ctx["window"]
    return 1e-6 * w["cells"] / w["wall_s"] if w["cells"] else None
