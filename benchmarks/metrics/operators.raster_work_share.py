"""Cells the dense-window rasterizer evaluated over the cells the full
sweep of its window would have (the program's counters
operators.raster_cells and operators.raster_sweep_cells, raised once per
body per step where the per-step path calls its CreateObstacles program
and K times per scan dispatch): 100 where every segment is evaluated over
the whole window, the box's share of it where each group of segments is
evaluated only in a box around itself.  Nothing where the program has no
such counters (the forest, a flow with no body, the parent)."""

META = {"name": "operators.raster_work_share", "layer": "operators", "unit": "%", "moves": "step_ms",
        "source": "program_counter", "better": "lower"}


def read(ctx):
    obs = ctx["obs"]
    sweep = obs.get("operators.raster_sweep_cells", 0)
    return 100.0 * obs.get("operators.raster_cells", 0) / sweep if sweep else None
