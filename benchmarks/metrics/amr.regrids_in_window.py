"""Adaptation passes in the window that changed the mesh (the program's
counter amr.regrids; amr.regrid_noops counts the passes that left it as
it was and is printed beside it, under "grid" in the result).  Nothing
where no pass ran at all."""

META = {"name": "amr.regrids_in_window", "layer": "forest", "unit": "count", "moves": "step_ms",
        "source": "program_counter", "better": "lower"}


def read(ctx):
    obs = ctx["obs"]
    regrids = obs.get("amr.regrids", 0)
    if not regrids and not obs.get("amr.regrid_noops", 0):
        return None
    return float(regrids)
