"""Host wall of the AdaptMesh section of the program's profiler (the
adaptation pass: tags read, plan, transfer, rebuild where the mesh
changes), over the window's steps."""

META = {"name": "amr.adapt_host_ms_per_step", "layer": "forest", "unit": "ms", "moves": "step_ms",
        "source": "program_span", "better": "lower"}


def read(ctx):
    t = ctx["profiler"].get("AdaptMesh")
    w = ctx["window"]
    if not t or not w["steps"]:
        return None
    return 1e3 * t / w["steps"]
