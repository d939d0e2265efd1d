"""Host wall of the ComputeForces section of the program's profiler, over
steps (per-step paths only: a scan has no such section; a flow with no
body never opens it)."""

META = {"name": "operators.compute_forces_host_ms", "layer": "operators", "unit": "ms", "moves": "step_ms",
        "source": "program_span", "better": "lower"}


def read(ctx):
    t = ctx["profiler"].get("ComputeForces")
    w = ctx["window"]
    if not t or not w["steps"]:
        return None
    return 1e3 * t / w["steps"]
