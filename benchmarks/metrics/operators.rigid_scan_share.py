"""Share of the window's rigid-body steps whose body stage ran inside
the scan dispatch (the program's counter operators.rigid_scan_steps,
raised scan_k times a dispatch whose body has no midline), and not as
the per-step uniform CreateObstacles (operators.rigid_host_steps, raised
once a call for such a body): 100 where every step of the rigid body
went through the scan.  Nothing where the program has neither counter (a
fish, a flow with no body, the parent)."""

META = {"name": "operators.rigid_scan_share", "layer": "operators", "unit": "%", "moves": "step_ms",
        "source": "program_counter", "better": "higher"}


def read(ctx):
    obs = ctx["obs"]
    scan = obs.get("operators.rigid_scan_steps", 0)
    steps = scan + obs.get("operators.rigid_host_steps", 0)
    return 100.0 * scan / steps if steps else None
