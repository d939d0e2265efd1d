"""Share of the window's forced steps whose streamwise forcing ran inside
the scan dispatch (the program's counter operators.flux_scan_steps,
raised scan_k times a dispatch of a forced flow), and not as the
per-step operator (operators.flux_host_steps, raised once per per-step
FixMassFlux or ExternalForcing call): 100 where every forced step went
through the scan.  Nothing where the program has neither counter (a flow
that is not forced, the parent)."""

META = {"name": "operators.flux_scan_share", "layer": "operators", "unit": "%", "moves": "step_ms",
        "source": "program_counter", "better": "higher"}


def read(ctx):
    obs = ctx["obs"]
    scan = obs.get("operators.flux_scan_steps", 0)
    steps = scan + obs.get("operators.flux_host_steps", 0)
    return 100.0 * scan / steps if steps else None
