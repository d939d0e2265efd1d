"""Share of the window's steps whose Penalization ran with no contact
work, every body's velocity field built inside the one program (the
program's counter operators.body_steps_fused), and not after the op-by-op
contact branch (operators.body_steps_contact): 100 while no two bodies
touch.  Nothing where the program has neither counter, or no step with a
body ran."""

META = {"name": "operators.body_fused_share", "layer": "operators", "unit": "%", "moves": "step_ms",
        "source": "program_counter", "better": "higher"}


def read(ctx):
    obs = ctx["obs"]
    fused = obs.get("operators.body_steps_fused", 0)
    steps = fused + obs.get("operators.body_steps_contact", 0)
    return 100.0 * fused / steps if steps else None
