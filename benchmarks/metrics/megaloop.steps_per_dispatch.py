"""Steps of the window over the scan dispatches the program counted in it
(its counter megaloop.dispatches, raised once per advance_megaloop call):
the harness's own count of steps, not a second counter of the program's,
so a dispatch that advanced fewer steps than scan_k, or steps that came
from elsewhere, move the number off scan_k.  Nothing where the program
has no such counter, or no dispatch ran."""

META = {"name": "megaloop.steps_per_dispatch", "layer": "drivers", "unit": "count", "moves": "step_ms",
        "source": "program_counter", "better": "higher"}


def read(ctx):
    dispatches = ctx["obs"].get("megaloop.dispatches", 0)
    steps = ctx["window"]["steps"]
    return steps / dispatches if dispatches and steps else None
