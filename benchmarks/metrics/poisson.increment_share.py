"""Share of the window's uniform Poisson solves that entered BiCGSTAB in
the increment form (the program's counter poisson.increment_solves: the
set-up's norm and r0 = b - A x0 on the natural grid, one transpose in)
and not in the composed form (poisson.composed_solves: b and x0
transposed, the norm and r0 taken in the lanes layout), each raised
once per per-step projection call and scan_k times per scan dispatch:
100 where every solve took the increment entry.  Nothing where the
program has neither counter (the forest, the spectral solve, a program
from before the counters)."""

META = {"name": "poisson.increment_share", "layer": "Poisson solve", "unit": "%", "moves": "step_ms",
        "source": "program_counter", "better": "higher"}


def read(ctx):
    obs = ctx["obs"]
    inc = obs.get("poisson.increment_solves", 0)
    solves = inc + obs.get("poisson.composed_solves", 0)
    return 100.0 * inc / solves if solves else None
