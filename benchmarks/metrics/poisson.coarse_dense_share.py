"""Share of the window's solves whose preconditioner ran its coarse solve
as one dense product (the program's counter poisson.coarse_dense_solves)
and not as the CG loop over the gathered block graph
(poisson.coarse_cg_solves): 100 on a forest of at most
krylov.DENSE_COARSE_MAX rows, 0 above.  Nothing where the program has
neither counter, or no solve with a coarse level ran."""

META = {"name": "poisson.coarse_dense_share", "layer": "Poisson solve", "unit": "%", "moves": "step_ms",
        "source": "program_counter", "better": "higher"}


def read(ctx):
    obs = ctx["obs"]
    dense = obs.get("poisson.coarse_dense_solves", 0)
    solves = dense + obs.get("poisson.coarse_cg_solves", 0)
    return 100.0 * dense / solves if solves else None
