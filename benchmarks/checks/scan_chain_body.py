"""Check of a cell whose timed entry is the K-step scan megaloop of one
body that the reference makes itself: the rigid sphere of a free-space
box (``grids/freespace.py``, ``lib/reference_sphere.py``).

As ``scan_chain.py`` does for the fish: after the window the harness
takes one more dispatch through ``simulate()`` with the carry, the CFL
row and the rows it produced kept aside, then drives THE SAME jitted scan
from that carry one step at a time.  The chain's end has to be the timed
dispatch's product (``scan_chain_gap``), and the reference follows two
links: the chain's first step and its last, which ends on the timed
product and its row.

A capture hands the reference the body as the step starts (``sphere``:
radius, centre, velocities, the forced and blocked masks the
configuration states) and what the program reported after it
(``reported``: the centre of mass of the carry, the penalisation force of
the row).  ``compare.judge`` forms the fluid's numbers of each link
(``vel_step_gap``, ``vel_step_gap_max``, ``poisson_resid``); the body's
(``chi_gap``, ``chi_volume_gap``, ``pen_force_gap``, ``rigid_cm_gap_h``:
the adapter's ``body_numbers``) are the worst of the two links."""

import numpy as np

from benchmarks.lib import compare, drive

KEYS = ("vel", "p", "chi", "udef", "rigid", "dt", "time")
#: the row's penalisation force (sim/megaloop.py FISH_ROW: rigid pack
#: 0:29, penalisation force and torque 29:35)
PEN_FORCE = slice(29, 32)


def _host(carry):
    return {k: np.asarray(drive.need(carry, k)) for k in KEYS}


def _gap(timed, chain, start):
    """Largest relative gap between the timed dispatch's product and the
    chain's end: the velocity against its change over the K steps, the
    other values against their own size (a value that is zero on both,
    such as a rigid body's u_def, has to stay zero)."""
    f = lambda a: np.asarray(a, np.float64)
    norm = lambda a: float(np.sqrt(np.sum(np.square(f(a)))))
    ratio = lambda a, b: (norm(f(a) - f(b)) / norm(b) if norm(b) > 0
                          else (0.0 if norm(a) == 0 else float("inf")))
    gaps = [norm(f(timed["vel"]) - f(chain["vel"]))
            / norm(f(chain["vel"]) - f(start["vel"]))]
    gaps += [ratio(timed[k], chain[k]) for k in KEYS[1:]]
    return max(gaps)


def _sphere(state, shape):
    rigid = np.asarray(state["rigid"], np.float64)
    forced = np.full(3, bool(shape["forced"]))
    return {"radius": float(shape["radius"]), "pos": rigid[6:9],
            "trans": rigid[0:3], "ang": rigid[3:6], "cm": rigid[12:15],
            # a forced body's rotation is blocked too (upstream's default)
            "forced": forced, "blocked": forced}


def _link(pre, post, row, geom, shape, uinf):
    cap = lambda st: {**geom, **{k: st[k] for k in ("vel", "p", "chi",
                                                    "udef")},
                      "time": float(st["time"]), "dt": float(st["dt"]),
                      "bodies": []}
    a, b = cap(pre), cap(post)
    b["sphere"] = _sphere(pre, shape)
    b["uinf"] = (-b["sphere"]["trans"] if shape["fixes_frame"]
                 else np.asarray(uinf, np.float64))
    b["reported"] = {"cm": np.asarray(post["rigid"], np.float64)[12:15],
                     "pen_force": np.asarray(row, np.float64)[PEN_FORCE]}
    return a, b


def links(driver, grid, traffic, config, spans, seed):
    import jax
    import jax.numpy as jnp

    fn, row_w = drive.need(driver, "_megaloop")
    kept = {}

    def keeping(carry, cfl):
        kept["carry"] = {k: jnp.copy(v) for k, v in carry.items()}
        kept["cfl"] = cfl
        out = fn(carry, cfl)
        kept["rows"] = jnp.copy(out[1])
        return out

    driver._megaloop = (keeping, row_w)
    try:
        drive.run_steps(driver, int(traffic["check_unit_steps"]))
        drive.sync(driver)
    finally:
        driver._megaloop = (fn, row_w)
    if "carry" not in kept:  # the unit never reached the scan
        return [], {"scan_chain_gap": float("inf")}
    timed = _host(drive.need(driver, "_scan_carry"))
    # state and carry may differ if something rewrote the state after
    # the dispatch: what the driver would hand on is what is judged
    timed.update({k: grid.host(driver, drive.need(driver.sim.state, k))
                  for k in ("vel", "p", "chi", "udef")})
    timed_rows = np.asarray(kept["rows"], np.float64)

    k_steps = int(kept["cfl"].shape[0])
    carry = kept["carry"]
    states, rows = {0: _host(carry)}, {}
    for k in range(k_steps):
        carry, row = fn(carry, kept["cfl"][k:k + 1])
        if k + 1 in (1, k_steps - 1, k_steps):
            states[k + 1] = _host(carry)
            rows[k + 1] = np.asarray(row, np.float64)[0]
    jax.block_until_ready(carry)
    del carry, kept

    geom = grid.geometry(driver, config)
    (shape,) = drive.body_shapes(config)
    uinf = np.array(drive.need(driver.sim, "uinf"), np.float64)
    out = [_link(states[0], states[1], rows[1], geom, shape, uinf),
           # the last link ends on the timed dispatch's own product
           _link(states[k_steps - 1], timed, timed_rows[-1], geom, shape,
                 uinf)]
    extra = {"scan_chain_gap": _gap(timed, states[k_steps], states[0])}
    phys = config["physics"]
    for pre, post in out:
        r = compare.reference_step(grid, pre, post, phys)
        for k, v in grid.body_numbers(pre, post, phys, r).items():
            extra[k] = max(extra.get(k, 0.0), v)
    return out, extra
