"""Check of a cell whose timed entry is the K-step scan megaloop of a flow
with no body (the obstacle-free scan body, forced or not).

As ``scan_chain.py`` does for a body: after the window the harness takes
one more dispatch through ``simulate()`` with the carry and the CFL row
it was handed kept aside, then drives THE SAME jitted scan from that
carry one step at a time.  The chain's end has to be the timed
dispatch's product (``scan_chain_gap``, over the velocity, the pressure,
dt and time), and the reference follows two links: the chain's first
step and its last, which ends on the timed product itself.

One number more, read on the timed product: ``bulk_velocity_gap``, how
far the bulk velocity (the mean of u_x + uinf_x over the cells) is from
the one the configuration's forcing holds, over that target."""

import numpy as np

from benchmarks.lib import drive

KEYS = ("vel", "p", "dt", "time")


def _host(carry):
    return {k: np.asarray(drive.need(carry, k)) for k in KEYS}


def _capture(state, geom, uinf):
    """A capture of a flow with no body: chi and udef are 0 (views that
    hold no memory)."""
    shape = np.shape(state["vel"])
    return {**geom, "vel": state["vel"], "p": state["p"],
            "chi": np.broadcast_to(np.float64(0.0), shape[:3]),
            "udef": np.broadcast_to(np.float64(0.0), shape),
            "time": float(state["time"]), "dt": float(state["dt"]),
            "bodies": [], "uinf": uinf}


def _gap(timed, chain, start):
    """Largest relative gap between the timed dispatch's product and the
    chain's end: the velocity against its change over the K steps, the
    other values against their own size."""
    f = lambda a: np.asarray(a, np.float64)
    norm = lambda a: float(np.sqrt(np.sum(np.square(f(a)))))
    gaps = [norm(f(timed["vel"]) - f(chain["vel"]))
            / norm(f(chain["vel"]) - f(start["vel"]))]
    for k in KEYS[1:]:
        gaps.append(norm(f(timed[k]) - f(chain[k])) / norm(chain[k]))
    return max(gaps)


def _bulk_gap(vel, uinf, forcing):
    target = 2.0 / 3.0 * float(forcing["uMax_forced"])
    bulk = float(np.mean(vel[..., 0], dtype=np.float64)) + float(uinf[0])
    return abs(bulk - target) / target


def links(driver, grid, traffic, config, spans, seed):
    import jax
    import jax.numpy as jnp

    fn, row_w = drive.need(driver, "_megaloop")
    kept = {}

    def keeping(carry, cfl):
        kept["carry"] = {k: jnp.copy(v) for k, v in carry.items()}
        kept["cfl"] = cfl
        return fn(carry, cfl)

    driver._megaloop = (keeping, row_w)
    try:
        drive.run_steps(driver, int(traffic["check_unit_steps"]))
        drive.sync(driver)
    finally:
        driver._megaloop = (fn, row_w)
    if "carry" not in kept:  # the unit never reached the scan
        return [], {"scan_chain_gap": float("inf"),
                    "bulk_velocity_gap": float("inf")}
    timed = _host(drive.need(driver, "_scan_carry"))
    # state and carry may differ if something rewrote the state after
    # the dispatch: what the driver would hand on is what is judged
    timed.update({k: grid.host(driver, drive.need(driver.sim.state, k))
                  for k in ("vel", "p")})
    uinf = np.array(drive.need(driver.sim, "uinf"), np.float64)

    k_steps = int(kept["cfl"].shape[0])
    carry = kept["carry"]
    states = {0: _host(carry)}
    for k in range(k_steps):
        carry, _ = fn(carry, kept["cfl"][k:k + 1])
        if k + 1 in (1, k_steps - 1, k_steps):
            states[k + 1] = _host(carry)
    jax.block_until_ready(carry)
    del carry, kept

    geom = grid.geometry(driver, config)
    cap = lambda st: _capture(st, geom, uinf)
    out = [(cap(states[0]), cap(states[1])),
           # the last link ends on the timed dispatch's own product
           (cap(states[k_steps - 1]), cap(timed))]
    return out, {
        "scan_chain_gap": _gap(timed, states[k_steps], states[0]),
        "bulk_velocity_gap": _bulk_gap(timed["vel"], uinf,
                                       config["physics"]["forcing"])}
