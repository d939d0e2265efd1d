"""Check of a cell whose timed entry is the K-step scan megaloop.

One dispatch leaves no state between its K steps, and the reference has
no midline model to follow them alone.  So after the window the harness
takes one more dispatch through ``simulate()`` (the timed program, K
steps in one call) with the carry and the CFL row it was handed kept
aside, then drives THE SAME jitted scan from that carry one step at a
time (a row of one CFL value: the same scan body, trip count 1).  The
chain's end has to be the timed dispatch's product (``scan_chain_gap``),
and the reference follows links of the chain step by step exactly as it
follows a per-step cell: the first, the last and as many more, drawn
from the seed, as the traffic file says.  The timed product is thereby
held to the reference through every stage of the step."""

import numpy as np

from benchmarks.lib import drive

RIGID = {"trans": slice(0, 3), "ang": slice(3, 6), "cm": slice(12, 15)}


def _host(carry):
    return {k: np.asarray(drive.need(carry, k)) for k in
            ("vel", "p", "chi", "udef", "rigid", "dt", "time")}


def _capture(state, grid, shape):
    rigid = np.asarray(state["rigid"], np.float64)
    body = {**shape, "chi": state["chi"], "udef": state["udef"],
            **{k: rigid[s] for k, s in RIGID.items()}}
    return {**grid, **{k: state[k] for k in ("vel", "p", "chi", "udef")},
            "time": float(state["time"]), "dt": float(state["dt"]),
            "bodies": [body]}


def _gap(timed, chain, start):
    """Largest relative gap between the timed dispatch's product and the
    chain's end: the velocity against its change over the K steps, the
    other fields and the rigid state against their own size."""
    f = lambda a: np.asarray(a, np.float64)
    norm = lambda a: float(np.sqrt(np.sum(np.square(f(a)))))
    gaps = [norm(f(timed["vel"]) - f(chain["vel"]))
            / norm(f(chain["vel"]) - f(start["vel"]))]
    for k in ("p", "chi", "udef", "rigid", "dt", "time"):
        gaps.append(norm(f(timed[k]) - f(chain[k])) / norm(chain[k]))
    return max(gaps)


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
        return [], {"scan_chain_gap": float("inf")}
    timed = _host(drive.need(driver, "_scan_carry"))
    # state and carry may differ if something rewrote the state after
    # the dispatch: what the driver would hand on is what is judged
    timed.update({k: grid.host(driver, drive.need(driver.sim.state, k))
                  for k in ("vel", "p", "chi", "udef")})

    k_steps = int(kept["cfl"].shape[0])
    rng = np.random.default_rng(int(seed))
    chosen = {0, k_steps - 1} | set(
        int(i) for i in rng.choice(np.arange(1, k_steps - 1),
                                   int(traffic["check"]["more_links"]),
                                   replace=False))
    carry = kept["carry"]
    states = {0: _host(carry)}
    for k in range(k_steps):
        carry, _ = fn(carry, kept["cfl"][k:k + 1])
        if k in chosen or k + 1 in chosen or k + 1 == k_steps:
            states[k + 1] = _host(carry)
    jax.block_until_ready(carry)
    del carry, kept

    geom = grid.geometry(driver, config)
    (shape,) = drive.body_shapes(config)
    out = []
    for k in sorted(chosen):
        pre = _capture(states[k], geom, shape)
        post = _capture(states[k + 1], geom, shape)
        # a body that fixes the frame: the step advects with minus the
        # body's velocity before its update
        post["uinf"] = (-pre["bodies"][0]["trans"] if shape["fixes_frame"]
                        else np.array(driver.sim.uinf, np.float64))
        out.append((pre, post))
    # the last link ends on the timed dispatch's own product
    last = _capture(timed, geom, shape)
    last["uinf"] = out[-1][1]["uinf"]
    out[-1] = (out[-1][0], last)
    return out, {"scan_chain_gap": _gap(timed, states[k_steps], states[0])}
