"""The comparison that decides ``correct``.

A check (``benchmarks/checks/<kind>.py``, named by the cell's traffic
file) hands over *links*: pairs of captures ``(pre, post)`` one time step
apart, taken from what the timed entry produced.  The plain reference
(NumPy float64 on the host; ``reference.py`` on a uniform grid,
``reference_forest.py`` on an octree of blocks, whichever the
configuration's grid adapter hands over) repeats each step from ``pre``
on every cell of the grid and every stage is compared:
advection-diffusion, the rigid update, penalisation and the projection
through the velocity, the solve through the pressure equation's residual
against the reference's own right-hand side, and the body's
rasterisation through its volume (against the published profiles).  chi
and the deformation velocity are the program's (the reference has no
midline model); everything else of the step is the reference's own.

Each number is printed beside its limit; ``correct`` is their conjunction
with the configuration's guarantees."""

from __future__ import annotations

import numpy as np

from . import reference as ref


def bf16_store(x):
    """What a run that keeps its fields in bfloat16 would hold."""
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)


def _norm(a):
    return float(np.sqrt(np.sum(np.square(a, dtype=np.float64))))


def _ratio(num, den):
    return float(num / den) if den > 0 else float("inf")


def _bodies(pre, post):
    """The bodies of ``post`` as the reference's step wants them."""
    return [{**b, "cm_guess": a["cm"]}
            for a, b in zip(pre["bodies"], post["bodies"])]


def reference_step(grid, pre, post, phys, store=None, on=None):
    """The reference's step from ``pre``, with the dt, the frame velocity
    and the bodies (chi, udef, the rigid velocities penalised towards)
    that ``post`` reports.  ``store`` rounds every stage as a run in that
    precision would keep it; ``on`` is the reference to step with where
    it is not the sound one of ``post``'s grid (a planted fault)."""
    on = on or grid.reference(post)
    return on.one_step(
        np.asarray(pre["vel"], np.float64), post["dt"], phys["nu"],
        post["uinf"], _bodies(pre, post), phys["DLM"],
        store or (lambda x: x))


def link_numbers(grid, pre, post, phys, r=None):
    """Every number of one step: ``post`` holds what is judged.  ``r`` is
    the reference's step where the caller has it already (it depends on
    ``pre`` and on the dt, frame velocity and bodies of ``post`` only)."""
    r = r or reference_step(grid, pre, post, phys)
    on = grid.reference(post)
    dt = post["dt"]
    vel0 = np.asarray(pre["vel"], np.float64)
    vel1 = np.asarray(post["vel"], np.float64)
    p1 = np.asarray(post["p"], np.float64)
    # the projection with the judged pressure: the pressure itself is held
    # to the reference's equation below, at the tolerance the
    # configuration states, and the velocity stays sharp beside it
    u1 = r["u_pen"] - dt * on.gradient(p1)
    gap, change = vel1 - u1, u1 - vel0
    resid = on.laplacian(p1) - r["rhs"]
    out = {
        "vel_step_gap": _ratio(on.norm(gap), on.norm(change)),
        "vel_step_gap_max": _ratio(np.abs(gap).max(), np.abs(change).max()),
        "poisson_resid": _ratio(on.norm(resid - on.mean(resid)),
                                on.norm(r["rhs"] - on.mean(r["rhs"]))),
    }
    if not post["bodies"]:  # a flow with no body has no more numbers
        return out
    vel_gap = cm_gap = vol_gap = 0.0
    for b, mine in zip(post["bodies"], r["rigid"]):
        rg = mine["gyration"]
        vel_gap = max(vel_gap, _ratio(
            _norm(b["trans"] - mine["trans"])
            + rg * _norm(b["ang"] - mine["ang"]),
            _norm(mine["trans"]) + rg * _norm(mine["ang"])))
        cm_gap = max(cm_gap, _norm(b["cm"] - mine["cm"]) / on.h_finest)
        volume = ref.fish_volume(b["length"], b["width"], b["height"])
        vol_gap = max(vol_gap, abs(mine["mass"] - volume) / volume)
    out.update(rigid_vel_gap=vel_gap, rigid_cm_gap_h=cm_gap,
               chi_volume_gap=vol_gap)
    return out


def control_link(grid, pre, post, phys, on=None):
    """The reference put in the program's place, keeping every stage in
    bfloat16 (or, with ``on``, a reference with a fault planted in it,
    kept in float32): the ``post`` it would hand back (velocity, pressure,
    rigid state)."""
    store = bf16_store if on is None else (
        lambda x: np.asarray(x, np.float32).astype(np.float64))
    r = reference_step(grid, pre, post, phys, store=store, on=on)
    bodies = [{**b, "trans": store(m["trans"]),
               "ang": store(m["ang"]), "cm": store(m["cm"])}
              for b, m in zip(post["bodies"], r["rigid"])]
    return {**post, "vel": r["u1"], "p": store(r["p"]),
            "bodies": bodies}


def guarantees(grid, post):
    """The configuration's guarantees that a state can show by itself:
    fields finite, every body in chi, chi in [0, 1].  (Its solver
    tolerance is ``poisson_resid``'s limit, its divergence gate a number
    of its own in ``judge``.)"""
    on = grid.reference(post)
    finite = all(bool(np.isfinite(post[k]).all())
                 for k in ("vel", "p", "chi", "udef"))
    chi = np.asarray(post["chi"], np.float64)
    volumes = [on.volume(b["chi"]) for b in post["bodies"]]
    facts = {"fields_finite": finite, "chi_min": float(chi.min()),
             "chi_max": float(chi.max()),
             "chi_volume_min": min(volumes) if volumes else None,
             "cells_compared": on.check(post["vel"])}
    ok = (finite and all(v > 0.0 for v in volumes)
          and facts["chi_min"] >= 0.0 and facts["chi_max"] <= 1.0 + 1e-6)
    facts["div_fluid_max_at_end"] = fluid_divergence_max(grid, post)
    return ok, facts


def fluid_divergence_max(grid, state):
    """Largest ``|div u|`` in the fluid, away from the chi band, as the
    configuration's kind of grid states its gate."""
    return grid.reference(state).fluid_divergence_max(
        np.asarray(state["vel"], np.float64),
        np.asarray(state["chi"], np.float64))


def judge(grid, links, extra, at_open, config, limits):
    """Everything that decides ``correct``: (passed, numbers beside their
    limits, the guarantees' readings).  A number of several links is the
    worst of them; ``extra`` are numbers the check read itself.  The
    fluid's divergence is held to the configuration's gate where the
    configuration states it: on the state the window opened on
    (``at_open``), at the end of the warm-up."""
    phys = config["physics"]
    limits = {**limits, "div_fluid_max_at_open":
              config["guarantees"]["div_fluid_gate"]["limit"]}
    extra = {**extra,
             "div_fluid_max_at_open": fluid_divergence_max(grid, at_open)}
    numbers = dict(extra)
    ok, facts = True, {}
    for pre, post in links:
        for k, v in link_numbers(grid, pre, post, phys).items():
            numbers[k] = max(numbers.get(k, 0.0), v)
        good, facts = guarantees(grid, post)
        ok = ok and good
    # a gap with nothing under it reads as a very large number: the
    # result line stays plain JSON
    compared = {k: {"value": float(v) if np.isfinite(v) else 1e30,
                    "limit": limits[k]} for k, v in numbers.items()}
    passed = ok and bool(links) and all(
        c["value"] <= c["limit"] for c in compared.values())
    return bool(passed), compared, facts
