"""The comparison that decides ``correct``.

A check (``benchmarks/checks/<kind>.py``, named by the cell's traffic
file) hands over *links*: pairs of captures ``(pre, post)`` one time step
apart, taken from what the timed entry produced.  The plain reference
(``reference.py``, NumPy float64 on the host) repeats each step from
``pre`` on every cell of the periodic grid and every stage is compared:
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


def reference_step(pre, post, phys, store=None):
    """The reference's step from ``pre``, with the dt, the frame velocity
    and the bodies (chi, udef, the rigid velocities penalised towards)
    that ``post`` reports.  ``store`` rounds every stage as a run in that
    precision would keep it."""
    return ref.one_step(
        np.asarray(pre["vel"], np.float64), post["dt"], phys["nu"],
        post["uinf"], post["h"], post["x"], _bodies(pre, post), phys["DLM"],
        store=store or (lambda x: x))


def link_numbers(pre, post, phys, r=None):
    """Every number of one step: ``post`` holds what is judged.  ``r`` is
    the reference's step where the caller has it already (it depends on
    ``pre`` and on the dt, frame velocity and bodies of ``post`` only)."""
    r = r or reference_step(pre, post, phys)
    h, dt = post["h"], post["dt"]
    vel0 = np.asarray(pre["vel"], np.float64)
    vel1 = np.asarray(post["vel"], np.float64)
    p1 = np.asarray(post["p"], np.float64)
    # the projection with the judged pressure: the pressure itself is held
    # to the reference's equation below, at the tolerance the
    # configuration states, and the velocity stays sharp beside it
    u1 = r["u_pen"] - dt * ref.gradient(p1, h)
    gap, change = vel1 - u1, u1 - vel0
    resid = ref.laplacian(p1, h) - r["rhs"]
    out = {
        "vel_step_gap": _ratio(_norm(gap), _norm(change)),
        "vel_step_gap_max": _ratio(np.abs(gap).max(), np.abs(change).max()),
        "poisson_resid": _ratio(_norm(resid - resid.mean()),
                                _norm(r["rhs"] - r["rhs"].mean())),
    }
    vel_gap = cm_gap = vol_gap = 0.0
    for b, mine in zip(post["bodies"], r["rigid"]):
        rg = mine["gyration"]
        vel_gap = max(vel_gap, _ratio(
            _norm(b["trans"] - mine["trans"])
            + rg * _norm(b["ang"] - mine["ang"]),
            _norm(mine["trans"]) + rg * _norm(mine["ang"])))
        cm_gap = max(cm_gap, _norm(b["cm"] - mine["cm"]) / h)
        volume = ref.fish_volume(b["length"], b["width"], b["height"])
        vol_gap = max(vol_gap, abs(mine["mass"] - volume) / volume)
    out.update(rigid_vel_gap=vel_gap, rigid_cm_gap_h=cm_gap,
               chi_volume_gap=vol_gap)
    return out


def control_link(pre, post, phys):
    """The reference put in the program's place, keeping every stage in
    bfloat16: the ``post`` it would hand back (velocity, pressure, rigid
    state)."""
    r = reference_step(pre, post, phys, store=bf16_store)
    bodies = [{**b, "trans": bf16_store(m["trans"]),
               "ang": bf16_store(m["ang"]), "cm": bf16_store(m["cm"])}
              for b, m in zip(post["bodies"], r["rigid"])]
    return {**post, "vel": r["u1"], "p": bf16_store(r["p"]),
            "bodies": bodies}


def guarantees(post):
    """The configuration's guarantees that a state can show by itself:
    fields finite, every body in chi, chi in [0, 1].  (Its solver
    tolerance is ``poisson_resid``'s limit, its divergence gate a number
    of its own in ``judge``.)"""
    finite = all(bool(np.isfinite(post[k]).all())
                 for k in ("vel", "p", "chi", "udef"))
    chi = np.asarray(post["chi"], np.float64)
    volumes = [float(np.sum(b["chi"], dtype=np.float64)) * post["h"] ** 3
               for b in post["bodies"]]
    facts = {"fields_finite": finite, "chi_min": float(chi.min()),
             "chi_max": float(chi.max()), "chi_volume_min": min(volumes)}
    ok = (finite and facts["chi_volume_min"] > 0.0
          and facts["chi_min"] >= 0.0 and facts["chi_max"] <= 1.0 + 1e-6)
    facts["div_fluid_max_at_end"] = fluid_divergence_max(post)
    return ok, facts


def fluid_divergence_max(state):
    """Largest ``|div u|`` at least three cells from the chi band."""
    dv = np.abs(ref.divergence(np.asarray(state["vel"], np.float64),
                               state["h"]))
    mask = ref.fluid_mask(np.asarray(state["chi"], np.float64))
    return float(dv[mask].max()) if mask.any() else 0.0


def judge(links, extra, at_open, config, limits):
    """Everything that decides ``correct``: (passed, numbers beside their
    limits, the guarantees' readings).  A number of several links is the
    worst of them; ``extra`` are numbers the check read itself.  The
    fluid's divergence is held to the configuration's gate where the
    configuration states it: on the state the window opened on
    (``at_open``), at the end of the CFL ramp."""
    phys = config["physics"]
    limits = {**limits, "div_fluid_max_at_open":
              config["guarantees"]["div_fluid_gate"]["limit"]}
    extra = {**extra,
             "div_fluid_max_at_open": fluid_divergence_max(at_open)}
    numbers = dict(extra)
    ok, facts = True, {}
    for pre, post in links:
        for k, v in link_numbers(pre, post, phys).items():
            numbers[k] = max(numbers.get(k, 0.0), v)
        good, facts = guarantees(post)
        ok = ok and good
    # a gap with nothing under it reads as a very large number: the
    # result line stays plain JSON
    compared = {k: {"value": float(v) if np.isfinite(v) else 1e30,
                    "limit": limits[k]} for k, v in numbers.items()}
    passed = ok and bool(links) and all(
        c["value"] <= c["limit"] for c in compared.values())
    return bool(passed), compared, facts
