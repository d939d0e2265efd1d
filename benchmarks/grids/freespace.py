"""The dense uniform grid of the uniform driver with free-space faces on
every axis and one rigid sphere in it (``driver.kind: "freespace"``):
the towed sphere.  Everything the harness does that depends on this kind
of grid; the adapter's interface is the one ``grids/uniform.py`` lists.

The plain reference is ``lib/reference_sphere.py``.  It makes its own
chi of the sphere, so a capture hands over the body as the step starts
(``sphere``: radius, centre, velocities, masks) and not the program's
chi: the captures' ``bodies`` stay empty and ``compare.link_numbers``
forms the fluid's numbers, the check (``checks/scan_chain_body.py``)
the body's (``body_numbers``).

What this one takes from the program (``drive.need``): ``grid.shape``,
``grid.h``, ``grid.bc``; ``sim.state`` (vel, p, chi, udef) and ``sim.dt``
for the probe.
"""

from __future__ import annotations

import hashlib

import numpy as np

from benchmarks.lib import counts, reference as ref
from benchmarks.lib import reference_sphere as rs
from benchmarks.lib.drive import need


def cells(grid) -> int:
    return int(np.prod(need(grid, "shape")))


def host(driver, array):
    return np.asarray(array)


def geometry(driver, config) -> dict:
    grid = need(driver.sim, "grid")
    return {"h": float(need(grid, "h")),
            "bc": tuple(str(getattr(b, "value", b))
                        for b in need(grid, "bc"))}


#: faults only this grid has, each planted in the reference that is put
#: in the program's place (``stand_in``): the keyword arguments of
#: :class:`Reference`
FAULTS = {
    "periodic": {"bc": (rs.PERIODIC,) * 3},
    "ghost_copy": {"ghosts": "copy"},
    "chi_off": {"chi_shift": 1},
    "uinf_flipped": {"uinf_sign": -1.0},
}

#: reference steps a capture keeps (``Reference.one_step``)
KEEP_STEPS = 2


def _key(u0, *rest):
    digest = hashlib.blake2b(np.ascontiguousarray(u0).view(np.uint8),
                             digest_size=16).hexdigest()
    return (digest, u0.shape) + tuple(repr(r) for r in rest)


class Reference:
    """``reference_sphere.py`` on one dense array of spacing ``h`` with
    the sphere of the capture ``geom``; ``fault`` overrides the box's
    ``bc`` or ``ghosts``, places chi ``chi_shift`` cells off, or steps
    with the frame velocity times ``uinf_sign`` (a planted fault).

    The capture keeps the last steps taken from it, by what they were
    computed from (``reference_steps``): the check forms the body's
    numbers of a link and ``compare.judge`` the fluid's from one step."""

    def __init__(self, geom, **fault):
        self.h = self.h_finest = float(geom["h"])
        self.body = geom.get("sphere")
        self.fault = fault
        self.steps = geom.setdefault("reference_steps", {})
        self.box = rs.FreeSpace(self.h, fault.get("bc", geom["bc"]),
                                fault.get("ghosts", "negate"))

    def check(self, field):
        """Cells of ``field`` that the numbers run over: all of them."""
        return int(np.prod(np.shape(field)[:3]))

    def one_step(self, u0, dt, nu, uinf, bodies, lam_dt, store):
        if bodies or self.body is None:
            raise SystemExit("benchmark: the free-space reference takes its "
                             "sphere from the capture (`sphere`), not chi")
        uinf = self.fault.get("uinf_sign", 1.0) * np.asarray(uinf, np.float64)
        probe = store(np.array([1.0 / 3.0]))
        key = _key(u0, dt, nu, uinf, lam_dt, probe, sorted(self.body.items()),
                   sorted(self.fault.items()), self.box.bc)
        if key not in self.steps:
            while len(self.steps) >= KEEP_STEPS:
                self.steps.pop(next(iter(self.steps)))
            step = rs.TowedSphere(self.h, self.body, self.box.bc,
                                  self.box.ghosts,
                                  self.fault.get("chi_shift", 0))
            self.steps[key] = step.one_step(u0, dt, nu, uinf, lam_dt, store)
        return self.steps[key]

    def gradient(self, p):
        return self.box.gradient(p)

    def laplacian(self, p):
        return self.box.laplacian(p)

    def divergence(self, u):
        return self.box.divergence(u)

    def fluid_divergence_max(self, u, chi):
        """Largest ``|div u|`` at least three cells from the chi band."""
        dv = np.abs(self.box.divergence(u))
        mask = ref.fluid_mask(chi)
        return float(dv[mask].max()) if mask.any() else 0.0

    @staticmethod
    def norm(a):
        return float(np.sqrt(np.sum(np.square(a, dtype=np.float64))))

    @staticmethod
    def mean(a):
        return a.mean()

    def volume(self, chi):
        return float(np.sum(chi, dtype=np.float64)) * self.h ** 3


def reference(geom) -> Reference:
    return Reference(geom)


def body_numbers(pre, post, phys, r) -> dict:
    """The body's numbers of one link, ``post`` holding what is judged
    (its chi, and under ``reported`` the centre of mass and the
    penalisation force the program's row gave), ``r`` the reference's
    step (its own chi, rigid update and penalisation force):

    - ``chi_gap``: ``|chi - chi_ref|_1 / |chi_ref|_1``;
    - ``chi_volume_gap``: the volume of chi against the sphere's;
    - ``pen_force_gap``: ``|F - F_ref| / |F_ref|`` of the penalisation
      force;
    - ``rigid_cm_gap_h``: the centre of mass after the step, in cells."""
    body, got = post["sphere"], post["reported"]
    chi = np.asarray(post["chi"], np.float64)
    ref_chi = r["chi"]
    exact = rs.sphere_volume(float(body["radius"]))
    h = float(post["h"])
    f, f_ref = (np.asarray(v, np.float64)
                for v in (got["pen_force"], r["pen_force"]))
    return {
        "chi_gap": float(np.abs(chi - ref_chi).sum() / ref_chi.sum()),
        "chi_volume_gap": abs(chi.sum() * h ** 3 - exact) / exact,
        "pen_force_gap": float(np.linalg.norm(f - f_ref)
                               / np.linalg.norm(f_ref)),
        "rigid_cm_gap_h": float(np.linalg.norm(
            np.asarray(got["cm"], np.float64) - r["cm"]) / h),
    }


def stand_in(post, r, store=lambda x: x):
    """``post`` as a program that computed the reference's step ``r``
    would hand it back (``store`` rounds what it keeps)."""
    return {**post, "vel": r["u1"], "p": store(r["p"]),
            "chi": store(r["chi"]),
            "reported": {"cm": store(r["cm"]),
                         "pen_force": store(r["pen_force"])}}


def live_system(driver, p_before):
    """(rhs, x0, solver keywords) of the last step's pressure equation,
    on the device: ``(div u_pen - chi div u_def) / dt`` with the
    penalised velocity recovered as ``u + dt grad p``, on the program's
    ghosts (the face-normal velocity component minus the edge cell, the
    others and pressure the edge cell)."""
    import jax.numpy as jnp

    d = driver.sim
    h, dt = float(need(d.grid, "h")), float(need(d, "dt"))
    vel, p, chi, udef = (need(d.state, k)
                         for k in ("vel", "p", "chi", "udef"))

    def pad(a, comp=None):
        for axis in range(3):
            pads = [(0, 0)] * a.ndim
            pads[axis] = (1, 1)
            a = jnp.pad(a, pads, mode="edge")
            if comp == axis:
                lo = [slice(None)] * a.ndim
                hi = [slice(None)] * a.ndim
                lo[axis], hi[axis] = slice(0, 1), slice(-1, None)
                a = a.at[tuple(lo)].multiply(-1.0) \
                    .at[tuple(hi)].multiply(-1.0)
        return a

    def d1(ap, axis):
        hi = [slice(1, -1)] * 3
        lo = [slice(1, -1)] * 3
        hi[axis], lo[axis] = slice(2, None), slice(0, -2)
        return (ap[tuple(hi)] - ap[tuple(lo)]) / (2.0 * h)

    div = lambda u: sum(d1(pad(u[..., c], c), c) for c in range(3))
    pp = pad(p)
    u_pen = vel + dt * jnp.stack([d1(pp, c) for c in range(3)], axis=-1)
    return (div(u_pen) - chi * div(udef)) / dt, p_before, {}


def iteration_work(grid) -> dict:
    """The boundary changes no byte of the iteration: 80 B and 48 flop
    an unknown, as on the periodic grid."""
    return counts.bicgstab_iteration(cells(grid))


def counters(obs: dict) -> dict:
    return {}
