"""The dense uniform grid of the uniform driver with no-slip walls across
some axes (``physics.bc`` of the configuration, one entry per axis) and
a streamwise forcing (``physics.forcing``): the wall-bounded channel.
Everything the harness does that depends on this kind of grid; the
adapter's interface is the one ``grids/uniform.py`` lists.

The plain reference is ``lib/reference_channel.py``.  A flow with no
body: chi is 0 everywhere, so the fluid's divergence is read on every
cell.

What this one takes from the program (``drive.need``): ``grid.shape``,
``grid.h``; ``sim.state`` (vel, p) and ``sim.dt`` for the probe.
"""

from __future__ import annotations

import numpy as np

from benchmarks.lib import counts, reference_channel as rc
from benchmarks.lib.drive import need


def cells(grid) -> int:
    return int(np.prod(need(grid, "shape")))


def host(driver, array):
    return np.asarray(array)


def geometry(driver, config) -> dict:
    phys = config["physics"]
    return {"h": float(need(need(driver.sim, "grid"), "h")),
            "bc": tuple(phys["bc"]), "forcing": dict(phys["forcing"])}


#: faults only a walled, forced grid has, each planted in the reference
#: that is put in the program's place (``compare.control_link``'s
#: ``on``): the keyword arguments of :class:`Reference`
FAULTS = {
    "y_periodic": {"bc": (rc.PERIODIC, rc.PERIODIC, rc.PERIODIC)},
    "no_flux": {"forcing": {"kind": None}},
    "ghost_copy": {"ghosts": "copy"},
}


class Reference:
    """``reference_channel.py`` on one dense array of spacing ``h``;
    ``fault`` overrides the channel's ``bc``, ``forcing`` or ``ghosts``
    (a planted fault)."""

    def __init__(self, geom, **fault):
        self.h = self.h_finest = float(geom["h"])
        self.channel = rc.Channel(
            self.h, fault.get("bc", geom["bc"]),
            fault.get("forcing", geom["forcing"]),
            fault.get("ghosts", "negate"))

    def check(self, field):
        """Cells of ``field`` that the numbers run over: all of them."""
        return int(np.prod(np.shape(field)[:3]))

    def one_step(self, u0, dt, nu, uinf, bodies, lam_dt, store):
        if bodies:
            raise SystemExit("benchmark: the channel's reference has no "
                             "body")
        return self.channel.one_step(u0, dt, nu, uinf, store)

    def gradient(self, p):
        return self.channel.gradient(p)

    def laplacian(self, p):
        return self.channel.laplacian(p)

    def divergence(self, u):
        return self.channel.divergence(u)

    def fluid_divergence_max(self, u, chi):
        """Largest ``|div u|`` over every cell (no body)."""
        return float(np.abs(self.channel.divergence(u)).max())

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


def live_system(driver, p_before):
    """(rhs, x0, solver keywords) of the last step's pressure equation,
    on the device: ``div u_f / dt`` with the forced velocity recovered as
    ``u + dt grad p``, on the program's ghosts (velocity minus the edge
    cell across a wall, pressure the edge cell) and chi 0."""
    import jax.numpy as jnp

    d = driver.sim
    h, dt = float(need(d.grid, "h")), float(need(d, "dt"))
    vel, p = (need(d.state, k) for k in ("vel", "p"))
    bc = tuple(str(getattr(b, "value", b)) for b in need(d.grid, "bc"))

    def pad(a, sign):
        for axis, b in enumerate(bc):
            pads = [(0, 0)] * a.ndim
            pads[axis] = (1, 1)
            if b == rc.PERIODIC:
                a = jnp.pad(a, pads, mode="wrap")
                continue
            a = jnp.pad(a, pads, mode="edge")
            lo = [slice(None)] * a.ndim
            hi = [slice(None)] * a.ndim
            lo[axis], hi[axis] = slice(0, 1), slice(-1, None)
            a = a.at[tuple(lo)].multiply(sign).at[tuple(hi)].multiply(sign)
        return a

    def d1(ap, axis):
        hi = [slice(1, -1)] * 3
        lo = [slice(1, -1)] * 3
        hi[axis], lo[axis] = slice(2, None), slice(0, -2)
        return (ap[tuple(hi)] - ap[tuple(lo)]) / (2.0 * h)

    pp = pad(p, 1.0)
    u_f = vel + dt * jnp.stack([d1(pp, c) for c in range(3)], axis=-1)
    div = sum(d1(pad(u_f[..., c], -1.0), c) for c in range(3))
    return div / dt, p_before, {}


def iteration_work(grid) -> dict:
    """Walls change no byte of the iteration: 80 B and 48 flop an
    unknown, as on the periodic grid."""
    return counts.bicgstab_iteration(cells(grid))


def counters(obs: dict) -> dict:
    return {}
