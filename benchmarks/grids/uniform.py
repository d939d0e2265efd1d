"""The dense uniform periodic grid of the uniform driver
(``sim/simulation.py``): everything the harness does that depends on the
kind of grid.  A configuration names its adapter under ``driver.kind``;
``spec.load_grid`` finds this file by that name.

An adapter gives:

- ``cells(grid)``: leaf cells one step advances;
- ``host(driver, array)``: a field of the driver's state as the
  comparison holds it (host copy, nothing but cells that exist);
- ``geometry(driver, config)``: what a capture keeps of the grid;
- ``reference(geometry)``: the plain reference on that grid, as the
  operators, norms and the step ``compare.py`` calls;
- ``live_system(driver, p_before)``: the pressure system of the step
  just driven, for the solve probe, with the arguments the driver's
  solver wants beside it;
- ``iteration_work(grid)``: bytes and flops of one Krylov iteration
  on the driver's grid;
- ``counters(obs)``: what the program counted over the window that only
  this kind of grid has, printed in the result under ``grid``.

What this one takes from the program (``drive.need``): ``grid.shape``,
``grid.h``, ``grid.cell_centers``; ``sim.state`` (vel, p, chi, udef) and
``sim.dt`` for the probe.
"""

from __future__ import annotations

import numpy as np

from benchmarks.lib import counts, reference as ref
from benchmarks.lib.drive import need


def cells(grid) -> int:
    return int(np.prod(need(grid, "shape")))


def host(driver, array):
    return np.asarray(array)


def geometry(driver, config) -> dict:
    grid = need(driver.sim, "grid")
    return {"x": np.asarray(need(grid, "cell_centers")(np.float64)),
            "h": float(need(grid, "h"))}


class Reference:
    """``reference.py`` on one dense periodic array of spacing ``h``."""

    def __init__(self, geom):
        self.h = float(geom["h"])
        self.x = geom.get("x")
        #: the length a centre-of-mass gap is counted in
        self.h_finest = self.h

    def check(self, field):
        """Cells of ``field`` that the numbers run over: all of them."""
        return int(np.prod(np.shape(field)[:3]))

    def one_step(self, u0, dt, nu, uinf, bodies, lam_dt, store):
        return ref.one_step(u0, dt, nu, uinf, self.h, self.x, bodies,
                            lam_dt, store=store)

    def gradient(self, p):
        return ref.gradient(p, self.h)

    def laplacian(self, p):
        return ref.laplacian(p, self.h)

    def divergence(self, u):
        return ref.divergence(u, self.h)

    def fluid_divergence_max(self, u, chi):
        """Largest ``|div u|`` at least three cells from the chi band."""
        dv = np.abs(ref.divergence(u, self.h))
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


def live_system(driver, p_before):
    """(rhs, x0, solver keywords) of the last step's pressure equation,
    on the device: the right-hand side ``(div u_pen - chi div u_def) /
    dt`` with the penalised velocity recovered as ``u + dt grad p``."""
    import jax.numpy as jnp

    d = driver.sim
    h, dt = float(need(d.grid, "h")), float(need(d, "dt"))
    vel, p, chi, udef = (need(d.state, k)
                         for k in ("vel", "p", "chi", "udef"))

    def d1(a, axis):
        return (jnp.roll(a, -1, axis) - jnp.roll(a, 1, axis)) / (2.0 * h)

    div = lambda u: sum(d1(u[..., c], c) for c in range(3))
    u_pen = vel + dt * jnp.stack([d1(p, c) for c in range(3)], axis=-1)
    return (div(u_pen) - chi * div(udef)) / dt, p_before, {}


def iteration_work(grid) -> dict:
    return counts.bicgstab_iteration(cells(grid))


def counters(obs: dict) -> dict:
    return {}
