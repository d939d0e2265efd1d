"""The forest of the adaptive driver (``sim/amr.py``): leaves of an
octree, each a block of ``bs^3`` cells, fields laid out ``(rows, bs, bs,
bs[, 3])`` with one row per leaf and, past them, the padding rows of the
capacity bucket.  Padding is dropped on the way to the host: it is never
counted as cells and never compared.  (What an adapter gives:
``grids/uniform.py``.)

What this one takes from the program (``drive.need``; the list at the top
of ``lib/drive.py`` has it too): ``grid.keys``, ``grid.nb``, ``grid.bs``;
for the solve probe ``sim._geom``, ``sim._tab1``, ``sim._ftab``,
``sim.state``, ``sim.dt`` and the program's own forest operators
``ops.amr_ops.grad_blocks`` and ``pressure_rhs_blocks``.  The box (blocks
of level 0 per axis, its extent) is the configuration's, not the
program's.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmarks.lib import counts, reference_forest as rf
from benchmarks.lib.drive import need


def cells(grid) -> int:
    return int(need(grid, "nb")) * int(need(grid, "bs")) ** 3


def host(driver, array):
    nb = int(need(need(driver.sim, "grid"), "nb"))
    a = np.asarray(array)
    if a.shape[0] < nb:
        raise SystemExit(f"benchmark: a field of {a.shape[0]} rows on a "
                         f"forest of {nb} leaves")
    return a[:nb]


def geometry(driver, config) -> dict:
    grid = need(driver.sim, "grid")
    leaves = np.array(need(grid, "keys"), np.int64).reshape(-1, 4)
    if len(leaves) != int(need(grid, "nb")):
        raise SystemExit("benchmark: the forest's leaf list and its count "
                         "of blocks disagree")
    bs = int(need(grid, "bs"))
    blocks0 = tuple(int(b) for b in need(config["driver"], "blocks0"))
    return {"leaves": leaves, "bs": bs, "blocks0": blocks0,
            "h0": float(config["physics"]["extent"]) / (blocks0[0] * bs)}


@functools.lru_cache(maxsize=8)
def _forest(leaves_bytes, blocks0, bs, h0):
    leaves = np.frombuffer(leaves_bytes, np.int64).reshape(-1, 4)
    return rf.Forest(leaves, blocks0, bs, h0)


class Reference:
    """``reference_forest.py`` on the leaves a capture lists.  ``forest``
    replaces the sound composite grid by one with a fault planted
    (``tests/faults.py``)."""

    def __init__(self, geom, forest=None):
        self.forest = forest or _forest(
            np.ascontiguousarray(geom["leaves"], np.int64).tobytes(),
            tuple(geom["blocks0"]), int(geom["bs"]), float(geom["h0"]))
        #: the length a centre-of-mass gap is counted in
        self.h_finest = self.forest.h_of(self.forest.lmax)

    def check(self, field):
        """Cells of ``field`` that the numbers run over: every cell of
        every leaf, and nothing else."""
        f = self.forest
        if np.shape(field)[:4] != (f.nb,) + (f.bs,) * 3:
            raise SystemExit(
                f"benchmark: a field of shape {np.shape(field)} is "
                f"compared on {f.nb} leaves of {f.bs}^3 cells")
        return f.nb * f.bs ** 3

    def one_step(self, u0, dt, nu, uinf, bodies, lam_dt, store):
        self.check(u0)
        return rf.one_step(u0, dt, nu, uinf, self.forest, bodies, lam_dt,
                           store=store)

    def gradient(self, p):
        return self.forest.gradient(p)

    def laplacian(self, p):
        return self.forest.laplacian(p)

    def divergence(self, u):
        return self.forest.divergence(u)

    def fluid_divergence_max(self, u, chi):
        """The forest's gate is stated by blocks: the largest ``|div u|``
        over the leaves that hold no chi and touch none across a face."""
        return self.forest.fluid_divergence_max(u, chi)

    def norm(self, a):
        return self.forest.norm(a)

    def mean(self, a):
        return self.forest.wmean(a)

    def volume(self, chi):
        return self.forest.wsum(chi)


def reference(geom) -> Reference:
    return Reference(geom)


def live_system(driver, p_before):
    """(rhs, x0, solver keywords) of the last step's pressure equation on
    the device, padded rows and all, as the driver's projection builds
    it: the program's own right-hand-side operator on the penalised
    velocity recovered as ``u + dt grad p``."""
    from cup3d_tpu.ops import amr_ops

    d = driver.sim
    geom, tab1, ftab = (need(d, k) for k in ("_geom", "_tab1", "_ftab"))
    dt = float(need(d, "dt"))
    vel, p, chi, udef = (need(d.state, k)
                         for k in ("vel", "p", "chi", "udef"))
    bs = int(need(geom, "bs"))
    gp = need(amr_ops, "grad_blocks")(
        geom, tab1.assemble_scalar(p, bs), tab1.width)
    rhs = need(amr_ops, "pressure_rhs_blocks")(
        geom, vel + dt * gp, dt, tab1, ftab, chi, udef)
    return rhs, p_before, {"tab_arg": tab1, "flux_arg": ftab}


def iteration_work(grid) -> dict:
    return counts.forest_bicgstab_iteration(cells(grid),
                                            bs=int(need(grid, "bs")))


def counters(obs: dict) -> dict:
    """Adaptation passes of the window: those that changed the mesh and
    those that left it as it was (the program's counters)."""
    return {k: obs.get(k, 0) for k in ("amr.regrids", "amr.regrid_noops")}
