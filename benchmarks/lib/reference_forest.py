"""Plain reference of one time step on an octree of cubic blocks, in NumPy
float64.

Imports nothing of ``cup3d_tpu``.  The step is the published one of
``reference.py`` (RK3 advection-diffusion with the 5th-order biased-upwind
derivative and the 7-point Laplacian, the rigid update from the momenta
each body covers, implicit penalisation, the right-hand side
``(div u - chi div u_def) / dt``, the Poisson equation, the
centred-gradient projection); what is new is the grid: leaves of several
levels, each a block of ``bs^3`` cells, on a periodic box.

The composite grid is held as one dense periodic array per level
(``Forest.fill``):

- cells of a level's own leaves hold their values;
- cells under finer leaves hold the 8-to-1 average of the finer level;
- cells under coarser leaves hold the separable quadratic interpolation
  of the coarser level's array: a fine cell's centre lies a quarter of a
  coarse cell off its parent's, and along each axis the value is the
  parabola through the parent and its two neighbours, taken there.

A uniform stencil of ``reference.py`` on a level's array, read on that
level's leaves, is then the composite stencil.  The conservative
operators (Laplacian, divergence, the diffusive part of
advection-diffusion) are corrected on the coarse side of every
coarse-fine face: the coarse cell's flux through that face is replaced
by the mean of the four fine fluxes through it, so that what leaves one
side enters the other.

The pressure equation is solved here, by BiCGSTAB in float64 on the
composite operator, preconditioned by the exact periodic solve on the
finest level (values injected up, the answer averaged down), to a
relative residual of 1e-10, volume-weighted means removed.
"""

from __future__ import annotations

import numpy as np

from . import reference as ref


def _parabola_weights(offset):
    """Weights on (parent - 1, parent, parent + 1), one coarse cell apart,
    of the parabola through them, taken ``offset`` coarse cells off the
    parent's centre."""
    t = float(offset)
    return (0.5 * t * (t - 1.0), 1.0 - t * t, 0.5 * t * (t + 1.0))


LOW_CHILD = _parabola_weights(-0.25)
HIGH_CHILD = _parabola_weights(0.25)


def restrict(a):
    """8-to-1 average: a level's array from the next finer one."""
    n = a.shape[0] // 2, a.shape[1] // 2, a.shape[2] // 2
    v = a.reshape((n[0], 2, n[1], 2, n[2], 2) + a.shape[3:])
    return v.mean(axis=(1, 3, 5))


def prolong_quadratic(a):
    """The next finer level's array by the separable quadratic
    interpolation, periodic."""
    for axis in range(3):
        lo = sum(w * ref.at(a, axis, k) for w, k in zip(LOW_CHILD, (-1, 0, 1)))
        hi = sum(w * ref.at(a, axis, k) for w, k in zip(HIGH_CHILD, (-1, 0, 1)))
        both = np.stack([lo, hi], axis=axis + 1)
        shape = list(a.shape)
        shape[axis] *= 2
        a = both.reshape(shape)
    return a


def prolong_inject(a):
    """The next finer level's array by copying each cell to its eight
    children (a preconditioner's transfer; as a ghost fill it is the
    planted fault ``ghost_inject``)."""
    for axis in range(3):
        a = np.repeat(a, 2, axis=axis)
    return a


class Forest:
    """The leaves of an octree of ``bs^3``-cell blocks on a periodic box
    whose level 0 has ``blocks0`` blocks per axis of spacing ``h0``.
    ``leaves``: one row ``(level, i, j, k)`` per block, in the order of
    the fields' first axis.  ``reflux``, ``prolong`` and ``solve`` exist
    for the planted faults; a sound reference leaves them alone (without
    the flux correction the pressure equation has no solution, and a
    program with that fault stops at its own tolerance or cap: ``solve``
    is ``(relative residual, iterations, whether missing it raises)``)."""

    def __init__(self, leaves, blocks0, bs, h0, reflux=True,
                 prolong=prolong_quadratic, solve=(1e-10, 400, True)):
        leaves = np.asarray(leaves, np.int64).reshape(-1, 4)
        self.leaves, self.bs, self.h0 = leaves, int(bs), float(h0)
        self.blocks0 = tuple(int(b) for b in blocks0)
        self.nb = len(leaves)
        self.level = leaves[:, 0]
        self.lmin, self.lmax = int(self.level.min()), int(self.level.max())
        self.levels = range(self.lmin, self.lmax + 1)
        self.reflux, self.prolong, self.solve = reflux, prolong, solve
        self.h = self.h0 / (1 << self.level).astype(np.float64)
        self.hcol = self.h.reshape(-1, 1, 1, 1)
        self.vol = np.broadcast_to(self.hcol ** 3, (self.nb,) + (bs,) * 3)
        self.volume = float(self.vol.sum())
        loc = np.stack(np.meshgrid(*[np.arange(bs) + 0.5] * 3,
                                   indexing="ij"), axis=-1)
        self.x = (leaves[:, None, None, None, 1:] * bs + loc[None]) \
            * self.h.reshape(-1, 1, 1, 1, 1)
        self.rows = {l: np.flatnonzero(self.level == l) for l in self.levels}
        # which level owns each cell of the finest level's array
        owner = np.full(self.shape(self.lmax), -1, np.int64)
        for l in self.levels:
            view = self._blocks(owner, bs << (self.lmax - l))
            i, j, k = self._ijk(l)
            if (view[i, j, k] != -1).any():
                raise ValueError("forest: two leaves cover one cell")
            view[i, j, k] = l
        if (owner < 0).any():
            raise ValueError("forest: the leaves do not cover the box")
        self.own, self.finer, self.coarser = {}, {}, {}
        for l in self.levels:
            s = 1 << (self.lmax - l)
            lev = owner[::s, ::s, ::s]
            # under a finer leaf the sample is that leaf's level (> l)
            self.own[l], self.finer[l], self.coarser[l] = \
                lev == l, lev > l, lev < l

    # -- leaves <-> dense level arrays --------------------------------------
    def shape(self, l):
        return tuple((b * self.bs) << l for b in self.blocks0)

    def h_of(self, l):
        return self.h0 / (1 << l)

    def _ijk(self, l):
        """Block indices of level ``l``'s leaves, in the order of
        ``rows[l]``."""
        return self.leaves[self.rows[l], 1:].T

    @staticmethod
    def _blocks(dense, size):
        """View of a level's array as blocks of ``size`` cells per
        axis: ``[i, j, k]`` is one block."""
        n = [s // size for s in dense.shape[:3]]
        v = dense.reshape((n[0], size, n[1], size, n[2], size)
                          + dense.shape[3:])
        return np.moveaxis(v, (2, 4), (1, 2))

    def fill(self, field):
        """``{level: dense array}`` of a field given on the leaves,
        ``(nb, bs, bs, bs[, c])``: own leaves, averages of finer leaves,
        interpolation of coarser ones."""
        field = np.asarray(field, np.float64)
        if field.shape[:4] != (self.nb,) + (self.bs,) * 3:
            raise ValueError(f"forest: field of shape {field.shape} on "
                             f"{self.nb} blocks of {self.bs}^3")
        comp = field.shape[4:]
        dense = {}
        for l in reversed(self.levels):
            a = (restrict(dense[l + 1]) if l < self.lmax
                 else np.zeros(self.shape(l) + comp))
            i, j, k = self._ijk(l)
            self._blocks(a, self.bs)[i, j, k] = field[self.rows[l]]
            dense[l] = a
        for l in self.levels:
            if l > self.lmin and self.coarser[l].any():
                m = self.coarser[l]
                dense[l][m] = self.prolong(dense[l - 1])[m]
        return dense

    def read(self, dense):
        """The leaves' values out of ``{level: dense array}``."""
        comp = dense[self.lmax].shape[3:]
        out = np.empty((self.nb,) + (self.bs,) * 3 + comp,
                       dense[self.lmax].dtype)
        for l in self.levels:
            i, j, k = self._ijk(l)
            out[self.rows[l]] = self._blocks(dense[l], self.bs)[i, j, k]
        return out

    # -- norms and means over the leaves' cells ------------------------------
    def wsum(self, a):
        return float(np.sum(np.asarray(a, np.float64) * self.vol))

    def wmean(self, a):
        return self.wsum(a) / self.volume

    def norm(self, a):
        """Volume-weighted 2-norm over every leaf cell (components
        summed)."""
        a = np.asarray(a, np.float64)
        sq = np.square(a) if a.ndim == 4 else np.square(a).sum(axis=-1)
        return float(np.sqrt(np.sum(sq * self.vol)))

    # -- the flux correction -------------------------------------------------
    @staticmethod
    def _pool_face(flux, axis, parity):
        """Mean of the four fine face fluxes over each coarse face: the
        planes of the given parity along ``axis``, 2x2 means across it."""
        n = [s // 2 for s in flux.shape[:3]]
        v = flux.reshape((n[0], 2, n[1], 2, n[2], 2) + flux.shape[3:])
        pick = [slice(None)] * 6
        pick[2 * axis + 1] = slice(parity, parity + 1)
        return v[tuple(pick)].mean(axis=(1, 3, 5))

    def _refluxed(self, out, dense, low_flux, high_flux):
        """``out`` (per level, ``sum of outward fluxes / h``) with the
        coarse side of every coarse-fine face corrected.  ``low_flux(a,
        axis, h)`` and ``high_flux`` give a dense array's outward flux
        per unit area through each cell's low and high face."""
        if not self.reflux:
            return out
        for l in self.levels:
            if l == self.lmax or not self.finer[l].any():
                continue
            h, hf = self.h_of(l), self.h_of(l + 1)
            for axis in range(3):
                # fine cells just above a coarse cell: their low faces
                fine_lo = self._pool_face(low_flux(dense[l + 1], axis, hf),
                                          axis, 0)
                # fine cells just below a coarse cell: their high faces
                fine_hi = self._pool_face(high_flux(dense[l + 1], axis, hf),
                                          axis, 1)
                up = self.own[l] & ref.at(self.finer[l], axis, 1)
                dn = self.own[l] & ref.at(self.finer[l], axis, -1)
                mine_hi = high_flux(dense[l], axis, h)
                mine_lo = low_flux(dense[l], axis, h)
                # the fine side's outward flux is the coarse side's
                # inward one
                out[l][up] += (-ref.at(fine_lo, axis, 1)[up]
                               - mine_hi[up]) / h
                out[l][dn] += (-ref.at(fine_hi, axis, -1)[dn]
                               - mine_lo[dn]) / h
        return out

    # -- operators -----------------------------------------------------------
    def laplacian(self, p):
        dense = self.fill(p)
        out = {l: ref.laplacian(dense[l], self.h_of(l)) for l in self.levels}
        out = self._refluxed(
            out, dense,
            lambda a, ax, h: (ref.at(a, ax, -1) - a) / h,
            lambda a, ax, h: (ref.at(a, ax, 1) - a) / h)
        return self.read(out)

    def divergence(self, u, reflux=True):
        dense = self.fill(u)
        out = {l: ref.divergence(dense[l], self.h_of(l))
               for l in self.levels}
        if reflux:
            out = self._refluxed(
                out, dense,
                lambda a, ax, h: -0.5 * (a[..., ax] + ref.at(a[..., ax],
                                                             ax, -1)),
                lambda a, ax, h: 0.5 * (a[..., ax] + ref.at(a[..., ax],
                                                            ax, 1)))
        return self.read(out)

    def gradient(self, p):
        dense = self.fill(p)
        return self.read({l: ref.gradient(dense[l], self.h_of(l))
                          for l in self.levels})

    def advection_diffusion_rhs(self, u, nu, uinf):
        """``nu lap u - ((u + uinf) . grad) u``; the diffusive fluxes are
        corrected at coarse-fine faces, the advective derivative is not
        in flux form and is left as it is."""
        dense = self.fill(u)
        out = {l: ref.advection_diffusion_rhs(dense[l], nu, uinf,
                                              self.h_of(l))
               for l in self.levels}
        out = self._refluxed(
            out, dense,
            lambda a, ax, h: nu * (ref.at(a, ax, -1) - a) / h,
            lambda a, ax, h: nu * (ref.at(a, ax, 1) - a) / h)
        return self.read(out)

    def rk3_step(self, u, dt, nu, uinf, store=lambda x: x):
        k = np.zeros_like(u)
        for a, b in zip(ref.RK3_A, ref.RK3_B):
            k = store(a * k + dt * self.advection_diffusion_rhs(u, nu, uinf))
            u = store(u + b * k)
        return u

    def fluid_blocks(self, chi, eps=1e-6):
        """The leaves that lie in the fluid, as the forest's divergence
        gate counts them: a block none of whose cells, and none of the
        cells one across each of its faces, has chi (of finer and coarser
        neighbours as ``fill`` gives it) of ``eps`` or more."""
        dense = self.fill(chi)
        fluid = np.empty(self.nb, bool)
        for l in self.levels:
            band = dense[l] >= eps
            near = band.copy()
            # one cell across a face, and not across an edge or a corner
            for axis in range(3):
                near |= ref.at(band, axis, 1) | ref.at(band, axis, -1)
            touched = self._blocks(near, self.bs).any(axis=(3, 4, 5))
            i, j, k = self._ijk(l)
            fluid[self.rows[l]] = ~touched[i, j, k]
        return fluid

    def fluid_divergence_max(self, u, chi):
        """Largest ``|div u|`` (the centred divergence, uncorrected, as
        the gate's source takes it) over the leaves in the fluid."""
        fluid = self.fluid_blocks(chi)
        if not fluid.any():
            return 0.0
        return float(np.abs(self.divergence(u, reflux=False))[fluid].max())

    # -- the pressure equation -----------------------------------------------
    def _to_finest(self, r):
        """Leaf values copied onto the finest level's array."""
        a = None
        for l in self.levels:
            a = (np.zeros(self.shape(l)) if a is None else prolong_inject(a))
            i, j, k = self._ijk(l)
            self._blocks(a, self.bs)[i, j, k] = r[self.rows[l]]
        return a

    def _from_finest(self, a):
        dense = {self.lmax: a}
        for l in reversed(self.levels):
            if l < self.lmax:
                dense[l] = restrict(dense[l + 1])
        return self.read(dense)

    def _precondition(self, r):
        return self._from_finest(
            ref.poisson_fft(self._to_finest(r), self.h_of(self.lmax)))

    def poisson(self, rhs):
        """Zero-mean solution of ``laplacian(p) = rhs - mean(rhs)`` (means
        by volume), by right-preconditioned BiCGSTAB."""
        rtol, maxiter, strict = self.solve
        dot = lambda a, b: float(np.sum(a * b))
        b = np.asarray(rhs, np.float64) - self.wmean(rhs)
        target = rtol * np.sqrt(dot(b, b))
        x = np.zeros_like(b)
        r = b.copy()
        if np.sqrt(dot(r, r)) <= target:
            return x
        r0, rho, alpha, omega = r.copy(), 1.0, 1.0, 1.0
        v = p = np.zeros_like(b)
        for _ in range(maxiter):
            rho_new = dot(r0, r)
            p = r + (rho_new / rho) * (alpha / omega) * (p - omega * v)
            rho = rho_new
            y = self._precondition(p)
            v = self.laplacian(y)
            alpha = rho / dot(r0, v)
            s = r - alpha * v
            z = self._precondition(s)
            t = self.laplacian(z)
            omega = dot(t, s) / dot(t, t)
            x = x + alpha * y + omega * z
            r = s - omega * t
            if np.sqrt(dot(r, r)) <= target:
                return x - self.wmean(x)
        if strict:
            raise RuntimeError("reference_forest: the pressure solve did "
                               f"not reach {rtol:g} in {maxiter} iterations")
        return x - self.wmean(x)


def one_step(u0, dt, nu, uinf, forest, bodies, lam_dt, store=lambda x: x):
    """``reference.one_step`` on the leaves of ``forest``: every field is
    ``(nb, bs, bs, bs[, 3])``.  A flow with no body is advected, diffused
    and projected."""
    x = forest.x
    u_adv = forest.rk3_step(u0, dt, nu, uinf, store)
    rigid = [ref.rigid_update(x, forest.hcol, b["chi"], u_adv,
                              b["cm_guess"], uinf, dt) for b in bodies]
    u_pen, rhs = u_adv, forest.divergence(u_adv) / dt
    if bodies:
        chi, ubody, udef = ref.body_velocity(x, bodies)
        u_pen = store(ref.penalize(u_adv, chi, ubody, lam_dt))
        rhs = (forest.divergence(u_pen)
               - chi * forest.divergence(udef, reflux=False)) / dt
    rhs = store(rhs)
    p = store(forest.poisson(rhs))
    u1 = store(u_pen - dt * forest.gradient(p))
    return {"u1": u1, "p": p, "rhs": rhs, "u_pen": u_pen, "rigid": rigid}
