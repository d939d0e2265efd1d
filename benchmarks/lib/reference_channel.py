"""Plain reference of one time step of the forced channel, in NumPy
float64.

Imports nothing of ``cup3d_tpu`` and no JAX.  A dense uniform grid of
spacing ``h``, periodic along x and z, no-slip walls at the two faces of
y (each axis's boundary is named in ``bc``):

- velocity ghosts across a wall are minus the edge cell, for every
  component and every ghost layer (the copy-edge convention of the
  upstream solver's BlockLab): the face value, the mean of the edge cell
  and its ghost, is 0;
- pressure ghosts across a wall are the edge cell (zero gradient);
- advection-diffusion as ``reference.py`` has it (5th-order biased
  upwind, 7-point Laplacian, low-storage RK3) on those ghosts;
- the streamwise forcing as the program documents it
  (``sim/operators.py::forcing_stage``): FixMassFlux measures the bulk
  velocity (the mean of u_x + uinf_x over the cells) and adds the
  deficit against 2/3 uMax_forced times the parabola 6 eta (1 - eta)
  normalised to a mean of exactly 1 over the cell centres, restoring the
  deficit exactly (where upstream's ``main.cpp`` restores six times
  it); ExternalForcing adds 8 nu uMax_forced / L_y^2 dt;
- the pressure equation ``lap p = div u / dt`` solved exactly: the FFT
  along the periodic axes and the DCT-II along a walled one diagonalise
  the 7-point Laplacian with zero-gradient ghosts; the zero mode is
  dropped, which fixes the gauge by the mean and leaves out the part of
  the right-hand side no pressure can balance;
- the centred-gradient projection.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from scipy import fft as sfft

from . import reference as ref

PERIODIC, WALL = "periodic", "wall"
WORKERS = os.cpu_count() or 1


class Channel:
    """The operators of one channel grid.  ``forcing``: ``{"kind":
    "FixMassFlux" | "ExternalForcing" | None, "uMax_forced": ...}``.
    ``ghosts``: ``"negate"`` is the no-slip wall; ``"copy"`` copies the
    edge cell into the velocity ghosts (a planted fault)."""

    def __init__(self, h, bc=(PERIODIC, WALL, PERIODIC), forcing=None,
                 ghosts="negate"):
        self.h = float(h)
        self.bc = tuple(bc)
        self.forcing = forcing or {"kind": None}
        self.ghosts = ghosts

    # -- ghosts ------------------------------------------------------------
    def pad(self, a, width, vector=False):
        """``a`` (a scalar, or with ``vector`` a (..., 3) field) padded
        by ``width`` ghost cells on every face of every axis."""
        for axis, bc in enumerate(self.bc):
            pads = [(0, 0)] * a.ndim
            pads[axis] = (width, width)
            if bc == PERIODIC:
                a = np.pad(a, pads, mode="wrap")
                continue
            a = np.pad(a, pads, mode="edge")
            if vector and self.ghosts == "negate":
                for side in (slice(0, width), slice(-width, None)):
                    idx = [slice(None)] * a.ndim
                    idx[axis] = side
                    a[tuple(idx)] *= -1.0
        return a

    def _central(self, ap, axis):
        """Centred difference along ``axis`` of a copy padded by 1."""
        hi = [slice(1, -1)] * 3
        lo = [slice(1, -1)] * 3
        hi[axis] = slice(2, None)
        lo[axis] = slice(0, -2)
        return (ap[tuple(hi)] - ap[tuple(lo)]) / (2.0 * self.h)

    def divergence(self, u):
        up = self.pad(u, 1, vector=True)
        return sum(self._central(up[..., c], c) for c in range(3))

    def gradient(self, p):
        pp = self.pad(p, 1)
        return np.stack([self._central(pp, c) for c in range(3)], axis=-1)

    def laplacian(self, p):
        pp = self.pad(p, 1)
        c = pp[1:-1, 1:-1, 1:-1]
        out = -6.0 * c
        for axis in range(3):
            for k in (-1, 1):
                idx = [slice(1, -1)] * 3
                idx[axis] = slice(1 + k, pp.shape[axis] - 1 + k)
                out = out + pp[tuple(idx)]
        return out / (self.h * self.h)

    # -- the step's stages -------------------------------------------------
    def advection_diffusion_rhs(self, u, nu, uinf):
        """``nu lap u - ((u + uinf) . grad) u`` on the channel's ghosts,
        x cut into slabs that threads work through side by side."""
        up = self.pad(u, 3, vector=True)
        n = u.shape[0]
        cuts = np.linspace(0, n, min(WORKERS, n) + 1).astype(int)
        with ThreadPoolExecutor(len(cuts) - 1) as pool:
            parts = pool.map(
                lambda ab: ref._advdiff_slab(up[ab[0]:ab[1] + 6], nu, uinf,
                                             self.h),
                zip(cuts[:-1], cuts[1:]))
            return np.concatenate(list(parts), axis=0)

    def rk3_step(self, u, dt, nu, uinf, store=lambda x: x):
        k = np.zeros_like(u)
        for a, b in zip(ref.RK3_A, ref.RK3_B):
            k = store(a * k + dt * self.advection_diffusion_rhs(u, nu, uinf))
            u = store(u + b * k)
        return u

    def force(self, u, uinf, dt, nu):
        """The configured streamwise forcing of ``u``."""
        kind = self.forcing.get("kind")
        if kind is None:
            return u
        u = u.copy()
        umax = float(self.forcing["uMax_forced"])
        if kind == "FixMassFlux":
            bulk = float(u[..., 0].mean()) + float(uinf[0])
            ny = u.shape[1]
            eta = (np.arange(ny) + 0.5) / ny
            prof = 6.0 * eta * (1.0 - eta)
            u[..., 0] += (2.0 / 3.0 * umax - bulk) \
                * (prof / prof.mean())[None, :, None]
        elif kind == "ExternalForcing":
            ly = u.shape[1] * self.h
            u[..., 0] += 8.0 * nu * umax / (ly * ly) * dt
        else:
            raise ValueError(f"unknown forcing {kind!r}")
        return u

    def poisson(self, rhs):
        """Exact zero-mean solution of the 7-point Poisson equation with
        the channel's ghosts (periodic: FFT; wall: DCT-II)."""
        f = np.asarray(rhs, np.float64)
        lam = 0.0
        for axis, (n, bc) in enumerate(zip(f.shape, self.bc)):
            k = np.arange(n)
            theta = (2.0 * np.pi * k / n) if bc == PERIODIC \
                else (np.pi * k / n)
            ev = (2.0 * np.cos(theta) - 2.0) / (self.h * self.h)
            lam = lam + ev.reshape([-1 if a == axis else 1
                                    for a in range(3)])
        walls = [a for a, bc in enumerate(self.bc) if bc != PERIODIC]
        periodic = [a for a, bc in enumerate(self.bc) if bc == PERIODIC]
        for a in walls:
            f = sfft.dct(f, type=2, norm="ortho", axis=a, workers=WORKERS)
        f = sfft.fftn(f, axes=periodic, workers=WORKERS)
        zero = lam == 0.0
        f = f / np.where(zero, 1.0, lam)
        f[zero] = 0.0
        f = sfft.ifftn(f, axes=periodic, workers=WORKERS).real
        for a in walls:
            f = sfft.idct(f, type=2, norm="ortho", axis=a, workers=WORKERS)
        return f

    def one_step(self, u0, dt, nu, uinf, store=lambda x: x):
        """RK3 advection-diffusion, the forcing, the exact solve, the
        projection.  ``u_pen`` is the velocity the projection starts
        from (the forced one), as ``compare.link_numbers`` reads it."""
        uinf = np.asarray(uinf, np.float64)
        u_adv = self.rk3_step(u0, dt, nu, uinf, store)
        u_f = store(self.force(u_adv, uinf, dt, nu))
        rhs = store(self.divergence(u_f) / dt)
        p = store(self.poisson(rhs))
        u1 = store(u_f - dt * self.gradient(p))
        return {"u1": u1, "p": p, "rhs": rhs, "u_pen": u_f, "rigid": []}
