"""Plain reference of one time step of a towed rigid sphere in a
free-space box, in NumPy float64.

Imports nothing of ``cup3d_tpu`` and no JAX.  A dense uniform grid of
spacing ``h`` whose cell ``(i, j, k)`` is centred at ``((i, j, k) + 1/2)
h``; every face of the box is free space (upstream BlockLab's far-field
condition):

- velocity ghosts: the face-normal component is minus the edge cell (no
  flow through the face), the two tangential components are the edge
  cell (free slip), for every ghost layer;
- pressure ghosts are the edge cell (zero gradient);
- advection-diffusion as ``reference.py`` has it (5th-order biased
  upwind on ``u + uinf``, 7-point Laplacian, low-storage RK3) on those
  ghosts (``reference_channel.Channel`` with the ghosts above);
- chi built here, from the sphere's analytic signed distance ``R - |x -
  c|`` at the centre the step starts from, by the Towers construction
  (the mollified Heaviside upstream's KernelCharacteristicFunction
  builds): the sharp indicator outside ``|sdf| <= h``, and inside it
  ``(grad I+ . grad sdf) / |grad sdf|^2`` with ``I+ = max(sdf, 0)`` and
  centred differences;
- the rigid update of a body that may be forced: the chi-weighted fluid
  momenta of the advected velocity give ``u_T = P / m`` and ``omega =
  J^-1 L`` (``reference.rigid_update``), a forced component keeps its
  prescribed value and a blocked rotation its own; the centre of mass is
  measured from chi and moved on by ``dt (u_T + uinf)``;
- penalisation towards ``u_T + omega x (x - cm)`` with ``lambda dt =
  DLM``, and the momentum it injects: the penalisation force ``-sum (u_pen
  - u_adv) h^3 / dt`` (minus the fluid's gain, which the body feels);
- the right-hand side ``div u_pen / dt`` (a rigid body deforms nowhere);
- the pressure equation solved exactly: the DCT-II along each axis
  diagonalises the 7-point Laplacian with zero-gradient ghosts; the zero
  mode is dropped, which fixes the gauge by the mean;
- the centred-gradient projection.
"""

from __future__ import annotations

import numpy as np

from . import reference as ref
from . import reference_channel as rc

FREE = "freespace"
PERIODIC = rc.PERIODIC


class FreeSpace(rc.Channel):
    """The operators of a box with free-space faces (``bc`` per axis, a
    planted fault may make some periodic).  ``ghosts``: ``"negate"``
    flips the face-normal velocity component; ``"copy"`` copies it (a
    planted fault)."""

    def __init__(self, h, bc=(FREE, FREE, FREE), ghosts="negate"):
        super().__init__(h, bc, None, ghosts)

    def pad(self, a, width, vector=False):
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
                    idx[axis], idx[-1] = side, axis
                    a[tuple(idx)] *= -1.0
        return a


def sphere_box(shape, h, centre, radius, margin=3):
    """The cells of a box that holds the sphere, its chi band and
    ``margin`` cells more, clipped to the grid: (slices, cell centres)."""
    lo = np.maximum(np.floor((centre - radius) / h).astype(int) - margin, 0)
    hi = np.minimum(np.ceil((centre + radius) / h).astype(int) + margin + 1,
                    shape)
    box = tuple(slice(int(a), int(b)) for a, b in zip(lo, hi))
    axes = [(np.arange(s.start, s.stop) + 0.5) * h for s in box]
    return box, np.stack(np.meshgrid(*axes, indexing="ij"), axis=-1)


def towers_chi(sdf_lab, h):
    """chi of the cells inside a signed distance given with one ghost
    cell on every side (> 0 inside)."""
    c = sdf_lab[1:-1, 1:-1, 1:-1]
    num = grad2 = 0.0
    for axis in range(3):
        hi = [slice(1, -1)] * 3
        lo = [slice(1, -1)] * 3
        hi[axis], lo[axis] = slice(2, None), slice(0, -2)
        p, m = sdf_lab[tuple(hi)], sdf_lab[tuple(lo)]
        grad2 = grad2 + (p - m) ** 2
        num = num + (np.maximum(p, 0.0) - np.maximum(m, 0.0)) * (p - m)
    band = num / (grad2 + 1e-300)
    return np.where(c > h, 1.0, np.where(c < -h, 0.0, band))


def sphere_chi(shape, h, centre, radius):
    """(box, chi on the box): chi of the sphere, zero outside the box."""
    box, x = sphere_box(shape, h, centre, radius)
    # the signed distance on the box and one cell more on every side
    axes = [(np.arange(s.start - 1, s.stop + 1) + 0.5) * h for s in box]
    xl = np.stack(np.meshgrid(*axes, indexing="ij"), axis=-1)
    sdf = radius - np.linalg.norm(xl - centre, axis=-1)
    return box, x, towers_chi(sdf, h)


def sphere_volume(radius):
    return 4.0 / 3.0 * np.pi * radius ** 3


class TowedSphere:
    """One step of the box with one sphere in it.  ``body``: ``radius``,
    and as the step starts ``pos`` (the centre chi is placed at),
    ``trans``, ``ang``, ``cm`` (where the moments are taken), and the
    ``forced`` and ``blocked`` masks (3 booleans each).  ``chi_shift``
    (cells along x) places chi off the body: a planted fault."""

    def __init__(self, h, body, bc=(FREE, FREE, FREE), ghosts="negate",
                 chi_shift=0):
        self.box_ops = FreeSpace(h, bc, ghosts)
        self.h = float(h)
        self.body = body
        self.chi_shift = int(chi_shift)

    def chi(self, shape):
        """(box, cell centres of the box, chi on the box)."""
        b = self.body
        centre = np.asarray(b["pos"], np.float64) \
            + np.array([self.chi_shift * self.h, 0.0, 0.0])
        return sphere_chi(shape, self.h, centre, float(b["radius"]))

    def one_step(self, u0, dt, nu, uinf, lam_dt, store=lambda x: x):
        """The stages above; ``store`` rounds each as a run in that
        precision would keep it.  Returns the end velocity ``u1``, ``p``,
        ``rhs``, ``u_pen``, chi on the whole grid, and the body's
        ``trans``, ``ang``, ``cm`` after the update and ``pen_force``."""
        b, h = self.body, self.h
        ops = self.box_ops
        uinf = np.asarray(uinf, np.float64)
        u_adv = ops.rk3_step(u0, dt, nu, uinf, store)
        box, x, chi_b = self.chi(u0.shape[:3])
        free = ref.rigid_update(x, h, chi_b, u_adv[box],
                                np.asarray(b["cm"], np.float64), uinf, dt)
        trans = np.where(b["forced"], b["trans"], free["trans"])
        ang = np.where(b["blocked"], b["ang"], free["ang"])
        cm = free["cm"] - dt * (free["trans"] - trans)
        ubody = trans + np.cross(np.broadcast_to(ang, x.shape), x - cm)
        u_pen = u_adv.copy()
        u_pen[box] = ref.penalize(u_adv[box], chi_b, ubody, lam_dt)
        u_pen = store(u_pen)
        pen_force = -np.sum(u_pen[box] - u_adv[box], axis=(0, 1, 2)) \
            * h ** 3 / dt
        rhs = store(ops.divergence(u_pen) / dt)
        p = store(ops.poisson(rhs))
        u1 = store(u_pen - dt * ops.gradient(p))
        chi = np.zeros(u0.shape[:3])
        chi[box] = chi_b
        return {"u1": u1, "p": p, "rhs": rhs, "u_pen": u_pen, "chi": chi,
                "trans": trans, "ang": ang, "cm": cm,
                "pen_force": pen_force, "rigid": []}
