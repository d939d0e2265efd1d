"""Plain reference of one time step of the solver, in NumPy float64.

Imports nothing of ``cup3d_tpu``.  It follows the published scheme of the
upstream solver (CubismUP3D): low-storage RK3 advection-diffusion with the
5th-order biased-upwind advective derivative and the 7-point Laplacian,
the rigid update of each body from the fluid momenta it covers, implicit
Brinkman penalisation towards the body velocity, the pressure
right-hand side ``(div u - chi div u_def) / dt``, the 7-point Poisson
equation and the centred-gradient projection.  All boundaries periodic.

Fields live on dense arrays of the uniform periodic grid.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

RK3_A = (0.0, -5.0 / 9.0, -153.0 / 128.0)
RK3_B = (1.0 / 3.0, 15.0 / 16.0, 8.0 / 15.0)


def at(a: np.ndarray, axis: int, k: int) -> np.ndarray:
    """``a[i + k]`` along ``axis``, periodic."""
    return np.roll(a, -k, axis=axis)


def d1_central(a, axis, h):
    return (at(a, axis, 1) - at(a, axis, -1)) / (2.0 * h)


def _shifted(qp, axis, k, g=3):
    """View of ``q[i + k]`` along ``axis`` of an array padded by ``g``."""
    sl = [slice(g, -g)] * 3
    sl[axis] = slice(g + k, qp.shape[axis] - g + k)
    return qp[tuple(sl)]


def d1_upwind5(qp, axis, vel, h):
    """6-point biased-upwind derivative of the field whose copy padded by
    3 is ``qp``; side chosen by the sign of ``vel``."""
    m3, m2, m1, q, p1, p2, p3 = (_shifted(qp, axis, k)
                                 for k in range(-3, 4))
    plus = (-2.0 * m3 + 15.0 * m2 - 60.0 * m1 + 20.0 * q + 30.0 * p1
            - 3.0 * p2)
    minus = (2.0 * p3 - 15.0 * p2 + 60.0 * p1 - 20.0 * q - 30.0 * m1
             + 3.0 * m2)
    return np.where(vel > 0, plus, minus) / (60.0 * h)


def laplacian(a, h):
    out = -6.0 * a
    for axis in range(3):
        out = out + at(a, axis, 1) + at(a, axis, -1)
    return out / (h * h)


def divergence(u, h):
    return sum(d1_central(u[..., c], c, h) for c in range(3))


def gradient(p, h):
    return np.stack([d1_central(p, c, h) for c in range(3)], axis=-1)


def _advdiff_slab(up, nu, uinf, h):
    """The rate on the interior of a slab padded by 3 on every side."""
    uadv = [up[3:-3, 3:-3, 3:-3, c] + uinf[c] for c in range(3)]
    out = []
    for c in range(3):
        qp = up[..., c]
        adv = sum(uadv[a] * d1_upwind5(qp, a, uadv[a], h) for a in range(3))
        lap = sum(_shifted(qp, a, 1) + _shifted(qp, a, -1)
                  for a in range(3)) - 6.0 * qp[3:-3, 3:-3, 3:-3]
        out.append(nu * lap / (h * h) - adv)
    return np.stack(out, axis=-1)


def advection_diffusion_rhs(u, nu, uinf, h, slabs=8):
    """``nu lap u - ((u + uinf) . grad) u``, periodic; the x axis is cut
    into slabs that threads work through side by side (the stencils are
    local, so the result does not depend on the cut)."""
    up = np.pad(u, [(3, 3)] * 3 + [(0, 0)], mode="wrap")
    cuts = np.linspace(0, u.shape[0], min(slabs, u.shape[0]) + 1).astype(int)
    with ThreadPoolExecutor(len(cuts) - 1) as pool:
        parts = pool.map(
            lambda ab: _advdiff_slab(up[ab[0]:ab[1] + 6], nu, uinf, h),
            zip(cuts[:-1], cuts[1:]))
        return np.concatenate(list(parts), axis=0)


def rk3_step(u, dt, nu, uinf, h, store=lambda x: x):
    """``store`` rounds what a lower-precision run would keep in memory."""
    k = np.zeros_like(u)
    for a, b in zip(RK3_A, RK3_B):
        k = store(a * k + dt * advection_diffusion_rhs(u, nu, uinf, h))
        u = store(u + b * k)
    return u


def body_velocity(x, bodies):
    """The body velocity ``uT + w x r + udef`` and the deformation
    velocity that the fluid is penalised towards, and chi: one body's own
    fields; of several bodies the chi-weighted mean where any chi is
    positive, and the largest chi."""
    chis = [np.asarray(b["chi"], np.float64) for b in bodies]
    total = sum(chis)
    den = np.where(total > 0, total, 1.0)[..., None]
    num_ub = num_ud = 0.0
    for b, chi in zip(bodies, chis):
        r = x - b["cm"]
        ub = b["trans"] + np.cross(np.broadcast_to(b["ang"], r.shape), r) \
            + b["udef"]
        num_ub = num_ub + chi[..., None] * ub
        num_ud = num_ud + chi[..., None] * b["udef"]
    return np.maximum.reduce(chis), num_ub / den, num_ud / den


def rigid_update(x, h, chi, u, cm_guess, uinf, dt):
    """The 6-DOF update of a free body from the chi-weighted momenta of
    the fluid it covers (upstream ``computeVelocities`` and ``update``):
    ``u_T = P / m``, ``omega = J^-1 L`` with the moments taken about
    ``cm_guess``, and the centre of mass measured from chi, moved on by
    ``dt (u_T + uinf)``."""
    w = (np.asarray(chi, np.float64) * h ** 3).reshape(-1)
    xf = np.asarray(x, np.float64).reshape(-1, 3)
    uf = np.asarray(u, np.float64).reshape(-1, 3)
    keep = w > 0
    w, xf, uf = w[keep], xf[keep], uf[keep]
    m = w.sum()
    r = xf - cm_guess
    lin = w @ uf
    ang = w @ np.cross(r, uf)
    inertia = (w @ (r * r).sum(-1)) * np.eye(3) \
        - np.einsum("n,na,nb->ab", w, r, r)
    trans = lin / m
    return {"trans": trans, "ang": np.linalg.solve(inertia, ang),
            "cm": (w @ xf) / m + dt * (trans + uinf), "mass": float(m),
            "gyration": float(np.sqrt(np.trace(inertia) / (2.0 * m)))}


def penalize(u, chi, ubody, lam_dt):
    x = lam_dt * chi
    return u + (x / (1.0 + x))[..., None] * (ubody - u)


def pressure_rhs(u, chi, udef, dt, h):
    return (divergence(u, h) - chi * divergence(udef, h)) / dt


def poisson_fft(rhs, h):
    """Exact zero-mean solution of the periodic 7-point Poisson equation."""
    n = rhs.shape
    lam = 0.0
    for axis, m in enumerate(n):
        k = np.arange(m)
        ev = (2.0 * np.cos(2.0 * np.pi * k / m) - 2.0) / (h * h)
        lam = lam + ev.reshape([-1 if a == axis else 1 for a in range(3)])
    lam = np.where(lam == 0.0, 1.0, lam)
    ph = np.fft.fftn(rhs) / lam
    ph[0, 0, 0] = 0.0
    return np.real(np.fft.ifftn(ph))


def fluid_mask(chi, halo=3):
    """Cells at least ``halo`` cells (Chebyshev) from the chi band."""
    grow = chi > 1e-6
    for axis in range(3):
        g = grow
        for k in range(1, halo + 1):
            g = g | at(grow, axis, k) | at(grow, axis, -k)
        grow = g
    return ~grow


def one_step(u0, dt, nu, uinf, h, x, bodies, lam_dt, store=lambda x: x):
    """The step's stages on one dense periodic level: advection-diffusion,
    the rigid update of every body from the momenta it covers,
    penalisation towards the body velocity the PROGRAM reports (so that
    the velocity comparison stays sharp; the reference's own rigid update
    is compared beside it), the pressure equation solved exactly, and the
    projection."""
    chi, ubody, udef = body_velocity(x, bodies)
    u_adv = rk3_step(u0, dt, nu, uinf, h, store)
    rigid = [rigid_update(x, h, b["chi"], u_adv, b["cm_guess"], uinf, dt)
             for b in bodies]
    u_pen = store(penalize(u_adv, chi, ubody, lam_dt))
    rhs = store(pressure_rhs(u_pen, chi, udef, dt, h))
    p = store(poisson_fft(rhs, h))
    u1 = store(u_pen - dt * gradient(p, h))
    return {"u1": u1, "p": p, "rhs": rhs, "u_pen": u_pen, "rigid": rigid}


# -- the body's published shape (upstream MidlineShapes, main.cpp) --------
def stefan_width(length, s):
    sb, st, wt, wh = 0.04 * length, 0.95 * length, 0.01 * length, \
        0.04 * length
    head = np.sqrt(np.maximum(2.0 * wh * s - s * s, 0.0))
    mid = wh - (wh - wt) * ((s - sb) / (st - sb)) ** 2
    tail = wt * (length - s) / (length - st)
    return np.where(s < sb, head, np.where(s < st, mid, tail))


DANIO_HEIGHT_BREAKS = (0, 0.01, 0.05, 0.1, 0.3, 0.5, 0.7, 0.8, 0.85, 0.87,
                       0.9, 0.993, 0.996, 0.998, 1)
DANIO_HEIGHT_CUBICS = (
    (0.0011746, 1.345, 2.2204e-14, -578.62),
    (0.014046, 1.1715, -17.359, 128.6),
    (0.041361, 0.40004, -1.9268, 9.7029),
    (0.057759, 0.28013, -0.47141, -0.08102),
    (0.094281, 0.081843, -0.52002, -0.76511),
    (0.083728, -0.21798, -0.97909, 3.9699),
    (0.032727, -0.13323, 1.4028, 2.5693),
    (0.036002, 0.22441, 2.1736, -13.194),
    (0.051007, 0.34282, 0.19446, 16.642),
    (0.058075, 0.37057, 1.193, -17.944),
    (0.069781, 0.3937, -0.42196, -29.388),
    (0.079107, -0.44731, -8.6211, -1.8283e5),
    (0.072751, -5.4355, -1654.1, -2.9121e5),
    (0.052934, -15.546, -3401.4, 5.6689e5),
)


def danio_height(length, s):
    sn = np.clip(s / length, 0.0, 1.0)
    brk = np.asarray(DANIO_HEIGHT_BREAKS, np.float64)
    seg = np.clip(np.searchsorted(brk, sn, side="right") - 1, 0,
                  len(brk) - 2)
    c = np.asarray(DANIO_HEIGHT_CUBICS)[seg]
    t = sn - brk[seg]
    return length * (c[:, 0] + c[:, 1] * t + c[:, 2] * t ** 2
                     + c[:, 3] * t ** 3)


PROFILES = {"stefan": stefan_width, "danio": danio_height}


def fish_volume(length, width, height, samples=20001):
    """Volume of a body of elliptical cross-sections: ``pi int w h ds``."""
    s = np.linspace(0.0, length, samples)
    f = PROFILES[width](length, s) * PROFILES[height](length, s)
    return float(np.pi * np.sum(0.5 * (f[1:] + f[:-1]) * np.diff(s)))
