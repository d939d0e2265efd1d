"""Device-resident fish midline: the pure-jnp twin of the host gait path.

The host pipeline (curvature.py -> frenet.py -> midline.py) re-evaluates the
midline in NumPy every step and re-stages the (Nm, 20) pack on the device
— a constant ~28-43 ms/step of host time (BENCH_r05, round-5 chip run,
record removed).  For the scan
megaloop the whole chain must be a pure function of ``(t, dt, carry)``, so
this module freezes the *gait parameters* (scheduler states, PID outputs,
wave phase bookkeeping) once per megaloop build and evaluates the midline as
jnp ops inside the jitted scan body.

Freezability: the scheduler states only mutate through RL actions and PID
controllers.  ``device_midline_eligible`` admits exactly the steady-gait
fish (no TperiodPID, no torsion control, no period transition in flight, no
position/depth/roll PID), for which every frozen parameter is constant over
any future window.  The wave-phase bookkeeping (``time0``/``timeshift``) is
safe to freeze because the host's in-window rewrite
``timeshift += (t - time0)/Tp; time0 = t`` preserves the wave argument
``2 pi ((t - time0)/Tp + timeshift)`` exactly when the period is constant —
so host fallback after a megaloop resumes bit-compatibly.

Every stage is a line-for-line port of the host algorithm (the references
cite the same main.cpp ranges as the host files); equivalence at several
gait phases is asserted by tests/test_megaloop.py.
"""

from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np

from cup3d_tpu.models.base import quat_to_rot_dev
from cup3d_tpu.models.fish.interpolation import natural_cubic_spline

# geometric reductions pin HIGHEST matmul precision for the same reason as
# models/fish/rasterize.py: default bf16-grade precision on TPU perturbs the
# midline at the SDF scale of a thin section
_HI = jax.lax.Precision.HIGHEST
# the host renorm / inertia-floor threshold (float64 eps even in f32 runs:
# it is a do-not-divide-by-zero guard, not a solver tolerance)
_EPS64 = float(np.finfo(np.float64).eps)

# gait spline constants (compute_midline, main.cpp:15475-15479)
_CURV_POINTS = np.array([0.0, 0.15, 0.4, 0.65, 0.9, 1.0])
_CURV_VALUES = np.array([0.82014, 1.46515, 2.57136, 3.75425, 5.09147, 5.70449])
_BEND_POINTS = np.array([-0.5, -0.25, 0.0, 0.25, 0.5, 0.75, 1.0])


def device_midline_eligible(ob) -> bool:
    """True when the fish's gait is frozen-parameter representable: every
    scheduler/PID input that could mutate between steps is inactive, so
    ``freeze_gait`` captures the exact kinematics for all future t."""
    cf = getattr(ob, "myFish", None)
    if cf is None:
        return False
    if getattr(ob, "_is_blocks", True):
        return False  # uniform dense window only (the megaloop's layout)
    if cf.TperiodPID or cf.control_torsion:
        return False
    if cf.current_period != cf.next_period:
        return False
    if ob.bCorrectPosition or ob.bCorrectPositionZ or ob.bCorrectRoll:
        return False
    return ob.supports_device_update()


def freeze_gait(ob, t: float, dtype):
    """Snapshot the gait parameters at host time ``t`` into a dict of device
    arrays + python scalars that ``midline_state_device`` consumes.

    Returns None when the scheduler state is not provably constant over
    future steps (e.g. a period transition is mid-flight), in which case
    the caller must stay on the host midline path.
    """
    cf = ob.myFish
    L = float(cf.length)

    # -- period: replicate compute_midline's scheduler interplay on a
    # scratch copy (main.cpp:15467-15474) and demand a constant outcome
    sched = copy.deepcopy(cf.periodScheduler)
    sched.transition_scalar(
        t, cf.transition_start,
        cf.transition_start + cf.transition_duration,
        cf.current_period, cf.next_period,
    )
    if float(np.max(np.abs(sched.dparams_t0))) != 0.0:
        return None
    p0, p1 = float(sched.params_t0[0]), float(sched.params_t1[0])
    if not (p0 == p1 == cf.current_period == cf.next_period):
        return None
    Tp, dTp = sched.get_scalar(t)
    if dTp != 0.0 or Tp <= 0.0:
        return None

    # -- amplitude envelope: the host forces this exact transition every
    # step (compute_midline, main.cpp:15480-15483), so replicating it once
    # captures the scheduler's fixed point
    env = copy.deepcopy(cf.curvatureScheduler)
    curvature_points = _CURV_POINTS * L
    curvature_values = _CURV_VALUES / L
    env.transition_between(0.0, 0.0, cf.Tperiod, np.zeros(6), curvature_values)
    env_p0 = natural_cubic_spline(curvature_points, env.params_t0, cf.rS)
    env_p1 = natural_cubic_spline(curvature_points, env.params_t1, cf.rS)
    env_dp0 = natural_cubic_spline(curvature_points, env.dparams_t0, cf.rS)
    env_t0, env_t1 = float(env.t0), float(env.t1)
    if env_t0 < 0:
        # never-started scheduler returns params_t0 for all t: encode as a
        # saturated past window so the device gate picks env_p1 == env_p0
        env_p1 = env_p0.copy()
        env_dp0 = np.zeros_like(env_p0)
        env_t0, env_t1 = 0.0, -1.0

    # -- pitching cylinder: gamma/dgamma only move under the depth PID
    # (excluded by eligibility), so R/Rdot freeze (main.cpp:15524-15530)
    if abs(cf.gamma) > 1e-10:
        R = 1.0 / cf.gamma
        Rdot = -cf.dgamma / cf.gamma ** 2
    else:
        R = 1e10 if cf.gamma >= 0 else -1e10
        Rdot = 0.0

    arr = lambda a: jnp.asarray(a, dtype)
    return {
        "rs": arr(cf.rS),
        "width": arr(cf.width),
        "height": arr(cf.height),
        "env_p0": arr(env_p0), "env_p1": arr(env_p1), "env_dp0": arr(env_dp0),
        "env_t0": env_t0, "env_t1": env_t1,
        "rb_p": arr(cf.rlBendingScheduler.params_t0),
        "rb_t0": float(cf.rlBendingScheduler.t0),
        "bend": arr(_BEND_POINTS),
        "Tp": float(Tp),
        "time0": float(cf.time0),
        "timeshift": float(cf.timeshift),
        "phase": float(cf.phaseShift),
        "wavelen": float(cf.waveLength),
        "L": L,
        "af": float(cf.amplitudeFactor),
        "alpha": float(cf.alpha), "dalpha": float(cf.dalpha),
        "beta": float(cf.beta), "dbeta": float(cf.dbeta),
        "R": float(R), "Rdot": float(Rdot),
    }


def _hermite_dev(x0, x1, x, y0, y1, dy0, dy1):
    """jnp twin of interpolation.cubic_hermite; returns (y, dy/dx)."""
    xr = x - x0
    dx = x1 - x0
    a = (dy0 + dy1) / (dx * dx) - 2.0 * (y1 - y0) / (dx * dx * dx)
    b = (-2.0 * dy0 - dy1) / dx + 3.0 * (y1 - y0) / (dx * dx)
    y = a * xr ** 3 + b * xr ** 2 + dy0 * xr + y0
    dy = 3.0 * a * xr ** 2 + 2.0 * b * xr + dy0
    return y, dy


def _frenet_scan_dev(rs, curv, dcurv):
    """lax.scan twin of frenet.frenet_solve with zero torsion (torsion
    control is excluded by eligibility): forward-Euler Frenet-Serret
    integration carrying frame + time derivative, renormalizing each step."""
    dtype = rs.dtype
    ds = rs[1:] - rs[:-1]
    z3 = jnp.zeros(3, dtype)
    e_x = jnp.asarray([1.0, 0.0, 0.0], dtype)
    e_y = jnp.asarray([0.0, 1.0, 0.0], dtype)
    e_z = jnp.asarray([0.0, 0.0, 1.0], dtype)

    def renorm(vec):
        d = jnp.dot(vec, vec, precision=_HI)
        return jnp.where(d > _EPS64,
                         vec * jax.lax.rsqrt(jnp.maximum(d, _EPS64)), vec)

    def body(carry, x):
        ksi, vksi, r, v, n0, vn0, b0, vb0 = carry
        k, dk, dsi = x
        dksi = k * n0
        dnu = -k * ksi
        dvksi = dk * n0 + k * vn0
        dvnu = -dk * ksi - k * vksi  # OLD vksi, as the host loop
        r_i = r + dsi * ksi          # OLD ksi
        nor_i = renorm(n0 + dsi * dnu)
        ksi_n = renorm(ksi + dsi * dksi)
        bin_i = renorm(b0)           # torsion = 0: dbin = 0
        v_i = v + dsi * vksi         # OLD vksi
        vnor_i = vn0 + dsi * dvnu
        vksi_n = vksi + dsi * dvksi
        vbin_i = vb0                 # dvbin = 0
        new = (ksi_n, vksi_n, r_i, v_i, nor_i, vnor_i, bin_i, vbin_i)
        return new, (r_i, v_i, nor_i, vnor_i, bin_i, vbin_i)

    init = (e_x, z3, z3, z3, e_y, z3, e_z, z3)
    _, ys = jax.lax.scan(body, init, (curv[:-1], dcurv[:-1], ds))
    row0 = (z3, z3, e_y, z3, e_z, z3)
    out = tuple(jnp.concatenate([first[None], rest], axis=0)
                for first, rest in zip(row0, ys))
    return dict(zip(("r", "v", "nor", "vnor", "bin", "vbin"), out))


def _pitching_dev(r, v, R, Rdot):
    """jnp twin of perform_pitching_motion (main.cpp:15521-15571)."""
    x0N, y0N = r[-1, 0], r[-1, 1]
    x0Nd, y0Nd = v[-1, 0], v[-1, 1]
    phi = jnp.arctan2(y0N, x0N)
    phidot = (y0Nd / x0N - y0N * x0Nd / x0N ** 2) / (1.0 + (y0N / x0N) ** 2)
    M = jnp.hypot(x0N, y0N)
    Mdot = (x0N * x0Nd + y0N * y0Nd) / M
    cphi, sphi = jnp.cos(phi), jnp.sin(phi)
    x0, y0 = r[:, 0], r[:, 1]
    x0d, y0d = v[:, 0], v[:, 1]
    x1 = cphi * x0 - sphi * y0
    y1 = sphi * x0 + cphi * y0
    x1d = cphi * x0d - sphi * y0d + (-sphi * x0 - cphi * y0) * phidot
    y1d = sphi * x0d + cphi * y0d + (cphi * x0 - sphi * y0) * phidot
    theta = (M - x1) / R
    cth, sth = jnp.cos(theta), jnp.sin(theta)
    thetad = (Mdot - x1d) / R - (M - x1) / R ** 2 * Rdot
    r_new = jnp.stack([M - R * sth, y1, R - R * cth], axis=1)
    v_new = jnp.stack(
        [Mdot - Rdot * sth - R * cth * thetad, y1d,
         Rdot - Rdot * cth + R * sth * thetad], axis=1)
    return r_new, v_new


def _recompute_normals_dev(rs, r, v, nor, vnor):
    """jnp twin of recompute_normal_vectors (main.cpp:15572-15667)."""
    hp = (rs[2:] - rs[1:-1])[:, None]
    hm = (rs[1:-1] - rs[:-2])[:, None]
    frac = hp / hm
    am = -frac * frac
    a = frac * frac - 1.0
    denom = 1.0 / (hp * (1.0 + frac))
    t_mid = (am * r[:-2] + a * r[1:-1] + r[2:]) * denom
    dt_mid = (am * v[:-2] + a * v[1:-1] + v[2:]) * denom
    ids0 = 1.0 / (rs[1] - rs[0])
    idsN = 1.0 / (rs[-2] - rs[-1])
    t_vec = jnp.concatenate(
        [((r[1] - r[0]) * ids0)[None], t_mid, ((r[-2] - r[-1]) * idsN)[None]])
    dt_vec = jnp.concatenate(
        [((v[1] - v[0]) * ids0)[None], dt_mid, ((v[-2] - v[-1]) * idsN)[None]])
    dot = jnp.sum(nor * t_vec, axis=1, keepdims=True)
    ddot = (jnp.sum(vnor * t_vec, axis=1)
            + jnp.sum(nor * dt_vec, axis=1))[:, None]
    nor_new = nor - dot * t_vec
    nor_out = nor_new / jnp.linalg.norm(nor_new, axis=1, keepdims=True)
    vnor_out = vnor - ddot * t_vec - dot * dt_vec
    bin_new = jnp.cross(t_vec, nor_out)
    bin_out = bin_new / jnp.linalg.norm(bin_new, axis=1, keepdims=True)
    vbin_out = jnp.cross(dt_vec, nor_out) + jnp.cross(t_vec, vnor_out)
    return nor_out, vnor_out, bin_out, vbin_out


def _d_ds_dev(rs, vals):
    """jnp twin of midline._d_ds (one-sided ends, averaged interior)."""
    ds = rs[1:] - rs[:-1]
    if vals.ndim == 2:
        ds = ds[:, None]
    fwd = (vals[1:] - vals[:-1]) / ds
    return jnp.concatenate([fwd[:1], 0.5 * (fwd[1:] + fwd[:-1]), fwd[-1:]],
                           axis=0)


def _section_integrals_dev(rs, r, nor, bin_, width, height):
    """jnp twin of FishMidlineData._section_integrals."""
    ds = jnp.concatenate([
        (0.5 * (rs[1] - rs[0]))[None],
        0.5 * (rs[2:] - rs[:-2]),
        (0.5 * (rs[-1] - rs[-2]))[None],
    ])
    c = jnp.cross(nor, bin_)
    cR = jnp.sum(c * _d_ds_dev(rs, r), axis=1)
    cN = jnp.sum(c * _d_ds_dev(rs, nor), axis=1)
    cB = jnp.sum(c * _d_ds_dev(rs, bin_), axis=1)
    m00 = width * height
    m11 = 0.25 * width ** 3 * height
    m22 = 0.25 * width * height ** 3
    return ds, cR, cN, cB, m00, m11, m22


def _remove_linear_momentum_dev(si, r, v, nor, vnor, bin_, vbin):
    """jnp twin of integrate_linear_momentum (main.cpp:10961-11012)."""
    ds, cR, cN, cB, m00, m11, m22 = si
    aux1 = m00 * cR * ds
    aux2 = m11 * cN * ds
    aux3 = m22 * cB * ds
    vol = jnp.sum(aux1) * jnp.pi
    dot = lambda w, x: jnp.einsum("i,ij->j", w, x, precision=_HI)
    cm = (dot(aux1, r) + dot(aux2, nor) + dot(aux3, bin_)) * jnp.pi / vol
    lm = (dot(aux1, v) + dot(aux2, vnor) + dot(aux3, vbin)) * jnp.pi / vol
    return r - cm, v - lm


def _remove_angular_momentum_dev(si, dt, qint, r, v, nor, vnor, bin_, vbin):
    """jnp twin of integrate_angular_momentum (main.cpp:11013-11219):
    J w = L solve, backwards internal-quaternion step, counter-rotation.
    Returns (r, v, nor, vnor, bin, vbin, qint_new)."""
    ds, cR, cN, cB, m00, m11, m22 = si

    def moment2(a, an, ab_, b, bn, bb):
        return (cR * (a * b * m00 + an * bn * m11 + ab_ * bb * m22)
                + cN * m11 * (a * bn + b * an)
                + cB * m22 * (a * bb + b * ab_))

    n, b_ = nor, bin_
    X, Y, Z = r[:, 0], r[:, 1], r[:, 2]
    JXY = -jnp.sum(ds * moment2(X, n[:, 0], b_[:, 0], Y, n[:, 1], b_[:, 1]))
    JZX = -jnp.sum(ds * moment2(Z, n[:, 2], b_[:, 2], X, n[:, 0], b_[:, 0]))
    JYZ = -jnp.sum(ds * moment2(Y, n[:, 1], b_[:, 1], Z, n[:, 2], b_[:, 2]))
    XX = ds * moment2(X, n[:, 0], b_[:, 0], X, n[:, 0], b_[:, 0])
    YY = ds * moment2(Y, n[:, 1], b_[:, 1], Y, n[:, 1], b_[:, 1])
    ZZ = ds * moment2(Z, n[:, 2], b_[:, 2], Z, n[:, 2], b_[:, 2])
    JXX = jnp.sum(YY + ZZ)
    JYY = jnp.sum(ZZ + XX)
    JZZ = jnp.sum(YY + XX)  # reference parity (main.cpp:11076)

    xd_y = moment2(v[:, 0], vnor[:, 0], vbin[:, 0], Y, n[:, 1], b_[:, 1])
    x_yd = moment2(X, n[:, 0], b_[:, 0], v[:, 1], vnor[:, 1], vbin[:, 1])
    xd_z = moment2(v[:, 0], vnor[:, 0], vbin[:, 0], Z, n[:, 2], b_[:, 2])
    x_zd = moment2(X, n[:, 0], b_[:, 0], v[:, 2], vnor[:, 2], vbin[:, 2])
    yd_z = moment2(v[:, 1], vnor[:, 1], vbin[:, 1], Z, n[:, 2], b_[:, 2])
    y_zd = moment2(Y, n[:, 1], b_[:, 1], v[:, 2], vnor[:, 2], vbin[:, 2])
    am = jnp.stack([
        jnp.sum((y_zd - yd_z) * ds),
        jnp.sum((xd_z - x_zd) * ds),
        jnp.sum((x_yd - xd_y) * ds),
    ]) * jnp.pi

    eps = jnp.asarray(_EPS64, r.dtype)
    J = jnp.stack([
        jnp.stack([jnp.maximum(JXX, eps), JXY, JZX]),
        jnp.stack([JXY, jnp.maximum(JYY, eps), JYZ]),
        jnp.stack([JZX, JYZ, jnp.maximum(JZZ, eps)]),
    ]) * jnp.pi
    w = jnp.linalg.solve(J, am)

    q = qint
    dqdt = 0.5 * jnp.stack([
        -w[0] * q[1] - w[1] * q[2] - w[2] * q[3],
        +w[0] * q[0] + w[1] * q[3] - w[2] * q[2],
        -w[0] * q[3] + w[1] * q[0] + w[2] * q[1],
        +w[0] * q[2] - w[1] * q[1] + w[2] * q[0],
    ])
    q = q - dt * dqdt  # backwards: counter-rotation
    q = q / jnp.linalg.norm(q)
    R = quat_to_rot_dev(q)

    def rot(pos, vel):
        pos_r = jnp.einsum("ij,kj->ik", pos, R, precision=_HI)
        vel_r = jnp.einsum("ij,kj->ik", vel, R, precision=_HI)
        # -w x r counter-rotation, with the ROTATED positions (host order)
        vel_r = vel_r - jnp.cross(jnp.broadcast_to(w, pos_r.shape), pos_r)
        return pos_r, vel_r

    r, v = rot(r, v)
    nor, vnor = rot(nor, vnor)
    bin_, vbin = rot(bin_, vbin)
    return r, v, nor, vnor, bin_, vbin, q


@jax.named_scope("CreateObstacles")
def midline_state_device(gait, t, dt, qint):
    """Evaluate the full midline state at traced time ``t``: gait wave ->
    Frenet integration -> pitching wrap -> normal re-orthonormalization ->
    deformation-momentum removal.  ``qint`` is the carried internal
    quaternion (4,).  Returns (midline dict for rasterize_midline,
    updated qint)."""
    rs = gait["rs"]
    t = jnp.asarray(t, rs.dtype)
    L, Tp = gait["L"], gait["Tp"]

    # amplitude envelope (VectorScheduler.get_fine on frozen fine arrays)
    y, dy = _hermite_dev(gait["env_t0"], gait["env_t1"], t,
                         gait["env_p0"], gait["env_p1"], gait["env_dp0"], 0.0)
    rC = jnp.where(t > gait["env_t1"], gait["env_p1"],
                   jnp.where(t < gait["env_t0"], gait["env_p0"], y))
    inside = (t >= gait["env_t0"]) & (t <= gait["env_t1"])
    vC = jnp.where(inside, dy, jnp.zeros_like(dy))

    # RL bending riding the wave (LearnWaveScheduler.get_fine, frozen
    # history): values at wave coordinate c = s/L - (t - t0)/Twave
    bp, pb = gait["bend"], gait["rb_p"]
    c = rs / L - (t - gait["rb_t0"]) / Tp
    below = c < bp[0]
    above = c > bp[-1]
    j = jnp.clip(jnp.searchsorted(bp, c, side="left"), 1, bp.shape[0] - 1)
    yb, dyb = _hermite_dev(bp[j - 1], bp[j], c, pb[j - 1], pb[j], 0.0, 0.0)
    rB = jnp.where(below, pb[0], jnp.where(above, pb[-1], yb))
    vB = jnp.where(below | above, jnp.zeros_like(dyb), -dyb / Tp)

    # traveling wave (compute_midline, main.cpp:15484-15519)
    darg = 2.0 * jnp.pi / Tp
    arg0 = (2.0 * jnp.pi * ((t - gait["time0"]) / Tp + gait["timeshift"])
            + jnp.pi * gait["phase"])
    arg = arg0 - 2.0 * jnp.pi * rs / (L * gait["wavelen"])
    curv = jnp.sin(arg) + rB + gait["beta"]
    dcurv = jnp.cos(arg) * darg + vB + gait["dbeta"]
    af = gait["af"]
    rK = gait["alpha"] * af * rC * curv
    vK = (gait["alpha"] * af * (vC * curv + rC * dcurv)
          + gait["dalpha"] * af * rC * curv)
    # NOTE: no host-style finite check here — a NaN propagates to the
    # carried umax and the megaloop consumer raises the recoverable
    # nan-velocity failure (sim/megaloop.py)

    sol = _frenet_scan_dev(rs, rK, vK)
    r, v = _pitching_dev(sol["r"], sol["v"], gait["R"], gait["Rdot"])
    nor, vnor, bin_, vbin = _recompute_normals_dev(rs, r, v,
                                                   sol["nor"], sol["vnor"])
    si = _section_integrals_dev(rs, r, nor, bin_, gait["width"],
                                gait["height"])
    r, v = _remove_linear_momentum_dev(si, r, v, nor, vnor, bin_, vbin)
    # the host recomputes the section integrals after the linear shift
    # (each integrate_* calls _section_integrals): replicate for bit parity
    si = _section_integrals_dev(rs, r, nor, bin_, gait["width"],
                                gait["height"])
    dt_eff = jnp.maximum(jnp.asarray(dt, rs.dtype), 1e-12)
    r, v, nor, vnor, bin_, vbin, qint_new = _remove_angular_momentum_dev(
        si, dt_eff, qint, r, v, nor, vnor, bin_, vbin)

    mid = {"r": r, "v": v, "nor": nor, "vnor": vnor, "bin": bin_,
           "vbin": vbin, "width": gait["width"], "height": gait["height"]}
    return mid, qint_new
