"""Surface-point force probing: the reference's KernelComputeForces
(main.cpp:12250-12494) + surface extraction (main.cpp:13291-13404) as a
dense TPU kernel.

The reference walks per-block ragged surface-point lists; each point
probes the velocity field up to 4 cells OUTSIDE the body along the
outward normal with one-sided 5th-order stencils and Taylor-corrects the
gradient back to the surface cell.  That machinery is what makes its drag
measure converge — the dense chi-band substitute under-reads pressure
inside the penalized band by a flat ~28% on the sphere (VALIDATION.md,
VERDICT r2 missing #1).

TPU formulation: obstacle surfaces live on finest-level blocks (grad-chi
tagging forces max refinement), so the band's neighborhood is locally
UNIFORM at hmin.  The driver gathers the obstacle's holding blocks into a
dense local window (block-granular gathers); every step of the reference
algorithm is then a static-shape dense computation over the window:

- surface measure: delta = (grad H . grad phi)/|grad phi|^2 per cell
  (Towers; reference Delta with the h factors made physical), surface
  cells = cells with delta > 0; outward normal n = -grad phi/|grad phi|
  (phi > 0 inside);
- probe point: first cell along round(k*n), k = 0..4, with chi < 0.01
  (else the last in-window candidate) — reference marching loop;
- velocity gradient at the probe point: 6-point one-sided 5th-order
  per axis in the sign(n) direction, falling back to 3-point/2-point
  when the window (reference: the lab) runs out; second + mixed
  derivatives Taylor-correct the gradient back to the surface cell;
- tractions: f = -P(surface cell) n dS + (nu/h) (grad_u . n dS) with
  UNDIVIDED derivatives (the reference's bookkeeping), and the same
  reductions: force/torque split, thrust/drag along velUnit, Pout,
  defPower, pLocom.

Everything is masked dense math + in-window gathers; no ragged lists.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_EPS = 1e-21
_C6 = (-137.0 / 60.0, 5.0, -5.0, 10.0 / 3.0, -5.0 / 4.0, 1.0 / 5.0)


def _shift(f, ox, oy, oz):
    """Zero-padded static shift: out[i] = f[i + o]."""
    pad = [(max(-ox, 0), max(ox, 0)), (max(-oy, 0), max(oy, 0)),
           (max(-oz, 0), max(oz, 0))] + [(0, 0)] * (f.ndim - 3)
    g = jnp.pad(f, pad)
    sl = tuple(
        slice(p[0] + o, p[0] + o + n)
        for p, o, n in zip(pad[:3], (ox, oy, oz), f.shape[:3])
    ) + (slice(None),) * (f.ndim - 3)
    return g[sl]


def _central(f, axis):
    """Undivided centered difference along axis (zero-padded edges)."""
    o = [0, 0, 0]
    o[axis] = 1
    hi = _shift(f, *o)
    o[axis] = -1
    lo = _shift(f, *o)
    return 0.5 * (hi - lo)


def _flat_index(ix, iy, iz, shape):
    return (ix * shape[1] + iy) * shape[2] + iz


def _gather(fflat, ix, iy, iz, shape):
    """Window gather with clamped indices (callers mask validity)."""
    ix = jnp.clip(ix, 0, shape[0] - 1)
    iy = jnp.clip(iy, 0, shape[1] - 1)
    iz = jnp.clip(iz, 0, shape[2] - 1)
    return fflat[_flat_index(ix, iy, iz, shape)]


def _surface_band(sdf, chi, valid, h):
    """Surface measure dS, outward unit normal and band mask of a window
    (KernelCharacteristicFunction): dense over the window, but all static
    shifts — cheap VPU passes."""
    gphi = jnp.stack([_central(sdf, a) for a in range(3)], -1)  # undivided*h
    gH = jnp.stack([_central(chi, a) for a in range(3)], -1)
    gphi2 = jnp.sum(gphi * gphi, -1) + _EPS
    # (gH.gphi)/|gphi|^2 with BOTH gradients undivided equals the physical
    # Towers surface density delta(x) [1/length]; dS = delta * h^3
    # (reference Delta = fac1*numD/gradUSq with its 2h/inv2h bookkeeping)
    dS = jnp.sum(gH * gphi, -1) / gphi2 * (h * h * h)
    nhat = -gphi / jnp.sqrt(gphi2)[..., None]  # outward unit normal
    return dS, nhat, (dS > 1e-12) & valid


# slots the probe evaluates per trip of its loop over the occupied ones:
# a trip costs ~1 us a slot and little else on a v5e, so a small chunk
# wastes least on the last trip (a forest window's band is ~700 cells)
_PROBE_CHUNK = 1024


@jax.named_scope("ComputeForces")
def surface_force_window(
    vel: jnp.ndarray,  # (Wx, Wy, Wz, 3) window velocity
    p: jnp.ndarray,  # (Wx, Wy, Wz)
    chi: jnp.ndarray,
    sdf: jnp.ndarray,  # phi > 0 inside
    udef: jnp.ndarray,  # (Wx, Wy, Wz, 3)
    valid: jnp.ndarray,  # (Wx, Wy, Wz) bool: cell carries real field data
    xc: jnp.ndarray,  # (Wx, Wy, Wz, 3) physical cell centers
    h,  # window spacing (finest level)
    nu: float,
    cm: jnp.ndarray,  # (3,)
    u_trans: jnp.ndarray,  # (3,)
    omega: jnp.ndarray,  # (3,)
    per_point: bool = False,
    max_points: int | None = None,
) -> Dict[str, jnp.ndarray]:
    """Reference KernelComputeForces on a dense uniform window.  Returns
    the force-integral dict of models.base.force_integrals (pres/visc
    force, torque, power, thrust/drag/def_power) measured at probed
    surface points.

    ``max_points`` (static) is the budget of slots, K = min(max_points,
    cells of the window); ``None`` makes every cell a slot.  The band is
    SPARSE — measured 2674 surface cells in an 88^3-cell (~680k) window
    for the 128^3 fish — while the marching/one-sided/mixed stencils cost
    ~60 gathered samples per evaluation point, ~1 us a slot on a v5e, so
    the probe costs what the slots it evaluates cost and little else:

    - ``lax.top_k`` of the measure fills the K slots, largest ``dS``
      first (the static-shape analogue of the reference's ragged
      per-block surface lists, main.cpp:7256-7478): 4.8 ms over a 144^3
      window on a v5e, whatever K.  The band's ``min(n_surf, K)`` cells
      are the first slots.  A band over the budget loses its
      smallest-measure tail; ``n_surf`` still returns the true count and
      the callers' sink counts such rows (models/base.store_force_qoi).
    - The slots are then evaluated ``_PROBE_CHUNK`` at a time in a loop
      over the OCCUPIED ones only, the sums of the force pack added up
      chunk by chunk: a budget of 20x the band (probe_max_points) costs
      what the band costs.  ``per_point`` evaluates all K slots at once
      and returns them.

    The evaluated cells and their order are the same whatever the budget,
    as long as the band fits; the last digits of the float32 sums depend
    on the chunking."""
    shape = vel.shape[:3]
    dtype = vel.dtype

    dS_w, nhat_w, surf_w = _surface_band(sdf, chi, valid, h)

    # flat once, here: a reshape inside probe() is a copy of the window
    # in every trip of the loop below
    def flat(fw):
        return fw.reshape((-1,) + fw.shape[3:])

    chif, validf, velf = flat(chi), flat(valid), flat(vel)
    dSf, nhatf, xcf, pf, udeff = (flat(f) for f in (dS_w, nhat_w, xc, p, udef))

    # -- compact the band to K static slots --------------------------------
    ncells = int(np.prod(shape))
    K = ncells if max_points is None else min(int(max_points), ncells)
    surf_flat = flat(surf_w)
    n_surf = jnp.sum(surf_flat.astype(jnp.int32))
    # top-K by dS (not first-K): if the band exceeds the budget, the
    # dropped cells are the SMALLEST-measure tail (graceful truncation
    # bounded by the tail's dS sum), not a spatially-biased trailing set
    iflat = jax.lax.top_k(jnp.where(surf_flat, dSf, 0.0), K)[1]
    # sorted descending, so the occupied slots are the first n_pts
    n_pts = jnp.minimum(n_surf, K)

    vel_norm = jnp.linalg.norm(u_trans)
    vel_unit = jnp.where(vel_norm > 1e-9, u_trans / jnp.where(
        vel_norm > 0, vel_norm, 1.0), 0.0)

    def inwin(ix, iy, iz):
        geo = (
            (ix >= 0) & (ix < shape[0]) & (iy >= 0) & (iy < shape[1])
            & (iz >= 0) & (iz < shape[2])
        )
        return geo & _gather(validf, ix, iy, iz, shape)

    def nbhd_ok(ix, iy, iz):
        """Probe-candidate acceptance: the cell AND its +-1 neighborhood
        must be in-window — the reference rejects marching candidates
        unless ix+dxi+-1 is inside the lab (guarding the centered second
        derivatives); with slot=-1 holes in the AMR window the clamped
        gathers would otherwise silently duplicate edge values (ADVICE r3)."""
        ok = inwin(ix, iy, iz)
        for a in range(3):
            o = [ix, iy, iz]
            for s in (-1, 1):
                o[a] = (ix, iy, iz)[a] + s
                ok = ok & inwin(*o)
        return ok

    def probe(iflat0, pt_ok):
        """The probe at the window cells ``iflat0`` (flat indices, one per
        slot; a slot where ``pt_ok`` is False counts for nothing): the ten
        sums of the force pack and the per-point record."""
        dS = jnp.where(pt_ok, dSf[iflat0], 0.0)
        surf = pt_ok & (dS > 0)
        nhat = nhatf[iflat0]
        x_base = xcf[iflat0]
        P = pf[iflat0]
        v_base = velf[iflat0]
        u_base = udeff[iflat0]
        base = (
            (iflat0 // (shape[1] * shape[2])).astype(jnp.int32),
            ((iflat0 // shape[2]) % shape[1]).astype(jnp.int32),
            (iflat0 % shape[2]).astype(jnp.int32),
        )

        # -- probe point: march outward to the first chi < 0.01 cell ------
        px, py, pz = base
        found = jnp.zeros_like(pt_ok)
        for k in range(5):
            cx = base[0] + jnp.round(k * nhat[..., 0]).astype(jnp.int32)
            cy = base[1] + jnp.round(k * nhat[..., 1]).astype(jnp.int32)
            cz = base[2] + jnp.round(k * nhat[..., 2]).astype(jnp.int32)
            ok = nbhd_ok(cx, cy, cz) & ~found
            px = jnp.where(ok, cx, px)
            py = jnp.where(ok, cy, py)
            pz = jnp.where(ok, cz, pz)
            found = found | (ok & (_gather(chif, cx, cy, cz, shape) < 0.01))

        sx = jnp.where(nhat[..., 0] > 0, 1, -1).astype(jnp.int32)
        sy = jnp.where(nhat[..., 1] > 0, 1, -1).astype(jnp.int32)
        sz = jnp.where(nhat[..., 2] > 0, 1, -1).astype(jnp.int32)

        def vat(ix, iy, iz):
            return _gather(velf, ix, iy, iz, shape)

        def axis_pts(axis, s):
            """Probe-relative sample positions k*s along one axis."""
            def at(k):
                o = [px, py, pz]
                o[axis] = o[axis] + k * s
                return o
            return at

        def one_sided(axis, s):
            """Undivided one-sided first derivative at the probe point:
            6-pt 5th order -> 3-pt 2nd order -> 2-pt 1st order, by range
            (reference inrange cascade)."""
            at = axis_pts(axis, s)
            v = [vat(*at(k)) for k in range(6)]
            d6 = s[..., None] * sum(c * vk for c, vk in zip(_C6, v))
            d3 = s[..., None] * (-1.5 * v[0] + 2.0 * v[1] - 0.5 * v[2])
            d2 = s[..., None] * (v[1] - v[0])
            # every intermediate sample must be valid, not just the endpoint:
            # an AMR-window hole (slot=-1) between probe and endpoint would be
            # zero-filled while the endpoint check passes (ADVICE r3)
            oks = [inwin(*at(k)) for k in range(6)]
            ok5 = (oks[1] & oks[2] & oks[3] & oks[4] & oks[5])[..., None]
            ok2 = (oks[1] & oks[2])[..., None]
            # final 2-pt fallback still reads at(1): zero the derivative when
            # even that neighbor is a hole (code-review r4)
            d2 = jnp.where(oks[1][..., None], d2, 0.0)
            return jnp.where(ok5, d6, jnp.where(ok2, d3, d2))

        dvdx = one_sided(0, sx)
        dvdy = one_sided(1, sy)
        dvdz = one_sided(2, sz)

        # when no marching candidate passed nbhd_ok the probe stays at base
        # with NO neighborhood guarantee: gate every centered/compact stencil
        # below so holes demote to a zero (lower-order) contribution instead of
        # reading clamped/zero-filled cells (code-review r4)
        probe_ok = nbhd_ok(px, py, pz)

        def second(axis):
            o = [px, py, pz]
            o2 = [px, py, pz]
            o = list(o)
            o[axis] = o[axis] + 1
            o2[axis] = o2[axis] - 1
            d2 = vat(*o) - 2.0 * vat(px, py, pz) + vat(*o2)
            return jnp.where(probe_ok[..., None], d2, 0.0)

        d2x, d2y, d2z = second(0), second(1), second(2)

        def mixed(a1, s1, a2, s2):
            """Nested one-sided mixed derivative (reference dveldxdy form),
            falling back to the compact 2x2 form when out of range."""
            def at(k1, k2):
                o = [px, py, pz]
                o[a1] = o[a1] + k1 * s1
                o[a2] = o[a2] + k2 * s2
                return o

            def row(k1):  # 3-pt one-sided along a2 at offset k1 along a1
                return (-1.5 * vat(*at(k1, 0)) + 2.0 * vat(*at(k1, 1))
                        - 0.5 * vat(*at(k1, 2)))

            full = (s1 * s2)[..., None] * (
                -0.5 * row(2) + 2.0 * row(1) - 1.5 * row(0)
            )
            # deliberate divergence: the reference's compact fallback applies
            # the sign product to only the first difference
            # (main.cpp:12399-12401), inverting one term whenever the two
            # normal signs differ; we use the mathematically consistent form
            compact = (s1 * s2)[..., None] * (
                (vat(*at(1, 1)) - vat(*at(1, 0)))
                - (vat(*at(0, 1)) - vat(*at(0, 0)))
            )
            # all nine samples of the nested form must be valid (ADVICE r3:
            # intermediate AMR-window holes must demote to the compact form);
            # the compact 2x2 form's own samples (incl. the diagonal, which
            # nbhd_ok never covers) must be valid too, else the mixed term
            # drops to zero (code-review r4)
            ok = jnp.ones_like(pt_ok)
            for k1 in range(3):
                for k2 in range(3):
                    ok = ok & inwin(*at(k1, k2))
            okc = (inwin(*at(0, 0)) & inwin(*at(0, 1)) & inwin(*at(1, 0))
                   & inwin(*at(1, 1)))
            compact = jnp.where(okc[..., None], compact, 0.0)
            return jnp.where(ok[..., None], full, compact)

        dxy = mixed(0, sx, 1, sy)
        dxz = mixed(0, sx, 2, sz)
        dyz = mixed(1, sy, 2, sz)

        # Taylor-correct the gradient from the probe point back to the
        # surface cell (integer offsets; undivided derivatives throughout)
        ox = (base[0] - px)[..., None].astype(dtype)
        oy = (base[1] - py)[..., None].astype(dtype)
        oz = (base[2] - pz)[..., None].astype(dtype)
        # (..., 3): du/dx, dv/dx, dw/dx
        gx = dvdx + d2x * ox + dxy * oy + dxz * oz
        gy = dvdy + d2y * oy + dyz * oz + dxy * ox
        gz = dvdz + d2z * oz + dxz * ox + dyz * oy

        # -- tractions -----------------------------------------------------
        n_meas = nhat * dS[..., None]  # outward normal * dS
        inv_h = nu / h
        fV = inv_h * (
            gx * n_meas[..., 0:1] + gy * n_meas[..., 1:2]
            + gz * n_meas[..., 2:3]
        )
        fP = -P[..., None] * n_meas
        fT = fV + fP

        r = x_base - cm
        pres_force = jnp.sum(fP, axis=0)
        visc_force = jnp.sum(fV, axis=0)
        torque = jnp.sum(jnp.cross(r, fT), axis=0)
        force_par = jnp.sum(fT * vel_unit, -1)
        thrust = jnp.sum(0.5 * (force_par + jnp.abs(force_par)))
        drag = -jnp.sum(0.5 * (force_par - jnp.abs(force_par)))
        # power = traction . FLUID velocity at the surface cell — the
        # reference's Pout (main.cpp:12461); the old band measure used
        # u_body here, a divergence this kernel removes.  p_locom is the
        # reference's traction . u_solid work (main.cpp:12470-2476).  The
        # *Bnd variants clip each point's power to its negative part before
        # summing (reference PoutBnd/defPowerBnd, main.cpp:12483-12485) —
        # the "useful work only" bound the swimming-efficiency outputs use.
        pow_pt = jnp.sum(fT * v_base, -1)
        defp_pt = jnp.sum(fT * u_base, -1)
        pow_out = jnp.sum(pow_pt)
        pout_bnd = jnp.sum(jnp.minimum(pow_pt, 0.0))
        def_power = jnp.sum(defp_pt)
        def_power_bnd = jnp.sum(jnp.minimum(defp_pt, 0.0))
        u_solid = u_trans + jnp.cross(jnp.broadcast_to(omega, r.shape), r)
        p_locom = jnp.sum(fT * u_solid)
        sums = {
            "pres_force": pres_force,
            "visc_force": visc_force,
            "torque": torque,
            "power": pow_out,
            "pout_bnd": pout_bnd,
            "thrust": thrust,
            "drag": drag,
            "def_power": def_power,
            "def_power_bnd": def_power_bnd,
            "p_locom": p_locom,
        }
        # per-surface-point record (the reference's ObstacleBlock
        # per-point arrays pX..pZ / P / fxP..fzV / vX..vzDef,
        # main.cpp:12300-12330 fill): (K, ...) slot arrays — host
        # consumers compact on the surf mask (compact_surface_points)
        points = {
            "surf": surf,
            "x": x_base,
            "n_dS": n_meas,
            "dS": dS,
            "p": P,
            "fP": fP,
            "fV": fV,
            "v": v_base,
            "vdef": u_base,
        }
        return sums, points

    slot = jnp.arange(K, dtype=jnp.int32)
    if per_point:
        out, points = probe(iflat, slot < n_pts)
        out["points"] = points
    else:
        # the occupied slots alone, a chunk at a time
        C = min(K, _PROBE_CHUNK)
        iflat = jnp.pad(iflat, (0, -K % C))

        def chunk(i, acc):
            at = i * C
            part, _ = probe(jax.lax.dynamic_slice(iflat, (at,), (C,)),
                            at + slot[:C] < n_pts)
            return jax.tree.map(jnp.add, acc, part)

        sums = jax.eval_shape(lambda: probe(iflat[:C], slot[:C] < 0)[0])
        zero = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), sums)
        out = jax.lax.fori_loop(0, -(-n_pts // C), chunk, zero)
    # diagnostics: real surface-cell count vs the K slots (overflow check
    # for max_points; store_force_qoi counts the rows that overflowed)
    out["n_surf"] = n_surf
    return out


# ---------------------------------------------------------------------------
# window extraction: dense local neighborhoods around one obstacle
# ---------------------------------------------------------------------------


def probe_margin(length: float, h: float) -> float:
    """Half-extent of an obstacle's working AABB: body half-length plus an
    8h band.  THE single source for the rasterizer's candidate search
    (StefanFish.block_inputs) and both probe windows — these must
    stay mutually consistent or surface cells silently fall outside the
    window.  8h also covers the pipelined host-mirror staleness (~8 steps
    x CFL*h <= 3.2h of position drift, sim/pack.py)."""
    return 0.625 * length + 8.0 * h


def window_size_cells(length: float, h: float, bs: int = 8) -> int:
    """Static window edge (cells): 2x probe_margin, rounded up to whole
    blocks so AMR gathers stay block-granular and jit retraces only on
    bucket changes."""
    half = probe_margin(length, h)
    return int(-(-2.0 * half / h // bs) * bs)


def probe_max_points(length: float, h) -> int:
    """Static surface-point slot budget of the probe.  The Towers band
    holds ~(L/h)^2 cells for a fish (measured 1.02x at 128^3) and
    ~pi (L/h)^2 for a sphere of diameter L, but the wide sine-mollifier
    chi (ops/chi.heaviside, tests/diagnostics) carries ~18 (L/h)^2 — 20x
    covers every construction.  Rounded to 1024 so jit retraces only on
    resolution buckets.  Generous costs nothing: the probe's top_k costs
    the same at every K and its loop runs over the occupied slots only
    (surface_force_window)."""
    n = 20.0 * (float(length) / float(h)) ** 2
    return int(max(4096, -(-n // 1024) * 1024))


def obstacle_probe_budget(ob, h) -> int:
    """The body's slot budget on a grid of spacing ``h``, noted on the
    body (``ob.probe_slots``) for the sink that counts the rows whose
    band overflowed it (models/base.store_force_qoi)."""
    ob.probe_slots = probe_max_points(ob.length, h)
    return ob.probe_slots


@partial(jax.jit, static_argnames=("wcells", "per_point", "max_points"))
@jax.named_scope("ComputeForces")
def _uniform_window_probe(vel, p, chi, sdf, udef, idx0, h, origin0, nu,
                          cm, u_trans, omega, wcells, per_point=False,
                          max_points=None):
    sl3 = (wcells,) * 3
    wv = jax.lax.dynamic_slice(vel, (idx0[0], idx0[1], idx0[2], 0),
                               sl3 + (3,))
    wu = jax.lax.dynamic_slice(udef, (idx0[0], idx0[1], idx0[2], 0),
                               sl3 + (3,))
    wp = jax.lax.dynamic_slice(p, tuple(idx0), sl3)
    wc = jax.lax.dynamic_slice(chi, tuple(idx0), sl3)
    ws = jax.lax.dynamic_slice(sdf, tuple(idx0), sl3)
    loc = jnp.stack(
        jnp.meshgrid(*[jnp.arange(wcells, dtype=vel.dtype) + 0.5] * 3,
                     indexing="ij"),
        axis=-1,
    )
    xc = origin0 + (idx0.astype(vel.dtype) + loc) * h
    valid = jnp.ones(sl3, bool)
    return surface_force_window(
        wv, wp, wc, ws, wu, valid, xc, h, nu, cm, u_trans, omega,
        per_point=per_point, max_points=max_points,
    )


def force_integrals_probe_uniform(grid, ob, vel, p, chi, sdf, udef, nu,
                                  cm, u_trans, omega,
                                  per_point: bool = False,
                                  max_points: int | None = None):
    """Uniform-grid driver entry: AABB window around the obstacle."""
    n = np.asarray(grid.shape)
    w = window_size_cells(ob.length, grid.h)
    w = int(min(w, n.min()))
    half = 0.5 * w * grid.h
    pos = np.asarray(ob.position)
    idx0 = np.clip(
        np.floor((pos - half) / grid.h).astype(np.int64), 0, n - w
    )
    if max_points is None:
        max_points = obstacle_probe_budget(ob, grid.h)
    return _uniform_window_probe(
        vel, p, chi, sdf, udef, jnp.asarray(idx0, jnp.int32),
        jnp.asarray(grid.h, vel.dtype), jnp.zeros(3, vel.dtype), nu,
        jnp.asarray(cm, vel.dtype), jnp.asarray(u_trans, vel.dtype),
        jnp.asarray(omega, vel.dtype), wcells=w, per_point=per_point,
        max_points=max_points,
    )


def block_window_slots(grid, position: np.ndarray, length: float):
    """Host: finest-level block slots covering the obstacle AABB.
    Returns (slots (nbx,nby,nbz) int32 with -1 for positions not owned at
    the finest level, window block origin (3,) ints, h_fine).

    The window SIZE depends only on (length, h, domain) — never on the
    position — so jitted consumers (the pipelined megastep) retrace only
    on re-layouts, not when the body crosses a block boundary."""
    lmax = len(grid._slot_maps) - 1
    h = grid.h0 / (1 << lmax)
    bs = grid.bs
    nbd = np.asarray(grid.tree.blocks_per_dim(lmax))
    half = probe_margin(length, h)
    nwin = np.minimum(int(np.ceil(2.0 * half / (bs * h))) + 1, nbd)
    b0 = np.floor((position - half) / (bs * h)).astype(np.int64)
    b0 = np.clip(b0, 0, nbd - nwin)
    rng = [np.arange(b0[a], b0[a] + nwin[a]) for a in range(3)]
    slots = grid._slot_maps[lmax][np.ix_(*rng)].astype(np.int32)
    return slots, b0, h


@jax.jit
def _gather_block_window(field, slots):
    """(nb, bs, bs, bs[,C]) + (nbx,nby,nbz) slots -> dense window; rows
    with slot -1 fill with zeros."""
    nbx, nby, nbz = slots.shape
    bs = field.shape[1]
    flat = jnp.take(field, slots.reshape(-1), axis=0, mode="fill",
                    fill_value=0)
    trail = field.shape[4:]
    wi = flat.reshape((nbx, nby, nbz, bs, bs, bs) + trail)
    wi = jnp.moveaxis(wi, 3, 1)  # (nbx, bs, nby, nbz, bs, bs, ...)
    wi = jnp.moveaxis(wi, 4, 3)
    return wi.reshape((nbx * bs, nby * bs, nbz * bs) + trail)


@jax.named_scope("ComputeForces")
def probe_blocks_core(vel, p, ob_chi, ob_sdf, ob_udef, slots, b0, h, nu,
                      cm, u_trans, omega, per_point: bool = False,
                      max_points: int | None = None):
    """Traceable AMR probe core: gather the finest-level holding blocks
    into a dense window (block-granular takes) and run the surface probe.
    ``slots``: (nbx,nby,nbz) int32 block slots, -1 where the position is
    not owned at the finest level — those window cells are invalid and
    probes fall back to shorter stencils there, mirroring the reference's
    lab-range cascade.  ``b0``: (3,) window origin in finest-block units.
    Callable inside jit (the pipelined megastep) or via the jitted
    wrapper below."""
    wv = _gather_block_window(vel, slots)
    wp = _gather_block_window(p, slots)
    wc = _gather_block_window(ob_chi, slots)
    ws = _gather_block_window(ob_sdf, slots)
    wu = _gather_block_window(ob_udef, slots)
    bs = vel.shape[1]
    valid = jnp.repeat(
        jnp.repeat(jnp.repeat(slots >= 0, bs, 0), bs, 1), bs, 2
    )
    shape = wv.shape[:3]
    dtype = wv.dtype
    loc = jnp.stack(
        jnp.meshgrid(*[jnp.arange(s, dtype=dtype) + 0.5 for s in shape],
                     indexing="ij"),
        axis=-1,
    )
    xc = (b0.astype(dtype) * bs + loc) * h
    return surface_force_window(
        wv, wp, wc, ws, wu, valid, xc, h, nu, cm, u_trans, omega,
        per_point=per_point, max_points=max_points,
    )


_probe_blocks_jit = jax.jit(
    probe_blocks_core, static_argnames=("nu", "per_point", "max_points")
)
_probe_blocks_pts_jit = partial(_probe_blocks_jit, per_point=True)


def force_integrals_probe_blocks(grid, state_fields, ob_chi, ob_sdf,
                                 ob_udef, nu, position, length, cm,
                                 u_trans, omega, per_point: bool = False,
                                 max_points: int | None = None):
    """Host-calling AMR entry: host computes the window slots, the jitted
    core does the rest."""
    slots, b0, h = block_window_slots(grid, np.asarray(position), length)
    vel, p = state_fields["vel"], state_fields["p"]
    dtype = vel.dtype
    if max_points is None:
        max_points = probe_max_points(length, h)
    fn = _probe_blocks_pts_jit if per_point else _probe_blocks_jit
    return fn(
        vel, p, ob_chi, ob_sdf, ob_udef, jnp.asarray(slots),
        jnp.asarray(b0, jnp.int32), jnp.asarray(h, dtype), float(nu),
        jnp.asarray(cm, dtype), jnp.asarray(u_trans, dtype),
        jnp.asarray(omega, dtype), max_points=max_points,
    )


# ---------------------------------------------------------------------------
# per-surface-point export (reference per-point arrays, main.cpp:12300-12330)
# ---------------------------------------------------------------------------

SURFACE_POINT_COLUMNS = (
    "x", "y", "z",              # surface-cell center
    "nx_dS", "ny_dS", "nz_dS",  # outward normal * dS
    "dS",
    "p",                        # surface-cell pressure
    "fxP", "fyP", "fzP",        # pressure traction * dS
    "fxV", "fyV", "fzV",        # viscous traction * dS
    "vx", "vy", "vz",           # fluid velocity at the surface cell
    "vxDef", "vyDef", "vzDef",  # body deformation velocity
)


def compact_surface_points(pts: Dict[str, jnp.ndarray]) -> np.ndarray:
    """Masked-dense window per-point record -> compact (n_pts, 20) host
    array, columns as SURFACE_POINT_COLUMNS.  One device fetch of the
    dense stack; the ragged compaction happens host-side (the TPU keeps
    static shapes, the reference's ragged surface_data lists are a host
    format)."""
    dense = jnp.concatenate(
        [pts["x"], pts["n_dS"], pts["dS"][..., None], pts["p"][..., None],
         pts["fP"], pts["fV"], pts["v"], pts["vdef"]],
        axis=-1,
    )
    mask = np.asarray(pts["surf"]).reshape(-1)
    flat = np.asarray(dense, np.float64).reshape(-1, dense.shape[-1])
    return flat[mask]


def dump_surface_points(path: str, grid, state_fields, ob, nu) -> int:
    """Write one obstacle's compacted surface-point record (positions,
    measures, tractions, velocities) to ``path`` (.npy via np.save).
    Returns the number of surface points written.  RL/logging parity with
    the reference's per-point ObstacleBlock arrays.  Dispatches on the
    grid type: AMR block forest or dense uniform grid."""
    if hasattr(grid, "_slot_maps"):  # BlockGrid
        out = force_integrals_probe_blocks(
            grid, state_fields, ob.chi, ob.sdf, ob.udef, nu, ob.position,
            ob.length, ob.centerOfMass, ob.transVel, ob.angVel,
            per_point=True,
        )
    else:
        out = force_integrals_probe_uniform(
            grid, ob, state_fields["vel"], state_fields["p"], ob.chi,
            ob.sdf, ob.udef, nu, ob.centerOfMass, ob.transVel, ob.angVel,
            per_point=True,
        )
    rows = compact_surface_points(out["points"])
    np.save(path, rows)
    return rows.shape[0]
