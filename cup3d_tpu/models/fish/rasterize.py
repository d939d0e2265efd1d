"""Midline -> (SDF, udef) rasterization: the device-side half of the fish.

Reference: PutFishOnBlocks (main.cpp:8212-8291, 11350-11739) marches surface
points per cross-section and scatters distances into per-block SDF arrays.
That shape is hostile to TPUs (data-dependent scatter, ragged work).  Here
the same geometry -- a tube of elliptical cross-sections along the midline,
semi-axis `width` along the normal and `height` along the binormal -- is
evaluated as a *gather*: a cell takes the union (min) of its signed
distances to the midline segments, in a `lax.fori_loop` over segments of
fused elementwise ops.  Two paths share the per-segment distance:

- ``rasterize_midline`` (the dense uniform window): each trip takes a
  group of consecutive segments and evaluates them only in a static box of
  cells around the group (``raster_box``: the body's largest section, the
  group's half-span and a band of ``BOX_MARGIN`` cells), so the work is the
  body's, not the window's;
- ``rasterize_points`` (arbitrary cell centers, the forest's candidate
  blocks): every point against every segment.

The deformation velocity at a cell is the reference's formula
udef = v + u * vNor + w * vBin at the plane offsets (u, w) of the cell in
the closest cross-section (surface-clamped outside, main.cpp:11476-11487
and 11677-11680).

Sign convention: sdf > 0 inside the body (as the reference's SDFLAB after
signedDistanceSqrt, main.cpp:11718-11739).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# body-frame rotations are position-critical: at this JAX build's default
# bf16-grade matmul precision the rotated coordinates carry ~1e-2 relative
# error, which exceeds the SDF scale of a thin fish section (the sharp
# Towers chi then loses every interior cell ON TPU while CPU runs are
# fine) — every geometric einsum here pins HIGHEST precision
_HI = jax.lax.Precision.HIGHEST

_WEPS = 1e-10  # degenerate-section guard (reference: width,height >= 1e-10)

# cells of band a segment's box keeps beyond the body's largest section:
# Towers chi reads sdf over +-h with centred differences, and the force
# probe's _surface_band differences sdf and chi around that band, so no
# consumer reads a cell farther than ~3h outside the surface
BOX_MARGIN = 4


class RasterBox(NamedTuple):
    """Static box of the dense-window rasterizer: ``shape`` (bx, by, bz)
    cells evaluated around each group of ``group`` consecutive segments."""

    shape: Tuple[int, int, int]
    group: int


def raster_box(width, height, rS, h, window_shape):
    """The body's RasterBox from its static profiles (arc-length grid
    ``rS``, semi-axes ``width``/``height``) at spacing ``h``.

    Segments are grouped while a group spans at most the margin
    (``group * max segment <= BOX_MARGIN h``); a box's half-edge covers
    the largest section, half the group's span and the margin.  Every
    point of a group lies within half its arc length of the mid-point of
    its end points, so a cell within ``BOX_MARGIN h`` of the group's
    surface lies in its box.  Capped at the window: a box as large as the
    window is the full sweep."""
    seg = float(np.max(np.diff(rS)))
    group = max(1, int(BOX_MARGIN * h // seg))
    radius = float(max(np.max(width), np.max(height)))
    half = int(np.ceil((radius + 0.5 * group * seg) / h)) + BOX_MARGIN
    shape = tuple(min(2 * half + 1, int(n)) for n in window_shape)
    return RasterBox(shape, group)


def raster_work(nm, window_shape, box):
    """(cells evaluated, cells the full sweep would evaluate) by one
    ``rasterize_midline`` call over a midline of ``nm`` points: trips x
    box cells x segments a trip, against segments x window cells."""
    trips = -(-(nm - 1) // box.group)
    return (trips * int(np.prod(box.shape)) * box.group,
            (nm - 1) * int(np.prod(window_shape)))


def _segment_distance(p, seg):
    """Signed distance (+outside) of points p (..., 3) to one elliptical
    cone segment, and the plane coordinates needed for udef.

    seg: dict of endpoint-pair arrays r0,r1 (3,), nor0,nor1, bin0,bin1,
    v0,v1, vnor0,vnor1, vbin0,vbin1, w0,w1, h0,h1 (scalars).
    """
    a = seg["r1"] - seg["r0"]
    alen2 = jnp.maximum(jnp.dot(a, a), 1e-30)
    delta = p - seg["r0"]
    t_raw = jnp.einsum("...c,c->...", delta, a, precision=_HI) / alen2
    t = jnp.clip(t_raw, 0.0, 1.0)
    # axial excess beyond the segment span, in physical length
    ax = (t_raw - t) * jnp.sqrt(alen2)

    def lerp(x0, x1):
        return x0 + t[..., None] * (x1 - x0) if jnp.ndim(x0) else x0 + t * (x1 - x0)

    rm = seg["r0"] + t[..., None] * (seg["r1"] - seg["r0"])
    nor = seg["nor0"] + t[..., None] * (seg["nor1"] - seg["nor0"])
    bn = seg["bin0"] + t[..., None] * (seg["bin1"] - seg["bin0"])
    w = jnp.maximum(lerp(seg["w0"], seg["w1"]), _WEPS)
    hh = jnp.maximum(lerp(seg["h0"], seg["h1"]), _WEPS)

    d2 = p - rm
    u = jnp.einsum("...c,...c->...", d2, nor, precision=_HI)
    v = jnp.einsum("...c,...c->...", d2, bn, precision=_HI)
    q = jnp.sqrt((u / w) ** 2 + (v / hh) ** 2 + 1e-30)
    # first-order signed distance to the ellipse: f/|grad f| with f = q - 1.
    # |grad f| = hypot(u/w^2, v/h^2)/q is computed via the *unit* direction
    # (t1, t2) = (u/w, v/h)/q so nothing divides by w^2/h^2 directly: at the
    # degenerate tip sections (w = h = 1e-10) u/w^2 overflows float32 to
    # inf, which used to zero the in-plane distance and mark far-field
    # cells as near-surface (spurious chi bands across the whole domain).
    t1 = (u / w) / q
    t2 = (v / hh) / q
    inv_ratio = jnp.sqrt((t1 / w) ** 2 + (t2 / hh) ** 2 + 1e-30)
    # infimum of |grad f| over directions is 1/max(w, h): floor it so the
    # exactly-on-axis case stays at the physical depth scale
    inv_ratio = jnp.maximum(inv_ratio, 1.0 / jnp.maximum(w, hh))
    # f/|grad f| is accurate only near the surface; for eccentric sections
    # it underestimates far-field distance by the axis ratio (the thin
    # tail would paint spurious near-surface bands across the domain).
    # hypot(u, v) - max(w, h) is a rigorous lower bound everywhere (point
    # distance to the section's bounding circle), exact in the far field:
    # take the larger of the two (both are lower bounds outside; inside,
    # the bound is positive only if the point is provably outside)
    d_plane = jnp.maximum(
        (q - 1.0) / inv_ratio,
        jnp.hypot(u, v) - jnp.maximum(w, hh),
    )
    ax_abs = jnp.abs(ax)
    d_signed = jnp.where(
        ax_abs > 0.0, jnp.hypot(jnp.maximum(d_plane, 0.0), ax_abs), d_plane
    )

    # deformation velocity, plane offsets clamped to the surface outside
    scale = jnp.minimum(1.0, 1.0 / q)[..., None]
    vmid = seg["v0"] + t[..., None] * (seg["v1"] - seg["v0"])
    vnor = seg["vnor0"] + t[..., None] * (seg["vnor1"] - seg["vnor0"])
    vbin = seg["vbin0"] + t[..., None] * (seg["vbin1"] - seg["vbin0"])
    udef = vmid + scale * (u[..., None] * vnor + v[..., None] * vbin)
    return d_signed, udef


def _segment(midline, ss):
    """The end-point pairs of segment ``ss`` (traced index) of a midline,
    as ``_segment_distance`` takes them."""
    seg = {}
    for name, key in (("r", "r"), ("v", "v"), ("nor", "nor"),
                      ("vnor", "vnor"), ("bin", "bin"), ("vbin", "vbin")):
        arr = midline[key]
        seg[name + "0"] = jax.lax.dynamic_slice(arr, (ss, 0), (1, 3))[0]
        seg[name + "1"] = jax.lax.dynamic_slice(arr, (ss + 1, 0), (1, 3))[0]
    for name, key in (("w", "width"), ("h", "height")):
        arr = midline[key]
        seg[name + "0"] = jax.lax.dynamic_slice(arr, (ss,), (1,))[0]
        seg[name + "1"] = jax.lax.dynamic_slice(arr, (ss + 1,), (1,))[0]
    return seg


@jax.jit
@jax.named_scope("CreateObstacles")
def rasterize_points(points, midline, position, rot):
    """Rasterize a midline tube at arbitrary cell centers.

    Every point against every segment: the path of the forest's candidate
    blocks (the TPU analogue of the reference's per-block PutFishOnBlocks,
    main.cpp:10718-10951), which are points with no dense window to box.

    Args:
      points: (..., 3) computational-frame cell-center coordinates.
      midline: dict of device arrays r, v, nor, vnor, bin, vbin (Nm, 3)
        and width, height (Nm,) -- body frame.
      position: (3,) body position in the computational frame.
      rot: (3, 3) body->computational rotation matrix.

    Returns (sdf, udef): points.shape[:-1] with sdf > 0 inside, and
    (..., 3) deformation velocity in the computational frame.
    """
    dtype = midline["r"].dtype
    # body frame: x_body = R^T (x_comp - position)
    p = jnp.einsum("...c,cd->...d", points - position, rot, precision=_HI)
    shape = p.shape[:-1]

    nm = midline["r"].shape[0]
    big = jnp.asarray(1e10, dtype)
    d0 = jnp.full(shape, big)
    u0 = jnp.zeros(shape + (3,), dtype)

    def body(ss, carry):
        dmin, udef = carry
        d, ud = _segment_distance(p, _segment(midline, ss))
        closer = d < dmin
        return jnp.minimum(d, dmin), jnp.where(closer[..., None], ud, udef)

    dmin, udef_body = jax.lax.fori_loop(0, nm - 1, body, (d0, u0))
    sdf = -dmin  # reference convention: positive inside
    udef_comp = jnp.einsum("...c,dc->...d", udef_body, rot, precision=_HI)
    return sdf, udef_comp


@partial(jax.jit, static_argnames=("window_shape", "box"))
@jax.named_scope("CreateObstacles")
def rasterize_midline(
    origin,
    h,
    window_shape,
    box,
    midline,
    position,
    rot,
):
    """Rasterize a midline tube over a dense uniform window.

    A trip of the loop takes ``box.group`` consecutive segments, places
    ``box.shape`` around the mid-point of their end points (start clipped
    into the window), evaluates the segments in order on the box's cells
    and writes the running (min, udef) back: the winning segment and its
    tie-break (first in index order) are the full sweep's wherever the
    winner's box holds the cell, which ``raster_box`` makes every cell
    within ``BOX_MARGIN`` cells of the surface.  A cell no box touches
    holds what the grid holds outside the window: sdf -1, udef 0.

    Args:
      origin: (3,) physical coordinate of the window corner (device).
      h: cell spacing (python float or scalar).
      window_shape: static (nx, ny, nz) of the window.
      box: static RasterBox of the body (``raster_box``).
      midline / position / rot: see rasterize_points.

    Returns (sdf, udef): (nx,ny,nz) with sdf > 0 inside, and (nx,ny,nz,3)
    deformation velocity in the computational frame.
    """
    dtype = midline["r"].dtype
    nm = midline["r"].shape[0]
    k = box.group
    bshape = box.shape
    half = jnp.asarray([b // 2 for b in bshape], jnp.int32)
    lim = jnp.asarray([n - b for n, b in zip(window_shape, bshape)],
                      jnp.int32)
    big = jnp.asarray(1e10, dtype)
    d0 = jnp.full(window_shape, big)
    # udef carried component-major: a trailing axis of 3 pads to 4 sublanes
    u0 = jnp.zeros((3,) + tuple(window_shape), dtype)
    r = midline["r"]

    def trip(i, carry):
        dmin, udef = carry
        s0 = i * k
        ends = jax.lax.dynamic_slice(r, (s0, 0), (1, 3))[0] + \
            jax.lax.dynamic_slice(
                r, (jnp.minimum(s0 + k, nm - 1), 0), (1, 3))[0]
        centre = position + jnp.einsum("dc,c->d", rot, 0.5 * ends,
                                       precision=_HI)
        ic = jnp.floor((centre - origin) / h).astype(jnp.int32)
        start = jnp.clip(ic - half, 0, lim)
        # cell centers as the window has them: origin + (index + 0.5) h
        ii, jj, kk = (
            (start[a] + jnp.arange(bshape[a], dtype=jnp.int32)).astype(dtype)
            for a in range(3))
        X = origin[0] + (ii[:, None, None] + 0.5) * h
        Y = origin[1] + (jj[None, :, None] + 0.5) * h
        Z = origin[2] + (kk[None, None, :] + 0.5) * h
        p_comp = jnp.stack(jnp.broadcast_arrays(X, Y, Z), axis=-1)
        p = jnp.einsum("...c,cd->...d", p_comp - position, rot, precision=_HI)
        starts = (start[0], start[1], start[2])
        db = jax.lax.dynamic_slice(dmin, starts, bshape)
        ub = jax.lax.dynamic_slice(udef, (0,) + starts, (3,) + bshape)
        for j in range(k):
            # the last trip's surplus re-evaluates the last segment: an
            # equal distance never wins under the strict <
            d, ud = _segment_distance(
                p, _segment(midline, jnp.minimum(s0 + j, nm - 2)))
            closer = d < db
            db = jnp.minimum(d, db)
            ub = jnp.where(closer, jnp.moveaxis(ud, -1, 0), ub)
        return (jax.lax.dynamic_update_slice(dmin, db, starts),
                jax.lax.dynamic_update_slice(udef, ub, (0,) + starts))

    trips = -(-(nm - 1) // k)
    dmin, udef_body = jax.lax.fori_loop(0, trips, trip, (d0, u0))
    sdf = jnp.where(dmin < big, -dmin, -1.0)  # positive inside
    udef_comp = jnp.einsum("...c,dc->...d", jnp.moveaxis(udef_body, 0, -1),
                           rot, precision=_HI)
    return sdf, udef_comp
