"""Midline -> (SDF, udef) rasterization: the device-side half of the fish.

Reference: PutFishOnBlocks (main.cpp:8212-8291, 11350-11739) marches surface
points per cross-section and scatters distances into per-block SDF arrays.
That shape is hostile to TPUs (data-dependent scatter, ragged work).  Here
the same geometry -- a tube of elliptical cross-sections along the midline,
semi-axis `width` along the normal and `height` along the binormal -- is
evaluated as a *gather*: every cell of a dense window computes its signed
distance to all midline segments with a `lax.fori_loop` over segments of
fused elementwise ops, taking the union (min) of per-segment signed
distances.  The deformation velocity at a cell is the reference's formula
udef = v + u * vNor + w * vBin at the plane offsets (u, w) of the cell in
the closest cross-section (surface-clamped outside, main.cpp:11476-11487
and 11677-11680).

Sign convention: sdf > 0 inside the body (as the reference's SDFLAB after
signedDistanceSqrt, main.cpp:11718-11739).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# body-frame rotations are position-critical: at this JAX build's default
# bf16-grade matmul precision the rotated coordinates carry ~1e-2 relative
# error, which exceeds the SDF scale of a thin fish section (the sharp
# Towers chi then loses every interior cell ON TPU while CPU runs are
# fine) — every geometric einsum here pins HIGHEST precision
_HI = jax.lax.Precision.HIGHEST

_WEPS = 1e-10  # degenerate-section guard (reference: width,height >= 1e-10)


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


@jax.jit
@jax.named_scope("CreateObstacles")
def rasterize_points(points, midline, position, rot):
    """Rasterize a midline tube at arbitrary cell centers.

    The layout-generic core shared by the dense uniform window and the
    per-candidate-block AMR path (the TPU analogue of the reference's
    per-block PutFishOnBlocks, main.cpp:10718-10951).

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
        d, ud = _segment_distance(p, seg)
        closer = d < dmin
        return jnp.minimum(d, dmin), jnp.where(closer[..., None], ud, udef)

    dmin, udef_body = jax.lax.fori_loop(0, nm - 1, body, (d0, u0))
    sdf = -dmin  # reference convention: positive inside
    udef_comp = jnp.einsum("...c,dc->...d", udef_body, rot, precision=_HI)
    return sdf, udef_comp


@partial(jax.jit, static_argnames=("window_shape",))
@jax.named_scope("CreateObstacles")
def rasterize_midline(
    origin,
    h,
    window_shape,
    midline,
    position,
    rot,
):
    """Rasterize a midline tube over a dense uniform window.

    Args:
      origin: (3,) physical coordinate of the window corner (device).
      h: cell spacing (python float or scalar).
      window_shape: static (nx, ny, nz) of the window.
      midline / position / rot: see rasterize_points.

    Returns (sdf, udef): (nx,ny,nz) with sdf > 0 inside, and (nx,ny,nz,3)
    deformation velocity in the computational frame.
    """
    nx, ny, nz = window_shape
    dtype = midline["r"].dtype
    ii = jnp.arange(nx, dtype=dtype)
    jj = jnp.arange(ny, dtype=dtype)
    kk = jnp.arange(nz, dtype=dtype)
    X = origin[0] + (ii[:, None, None] + 0.5) * h
    Y = origin[1] + (jj[None, :, None] + 0.5) * h
    Z = origin[2] + (kk[None, None, :] + 0.5) * h
    p_comp = jnp.stack(jnp.broadcast_arrays(X, Y, Z), axis=-1)
    return rasterize_points(p_comp, midline, position, rot)
