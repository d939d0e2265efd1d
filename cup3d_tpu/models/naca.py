"""Naca obstacle: rigid extruded NACA airfoil.

Reference: ``NacaMidlineData`` (main.cpp:12749-12810) — a straight midline
along body-x with ``MidlineShapes::naca_width`` as the width profile and a
constant half-height ``L*HoverL/2`` — rasterized by ``PutNacaOnBlocks``
(main.cpp:11740-11926), whose SDF is the *minimum* of the 2-D signed
profile distance in the (x, y) plane and the flat z-slab distance
``height - |z - z0|`` (main.cpp:11834-11837: ``min(signZ*distZ^2,
sign2d*dist1)``).  The reference's factory never constructs it (only
StefanFish, main.cpp:13235-13246); it is provided for upstream parity.

TPU shape: instead of marching surface points per block, every cell of the
dense grid evaluates its distance to the profile polyline with a
``fori_loop`` over boundary segments (the same union-of-segments gather as
the fish rasterizer), using the y-symmetry of the profile to cover both
surfaces with one polyline in the (x, |y|) half-plane.
"""

from __future__ import annotations

import jax
import jax
import jax.numpy as jnp

# position-critical rotation: default bf16-grade matmul precision corrupts
# thin-section SDFs on TPU (see models/fish/rasterize.py)
_HI = jax.lax.Precision.HIGHEST
import numpy as np

from cup3d_tpu.models.base import Obstacle
from cup3d_tpu.models.fish.midline import midline_arc_grid
from cup3d_tpu.models.fish.shapes import naca_width


@jax.jit
def _naca_sdf(points, position, rot, xs, ws, half_height):
    """Signed distance (>0 inside) of computational-frame ``points`` to the
    extruded airfoil: min(signed 2-D profile distance, z-slab distance)."""
    p = jnp.einsum("...c,cd->...d", points - position, rot, precision=_HI)  # body frame
    xb, yb, zb = p[..., 0], jnp.abs(p[..., 1]), p[..., 2]

    # inside test in the (x, |y|) half-plane: under the width graph
    w_at = jnp.interp(xb, xs, ws, left=0.0, right=0.0)
    inside2d = (xb >= xs[0]) & (xb <= xs[-1]) & (yb <= w_at)

    # distance to the profile polyline (x_i, w_i) -- (x_{i+1}, w_{i+1})
    nseg = xs.shape[0] - 1
    big = jnp.asarray(1e10, points.dtype)

    def body(i, dmin):
        x0 = jax.lax.dynamic_index_in_dim(xs, i, keepdims=False)
        x1 = jax.lax.dynamic_index_in_dim(xs, i + 1, keepdims=False)
        w0 = jax.lax.dynamic_index_in_dim(ws, i, keepdims=False)
        w1 = jax.lax.dynamic_index_in_dim(ws, i + 1, keepdims=False)
        ax, ay = x1 - x0, w1 - w0
        alen2 = jnp.maximum(ax * ax + ay * ay, 1e-30)
        t = jnp.clip(((xb - x0) * ax + (yb - w0) * ay) / alen2, 0.0, 1.0)
        dx = xb - (x0 + t * ax)
        dy = yb - (w0 + t * ay)
        return jnp.minimum(dmin, jnp.sqrt(dx * dx + dy * dy + 1e-30))

    dist2d = jax.lax.fori_loop(0, nseg, body, jnp.full(xb.shape, big))
    d2d = jnp.where(inside2d, dist2d, -dist2d)
    dz = half_height - jnp.abs(zb)
    return jnp.minimum(d2d, dz)


class Naca(Obstacle):
    def __init__(self, sim, spec):
        super().__init__(sim, spec)
        self.t_ratio = float(spec.get("tRatio", 0.12))
        self.HoverL = float(spec.get("HoverL", 1.0))
        self.half_height = 0.5 * self.length * self.HoverL
        h = float(np.min(np.asarray(sim.grid.h)))
        rs = midline_arc_grid(self.length, h)
        ws = naca_width(self.t_ratio, self.length, rs)
        dtype = sim.dtype
        # chord centered on the body origin, as the midline-frame fish
        self._xs = jnp.asarray(rs - 0.5 * self.length, dtype)
        self._ws = jnp.asarray(ws, dtype)
        self._half_height_dev = jnp.asarray(self.half_height, dtype)

    def rasterize(self, t: float):
        pos, rot = self.pos_rot_device(self.sim.dtype)
        sdf = _naca_sdf(self._cell_centers(), pos, rot, self._xs, self._ws,
                        self._half_height_dev)
        return sdf, None
