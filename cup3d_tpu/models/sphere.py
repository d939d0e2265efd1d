"""Sphere obstacle: the simplest concrete body.

Not present in the condensed reference (whose factory only builds StefanFish,
main.cpp:13235-13246) but part of upstream CubismUP_3D's obstacle family;
it exercises the full chi -> penalization -> 6-DOF -> forces pipeline with an
analytic SDF, and flow past a sphere is the classic drag validation.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from cup3d_tpu.models.base import Obstacle, pos_rot_traced
from cup3d_tpu.ops.surface import window_size_cells


@partial(jax.jit, static_argnames=("radius",))
def _sphere_sdf(x, frame, radius):
    pos, _ = pos_rot_traced(frame)
    return radius - jnp.linalg.norm(x - pos, axis=-1)  # > 0 inside


class Sphere(Obstacle):
    def __init__(self, sim, spec):
        super().__init__(sim, spec)
        self.radius = float(spec.get("radius", self.length / 2))
        # the force-probe window is sized from self.length
        # (ops/surface.probe_margin): an explicit radius > length/2 would
        # silently leave surface cells outside the window (dS=0, forces
        # under-measured) — keep length consistent with the actual extent
        # (ADVICE r3, medium)
        self.length = max(self.length, 2.0 * self.radius)

    def rasterize(self, t: float):
        return _sphere_sdf(
            self._cell_centers(), self.frame_device(self.sim.dtype),
            self.radius,
        ), None

    # -- the scan megaloop's body stage --------------------------------------

    def offers_scan_stage(self) -> bool:
        return not self._is_blocks and self.supports_device_update()

    @property
    def scan_window(self):
        """The force probe's window (ops/surface.window_size_cells):
        the sphere and 8h of band on every side."""
        w = window_size_cells(self.length, self.sim.grid.h)
        return tuple(min(w, n) for n in self.sim.grid.shape)

    def window_shape_device(self, gait, origin, h, pos, rigid, time, dt,
                            state):
        axes = [origin[a] + (jnp.arange(n, dtype=origin.dtype) + 0.5) * h
                for a, n in enumerate(self.scan_window)]
        x = jnp.stack(jnp.meshgrid(*axes, indexing="ij"), axis=-1)
        return self.radius - jnp.linalg.norm(x - pos, axis=-1), None, state
