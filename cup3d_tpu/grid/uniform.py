"""Uniform dense grid: the single-level fast path.

The reference stores fields as an octree of 8**3 blocks even when the mesh is
uniform.  On TPU a uniform level is better served by one dense array
``(nx, ny, nz[, 3])``: XLA tiles the stencils onto the VPU/MXU directly, and
under ``pjit`` the SPMD partitioner inserts halo exchanges for us.  The AMR
path (``cup3d_tpu.grid.blocks``) shares all cell-level kernel math with this
module; only halo assembly differs.

Boundary conditions mirror the reference's ``BlockLab`` family
(main.cpp:5920-6552):

- ``periodic``  — wrap.
- ``wall``      — ghost = -edge for every velocity component (no-slip),
                  ghost = edge for scalars (zero-gradient).
- ``freespace`` — ghost = -edge for the face-normal velocity component only
                  (no penetration, free slip), ghost = edge otherwise.

Scalar fields (chi, p, rhs) always get zero-gradient ghosts on non-periodic
faces, matching ``BlockLabNeumann`` (main.cpp:5920-6080).  Ghosts copy the
edge cell (not a mirror), matching the reference's copy-edge convention.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class BC(str, enum.Enum):
    periodic = "periodic"
    wall = "wall"
    freespace = "freespace"


@dataclass(frozen=True)
class UniformGrid:
    """Geometry + boundary conditions of one dense uniform level."""

    shape: Tuple[int, int, int]
    extent: Tuple[float, float, float]
    bc: Tuple[BC, BC, BC] = (BC.periodic, BC.periodic, BC.periodic)

    @property
    def h(self) -> float:
        return self.extent[0] / self.shape[0]

    @property
    def hmin(self) -> float:
        """Finest spacing (= h on a single-level grid); the layout-generic
        resolution query shared with BlockGrid."""
        return self.h

    @property
    def spacing(self) -> Tuple[float, float, float]:
        return tuple(e / n for e, n in zip(self.extent, self.shape))

    @property
    def ncells(self) -> int:
        return int(np.prod(self.shape))

    def __post_init__(self):
        hs = [e / n for e, n in zip(self.extent, self.shape)]
        if not np.allclose(hs, hs[0], rtol=1e-12):
            raise ValueError(f"anisotropic spacing not supported: {hs}")
        object.__setattr__(self, "bc", tuple(BC(b) for b in self.bc))

    def cell_centers(self, dtype=jnp.float32):
        """(nx,ny,nz,3) physical coordinates of cell centers."""
        axes = [
            (jnp.arange(n, dtype=dtype) + 0.5) * (e / n)
            for n, e in zip(self.shape, self.extent)
        ]
        return jnp.stack(jnp.meshgrid(*axes, indexing="ij"), axis=-1)

    # -- ghost-cell padding ------------------------------------------------

    @jax.named_scope("Halo")
    def pad_scalar(self, f: jnp.ndarray, width: int) -> jnp.ndarray:
        """Pad a (nx,ny,nz) scalar with `width` ghost cells on every face."""
        return _pad(f, width, self.bc)

    @jax.named_scope("Halo")
    def pad_vector(self, u: jnp.ndarray, width: int) -> jnp.ndarray:
        """Pad a (nx,ny,nz,3) velocity with BC-correct ghosts per component."""
        comps = []
        for c in range(3):
            comps.append(_pad(u[..., c], width, self.bc, comp=c))
        return jnp.stack(comps, axis=-1)


def _pad(f, width, bcs: Sequence[BC], comp: int | None = None):
    """Sequentially pad each axis, flipping ghost signs where the BC and
    velocity component require it.

    comp: velocity component index (None = scalar, zero-gradient ghosts).
    """
    for axis, bc in enumerate(bcs):
        if bc == BC.periodic:
            f = _pad_axis(f, axis, width, mode="wrap")
        else:
            f = _pad_axis(f, axis, width, mode="edge")
            flip = comp is not None and (bc == BC.wall or comp == axis)
            if flip:
                f = _negate_ghosts(f, axis, width)
    return f


def _pad_axis(f, axis, width, mode):
    pads = [(0, 0)] * f.ndim
    pads[axis] = (width, width)
    return jnp.pad(f, pads, mode=mode)


def _negate_ghosts(f, axis, width):
    n = f.shape[axis]
    idx_lo = [slice(None)] * f.ndim
    idx_lo[axis] = slice(0, width)
    idx_hi = [slice(None)] * f.ndim
    idx_hi[axis] = slice(n - width, n)
    f = f.at[tuple(idx_lo)].multiply(-1.0)
    f = f.at[tuple(idx_hi)].multiply(-1.0)
    return f
