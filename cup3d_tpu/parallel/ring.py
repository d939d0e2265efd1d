"""Ring-permute halo exchange for the sharded Krylov hot path.

The sharded layers move neighbor data two ways today: the face-table
assembly (parallel/faces.py) issues blocking ``lax.all_to_all``
collectives inside every Krylov iteration, and the uniform lanes
Laplacian simply isn't sharded at all.  On a TPU torus both patterns
leave ICI bandwidth on the table: halo traffic is *neighbor* traffic, so
the natural transport is a ring permute per direction — which Pallas can
issue as an **async remote copy** (``pltpu.make_async_remote_copy``,
SNIPPETS.md [1] / the distributed-Pallas ring idiom) that flies while
the interior stencil computes, and is awaited only where boundary tiles
consume it.

Three layers, each with a CPU-exact fallback so tier-1 stays green
without a TPU:

- :func:`ring_shift` — one ring permute step.  TPU (CUP3D_RING_DMA
  auto/on): a Pallas kernel that starts the send-sided DMA and returns;
  elsewhere: ``lax.ppermute`` (same dataflow, collective transport).
- :func:`ring_all_to_all` — drop-in for the halo-exchange
  ``lax.all_to_all(split_axis=0, concat_axis=0)`` built from D-1 ring
  steps, chunks landing as they arrive.  faces.py dispatches here under
  CUP3D_RING_HALO=1.
- :func:`make_laplacian_lanes_sharded` — the lanes Laplacian under
  shard_map with the x-slab halo exchanged by ring permutes that are
  issued BEFORE the interior-tile compute and consumed only in the
  final edge-plane concatenation, so XLA/Mosaic can overlap the ICI
  transfer with the intra-shard stencil.

Lane order is x-major (krylov.to_lanes: t = (tx*NBy + ty)*NBz + tz), so
sharding the lane axis evenly IS an x-slab decomposition and each
shard's boundary is one contiguous run of NBy*NBz lanes — the ring
messages are single dense slices, no gather.
"""

from __future__ import annotations

import os
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from cup3d_tpu.grid.uniform import UniformGrid
from cup3d_tpu.parallel.compat import shard_map

__all__ = [
    "use_ring_dma",
    "use_ring_halo",
    "ring_shift",
    "ring_all_to_all",
    "pad_slab_scalar",
    "pad_slab_vector",
    "make_laplacian_lanes_sharded",
]


def use_ring_dma() -> bool:
    """Whether ring_shift lowers to the Pallas async-remote-copy kernel.

    CUP3D_RING_DMA: ``auto`` (default) = on for the TPU backend only;
    ``1`` forces it (TPU expected — the kernel targets ICI); ``0``
    forces the ppermute transport everywhere."""
    v = os.environ.get("CUP3D_RING_DMA", "auto").strip().lower()
    if v in ("0", "false", "no"):
        return False
    if v in ("1", "true", "yes"):
        return True
    return jax.default_backend() == "tpu"


def use_ring_halo() -> bool:
    """Whether faces.py's entry exchange rides ring permutes instead of
    the blocking all_to_all (CUP3D_RING_HALO=1; default off — the
    all_to_all path remains the validated baseline)."""
    return os.environ.get("CUP3D_RING_HALO", "0") in ("1", "true", "yes")


def _ring_shift_pallas(x: jnp.ndarray, axis_name: str, shift: int,
                       axis_size: int) -> jnp.ndarray:
    """One ring step as a Pallas async remote copy (send-sided DMA to
    the (me + shift) mod D neighbor over ICI; SNIPPETS.md [1] idiom).
    Must run inside shard_map over ``axis_name`` on TPU."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(in_ref, out_ref, send_sem, recv_sem):
        me = jax.lax.axis_index(axis_name)
        dst = jax.lax.rem(me + shift + axis_size, axis_size)
        copy = pltpu.make_async_remote_copy(
            src_ref=in_ref,
            dst_ref=out_ref,
            send_sem=send_sem,
            recv_sem=recv_sem,
            # by axis name: the other axes of a 2-D (lanes, x) mesh keep
            # this shard's own coordinates
            device_id={axis_name: dst},
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        copy.start()
        copy.wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA] * 2,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid_spec=grid_spec,
    )(x)


def ring_shift(x: jnp.ndarray, axis_name: str, shift: int = 1):
    """Rotate ``x`` by ``shift`` positions around the mesh axis: each
    shard receives the chunk of shard (me - shift) mod D.  Must be
    called inside shard_map over ``axis_name``."""
    D = jax.lax.psum(1, axis_name)  # static axis size
    if use_ring_dma():
        return _ring_shift_pallas(x, axis_name, shift, D)
    perm = [(i, (i + shift) % D) for i in range(D)]
    return jax.lax.ppermute(x, axis_name, perm)


def ring_all_to_all(send: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Drop-in for ``lax.all_to_all(send, axis, split_axis=0,
    concat_axis=0)`` with ``send`` shaped (D, M, ...): D-1 ring permute
    steps, each carrying one shard-to-shard chunk.  On TPU every step is
    an async remote copy, so chunks stream around the ring instead of
    rendezvousing in one blocking collective; the diagonal (own) chunk
    never leaves the shard."""
    D = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    own = jax.lax.dynamic_slice_in_dim(send, me, 1, axis=0)
    out = jax.lax.dynamic_update_slice_in_dim(
        jnp.zeros_like(send), own, me, axis=0
    )
    for k in range(1, D):
        # send my chunk for shard (me+k) this round; the matching chunk
        # from shard (me-k) arrives and lands at its source row
        chunk = jax.lax.dynamic_slice_in_dim(
            send, jax.lax.rem(me + k, D), 1, axis=0
        )
        got = ring_shift(chunk, axis_name, shift=k)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, got, jax.lax.rem(me - k + D, D), axis=0
        )
    return out


def _pad_slab_x(grid: UniformGrid, f: jnp.ndarray, width: int,
                axis_name: str, comp):
    """x-ghosts of one (sx, ny, nz) slab: the cross-shard halo by ring
    permute (issued FIRST, so on TPU the async remote copy flies while
    the caller's y/z padding computes), with the GLOBAL x boundary
    reproduced bit-for-bit from grid/uniform._pad — periodic is the
    natural ring wrap; edge-copy (and the wall/normal-component sign
    flip) applies only on shard 0 / D-1."""
    from cup3d_tpu.grid.uniform import BC

    D = jax.lax.psum(1, axis_name)
    lo_own = jax.lax.slice_in_dim(f, 0, width, axis=0)
    hi_own = jax.lax.slice_in_dim(f, f.shape[0] - width, f.shape[0],
                                  axis=0)
    recv_lo = ring_shift(hi_own, axis_name, shift=+1)
    recv_hi = ring_shift(lo_own, axis_name, shift=-1)
    bc = grid.bc[0]
    if bc == BC.periodic:
        lo, hi = recv_lo, recv_hi
    else:
        me = jax.lax.axis_index(axis_name)
        edge_lo = jnp.repeat(jax.lax.slice_in_dim(f, 0, 1, axis=0),
                             width, axis=0)
        edge_hi = jnp.repeat(
            jax.lax.slice_in_dim(f, f.shape[0] - 1, f.shape[0], axis=0),
            width, axis=0)
        if comp is not None and (bc == BC.wall or comp == 0):
            edge_lo, edge_hi = -edge_lo, -edge_hi
        lo = jnp.where(me == 0, edge_lo, recv_lo)
        hi = jnp.where(me == D - 1, edge_hi, recv_hi)
    return jnp.concatenate([lo, f, hi], axis=0)


def _pad_slab_yz(grid: UniformGrid, f: jnp.ndarray, width: int, comp):
    """y/z ghosts of an x-padded slab — the unsharded axes, padded with
    the same sequential per-axis logic as grid/uniform._pad (so the
    ghost corners match the solo path exactly)."""
    from cup3d_tpu.grid import uniform as _u

    for axis in (1, 2):
        bc = grid.bc[axis]
        if bc == _u.BC.periodic:
            f = _u._pad_axis(f, axis, width, mode="wrap")
        else:
            f = _u._pad_axis(f, axis, width, mode="edge")
            if comp is not None and (bc == _u.BC.wall or comp == axis):
                f = _u._negate_ghosts(f, axis, width)
    return f


def pad_slab_scalar(grid: UniformGrid, f: jnp.ndarray, width: int,
                    axis_name: str) -> jnp.ndarray:
    """grid.pad_scalar for one x-slab inside shard_map over
    ``axis_name``: x-ghosts come from the ring halo (plus the global
    BC at shard 0 / D-1), y/z ghosts from the grid BCs.  Elementwise
    identical to slicing the solo padded array — the slab stencils
    built on top inherit bitwise equivalence."""
    return _pad_slab_yz(grid,
                        _pad_slab_x(grid, f, width, axis_name, None),
                        width, None)


def pad_slab_vector(grid: UniformGrid, u: jnp.ndarray, width: int,
                    axis_name: str) -> jnp.ndarray:
    """grid.pad_vector for one (sx, ny, nz, 3) x-slab inside shard_map:
    per-component ghosts with the solo path's BC sign flips.  The two
    ring messages per component are issued before the y/z padding and
    consumed only in the x-ghost concatenation, preserving the
    halos-before-interior overlap of make_laplacian_lanes_sharded."""
    comps = []
    for c in range(3):
        comps.append(_pad_slab_yz(
            grid, _pad_slab_x(grid, u[..., c], width, axis_name, c),
            width, c))
    return jnp.stack(comps, axis=-1)


def make_laplacian_lanes_sharded(grid: UniformGrid, mesh: Mesh,
                                 bs: int = 8) -> Callable:
    """The lanes-layout 7-point Laplacian (krylov.make_laplacian_lanes)
    sharded over the lane axis as x-slabs, with the cross-shard halo
    exchanged by ring permutes.

    Per shard, the two boundary messages (my lowest slab's low planes to
    the left neighbor, my highest slab's high planes to the right) are
    issued FIRST; the intra-shard stencil (the -6 diagonal, both y/z
    axes, and interior-x planes) computes while they fly; the received
    planes are consumed only in the final edge concatenation.  Global x
    BCs fall out of the ring: periodic is the natural wrap, zero-gradient
    clamps shard 0 / D-1 edges to their own planes.

    Requires a 1-D device mesh whose size divides the x tile count —
    anything else raises (the silently-degenerate sharding this replaces
    is exactly what parallel/mesh._factor2's divide= guard now rejects).
    """
    from cup3d_tpu.grid.uniform import BC

    axis = mesh.axis_names[0]
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    D = mesh_shape[axis]
    if int(np.prod(mesh.devices.shape)) != D:
        raise ValueError(
            f"make_laplacian_lanes_sharded needs a 1-D mesh; got "
            f"{mesh_shape}"
        )
    nb = tuple(s // bs for s in grid.shape)
    if any(s % bs for s in grid.shape):
        raise ValueError(f"grid {grid.shape} not divisible by bs={bs}")
    if nb[0] % D:
        raise ValueError(
            f"{D} devices cannot x-slab {nb[0]} tile columns "
            f"(grid {grid.shape}, bs={bs}): choose a mesh size dividing "
            f"nx/bs — see parallel.mesh.make_mesh(divide=...)"
        )
    nbx_loc = nb[0] // D
    nbyz = nb[1] * nb[2]
    T_loc = nbx_loc * nbyz
    strides = (nbyz, nb[2], 1)
    lanes = np.arange(T_loc)
    tco = (lanes // nbyz, lanes // nb[2] % nb[1], lanes % nb[2])
    inv_h2 = 1.0 / (grid.h * grid.h)
    periodic0 = grid.bc[0] == BC.periodic

    def neighbor_local(t, ax, sign):
        # axes 1/2 are unsharded: identical mask/wrap logic to
        # krylov.make_laplacian_lanes.neighbor on the local lane set
        periodic = grid.bc[ax] == BC.periodic
        n = t.shape[ax]
        st, nba = strides[ax], nb[ax]
        if sign > 0:
            inner = jax.lax.slice_in_dim(t, 1, n, axis=ax)
            edge = jax.lax.slice_in_dim(t, n - 1, n, axis=ax)
            src = jax.lax.slice_in_dim(t, 0, 1, axis=ax)
            plane = jnp.roll(src, -st, axis=-1)
            mask = jnp.asarray(tco[ax] == nba - 1)
            wrap = jnp.roll(src, (nba - 1) * st, axis=-1)
        else:
            inner = jax.lax.slice_in_dim(t, 0, n - 1, axis=ax)
            edge = jax.lax.slice_in_dim(t, 0, 1, axis=ax)
            src = jax.lax.slice_in_dim(t, n - 1, n, axis=ax)
            plane = jnp.roll(src, st, axis=-1)
            mask = jnp.asarray(tco[ax] == 0)
            wrap = jnp.roll(src, -(nba - 1) * st, axis=-1)
        plane = jnp.where(mask, wrap if periodic else edge, plane)
        parts = (inner, plane) if sign > 0 else (plane, inner)
        return jnp.concatenate(parts, axis=ax)

    def local_apply(t: jnp.ndarray) -> jnp.ndarray:
        # -- issue the halo ring transfers first (async DMA on TPU) ----
        p0 = jax.lax.slice_in_dim(t, 0, 1, axis=0)       # own low planes
        p1 = jax.lax.slice_in_dim(t, bs - 1, bs, axis=0)  # own high
        recv_lo = ring_shift(p1[..., -nbyz:], axis, shift=+1)
        recv_hi = ring_shift(p0[..., :nbyz], axis, shift=-1)
        # -- interior compute while the halo flies ---------------------
        out = -6.0 * t
        for ax in (1, 2):
            out = out + neighbor_local(t, ax, +1) + neighbor_local(t, ax, -1)
        # -- boundary tiles: consume the received planes ---------------
        if periodic0:
            edge_lo, edge_hi = recv_lo, recv_hi
        else:
            me = jax.lax.axis_index(axis)
            edge_lo = jnp.where(me == 0, p0[..., :nbyz], recv_lo)
            edge_hi = jnp.where(me == D - 1, p1[..., -nbyz:], recv_hi)
        hi = jnp.concatenate([p0[..., nbyz:], edge_hi], axis=-1)
        lo = jnp.concatenate([edge_lo, p1[..., :-nbyz]], axis=-1)
        out = out + jnp.concatenate([t[1:], hi], axis=0)
        out = out + jnp.concatenate([lo, t[:-1]], axis=0)
        return out * inv_h2

    spec = P(None, None, None, axis)
    return shard_map(local_apply, mesh=mesh, in_specs=(spec,),
                     out_specs=spec, check_vma=False)
