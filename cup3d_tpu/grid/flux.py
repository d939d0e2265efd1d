"""Conservative flux correction at coarse-fine faces (reference
FluxCorrection / FluxCorrectionMPI, main.cpp:555-802, 2546-2946).

Convention: kernels emit *outward, per-unit-area* face fluxes as a
``(nb, 6, bs, bs)`` array — faces ordered (-x, +x, -y, +y, -z, +z), the
(bs, bs) plane indexed by the two remaining axes in ascending order.  For a
cell-centered conservative operator ``out = (1/h) * sum_faces F_outward``,
the coarse side of every coarse-fine face is corrected by

    out[coarse boundary cell] += (mean of 4 fine fluxes * (-1) - F_coarse)/h_c

where the -1 re-orients the fine blocks' outward flux (their face normal
points opposite the coarse face's).  Only the coarse side is touched — the
fine side is already accurate (reference FillBlockCases, main.cpp:729-801).

Tables are host-built NumPy; ``apply`` is jittable gather/scatter-add.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

_FACE_AXIS = (0, 0, 1, 1, 2, 2)
_FACE_SIDE = (-1, 1, -1, 1, -1, 1)  # low/high


@dataclass
class FluxTables:
    """Precomputed coarse-side correction (empty tables are valid).

    ``apply`` is the protocol the AMR operators use; the sharded forest
    (parallel/forest.py) duck-types it with a cross-shard exchange."""

    tgt_cell: jnp.ndarray  # (nc,) flat index into (nb*bs^3) cell array
    tgt_flux: jnp.ndarray  # (nc,) flat index into (nb*6*bs^2) flux array
    src_flux: jnp.ndarray  # (nc, 4) fine-side flux indices
    inv_hc: jnp.ndarray  # (nc,) 1/h of the corrected (coarse) block
    ncorr: int

    def apply(self, out: jnp.ndarray, fluxes: jnp.ndarray) -> jnp.ndarray:
        return apply_flux_correction(out, fluxes, self)


# pytree registration: see grid/blocks.py LabTables — tables travel as jit
# arguments, not closure constants embedded in the HLO
jax.tree_util.register_pytree_node(
    FluxTables,
    lambda t: ((t.tgt_cell, t.tgt_flux, t.src_flux, t.inv_hc), (t.ncorr,)),
    lambda aux, ch: FluxTables(
        tgt_cell=ch[0], tgt_flux=ch[1], src_flux=ch[2], inv_hc=ch[3],
        ncorr=aux[0],
    ),
)


def build_flux_tables(grid) -> FluxTables:
    """grid: BlockGrid.  Enumerates every (coarse block, face) whose
    neighbor region is one level finer."""
    bs = grid.bs
    tree = grid.tree
    tgt_cell, tgt_flux, src_flux, inv_hc = [], [], [], []

    jj, kk = np.meshgrid(np.arange(bs), np.arange(bs), indexing="ij")
    jj, kk = jj.ravel(), kk.ravel()  # coarse face-cell coords (bs^2,)

    for s, (l, bi, bj, bk) in enumerate(grid.keys):
        for face in range(6):
            ax, side = _FACE_AXIS[face], _FACE_SIDE[face]
            npos = [bi, bj, bk]
            npos[ax] += side
            w = tree.wrap(l, npos)
            if w is None:
                continue
            # no try/except: a KeyError from owner_level always means a
            # broken tree, and silently skipping a coarse-fine face would
            # silently lose conservation
            own = tree.owner_level(l, w)
            if own != l + 1:
                continue
            # fine neighbor blocks: children of region w at level l+1 whose
            # face-adjacent layer touches this block
            t1, t2 = [a for a in range(3) if a != ax]
            # coarse boundary cell of this block at the face
            cell = np.zeros((bs * bs, 3), np.int64)
            cell[:, ax] = 0 if side < 0 else bs - 1
            cell[:, t1] = jj
            cell[:, t2] = kk
            flat_cell = (
                s * bs**3
                + cell[:, 0] * bs * bs
                + cell[:, 1] * bs
                + cell[:, 2]
            )
            flat_flux = s * 6 * bs * bs + face * bs * bs + jj * bs + kk

            # fine blocks: level l+1 positions 2*w + delta, delta[ax] fixed
            # to the side facing back at us
            fine_face = face + (1 if side < 0 else -1)  # their opposite face
            quad1, quad2 = 2 * jj // bs, 2 * kk // bs  # which child
            fpos = np.zeros((bs * bs, 3), np.int64)
            fpos[:, ax] = 2 * w[ax] + (1 if side < 0 else 0)
            fpos[:, t1] = 2 * w[t1] + quad1
            fpos[:, t2] = 2 * w[t2] + quad2
            fslot = grid._slot_maps[l + 1][fpos[:, 0], fpos[:, 1], fpos[:, 2]]
            if np.any(fslot < 0):
                raise KeyError("fine neighbor missing: unbalanced tree")
            # fine face-cell coords of the 4 subcells of each coarse cell
            fj = (2 * jj) % bs
            fk = (2 * kk) % bs
            quads = []
            for dj in (0, 1):
                for dk in (0, 1):
                    quads.append(
                        fslot.astype(np.int64) * 6 * bs * bs
                        + fine_face * bs * bs
                        + (fj + dj) * bs
                        + (fk + dk)
                    )
            tgt_cell.append(flat_cell)
            tgt_flux.append(flat_flux)
            src_flux.append(np.stack(quads, axis=-1))
            inv_hc.append(np.full(bs * bs, 1.0 / grid.h[s], np.float32))

    if not tgt_cell:
        z = np.zeros(0, np.int64)
        return FluxTables(
            jnp.asarray(z, jnp.int32),
            jnp.asarray(z, jnp.int32),
            jnp.asarray(np.zeros((0, 4), np.int64), jnp.int32),
            jnp.asarray(np.zeros(0, np.float32)),
            0,
        )
    return FluxTables(
        jnp.asarray(np.concatenate(tgt_cell), jnp.int32),
        jnp.asarray(np.concatenate(tgt_flux), jnp.int32),
        jnp.asarray(np.concatenate(src_flux), jnp.int32),
        jnp.asarray(np.concatenate(inv_hc)),
        sum(len(t) for t in tgt_cell),
    )


def pad_flux_tables(t: FluxTables, bs: int, cap: int) -> FluxTables:
    """Capacity-bucketed padding (grid/bucket.py): round the correction
    row count up its ladder with INERT rows so the table shapes are
    stable across regrids that stay within a bucket.

    Padding rows carry ``inv_hc = 0`` (their correction is exactly 0)
    and scatter into cell 0 of the last padding block (``cap - 1``,
    guaranteed to exist by the strict block-capacity ladder), so real
    cells are never touched — not even by a signed zero.  Empty tables
    stay empty (a no-coarse-face topology is its own bucket class)."""
    n = int(t.ncorr)
    if n == 0:
        return t
    from cup3d_tpu.grid import bucket as bk

    c = bk.count_capacity(n)
    if c == n:
        return t
    dump_cell = (cap - 1) * bs**3
    dump_flux = (cap - 1) * 6 * bs * bs
    return FluxTables(
        tgt_cell=jnp.asarray(
            bk.pad_rows(t.tgt_cell, c, fill=dump_cell), jnp.int32
        ),
        tgt_flux=jnp.asarray(
            bk.pad_rows(t.tgt_flux, c, fill=dump_flux), jnp.int32
        ),
        src_flux=jnp.asarray(
            bk.pad_rows(t.src_flux, c, fill=dump_flux), jnp.int32
        ),
        inv_hc=jnp.asarray(bk.pad_rows(t.inv_hc, c, fill=0.0)),
        ncorr=c,
    )


@jax.named_scope("FluxCorrection")
def apply_flux_correction(
    out: jnp.ndarray, fluxes: jnp.ndarray, tab: FluxTables
) -> jnp.ndarray:
    """out: (nb, bs,bs,bs) conservative-operator result; fluxes:
    (nb, 6, bs, bs) outward per-unit-area face fluxes.  Returns corrected
    out."""
    if tab.ncorr == 0:
        return out
    shape = out.shape
    flat = out.reshape(-1)
    fflat = fluxes.reshape(-1)
    fine_mean = jnp.mean(fflat[tab.src_flux], axis=-1)
    corr = (-fine_mean - fflat[tab.tgt_flux]) * tab.inv_hc
    flat = flat.at[tab.tgt_cell].add(corr.astype(flat.dtype))
    return flat.reshape(shape)
