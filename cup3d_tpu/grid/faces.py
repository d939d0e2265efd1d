"""Face-structured halo assembly: the TPU fast path for axis-stencil labs.

Every hot AMR operator (7-pt Laplacian, upwind-5 advection, centered
grad/div/curl, face fluxes) reads only AXIS-ALIGNED face ghosts — never
edge or corner ghosts.  The general per-ghost-cell gather tables
(grid/blocks.py LabTables) pay for that generality with scalar gathers:
measured on a v5e, one width-1 scalar lab at 1408 blocks costs ~92 ms,
~11M one-element gather rows at ~115M elem/s — the entire cost of the
production BiCGSTAB iteration (VERDICT round 2, item 1).

FaceTables replaces them on the hot path with block-granular gathers and
dense math (the structured-AMR "restriction pyramid" design):

- A *shadow* entry is kept for every internal octree node: the 8-to-1
  average of its children (computed bottom-up with dense average-pools, a
  few % extra cells).  With shadows, a same-level neighbor AND a finer
  neighbor both reduce to ONE case: copy the face plane of an "ext"
  buffer entry — a (nb,)-indexed gather of whole (w, bs, bs) slabs.
- A coarser neighbor interpolates from a 2x2x2 super-region of coarse
  entries around the face (parent side contributes one plane: the
  quadratic stencil of the first ghost plane reaches one coarse cell
  INSIDE the block's own footprint).  All 8 window entries exist as
  leaves or shadows by 26-neighbor 2:1 balance; the interpolation is the
  SAME separable quadratic as BlockLab (blocks.py _interp_matrix) applied
  as three small dense tensordots after one batched tangential slice.
- Closed domain boundaries clamp the block's own edge plane
  (zero-gradient) with per-component sign flips — a dense select.
- The only cells that keep per-cell gathers are degenerate: coarse faces
  whose interpolation window crosses a CLOSED domain boundary.  Those
  whole blocks fall back to a row-subset of the old LabTables (bit-equal
  to the reference path); on periodic domains the set is empty.

Reference counterpart: BlockLab/m_CoarsenedBlock coarse-fine interpolation
(main.cpp:3457-4628); the shadow pyramid replaces the reference's
AverageDownAndFill fine-side messages (main.cpp:1832-1905).  Unlike
LabTables, the result lab has ZERO edge/corner ghosts — callers must be
axis-stencil operators (every consumer in ops/amr_ops.py is).

The shadow restriction is exact hierarchical averaging at any subtree
depth, which removes LabTables' documented approximation (a) (middle-
octant sampling for regions two levels finer than the scratch).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cup3d_tpu.grid.uniform import BC

_HI = jax.lax.Precision.HIGHEST

__all__ = ["FaceTables", "build_face_tables", "pad_face_tables"]


def _cw(w: int) -> int:
    # coarse halo depth, same rule as blocks.py _build_lab_tables
    return max(2, (w + 1) // 2 + 1)


@dataclass
class FaceTables:
    """Device tables for face-slab halo assembly on one (topology, width).

    Duck-compatible with LabTables where it matters: ``width``,
    ``assemble_scalar``, ``assemble_vector``, ``assemble_component``.
    """

    width: int
    bs: int
    nb: int
    # restriction pyramid: per level-group (deepest first) the (ns_g, 8)
    # child entry indices; group g owns ext slots [starts[g], starts[g]+ns_g)
    child_idx: Tuple[jnp.ndarray, ...]
    shadow_starts: Tuple[int, ...]
    n_entries: int  # nb + total shadows (zero sentinel lives at n_entries)
    src: jnp.ndarray  # (6, nb) int32 ext entry per face (kind-0 rows)
    bmask: jnp.ndarray  # (6, nb) bool: closed-boundary face (clamp rows)
    bsign: Tuple[Tuple[float, float, float], ...]  # static (6, 3) signs
    # coarse faces, compacted per face
    cf_rows: Tuple[jnp.ndarray, ...]  # 6 x (ncf_f,) int32 block rows
    cf_src: Tuple[jnp.ndarray, ...]  # 6 x (ncf_f, 8) int32 window entries
    cf_toff: Tuple[jnp.ndarray, ...]  # 6 x (ncf_f, 2) int32 tangential offs
    interp_t: jnp.ndarray  # (bs, S) tangential quadratic weights
    interp_n_lo: jnp.ndarray  # (w, cw+1) normal weights, low-side face
    interp_n_hi: jnp.ndarray  # (w, cw+1) normal weights, high-side face
    # degenerate blocks: row-subset of the old per-cell tables
    fb_rows: Optional[jnp.ndarray]  # (nbi,) int32 or None
    fb_tables: Optional[object]  # LabTables subset (nbi rows) or None

    # -- protocol ----------------------------------------------------------
    # the component axis rides through the whole assembly (one restriction
    # pyramid + one gather pipeline for all 3 velocity components)

    def assemble_scalar(self, field: jnp.ndarray, bs: int) -> jnp.ndarray:
        return _assemble_multi(self, field[..., None], None)[..., 0]

    def assemble_vector(self, field: jnp.ndarray, bs: int) -> jnp.ndarray:
        return _assemble_multi(self, field, (0, 1, 2))

    def assemble_component(
        self, field: jnp.ndarray, bs: int, comp: int
    ) -> jnp.ndarray:
        return _assemble_multi(self, field[..., None], (comp,))[..., 0]


def _flatten(t: FaceTables):
    children = (
        t.child_idx, t.src, t.bmask, t.cf_rows, t.cf_src, t.cf_toff,
        t.interp_t, t.interp_n_lo, t.interp_n_hi, t.fb_rows, t.fb_tables,
    )
    aux = (t.width, t.bs, t.nb, t.shadow_starts, t.n_entries, t.bsign)
    return children, aux


def _unflatten(aux, ch):
    return FaceTables(
        width=aux[0], bs=aux[1], nb=aux[2], child_idx=ch[0],
        shadow_starts=aux[3], n_entries=aux[4], src=ch[1], bmask=ch[2],
        bsign=aux[5], cf_rows=ch[3], cf_src=ch[4], cf_toff=ch[5],
        interp_t=ch[6], interp_n_lo=ch[7], interp_n_hi=ch[8],
        fb_rows=ch[9], fb_tables=ch[10],
    )


jax.tree_util.register_pytree_node(FaceTables, _flatten, _unflatten)


# ---------------------------------------------------------------------------
# host builder
# ---------------------------------------------------------------------------


def build_face_tables(grid, width: int) -> FaceTables:
    """Build FaceTables for ``grid`` (a BlockGrid) at stencil width
    ``width``.  Pure host work; all outputs are device arrays."""
    from cup3d_tpu.grid.blocks import LabTables

    tree = grid.tree
    bs = grid.bs
    w = width
    cw = _cw(w)
    cbs = bs // 2
    S = cbs + 2 * cw
    nb = grid.nb
    L = bs + 2 * w

    # -- shadow slots: internal nodes grouped by level, deepest first ------
    internal = sorted(tree.internal_nodes(), key=lambda k: -k[0])
    shadow_slot = {}
    for i, key in enumerate(internal):
        shadow_slot[key] = nb + i
    ns = len(internal)
    n_entries = nb + ns
    sentinel = n_entries  # zero block

    def entry_of(key):
        """Ext entry of a block position: leaf slot or shadow slot."""
        s = grid.slot.get(key)
        if s is not None:
            return s
        return shadow_slot.get(key)

    child_idx: List[np.ndarray] = []
    shadow_starts: List[int] = []
    i = 0
    while i < ns:
        l = internal[i][0]
        j = i
        while j < ns and internal[j][0] == l:
            j += 1
        rows = np.empty((j - i, 8), np.int32)
        for r, (lv, bi, bj, bk) in enumerate(internal[i:j]):
            for di in (0, 1):
                for dj in (0, 1):
                    for dk in (0, 1):
                        ck = (lv + 1, 2 * bi + di, 2 * bj + dj, 2 * bk + dk)
                        e = entry_of(ck)
                        assert e is not None, f"missing child {ck}"
                        rows[r, di * 4 + dj * 2 + dk] = e
        child_idx.append(rows)
        shadow_starts.append(nb + i)
        i = j

    # -- per-face classification ------------------------------------------
    src = np.full((6, nb), sentinel, np.int32)
    bmask = np.zeros((6, nb), bool)
    bsign = []
    for a in range(3):
        for hi in (0, 1):
            if grid.bc[a] == BC.wall:
                bsign.append((-1.0, -1.0, -1.0))
            elif grid.bc[a] == BC.periodic:
                bsign.append((1.0, 1.0, 1.0))
            else:  # freespace: flip the face-normal component
                s = [1.0, 1.0, 1.0]
                s[a] = -1.0
                bsign.append(tuple(s))

    cf_rows: List[List[int]] = [[] for _ in range(6)]
    cf_src: List[List[List[int]]] = [[] for _ in range(6)]
    cf_toff: List[List[Tuple[int, int]]] = [[] for _ in range(6)]
    irregular: set = set()

    tang = {0: (1, 2), 1: (0, 2), 2: (0, 1)}
    for b in range(nb):
        l = int(grid.level[b])
        ijk = grid.ijk[b]
        for a in range(3):
            t1, t2 = tang[a]
            for hi in (0, 1):
                f = 2 * a + hi
                npos = ijk.copy()
                npos[a] += 1 if hi else -1
                wpos = tree.wrap(l, npos)
                if wpos is None:
                    bmask[f, b] = True  # closed boundary: clamp row
                    continue
                own = grid._owner_level_vec(l, np.asarray(wpos)[None])[0]
                if own == l:
                    src[f, b] = grid.slot[(l, *wpos)]
                elif own == l + 1:
                    e = shadow_slot.get((l, *wpos))
                    assert e is not None, "finer neighbor without shadow"
                    src[f, b] = e
                else:  # own == l - 1: coarse face
                    parent = (l - 1, ijk[0] // 2, ijk[1] // 2, ijk[2] // 2)
                    # window base per axis: parent pos, shifted -1 along a
                    # tangential axis when the block sits on the LOW octant
                    base = list(parent[1:])
                    toffs = []
                    for t in (t1, t2):
                        qa_low = (ijk[t] & 1) == 0
                        if qa_low:
                            base[t] -= 1
                            toffs.append(2 * bs // 2 - cw)  # bs - cw
                        else:
                            toffs.append(cbs - cw)
                    # normal: P side = parent, N side = coarse neighbor
                    ok = True
                    entries = []
                    for side in (0, 1):  # 0 = parent side, 1 = neighbor
                        for o1 in (0, 1):
                            for o2 in (0, 1):
                                p = list(base)
                                p[t1] += o1
                                p[t2] += o2
                                if side:
                                    p[a] += 1 if hi else -1
                                wp = tree.wrap(l - 1, p)
                                if wp is None:
                                    ok = False
                                    break
                                e = entry_of((l - 1, *wp))
                                if e is None:
                                    # region owned >=2 coarser: degenerate
                                    ok = False
                                    break
                                entries.append(e)
                            if not ok:
                                break
                        if not ok:
                            break
                    if not ok:
                        irregular.add(b)
                        continue
                    # parent side must include the parent itself
                    cf_rows[f].append(b)
                    cf_src[f].append(entries)
                    cf_toff[f].append(tuple(toffs))

    # -- interpolation matrices -------------------------------------------
    from cup3d_tpu.grid.blocks import BlockGrid

    W = BlockGrid._interp_matrix(L, S, w, cw)
    Tt = W[w:w + bs, :]  # (bs, S)
    Tn_lo = W[:w, : cw + 1]  # normal coords -cw..0
    Tn_hi = W[w + bs:, S - cw - 1:]  # normal coords cbs-1..cbs+cw-1
    assert not np.any(W[:w, cw + 1:]), "low-face normal support escapes"
    assert not np.any(W[w + bs:, : S - cw - 1]), "hi-face support escapes"

    # -- degenerate blocks: subset of the old per-cell tables --------------
    fb_rows = fb_tables = None
    if irregular:
        rows = np.array(sorted(irregular), np.int32)
        full = grid.lab_tables(w)
        fb_rows = jnp.asarray(rows)
        fb_tables = LabTables(
            width=w,
            ghost_xyz=full.ghost_xyz,
            g_idx=full.g_idx[rows],
            g_w=full.g_w[rows],
            g_sign=full.g_sign[rows],
            mask_coarse=full.mask_coarse[rows],
            s_idx=full.s_idx[rows],
            s_w=full.s_w[rows],
            s_sign=full.s_sign[rows],
            interp_w=full.interp_w,
            any_coarse=full.any_coarse,
        )
        # drop degenerate rows from the dense coarse lists (they are fully
        # overwritten anyway, but skipping keeps the window math clean)
        for f in range(6):
            keep = [i for i, r in enumerate(cf_rows[f]) if r not in irregular]
            cf_rows[f] = [cf_rows[f][i] for i in keep]
            cf_src[f] = [cf_src[f][i] for i in keep]
            cf_toff[f] = [cf_toff[f][i] for i in keep]

    def _i32(x, shape):
        arr = np.asarray(x, np.int32).reshape(shape)
        return jnp.asarray(arr)

    return FaceTables(
        width=w, bs=bs, nb=nb,
        child_idx=tuple(jnp.asarray(c) for c in child_idx),
        shadow_starts=tuple(shadow_starts),
        n_entries=n_entries,
        src=jnp.asarray(src),
        bmask=jnp.asarray(bmask),
        bsign=tuple(bsign),
        cf_rows=tuple(
            _i32(cf_rows[f], (len(cf_rows[f]),)) for f in range(6)
        ),
        cf_src=tuple(
            _i32(cf_src[f], (len(cf_src[f]), 8)) for f in range(6)
        ),
        cf_toff=tuple(
            _i32(cf_toff[f], (len(cf_toff[f]), 2)) for f in range(6)
        ),
        interp_t=jnp.asarray(Tt),
        interp_n_lo=jnp.asarray(Tn_lo),
        interp_n_hi=jnp.asarray(Tn_hi),
        fb_rows=fb_rows,
        fb_tables=fb_tables,
    )


# ---------------------------------------------------------------------------
# capacity-bucketed padding (grid/bucket.py): same-shape tables across
# regrids that stay within a bucket, so compiled consumers never retrace
# ---------------------------------------------------------------------------


def pad_face_tables(t: FaceTables, grid, cap: int) -> FaceTables:
    """Pad ``t`` (built for ``grid``, ``grid.nb`` real blocks) to block
    capacity ``cap`` (> nb) with INERT rows, bucketing every auxiliary
    row count up its own ladder (grid/bucket.py).

    Inertness: padding blocks' face sources point at the zero sentinel
    (their labs assemble to 0); padded shadow-group rows restrict zeros
    into padded shadow slots; padded coarse-face rows interpolate zeros
    and write them into the last padding block (``cap - 1``), whose lab
    is zero anyway; padded fallback rows gather the sentinel and write
    into the same dump block.  Two topologies with equal bucketed shapes
    produce tree-equal aux data (``nb``/``shadow_starts``/``n_entries``
    are capacity-derived), which is what lets jitted consumers reuse
    their compiled executables across regrids."""
    from cup3d_tpu.grid import bucket as bk
    from cup3d_tpu.grid.blocks import LabTables

    nb, bs, w = t.nb, t.bs, t.width
    if cap <= nb:
        raise ValueError(f"capacity {cap} must exceed nb={nb} "
                         "(>= 1 padding block is the dump-target invariant)")
    tree = grid.tree
    level_max = tree.cfg.level_max
    # identical expression to build_face_tables: same shadow ordering
    internal = sorted(tree.internal_nodes(), key=lambda k: -k[0])
    counts: dict = {}
    for k in internal:
        counts[k[0]] = counts.get(k[0], 0) + 1
    # one group per possible parent level, deepest first, ALWAYS emitted
    # (empty levels keep shape (0, 8)) so group ordering is bucket-stable
    levels = list(range(level_max - 2, -1, -1))
    caps_g = [bk.count_capacity(counts.get(l, 0)) for l in levels]
    starts_new, off = [], 0
    for c in caps_g:
        starts_new.append(cap + off)
        off += c
    n_entries_new = cap + off
    sent_new = n_entries_new

    # old entry index -> padded entry index
    remap = np.empty(t.n_entries + 1, np.int64)
    remap[:nb] = np.arange(nb)
    level_pos = dict(zip(levels, starts_new))
    seen: dict = {}
    for i, key in enumerate(internal):
        l = key[0]
        o = seen.get(l, 0)
        seen[l] = o + 1
        remap[nb + i] = level_pos[l] + o
    remap[t.n_entries] = sent_new

    present = sorted({k[0] for k in internal}, reverse=True)
    child_new = []
    for li, l in enumerate(levels):
        cnt = counts.get(l, 0)
        rows = np.full((caps_g[li], 8), sent_new, np.int64)
        if cnt:
            old = np.asarray(t.child_idx[present.index(l)], np.int64)
            rows[:cnt] = remap[old]
        child_new.append(jnp.asarray(rows, jnp.int32))

    src_new = np.full((6, cap), sent_new, np.int64)
    src_new[:, :nb] = remap[np.asarray(t.src, np.int64)]
    bmask_new = np.zeros((6, cap), bool)
    bmask_new[:, :nb] = np.asarray(t.bmask)

    dump_row = cap - 1  # guaranteed padding block
    cf_rows_new, cf_src_new, cf_toff_new = [], [], []
    for f in range(6):
        rows = np.asarray(t.cf_rows[f], np.int64)
        n = rows.shape[0]
        c = bk.count_capacity(n)
        r2 = np.full(c, dump_row, np.int64)
        s2 = np.full((c, 8), sent_new, np.int64)
        o2 = np.zeros((c, 2), np.int64)
        if n:
            r2[:n] = rows
            s2[:n] = remap[np.asarray(t.cf_src[f], np.int64)]
            o2[:n] = np.asarray(t.cf_toff[f], np.int64)
        cf_rows_new.append(jnp.asarray(r2, jnp.int32))
        cf_src_new.append(jnp.asarray(s2, jnp.int32))
        cf_toff_new.append(jnp.asarray(o2, jnp.int32))

    fb_rows = fb_tables = None
    if t.fb_rows is not None:
        old_rows = np.asarray(t.fb_rows, np.int64)
        n = old_rows.shape[0]
        c = bk.count_capacity(n)
        fb_rows = jnp.asarray(
            bk.pad_rows(old_rows, c, fill=dump_row), jnp.int32
        )
        tb = t.fb_tables
        cell_sent_old = nb * bs**3
        cell_sent_new = cap * bs**3

        def _remap_cells(idx):
            v = np.asarray(idx, np.int64).copy()
            v[v >= cell_sent_old] = cell_sent_new
            return bk.pad_rows(v, c, fill=cell_sent_new)

        fb_tables = LabTables(
            width=tb.width,
            ghost_xyz=tb.ghost_xyz,
            g_idx=jnp.asarray(_remap_cells(tb.g_idx), jnp.int32),
            g_w=jnp.asarray(bk.pad_rows(tb.g_w, c)),
            g_sign=jnp.asarray(bk.pad_rows(tb.g_sign, c, fill=1.0)),
            mask_coarse=jnp.asarray(
                bk.pad_rows(tb.mask_coarse, c, fill=False)
            ),
            s_idx=jnp.asarray(_remap_cells(tb.s_idx), jnp.int32),
            s_w=jnp.asarray(bk.pad_rows(tb.s_w, c)),
            s_sign=jnp.asarray(bk.pad_rows(tb.s_sign, c, fill=1.0)),
            interp_w=tb.interp_w,
            any_coarse=tb.any_coarse,
        )

    return FaceTables(
        width=w, bs=bs, nb=cap,
        child_idx=tuple(child_new),
        shadow_starts=tuple(starts_new),
        n_entries=n_entries_new,
        src=jnp.asarray(src_new, jnp.int32),
        bmask=jnp.asarray(bmask_new),
        bsign=t.bsign,
        cf_rows=tuple(cf_rows_new),
        cf_src=tuple(cf_src_new),
        cf_toff=tuple(cf_toff_new),
        interp_t=t.interp_t,
        interp_n_lo=t.interp_n_lo,
        interp_n_hi=t.interp_n_hi,
        fb_rows=fb_rows,
        fb_tables=fb_tables,
    )


# ---------------------------------------------------------------------------
# device assembly
# ---------------------------------------------------------------------------


def _restrict8(ch: jnp.ndarray, bs: int) -> jnp.ndarray:
    """(ns, 8, C, bs,bs,bs) child blocks -> (ns, C, bs,bs,bs) parent
    restriction (exact hierarchical 8-to-1 average)."""
    ns, C = ch.shape[0], ch.shape[2]
    c = ch.reshape(ns, 2, 2, 2, C, bs, bs, bs)
    c = c.transpose(0, 4, 1, 5, 2, 6, 3, 7).reshape(
        ns, C, 2 * bs, 2 * bs, 2 * bs
    )
    return c.reshape(ns, C, bs, 2, bs, 2, bs, 2).mean(axis=(3, 5, 7))


def _ext_buffer(t: FaceTables, fm: jnp.ndarray) -> jnp.ndarray:
    """(n_entries+1, C, bs, bs, bs): leaves, shadows (bottom-up), zero row.
    fm: (nb, C, bs, bs, bs) — the component axis sits at dim 1 so the
    innermost (TPU lane/sublane) dims stay the spatial block dims."""
    bs = t.bs
    n = t.n_entries
    C = fm.shape[1]
    ext = jnp.zeros((n + 1, C, bs, bs, bs), fm.dtype)
    ext = ext.at[: t.nb].set(fm)
    for ci, start in zip(t.child_idx, t.shadow_starts):
        ch = jnp.take(ext, ci, axis=0)  # (ns_g, 8, C, bs,bs,bs)
        ext = jax.lax.dynamic_update_slice(
            ext, _restrict8(ch, bs), (start, 0, 0, 0, 0)
        )
    return ext


def _slab(arr: jnp.ndarray, axis: int, start: int, depth: int):
    """Static slab slice along a block axis, normal axis moved to dim 2:
    (N, C, d, t1, t2)."""
    sl = jax.lax.slice_in_dim(arr, start, start + depth, axis=axis + 2)
    return jnp.moveaxis(sl, axis + 2, 2)


def _place(lab: jnp.ndarray, slab: jnp.ndarray, a: int, hi: int, w: int,
           bs: int) -> jnp.ndarray:
    """Write a (nb, C, w, bs, bs) slab into the (nb, C, L,L,L) lab's face
    region."""
    slab = jnp.moveaxis(slab, 2, a + 2)
    idx = [slice(None)] * 5
    idx[a + 2] = slice(w + bs, w + bs + w) if hi else slice(0, w)
    for t in range(3):
        if t != a:
            idx[t + 2] = slice(w, w + bs)
    return lab.at[tuple(idx)].set(slab)


def _coarse_halo(t: FaceTables, ext: jnp.ndarray, f: int) -> jnp.ndarray:
    """(ncf, C, w, bs, bs) interpolated halo slabs for face f's coarse
    rows."""
    a, hi = f // 2, f % 2
    bs, w = t.bs, t.width
    cw = t.interp_n_lo.shape[1] - 1
    S = t.interp_t.shape[1]
    src8 = t.cf_src[f]
    C = ext.shape[1]
    # parent side: ONE plane adjacent to the face; neighbor side: cw planes
    if hi:
        pp = _slab(ext, a, bs - 1, 1)  # parent's last plane
        npl = _slab(ext, a, 0, cw)  # neighbor's first cw planes
    else:
        pp = _slab(ext, a, 0, 1)  # parent's first plane
        npl = _slab(ext, a, bs - cw, cw)  # neighbor's last cw planes

    P = jnp.take(pp, src8[:, 0:4], axis=0)  # (ncf, 4, C, 1, bs, bs)
    N = jnp.take(npl, src8[:, 4:8], axis=0)  # (ncf, 4, C, cw, bs, bs)

    def arrange(x):
        n, _, _, d = x.shape[:4]
        y = x.reshape(n, 2, 2, C, d, bs, bs)
        y = y.transpose(0, 3, 4, 1, 5, 2, 6)
        return y.reshape(n, C, d, 2 * bs, 2 * bs)

    P16, N16 = arrange(P), arrange(N)
    # ascending coarse normal coordinate
    slab16 = (
        jnp.concatenate([P16, N16], axis=2)
        if hi
        else jnp.concatenate([N16, P16], axis=2)
    )

    def tslice(s, off):
        return jax.lax.dynamic_slice(
            s, (0, 0, off[0], off[1]), (C, cw + 1, S, S)
        )

    win = jax.vmap(tslice)(slab16, t.cf_toff[f])  # (ncf, C, cw+1, S, S)
    Tn = t.interp_n_hi if hi else t.interp_n_lo  # (w, cw+1)
    Tt = t.interp_t  # (bs, S)
    # each tensordot appends its output axis:
    # (n,C,d,S,S) -> (n,C,S,S,w) -> (n,C,S,w,bs) -> (n,C,w,bs,bs)
    # float32 operands as they are: the default precision rounds them to
    # bfloat16 on the TPU, and a ghost cell is then good to 3 digits
    out = jnp.tensordot(win, Tn.astype(win.dtype), axes=[[2], [1]],
                        precision=_HI)
    out = jnp.tensordot(out, Tt.astype(win.dtype), axes=[[2], [1]],
                        precision=_HI)
    out = jnp.tensordot(out, Tt.astype(win.dtype), axes=[[2], [1]],
                        precision=_HI)
    return out  # (ncf, C, w, bs, bs)


@jax.named_scope("Halo")
def _assemble_multi(
    t: FaceTables, fields: jnp.ndarray, sign_comps: Optional[Tuple[int, ...]]
) -> jnp.ndarray:
    """Core: (nb, bs,bs,bs, C) -> (nb, L,L,L, C) faces-only labs.
    ``sign_comps`` maps each trailing component to its BC-sign component
    (None: scalar semantics, zero-gradient ghosts, no sign flips).

    Internally the component axis lives at dim 1 (a batch dim) so the
    innermost dims stay spatial — a trailing size-1 axis would land on the
    TPU lane axis and serialize every op (measured ~3x slower)."""
    bs, w, nb = t.bs, t.width, t.nb
    L = bs + 2 * w
    C = fields.shape[-1]
    fm = jnp.moveaxis(fields, -1, 1)  # (nb, C, bs,bs,bs)
    ext = _ext_buffer(t, fm)

    lab = jnp.zeros((nb, C) + (L,) * 3, fields.dtype)
    lab = lab.at[:, :, w:w + bs, w:w + bs, w:w + bs].set(fm)

    for a in range(3):
        for hi in (0, 1):
            f = 2 * a + hi
            # kind-0: neighbor (leaf or shadow) face slab
            sl = _slab(ext, a, 0, w) if hi else _slab(ext, a, bs - w, w)
            slab = jnp.take(sl, t.src[f], axis=0)  # (nb, C, w, bs, bs)
            # boundary clamp: own edge plane replicated, with BC sign
            own = (
                _slab(ext[:nb], a, bs - 1, 1)
                if hi
                else _slab(ext[:nb], a, 0, 1)
            )
            own = jnp.broadcast_to(own, slab.shape)
            if sign_comps is not None:
                sgn = np.array([t.bsign[f][c] for c in sign_comps],
                               np.float32).reshape(1, C, 1, 1, 1)
                own = own * sgn
            bm = t.bmask[f][:, None, None, None, None]
            slab = jnp.where(bm, own.astype(slab.dtype), slab)
            # coarse faces: separable quadratic from the coarse window
            if t.cf_rows[f].shape[0]:
                halo = _coarse_halo(t, ext, f)
                slab = slab.at[t.cf_rows[f]].set(halo.astype(slab.dtype))
            lab = _place(lab, slab, a, hi, w, bs)

    # degenerate rows: old per-cell path, bit-equal to LabTables
    if t.fb_rows is not None:
        from cup3d_tpu.grid import blocks as B

        tb = t.fb_tables
        gx, gy, gz = tb.ghost_xyz
        for ci in range(C):
            field = fields[..., ci]
            comp = None if sign_comps is None else sign_comps[ci]
            sub = field[t.fb_rows]
            flat = jnp.concatenate(
                [field.reshape(-1), jnp.zeros(1, field.dtype)]
            )
            ghosts = B._gather_comp(flat, tb.g_idx, tb.g_w)
            if comp is not None:
                ghosts = ghosts * tb.g_sign[..., comp]
            if tb.any_coarse:
                scratch = B._gather_comp(flat, tb.s_idx, tb.s_w)
                if comp is not None:
                    scratch = scratch * tb.s_sign[..., comp]
                Ssc = tb.interp_w.shape[1]
                interp = B._upsample(
                    scratch.reshape(-1, Ssc, Ssc, Ssc), tb.interp_w
                )
                ghosts = jnp.where(
                    tb.mask_coarse, interp[:, gx, gy, gz], ghosts
                )
            sub_lab = jnp.zeros((sub.shape[0],) + (L,) * 3, field.dtype)
            sub_lab = sub_lab.at[:, w:w + bs, w:w + bs, w:w + bs].set(sub)
            sub_lab = sub_lab.at[:, gx, gy, gz].set(
                ghosts.astype(field.dtype)
            )
            lab = lab.at[t.fb_rows, ci].set(sub_lab)
    return jnp.moveaxis(lab, 1, -1)
