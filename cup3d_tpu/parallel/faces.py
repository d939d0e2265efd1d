"""Sharded face-structured halo assembly: the round-3 FaceTables fast path
(grid/faces.py) on the block-sharded forest (parallel/forest.py).

Round 3 left mesh mode on the per-ghost-cell gather tables — measured
10-80x slower than the face-slab design (VERDICT r3 weak item 3).  This
module ports the restriction-pyramid / face-slab assembly to shard_map:

- Entries (leaves + shadow nodes) are owned by shards: leaves by the
  Hilbert cut, a shadow by the owner of its first child.  Hilbert
  contiguity makes a node's children nearly always co-resident, so the
  cross-shard pyramid traffic is a handful of boundary entries.
- The pyramid runs bottom-up exactly as on one device, with one
  entry-granular ``all_to_all`` BEFORE each level group carrying the few
  remote children that group needs (full (C, bs^3) entries — the fine-side
  AverageDownAndFill messages of the reference, main.cpp:1832-1905,
  batched into a static collective).
- One final ``all_to_all`` fetches the remote face-source entries (same-
  level/shadow neighbors and coarse-window members), then the dense
  face-slab / separable-quadratic math of grid/faces.py runs shard-locally
  on the remapped tables.

Degenerate blocks (coarse windows crossing a CLOSED boundary) keep the
per-cell fallback only on the single-device path; topologies that need it
under a mesh raise — every periodic production config has none.

Address space per shard (entry granularity):
    [0, nbs)                      local leaves
    [nbs, nbs + ns_max)           local shadows (padded)
    [recv_g ... )                 received rows, one region per exchange
    zero sentinel                 (always-zero entry)
    scratch                       (padding writes land here)
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from cup3d_tpu.grid.faces import _HI, FaceTables, _place, _restrict8, _slab
from cup3d_tpu.parallel.compat import shard_map

__all__ = ["ShardedFaceTables", "build_sharded_face_tables"]


@dataclass
class _ExchangePlan:
    """One all_to_all at entry granularity: send_idx[t, s, :] = rows (in
    t's local address space) that shard s needs from t; recv region offset
    in the destination address space.  send_idx None = nothing crosses
    shards for this exchange; the kernel skips the collective."""

    send_idx: Optional[jnp.ndarray]  # (D, D, M) int32, sharded on axis 0
    M: int
    recv_off: int


class _EntrySpace:
    """Per-shard entry address bookkeeping for the host builder."""

    def __init__(self, D: int, nbs: int, ns_max: int):
        self.D = D
        self.nbs = nbs
        self.ns_max = ns_max
        self.recv_regions: List[int] = []  # sizes D*M per exchange
        # owner[global_entry] and local slot of each global entry
        self.owner: Dict[int, int] = {}
        self.slot: Dict[int, int] = {}
        # per-shard, per-exchange: global entry -> recv row index
        self.recv_maps: List[List[Dict[int, int]]] = []

    @property
    def n_recv(self) -> int:
        return sum(self.recv_regions)

    def local_size(self) -> int:
        # + zero sentinel + scratch
        return self.nbs + self.ns_max + self.n_recv + 2

    def zero_row(self) -> int:
        return self.nbs + self.ns_max + self.n_recv

    def scratch_row(self) -> int:
        return self.zero_row() + 1

    def resolve(self, e: int, shard: int, sentinel: int) -> int:
        """Global entry -> shard-local row (owned or received)."""
        if e == sentinel:
            return self.zero_row()
        if self.owner[e] == shard:
            return self.slot[e]
        off = self.nbs + self.ns_max
        for x, (size, maps) in enumerate(
            zip(self.recv_regions, self.recv_maps)
        ):
            row = maps[shard].get(e)
            if row is not None:
                return off + row
            off += size
        raise KeyError(f"entry {e} not routed to shard {shard}")


def _plan_exchange(
    space: _EntrySpace, needed: List[set], D: int
) -> Tuple[Optional[np.ndarray], int]:
    """needed[s] = set of global entries shard s must receive.  Returns
    (send_idx (D, D, M), M) and registers the recv region + maps.  When NO
    shard needs anything remote, returns (None, 0) and registers an empty
    region — the kernel skips the all_to_all entirely (Hilbert contiguity
    makes most pyramid groups fully shard-local, and one needless
    collective per group per assembly lands inside every Krylov
    iteration; code-review r4)."""
    groups = []
    for s in range(D):
        by_src: List[List[int]] = [[] for _ in range(D)]
        for e in sorted(needed[s]):
            t = space.owner[e]
            if t != s:
                by_src[t].append(e)
        groups.append(by_src)
    if not any(g for gs in groups for g in gs):
        space.recv_regions.append(0)
        space.recv_maps.append([dict() for _ in range(D)])
        return None, 0
    M = max([len(g) for gs in groups for g in gs] + [1])
    send_idx = np.zeros((D, D, M), np.int64)
    recv_maps: List[Dict[int, int]] = [dict() for _ in range(D)]
    for s in range(D):
        for t in range(D):
            g = groups[s][t]
            for j, e in enumerate(g):
                send_idx[t, s, j] = space.slot[e]
                # recv layout after all_to_all(split 0, concat 0):
                # rows arrive ordered by source shard t, then j
                recv_maps[s][e] = t * M + j
    space.recv_regions.append(D * M)
    space.recv_maps.append(recv_maps)
    return send_idx, M


def _exchange_entries(ext, send_idx, axis, region_off, M):
    """Send full entries (rows of ext) and write them into the recv
    region starting at region_off.  ext: (n_local, C, bs, bs, bs).

    CUP3D_RING_HALO=1 swaps the blocking all_to_all for the ring-permute
    transport (parallel/ring.py): same chunk routing, but on TPU each
    shard-to-shard chunk is an async remote copy streaming over ICI."""
    from cup3d_tpu.parallel import ring

    send = ext[send_idx]  # (D, M, C, bs, bs, bs)
    if ring.use_ring_halo():
        recv = ring.ring_all_to_all(send, axis)
    else:
        recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0)
    recv = recv.reshape((-1,) + ext.shape[1:])
    return jax.lax.dynamic_update_slice(
        ext, recv.astype(ext.dtype), (region_off, 0, 0, 0, 0)
    )


@dataclass
class ShardedFaceTables:
    """Duck-typed FaceTables running under shard_map (see module doc)."""

    width: int
    forest: object  # ShardedForest
    tab: FaceTables  # single-device tables of the SAME width (host ref)
    # static layout
    nbs: int
    ns_max: int
    n_local: int
    zero_row: int
    scratch_row: int
    # pyramid: per group (dst_rows (D, nsg_max), child (D, nsg_max, 8),
    # exchange plan)
    groups: Tuple[Tuple[jnp.ndarray, jnp.ndarray, _ExchangePlan], ...]
    final_plan: _ExchangePlan
    src: jnp.ndarray  # (D, 6, nbs) int32 remapped
    bmask: jnp.ndarray  # (D, 6, nbs) bool
    bsign: Tuple[Tuple[float, float, float], ...]
    cf_rows: Tuple[jnp.ndarray, ...]  # 6 x (D, ncf_max) local block rows
    cf_src: Tuple[jnp.ndarray, ...]  # 6 x (D, ncf_max, 8) remapped entries
    cf_toff: Tuple[jnp.ndarray, ...]  # 6 x (D, ncf_max, 2)
    interp_t: jnp.ndarray
    interp_n_lo: jnp.ndarray
    interp_n_hi: jnp.ndarray

    # -- protocol ----------------------------------------------------------

    def assemble_scalar(self, field: jnp.ndarray, bs: int) -> jnp.ndarray:
        return self._assemble(field[..., None], None)[..., 0]

    def assemble_vector(self, field: jnp.ndarray, bs: int) -> jnp.ndarray:
        return self._assemble(field, (0, 1, 2))

    def assemble_component(self, field, bs: int, comp: int) -> jnp.ndarray:
        return self._assemble(field[..., None], (comp,))[..., 0]

    @jax.named_scope("Halo")
    def _assemble(self, fields: jnp.ndarray,
                  sign_comps: Optional[Tuple[int, ...]]) -> jnp.ndarray:
        f = self.forest
        t = self.tab
        bs, w = t.bs, self.width
        L = bs + 2 * w
        C = fields.shape[-1]
        nbs = self.nbs
        axis = f.axis
        self_t = self

        def kernel(fields, src, bmask, grp_tabs, final_send, cf_tabs):
            fm = jnp.moveaxis(fields, -1, 1)  # (nbs, C, bs,bs,bs)
            ext = jnp.zeros(
                (self_t.n_local, C, bs, bs, bs), fields.dtype
            )
            ext = jax.lax.dynamic_update_slice(ext, fm, (0, 0, 0, 0, 0))
            # -- pyramid (deepest group first) ------------------------------
            for (dst, child, plan), (dst_a, child_a, send_a) in zip(
                self_t.groups, grp_tabs
            ):
                if send_a is not None:  # else: fully shard-local group
                    ext = _exchange_entries(
                        ext, send_a[0], axis, plan.recv_off, plan.M
                    )
                ch = jnp.take(ext, child_a[0], axis=0)  # (nsg,8,C,bs^3)
                sh = _restrict8(ch, bs)
                ext = ext.at[dst_a[0]].set(sh.astype(ext.dtype))
            # -- final exchange: face sources + coarse windows --------------
            if final_send is not None:
                ext = _exchange_entries(
                    ext, final_send[0], axis, self_t.final_plan.recv_off,
                    self_t.final_plan.M,
                )
            # -- dense face assembly (grid/faces.py math) -------------------
            lab = jnp.zeros((nbs, C) + (L,) * 3, fields.dtype)
            lab = lab.at[:, :, w:w + bs, w:w + bs, w:w + bs].set(fm)
            for a in range(3):
                for hi in (0, 1):
                    fc = 2 * a + hi
                    sl = (
                        _slab(ext, a, 0, w) if hi
                        else _slab(ext, a, bs - w, w)
                    )
                    slab = jnp.take(sl, src[0, fc], axis=0)
                    own = (
                        _slab(ext[:nbs], a, bs - 1, 1) if hi
                        else _slab(ext[:nbs], a, 0, 1)
                    )
                    own = jnp.broadcast_to(own, slab.shape)
                    if sign_comps is not None:
                        sgn = np.array(
                            [t.bsign[fc][c] for c in sign_comps],
                            np.float32,
                        ).reshape(1, C, 1, 1, 1)
                        own = own * sgn
                    bm = bmask[0, fc][:, None, None, None, None]
                    slab = jnp.where(bm, own.astype(slab.dtype), slab)
                    rows_a, src8_a, toff_a = cf_tabs[fc]
                    if rows_a.shape[1]:
                        halo = self_t._coarse_halo_shard(
                            ext, fc, src8_a[0], toff_a[0], C
                        )
                        # scratch row absorbs padded cf rows
                        slab = jnp.concatenate(
                            [slab, jnp.zeros_like(slab[:1])]
                        )
                        slab = slab.at[rows_a[0]].set(
                            halo.astype(slab.dtype)
                        )[:nbs]
                    lab = _place(lab, slab, a, hi, w, bs)
            return jnp.moveaxis(lab, 1, -1)

        pb = P(f.axis)
        grp_tabs = tuple(
            (dst, child, plan.send_idx) for dst, child, plan in self.groups
        )
        cf_tabs = tuple(
            (self.cf_rows[fc], self.cf_src[fc], self.cf_toff[fc])
            for fc in range(6)
        )
        return shard_map(
            kernel,
            mesh=f.mesh,
            in_specs=(pb, pb, pb, jax.tree_util.tree_map(
                lambda _: pb, grp_tabs), pb,
                jax.tree_util.tree_map(lambda _: pb, cf_tabs)),
            out_specs=pb,
            check_vma=False,
        )(fields, self.src, self.bmask, grp_tabs,
          self.final_plan.send_idx, cf_tabs)

    def _coarse_halo_shard(self, ext, fc, src8, toff, C):
        """grid/faces.py _coarse_halo with explicit (remapped) tables."""
        t = self.tab
        a, hi = fc // 2, fc % 2
        bs, w = t.bs, self.width
        cw = t.interp_n_lo.shape[1] - 1
        S = t.interp_t.shape[1]
        if hi:
            pp = _slab(ext, a, bs - 1, 1)
            npl = _slab(ext, a, 0, cw)
        else:
            pp = _slab(ext, a, 0, 1)
            npl = _slab(ext, a, bs - cw, cw)
        Pp = jnp.take(pp, src8[:, 0:4], axis=0)
        N = jnp.take(npl, src8[:, 4:8], axis=0)

        def arrange(x):
            n, _, _, d = x.shape[:4]
            y = x.reshape(n, 2, 2, C, d, bs, bs)
            y = y.transpose(0, 3, 4, 1, 5, 2, 6)
            return y.reshape(n, C, d, 2 * bs, 2 * bs)

        P16, N16 = arrange(Pp), arrange(N)
        slab16 = (
            jnp.concatenate([P16, N16], axis=2)
            if hi else jnp.concatenate([N16, P16], axis=2)
        )

        def tslice(s, off):
            return jax.lax.dynamic_slice(
                s, (0, 0, off[0], off[1]), (C, cw + 1, S, S)
            )

        win = jax.vmap(tslice)(slab16, toff)
        Tn = t.interp_n_hi if hi else t.interp_n_lo
        Tt = t.interp_t
        # float32 operands as they are, as grid/faces.py::_coarse_halo
        out = jnp.tensordot(win, Tn.astype(win.dtype), axes=[[2], [1]],
                            precision=_HI)
        out = jnp.tensordot(out, Tt.astype(win.dtype), axes=[[2], [1]],
                            precision=_HI)
        out = jnp.tensordot(out, Tt.astype(win.dtype), axes=[[2], [1]],
                            precision=_HI)
        return out


def build_sharded_face_tables(forest, width: int) -> ShardedFaceTables:
    """Host builder: shard the global FaceTables of ``forest.grid``."""
    g = forest.grid
    t: FaceTables = g.face_tables(width)
    if t.fb_rows is not None:
        raise ValueError(
            "sharded face tables: topology has degenerate (closed-boundary "
            "deep-coarsening) blocks — use the per-cell lab tables"
        )
    D, nbs = forest.D, forest.nbs
    nb = g.nb
    sentinel = t.n_entries

    # -- ownership ---------------------------------------------------------
    # leaves: Hilbert cut.  shadows: owner of first child (bottom-up).
    child_groups = [np.asarray(c) for c in t.child_idx]
    starts = list(t.shadow_starts)
    owner = {}
    for e in range(nb):
        owner[e] = min(e // nbs, D - 1)
    for ci, start in zip(child_groups, starts):  # deepest first
        for r in range(ci.shape[0]):
            owner[start + r] = owner[int(ci[r, 0])]

    # per-shard shadow slots (padded to ns_max)
    shadows_of: List[List[int]] = [[] for _ in range(D)]
    for ci, start in zip(child_groups, starts):
        for r in range(ci.shape[0]):
            e = start + r
            shadows_of[owner[e]].append(e)
    ns_max = max([len(sh) for sh in shadows_of] + [1])
    space = _EntrySpace(D, nbs, ns_max)
    space.owner = owner
    for e in range(nb):
        space.slot[e] = e - owner[e] * nbs
    for s in range(D):
        for j, e in enumerate(shadows_of[s]):
            space.slot[e] = nbs + j

    # -- pyramid exchange plans (deepest group first) ----------------------
    plans: List[Tuple[np.ndarray, np.ndarray, np.ndarray, int]] = []
    for ci, start in zip(child_groups, starts):
        nsg = ci.shape[0]
        # which remote children does each shard need for THIS group
        needed = [set() for _ in range(D)]
        rows_of: List[List[int]] = [[] for _ in range(D)]
        for r in range(nsg):
            s = owner[start + r]
            rows_of[s].append(r)
            for c in ci[r]:
                c = int(c)
                if owner[c] != s:
                    needed[s].add(c)
        send_idx, M = _plan_exchange(space, needed, D)
        nsg_max = max([len(r) for r in rows_of] + [1])
        plans.append((ci, start, send_idx, M, rows_of, nsg_max))

    # -- final exchange: face srcs + coarse windows ------------------------
    src = np.asarray(t.src, np.int64)  # (6, nb)
    needed_final = [set() for _ in range(D)]
    for fcb in range(6):
        for b in range(nb):
            s = owner[b]
            e = int(src[fcb, b])
            if e != sentinel and owner[e] != s:
                needed_final[s].add(e)
    cf_lists = []
    for fc in range(6):
        rows = np.asarray(t.cf_rows[fc], np.int64)
        src8 = np.asarray(t.cf_src[fc], np.int64)
        toff = np.asarray(t.cf_toff[fc], np.int64)
        cf_lists.append((rows, src8, toff))
        for i, b in enumerate(rows):
            s = owner[int(b)]
            for e in src8[i]:
                e = int(e)
                if owner[e] != s:
                    needed_final[s].add(e)
    final_send, final_M = _plan_exchange(space, needed_final, D)

    # region offsets now that ALL exchanges are planned
    region_offs = []
    off = nbs + ns_max
    for size in space.recv_regions:
        region_offs.append(off)
        off += size
    n_local = space.local_size()

    # -- remap pyramid tables ---------------------------------------------
    groups = []
    for x, (ci, start, send_idx, M, rows_of, nsg_max) in enumerate(plans):
        dst = np.full((D, nsg_max), space.scratch_row(), np.int64)
        child = np.full((D, nsg_max, 8), space.zero_row(), np.int64)
        for s in range(D):
            for j, r in enumerate(rows_of[s]):
                dst[s, j] = space.slot[start + r]
                for c8 in range(8):
                    child[s, j, c8] = space.resolve(
                        int(ci[r, c8]), s, sentinel
                    )
        groups.append((
            jnp.asarray(dst, jnp.int32),
            jnp.asarray(child, jnp.int32),
            _ExchangePlan(
                send_idx=(None if send_idx is None
                          else jnp.asarray(send_idx, jnp.int32)),
                M=M,
                recv_off=region_offs[x],
            ),
        ))

    # -- remap face tables -------------------------------------------------
    src_sh = np.full((D, 6, nbs), space.zero_row(), np.int64)
    bmask_sh = np.zeros((D, 6, nbs), bool)
    bmask_g = np.asarray(t.bmask)
    for b in range(nb):
        s = owner[b]
        ls = space.slot[b]
        for fc in range(6):
            bmask_sh[s, fc, ls] = bmask_g[fc, b]
            e = int(src[fc, b])
            src_sh[s, fc, ls] = space.resolve(e, s, sentinel)

    cf_rows_sh, cf_src_sh, cf_toff_sh = [], [], []
    for fc in range(6):
        rows, src8, toff = cf_lists[fc]
        per = [[] for _ in range(D)]
        for i, b in enumerate(rows):
            per[owner[int(b)]].append(i)
        ncf_max = max([len(p) for p in per] + [0])
        R = np.full((D, ncf_max), nbs, np.int64)  # nbs = scratch slab row
        S8 = np.full((D, ncf_max, 8), space.zero_row(), np.int64)
        TO = np.zeros((D, ncf_max, 2), np.int64)
        for s in range(D):
            for j, i in enumerate(per[s]):
                R[s, j] = space.slot[int(rows[i])]
                TO[s, j] = toff[i]
                for c8 in range(8):
                    S8[s, j, c8] = space.resolve(int(src8[i, c8]), s,
                                                 sentinel)
        cf_rows_sh.append(jnp.asarray(R, jnp.int32))
        cf_src_sh.append(jnp.asarray(S8, jnp.int32))
        cf_toff_sh.append(jnp.asarray(TO, jnp.int32))

    pad = forest.pad_aux
    return ShardedFaceTables(
        width=width,
        forest=forest,
        tab=t,
        nbs=nbs,
        ns_max=ns_max,
        n_local=n_local,
        zero_row=space.zero_row(),
        scratch_row=space.scratch_row(),
        groups=tuple(
            (pad(dst), pad(child),
             _ExchangePlan(
                 None if plan.send_idx is None else pad(plan.send_idx),
                 plan.M, plan.recv_off))
            for dst, child, plan in groups
        ),
        final_plan=_ExchangePlan(
            (None if final_send is None
             else pad(jnp.asarray(final_send, jnp.int32))),
            final_M,
            region_offs[-1],
        ),
        src=pad(jnp.asarray(src_sh, jnp.int32)),
        bmask=pad(jnp.asarray(bmask_sh)),
        bsign=t.bsign,
        cf_rows=tuple(pad(x) for x in cf_rows_sh),
        cf_src=tuple(pad(x) for x in cf_src_sh),
        cf_toff=tuple(pad(x) for x in cf_toff_sh),
        interp_t=t.interp_t,
        interp_n_lo=t.interp_n_lo,
        interp_n_hi=t.interp_n_hi,
    )
