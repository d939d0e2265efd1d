"""Block-axis sharding of the AMR forest over a device mesh.

This is the TPU-native rebuild of the reference's entire L0 layer:
GridMPI's block partition (main.cpp:2960-2988), the SynchronizerMPI_AMR
halo engine (pack / Isend / Irecv / unpack, main.cpp:1515-2545),
FluxCorrectionMPI's cross-rank face exchange (main.cpp:2546-2946) and the
LoadBalancer's Z-sorted contiguous partition (main.cpp:4906-5021).

Design
------
Blocks are laid out in cross-level Hilbert order (grid/sfc.py) and cut
into ``D`` contiguous chunks, one per device — Hilbert contiguity *is* the
balanced, locality-preserving partition the reference's LoadBalancer
maintains by migrating blocks.  Every field pads the block axis to a
multiple of ``D`` and shards it over a 1-D ``Mesh((D,), ("b",))``.

For each (topology, stencil width) pair the host computes once exactly
which remote cells each shard's halo gathers touch (the analogue of
``SynchronizerMPI_AMR::_Setup``).  Per lab assembly the device then runs,
inside ``shard_map``:

    local gather (pack) -> one all_to_all over ICI -> local gather (unpack)

The all_to_all payload is the union of cross-shard halo rows — the same
wire bytes the reference's nonblocking sends move, batched into a single
static collective, which is the shape ICI wants.  2:1 restriction weights,
coarse-scratch interpolation and BC signs ride in the same tables as the
single-device path; the operators in ops/amr_ops.py and ops/diffusion.py
run unchanged because ShardedLabTables / ShardedFluxTables duck-type the
LabTables / FluxTables assembly protocol.

Global reductions (Krylov dots, force integrals) stay ordinary ``jnp``
sums over the sharded arrays: under jit XLA lowers them to ``psum`` over
the mesh — the reference's MPI_Iallreduce (main.cpp:14486-14550).

Adaptation (a new topology) simply builds a new ShardedForest: re-setup of
all synchronizers (main.cpp:5153-5157) becomes re-deriving gather tables,
and the contiguous cut of the *new* Hilbert order is the rebalanced
partition (no diffusion balancing needed).
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cup3d_tpu.grid.blocks import BlockGrid, LabTables
from cup3d_tpu.grid.flux import FluxTables, build_flux_tables
from cup3d_tpu.parallel.compat import shard_map

_HI = jax.lax.Precision.HIGHEST


def make_block_mesh(devices=None, axis: str = "b") -> Mesh:
    """1-D mesh over the block axis.  jax.devices() order follows the
    physical torus, so contiguous Hilbert chunks land on ICI neighbors."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (axis,))


#: (octree signature, mesh device ids, axis) -> ShardedForest, LRU
_FOREST_MEMO: "OrderedDict[tuple, ShardedForest]" = OrderedDict()
_FOREST_MEMO_MAX = 4


def cached_forest(grid: BlockGrid, mesh: Optional[Mesh] = None
                  ) -> "ShardedForest":
    """Signature-keyed ShardedForest memo (the sharded twin of
    sim/amr.py's _table_memo discipline): a regrid that returns to a
    previously-seen topology — the dominant ping-pong pattern of
    adaptive runs — reuses the forest's host-derived gather/exchange
    tables AND, through sim/amr.py's executable memo keyed on the same
    signature, every compiled sharded step.  Two topologies with equal
    signatures have bitwise-equal tables, so the reuse is exact; a
    genuinely new topology still pays one setup + trace (its tables
    are closure constants by design, see module doc)."""
    if mesh is None:
        mesh = make_block_mesh()
    key = (
        grid.signature,
        tuple(int(d.id) for d in np.asarray(mesh.devices).flat),
        tuple(mesh.axis_names),
    )
    forest = _FOREST_MEMO.pop(key, None)
    from cup3d_tpu.obs import metrics as obs_metrics

    obs_metrics.counter(
        "forest.memo_hits" if forest is not None else "forest.memo_misses"
    ).inc()
    if forest is None:
        forest = ShardedForest(grid, mesh)
    _FOREST_MEMO[key] = forest
    while len(_FOREST_MEMO) > _FOREST_MEMO_MAX:
        _FOREST_MEMO.popitem(last=False)
    return forest


class ExecutableMemo:
    """Signature-keyed LRU of compiled step-executable bundles — the
    round-18 port of PR 3's capacity-bucketing discipline to the forest
    path.  The sharded forest's duck-typed tables are not pytrees, so
    its jits close over them and are only reusable for an IDENTICAL
    topology; equal octree signatures guarantee bitwise-equal tables,
    so a regrid that returns to a seen topology (the refine->coarsen
    ping-pong) swaps the whole bundle back in with zero retraces.
    Hits/misses surface as ``<name>_hits`` / ``<name>_misses``."""

    def __init__(self, max_entries: int = 4,
                 name: str = "forest.exec_memo"):
        self.max_entries = int(max_entries)
        self.name = name
        self._memo: "OrderedDict[object, dict]" = OrderedDict()

    def __len__(self) -> int:
        return len(self._memo)

    def get(self, sig) -> Optional[dict]:
        """The bundle compiled for ``sig``, refreshed in LRU order, or
        None on a genuinely new topology (counted either way)."""
        from cup3d_tpu.obs import metrics as obs_metrics

        bundle = self._memo.pop(sig, None)
        obs_metrics.counter(
            f"{self.name}_hits" if bundle is not None
            else f"{self.name}_misses"
        ).inc()
        if bundle is not None:
            self._memo[sig] = bundle
        return bundle

    def put(self, sig, bundle: dict) -> None:
        self._memo[sig] = bundle
        while len(self._memo) > self.max_entries:
            self._memo.popitem(last=False)


def bind_step_executable(fn, *bound, donate=(), name=None,
                         store_sig=None):
    """One compiled step executable with the forest's (non-pytree)
    tables closed over as trailing constants: ``fn(*args, *bound)``
    jitted with ``donate`` naming the caller-facing state argnums.

    This is THE jit-construction site for the forest path — callers on
    the adaptation path (sim/amr.py ``_rebuild``) bind here and memoize
    the result by octree signature (:class:`ExecutableMemo`), so a
    fresh jit object is only ever built once per NEW topology, never
    per regrid pass (the JX007 hazard class this helper burns down).

    Round 19: it is therefore also THE cost-accounting seam — under
    ``CUP3D_COSTS=1`` (obs/costs.enabled) the jitted object's first
    invocation additionally AOT-harvests the executable's compiler-
    counted FLOPs/bytes/HBM footprint into the obs registry under
    ``name`` (default: the wrapped fn's name).  One extra lowering per
    bound executable, a single cached bool test per call after that —
    the steady-state hot path is untouched.

    Round 21: and THE persistence seam — ``store_sig`` (the octree
    signature plus the config content the closure captures; equal sigs
    guarantee bitwise-equal bound tables) keys the executable into the
    persistent AOT store when ``CUP3D_AOT_STORE`` is active, so a
    restarted process loads the serialized executable instead of
    retracing.  With the store inactive or ``store_sig=None`` the
    returned object is the plain jitted callable, unchanged."""
    jitted = jax.jit(lambda *a: fn(*a, *bound), donate_argnums=donate)
    label = name or getattr(fn, "__name__", None) or "forest.step"
    if store_sig is not None:
        from cup3d_tpu.aot import store as aot_store

        jitted = aot_store.store_backed(
            jitted, ("forest", label, tuple(donate), store_sig),
            name=f"forest.{label}", donated=bool(donate))
    from cup3d_tpu.obs import costs as obs_costs

    if obs_costs.enabled():
        jitted = obs_costs.harvest_on_first_call(
            jitted, f"forest.{label}")
    return jitted


def bind_order_executables(fn, tabs, donate=(), store_sig=None) -> tuple:
    """(first_order, second_order) compiled executables for a pressure-
    order-switched step body: ``fn(*args, *tabs, second_order=...)``
    bound per order through :func:`bind_step_executable`.  The caller
    picks by step index at call time — the order switch is two cached
    executables, not a retrace."""
    return tuple(
        bind_step_executable(partial(fn, second_order=so), *tabs,
                             donate=donate,
                             name=f"{getattr(fn, '__name__', 'step')}"
                                  f"_o{2 if so else 1}",
                             store_sig=store_sig)
        for so in (False, True)
    )


class _Exchange:
    """Host-built routing for one (flat-array layout, reference set).

    ``unit``: flat entries per block.  Remaps global flat indices (with
    sentinel ``nb*unit``) into each destination shard's local address
    space: [0, nbs*unit) local, [nbs*unit, nbs*unit + D*M) received rows,
    nbs*unit + D*M the zero sentinel."""

    def __init__(self, forest: "ShardedForest", unit: int,
                 ref_lists: Dict[int, np.ndarray]):
        D, nbs = forest.D, forest.nbs
        self.unit = unit
        local_n = nbs * unit
        sent = forest.grid.nb * unit  # global sentinel

        def shard_of(f):
            return np.minimum(f // unit // nbs, D)  # sentinel -> D

        # per destination shard: remote refs grouped by source shard
        groups = []  # groups[s][t] = sorted unique global indices
        for s in range(D):
            refs = ref_lists.get(s)
            if refs is None or refs.size == 0:
                groups.append([np.zeros(0, np.int64)] * D)
                continue
            refs = refs[refs < sent]
            own = shard_of(refs)
            groups.append(
                [np.unique(refs[own == t]) if t != s else np.zeros(0, np.int64)
                 for t in range(D)]
            )
        # keep M >= 1 so the all_to_all payload shape never degenerates
        M = max([g.size for gs in groups for g in gs] + [1])
        self.M = M

        # send table: send_idx[t, s, :] = local flat indices (on t) of the
        # cells shard s needs from t; padded rows re-read cell 0
        send_idx = np.zeros((D, D, M), np.int64)
        for s in range(D):
            for t in range(D):
                g = groups[s][t]
                send_idx[t, s, : g.size] = g - t * local_n
        self.send_idx = jnp.asarray(send_idx, jnp.int32)
        self.groups = groups
        self.local_n = local_n
        self.zero_idx = local_n + D * M
        self._shard_of = shard_of
        self._sent = sent

    def remap(self, idx: np.ndarray, dst_shard: int) -> np.ndarray:
        """Global flat indices -> dst shard's local address space."""
        D = len(self.groups)
        out = np.full(idx.shape, self.zero_idx, np.int64)
        own = self._shard_of(idx)
        mine = own == dst_shard
        out[mine] = idx[mine] - dst_shard * self.local_n
        for t in range(D):
            if t == dst_shard:
                continue
            g = self.groups[dst_shard][t]
            sel = (own == t) & (idx < self._sent)
            if not np.any(sel) or g.size == 0:
                continue
            pos = np.searchsorted(g, idx[sel])
            out[sel] = self.local_n + t * self.M + pos
        return out


def _exchange_gather(flat: jnp.ndarray, send_idx: jnp.ndarray, axis: str):
    """flat: (local_n, C) shard-local values.  Returns (local_n + D*M + 1, C)
    extended array: local rows, received rows, zero sentinel."""
    send = flat[send_idx]  # (D, M, C)
    recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0)
    zero = jnp.zeros((1,) + flat.shape[1:], flat.dtype)
    return jnp.concatenate([flat, recv.reshape(-1, *flat.shape[1:]), zero])


@dataclass
class ShardedLabTables:
    """Duck-typed LabTables whose assembly runs under shard_map with a
    cross-shard halo exchange (see module docstring)."""

    width: int
    forest: "ShardedForest"
    ghost_xyz: Tuple[np.ndarray, np.ndarray, np.ndarray]
    g_idx: jnp.ndarray  # (nb_pad, ng, 8) shard-local addresses
    g_w: jnp.ndarray
    g_sign: jnp.ndarray
    mask_coarse: jnp.ndarray
    s_idx: jnp.ndarray
    s_w: jnp.ndarray
    s_sign: jnp.ndarray
    interp_w: jnp.ndarray
    any_coarse: bool
    send_idx: jnp.ndarray  # (D, D, M)

    @jax.named_scope("Halo")
    def _assemble(self, field: jnp.ndarray, bs: int, signed: bool):
        """field: (nb_pad, bs,bs,bs, C) sharded on axis 0 -> labs
        (nb_pad, L,L,L, C)."""
        f = self.forest
        w = self.width
        L = bs + 2 * w
        S = self.interp_w.shape[1]
        gx, gy, gz = self.ghost_xyz
        axis = f.axis
        any_coarse = self.any_coarse
        interp_w = np.asarray(self.interp_w)  # replicated closure constant

        def kernel(field, g_idx, g_w, g_sign, mask, s_idx, s_w, s_sign,
                   send_idx):
            nbs = field.shape[0]
            C = field.shape[-1]
            flat = field.reshape(-1, C)
            ext = _exchange_gather(flat, send_idx[0], axis)
            vals = ext[g_idx]  # (nbs, ng, 8, C)
            ghosts = jnp.sum(vals * g_w[..., None], axis=2)
            if signed:
                ghosts = ghosts * g_sign
            if any_coarse:
                sv = jnp.sum(ext[s_idx] * s_w[..., None], axis=2)
                if signed:
                    sv = sv * s_sign
                scratch = sv.reshape(nbs, S, S, S, C)
                interp = scratch
                for ax in (1, 2, 3):
                    interp = jnp.moveaxis(
                        jnp.tensordot(interp, interp_w,
                                      axes=([ax], [1]), precision=_HI),
                        -1, ax,
                    )
                ghosts = jnp.where(
                    mask[..., None], interp[:, gx, gy, gz], ghosts
                )
            lab = jnp.zeros((nbs, L, L, L, C), field.dtype)
            lab = lab.at[:, w : w + bs, w : w + bs, w : w + bs].set(field)
            return lab.at[:, gx, gy, gz].set(ghosts.astype(field.dtype))

        pb = P(f.axis)
        return shard_map(
            kernel,
            mesh=f.mesh,
            in_specs=(pb,) * 9,
            out_specs=pb,
            check_vma=False,
        )(field, self.g_idx, self.g_w, self.g_sign, self.mask_coarse,
          self.s_idx, self.s_w, self.s_sign, self.send_idx)

    def assemble_scalar(self, field: jnp.ndarray, bs: int) -> jnp.ndarray:
        return self._assemble(field[..., None], bs, signed=False)[..., 0]

    def assemble_vector(self, field: jnp.ndarray, bs: int) -> jnp.ndarray:
        return self._assemble(field, bs, signed=True)

    def assemble_component(self, field, bs: int, comp: int) -> jnp.ndarray:
        lab = self._assemble_signed_comp(field[..., None], bs, comp)
        return lab[..., 0]

    @jax.named_scope("Halo")
    def _assemble_signed_comp(self, field, bs: int, comp: int):
        # per-component sign labs: reuse the vector path with the component's
        # sign column broadcast over the single channel
        sub = ShardedLabTables(
            width=self.width, forest=self.forest, ghost_xyz=self.ghost_xyz,
            g_idx=self.g_idx, g_w=self.g_w,
            g_sign=self.g_sign[..., comp : comp + 1],
            mask_coarse=self.mask_coarse, s_idx=self.s_idx, s_w=self.s_w,
            s_sign=self.s_sign[..., comp : comp + 1],
            interp_w=self.interp_w, any_coarse=self.any_coarse,
            send_idx=self.send_idx,
        )
        return sub._assemble(field, bs, signed=True)


@dataclass
class ShardedFluxTables:
    """Duck-typed FluxTables: coarse-side corrections applied shard-locally
    after an all_to_all fetch of remote fine-face flux rows
    (FluxCorrectionMPI, main.cpp:2546-2946)."""

    forest: "ShardedForest"
    tgt_cell: jnp.ndarray  # (D*ncmax,) local cell addresses, sharded
    tgt_flux: jnp.ndarray  # (D*ncmax,) local flux addresses
    src_flux: jnp.ndarray  # (D*ncmax, 4) extended flux addresses
    inv_hc: jnp.ndarray  # (D*ncmax,) 0 on padding rows
    send_idx: jnp.ndarray  # (D, D, Mf)
    ncorr: int

    def apply(self, out: jnp.ndarray, fluxes: jnp.ndarray) -> jnp.ndarray:
        if self.ncorr == 0:
            return out
        f = self.forest
        axis = f.axis

        def kernel(out, fluxes, tgt_cell, tgt_flux, src_flux, inv_hc,
                   send_idx):
            fflat = fluxes.reshape(-1, 1)
            ext = _exchange_gather(fflat, send_idx[0], axis)[..., 0]
            fine_mean = jnp.mean(ext[src_flux], axis=-1)
            corr = (-fine_mean - ext[tgt_flux]) * inv_hc
            flat = out.reshape(-1)
            flat = flat.at[tgt_cell].add(corr.astype(flat.dtype))
            return flat.reshape(out.shape)

        pb = P(f.axis)
        return shard_map(
            kernel,
            mesh=f.mesh,
            in_specs=(pb,) * 7,
            out_specs=pb,
            check_vma=False,
        )(out, fluxes, self.tgt_cell, self.tgt_flux, self.src_flux,
          self.inv_hc, self.send_idx)


class _PaddedGeom:
    """Duck-typed BlockGrid view over the padded block axis: exactly the
    attributes ops/amr_ops.py touches (nb, bs, h).  Padding blocks get
    h=1 — their fields are zero, so every operator output on them is 0."""

    def __init__(self, grid: BlockGrid, nb_pad: int):
        self.bs = grid.bs
        self.nb = nb_pad
        self.h = np.concatenate(
            [grid.h, np.ones(nb_pad - grid.nb, grid.h.dtype)]
        )
        self.extent = grid.extent


class ShardedForest:
    """One AMR topology sharded over a 1-D device mesh (see module doc)."""

    def __init__(self, grid: BlockGrid, mesh: Optional[Mesh] = None):
        if mesh is None:
            mesh = make_block_mesh()
        if len(mesh.axis_names) != 1:
            raise ValueError("ShardedForest wants a 1-D mesh over blocks")
        self.grid = grid
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.D = mesh.devices.size
        # per-shard block count rounds up the capacity ladder
        # (grid/bucket.py, base 1: small shards stay exact): regrids
        # whose per-shard count stays within a rung keep every sharded
        # array shape, bounding allocator churn across re-layouts (the
        # forest still re-traces — its tables are closures by design)
        from cup3d_tpu.grid import bucket as bk

        self.nbs = bk.count_capacity(-(-grid.nb // self.D), base=1)
        self.nb_pad = self.nbs * self.D
        self.geom = _PaddedGeom(grid, self.nb_pad)
        self.block_sharding = NamedSharding(mesh, P(self.axis))
        self._lab_cache: Dict[int, ShardedLabTables] = {}
        self._flux_cache: Optional[ShardedFluxTables] = None
        # (nb_pad,1,1,1) cell volume, 0 on padding: reductions weighted by
        # vol automatically ignore the pad blocks
        vol = np.zeros((self.nb_pad, 1, 1, 1), np.float64)
        vol[: grid.nb, 0, 0, 0] = grid.h**3
        self.vol = self.pad_aux(jnp.asarray(vol, jnp.float32))
        pmask = np.zeros((self.nb_pad, 1, 1, 1), np.float32)
        pmask[: grid.nb] = 1.0
        self.pmask = self.pad_aux(jnp.asarray(pmask))

    # -- field layout ------------------------------------------------------

    def pad(self, field: jnp.ndarray) -> jnp.ndarray:
        """(nb, ...) -> (nb_pad, ...) zero-padded, sharded on the mesh."""
        extra = self.nb_pad - field.shape[0]
        if extra:
            field = jnp.concatenate(
                [field, jnp.zeros((extra,) + field.shape[1:], field.dtype)]
            )
        return jax.device_put(field, self.block_sharding)

    def pad_aux(self, arr: jnp.ndarray) -> jnp.ndarray:
        """Already nb_pad-long auxiliary array -> sharded."""
        return jax.device_put(arr, self.block_sharding)

    def unpad(self, field: jnp.ndarray) -> jnp.ndarray:
        return field[: self.grid.nb]

    # -- synchronizer setup (host) ----------------------------------------

    def lab_tables(self, width: int) -> ShardedLabTables:
        if width not in self._lab_cache:
            self._lab_cache[width] = self._build_lab(width)
        return self._lab_cache[width]

    def face_tables(self, width: int):
        """Sharded face-slab fast path (parallel/faces.py) — the round-3
        FaceTables design under shard_map.  Falls back to the per-ghost
        lab tables when the topology has degenerate closed-boundary blocks
        (empty on periodic domains)."""
        key = ("face", width)
        if key not in self._lab_cache:
            from cup3d_tpu.parallel.faces import build_sharded_face_tables

            try:
                self._lab_cache[key] = build_sharded_face_tables(self, width)
            except ValueError:
                self._lab_cache[key] = self.lab_tables(width)
        return self._lab_cache[key]

    def _build_lab(self, width: int) -> ShardedLabTables:
        g = self.grid
        t = g.lab_tables(width)
        D, nbs = self.D, self.nbs
        bs = g.bs
        unit = bs**3

        g_idx = np.asarray(t.g_idx, np.int64)  # (nb, ng, 8)
        s_idx = np.asarray(t.s_idx, np.int64)
        ref_lists = {}
        for s in range(D):
            lo, hi = s * nbs, min((s + 1) * nbs, g.nb)
            if lo >= g.nb:
                ref_lists[s] = np.zeros(0, np.int64)
                continue
            ref_lists[s] = np.concatenate(
                [g_idx[lo:hi].ravel(), s_idx[lo:hi].ravel()]
            )
        ex = _Exchange(self, unit, ref_lists)

        ng, ns = g_idx.shape[1], s_idx.shape[1]
        g_re = np.full((self.nb_pad, ng, 8), ex.zero_idx, np.int64)
        s_re = np.full((self.nb_pad, ns, 8), ex.zero_idx, np.int64)
        for s in range(D):
            lo, hi = s * nbs, min((s + 1) * nbs, g.nb)
            if lo >= g.nb:
                continue
            g_re[lo:hi] = ex.remap(g_idx[lo:hi], s)
            s_re[lo:hi] = ex.remap(s_idx[lo:hi], s)

        def padb(a, fill=0.0):
            pad = np.full((self.nb_pad - g.nb,) + a.shape[1:], fill, a.dtype)
            return jnp.asarray(np.concatenate([np.asarray(a), pad]))

        return ShardedLabTables(
            width=width,
            forest=self,
            ghost_xyz=t.ghost_xyz,
            g_idx=self.pad_aux(jnp.asarray(g_re, jnp.int32)),
            g_w=self.pad_aux(padb(t.g_w)),
            g_sign=self.pad_aux(padb(t.g_sign, 1.0)),
            mask_coarse=self.pad_aux(padb(t.mask_coarse, False)),
            s_idx=self.pad_aux(jnp.asarray(s_re, jnp.int32)),
            s_w=self.pad_aux(padb(t.s_w)),
            s_sign=self.pad_aux(padb(t.s_sign, 1.0)),
            interp_w=t.interp_w,
            any_coarse=t.any_coarse,
            send_idx=self.pad_aux(ex.send_idx),
        )

    @property
    def flux_tables(self) -> ShardedFluxTables:
        if self._flux_cache is None:
            self._flux_cache = self._build_flux()
        return self._flux_cache

    def _build_flux(self) -> ShardedFluxTables:
        g = self.grid
        t: FluxTables = build_flux_tables(g)
        D, nbs = self.D, self.nbs
        bs = g.bs
        funit = 6 * bs * bs
        cunit = bs**3

        if t.ncorr == 0:
            z = jnp.zeros(0, jnp.int32)
            return ShardedFluxTables(
                self, z, z, jnp.zeros((0, 4), jnp.int32),
                jnp.zeros(0, jnp.float32), jnp.zeros((D, D, 0), jnp.int32), 0
            )

        tgt_cell = np.asarray(t.tgt_cell, np.int64)
        tgt_flux = np.asarray(t.tgt_flux, np.int64)
        src_flux = np.asarray(t.src_flux, np.int64)
        inv_hc = np.asarray(t.inv_hc, np.float64)
        owner = tgt_cell // cunit // nbs  # shard of the corrected block

        ref_lists = {
            s: src_flux[owner == s].ravel() for s in range(D)
        }
        ex = _Exchange(self, funit, ref_lists)

        ncmax = max(int(np.sum(owner == s)) for s in range(D))
        TC = np.zeros((D, ncmax), np.int64)
        TF = np.zeros((D, ncmax), np.int64)
        SF = np.full((D, ncmax, 4), ex.zero_idx, np.int64)
        IH = np.zeros((D, ncmax), np.float64)
        for s in range(D):
            sel = owner == s
            n = int(np.sum(sel))
            if n == 0:
                continue
            TC[s, :n] = tgt_cell[sel] - s * nbs * cunit
            TF[s, :n] = tgt_flux[sel] - s * nbs * funit
            SF[s, :n] = ex.remap(src_flux[sel], s)
            IH[s, :n] = inv_hc[sel]

        return ShardedFluxTables(
            forest=self,
            tgt_cell=self.pad_aux(jnp.asarray(TC.reshape(-1), jnp.int32)),
            tgt_flux=self.pad_aux(jnp.asarray(TF.reshape(-1), jnp.int32)),
            src_flux=self.pad_aux(jnp.asarray(SF.reshape(D * ncmax, 4),
                                              jnp.int32)),
            inv_hc=self.pad_aux(jnp.asarray(IH.reshape(-1), jnp.float32)),
            send_idx=self.pad_aux(ex.send_idx),
            ncorr=t.ncorr,
        )

    # -- solvers -----------------------------------------------------------

    def build_poisson_solver(self, **kw):
        """Sharded getZ-preconditioned BiCGSTAB: the single-device builder
        with the forest's duck-typed tables, padded-aware volume weights,
        and a padding mask; halo exchange + refluxing ride the forest's
        collectives and the Krylov dots lower to psum over the mesh (the
        reference's overlapped MPI_Iallreduce, main.cpp:14486-14550).
        Round 4: the halo assembly inside the Krylov loop runs on the
        sharded face-slab fast path (parallel/faces.py)."""
        from cup3d_tpu.ops import amr_ops

        return amr_ops.build_amr_poisson_solver(
            self.geom, tab=self.face_tables(1), flux_tab=self.flux_tables,
            vol=self.vol, pmask=self.pmask, **kw,
        )

    def build_helmholtz_solver(self, **kw):
        """Sharded implicit-diffusion Helmholtz solve (the distributed
        DiffusionSolver, main.cpp:6896-7146)."""
        from cup3d_tpu.ops.diffusion import build_amr_helmholtz_solver

        return build_amr_helmholtz_solver(
            self.geom, tab=self.face_tables(1), flux_tab=self.flux_tables,
            **kw,
        )
