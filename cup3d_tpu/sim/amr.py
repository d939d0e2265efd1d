"""AMR simulation driver: the adaptive counterpart of sim/simulation.py
(reference Simulation + adaptMesh, main.cpp:15161-15326, 15179-15200).

Differences from the uniform driver are exactly the reference's:

- five conceptual fields live on a block forest; here one dict of
  (nb, bs, bs, bs[, 3]) arrays that are *re-laid-out* on adaptation
  (grid/adapt.py) instead of surgically edited;
- the mesh adapts every ``ADAPT_EVERY`` steps (and each of the first 10),
  tagging on max |vorticity| with grad-chi forcing near bodies
  (main.cpp:15314, 8540-8602);
- startup runs 3*levelMax rounds of {adapt; re-create obstacles; re-IC}
  so the initial grid converges onto the body (main.cpp:15172-15177);
- the Poisson solve is the getZ-preconditioned BiCGSTAB (there is no
  spectral shortcut on a multi-level mesh).

Single-device runs are CAPACITY-BUCKETED (grid/bucket.py): every block
array pads up a geometric capacity ladder and all topology data (gather
tables, per-block h, cell volumes/centers, the coarse block graph)
travels as traced jit ARGUMENTS, so a regrid that stays within a bucket
reuses every compiled executable — zero retraces — and only pays the
host table build (itself memoized by octree signature, so ping-pong
regrids A->B->A skip even that).

Every step kernel and both megasteps are written once, in
sim/amr_step.py, as functions of their state and one geometry view; this
module binds them two ways.  The BUCKETED binder (_rebuild_bucketed,
every single-device run) jits them with the _geo_args bundle as trailing
traced arguments and rebuilds the view inside the trace.  The SHARDED
FOREST binder (_rebuild, ``mesh=``) builds the view once per octree
signature and closes over it (per-shard scale is bounded, and its
duck-typed tables are not pytrees) — the reference's "re-_Setup all
synchronizers" cost model (main.cpp:5153-5157).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from cup3d_tpu.analysis.runtime import (
    blocking_read,
    device_scalar,
    sanctioned_transfer,
)
from cup3d_tpu.obs import trace as obs_trace
from cup3d_tpu.config import SimulationConfig, parse_factory
from cup3d_tpu.grid import adapt as ad
from cup3d_tpu.grid import bucket as bk
from cup3d_tpu.grid.blocks import BlockGrid
from cup3d_tpu.grid.flux import build_flux_tables
from cup3d_tpu.grid.octree import Octree, TreeConfig
from cup3d_tpu.grid.uniform import BC
from cup3d_tpu.io.logging import BufferedLogger, Profiler
from cup3d_tpu.models.base import (
    FORCE_PACK,
    RIGID_PACK,
    combine_obstacle_fields,
    log_forces,
    store_force_qoi,
    unpack_forces,
    unpack_moments,
    vel_unit,
)
from cup3d_tpu.ops import amr_ops
from cup3d_tpu.ops.chi import towers_chi
from cup3d_tpu.resilience import faults
from cup3d_tpu.resilience.recovery import SimulationFailure
from cup3d_tpu.sim.amr_step import GeomView, make_step_bodies

ADAPT_EVERY = 20  # reference cadence (main.cpp:15314)
_EPS = 1e-6

#: the complete per-topology executable bundle _rebuild assigns on the
#: forest (mesh) path.  Snapshotting these under the octree signature
#: and rebinding on a signature match is what makes within-signature
#: regrids (the refine->coarsen->refine ping-pong) retrace-free: the
#: closure-style sharded jits are only reusable for an IDENTICAL
#: topology, and equal signatures guarantee bitwise-equal tables.
_FOREST_EXEC_ATTRS = (
    "forest", "_tab1", "_tab3", "_ftab", "_solver", "_vol", "_h_col",
    "_xc", "_real_mask", "_geom", "_view", "_advdiff", "_project",
    "_project_2nd", "_penalize_bodies", "_ubody", "_divnorms",
    "_dissipation", "_gradchi", "_omega_mag", "_scores", "_moments_read",
    "_forces_ex", "_maxu", "_megastep", "_megastep_free", "_fix_flux",
    "_device_tags",
)

#: the step kernels both binders bind: amr_step body (also the program's
#: name and, after "_", the attribute) -> donated state argnums, the
#: buffers the caller rebinds from the return value (JX002 burn-down).
#: ``tags`` (bucketed only), ``fix_flux`` (donated by the bucketed
#: binder only) and ``forces_bodies`` (static probe budgets:
#: _forces_kernel) are bound beside this table.
_STEP_KERNELS = {
    "advdiff": (0,), "project": (0, 4), "project_2nd": (0, 4),
    "penalize_bodies": (0,), "ubody": (), "divnorms": (),
    "dissipation": (), "gradchi": (), "omega_mag": (), "scores": (),
    "moments_read": (),
}


class _ArgGeom:
    """Duck-typed BlockGrid over the bucket-padded block axis whose
    per-block spacing ``h`` is a (possibly traced) device array: the
    geometry object the bucketed executables construct from their traced
    arguments, so ops/amr_ops.py kernels embed NO topology constants in
    their lowered HLO.  ``nb`` is the static bucket capacity; padding
    blocks carry h = 1 (never divides by zero; their fields are zero, so
    every operator output on them is zero)."""

    __slots__ = ("bs", "nb", "h", "extent")

    def __init__(self, bs: int, nb: int, h, extent):
        self.bs = bs
        self.nb = nb
        self.h = h
        self.extent = extent


@jax.jit
@jax.named_scope("DtPolicy")
def _maxu_j(vel, uinf):
    return jnp.max(jnp.abs(vel + uinf))


from cup3d_tpu.sim.dtpolicy import (  # noqa: E402 (placed with jit helpers)
    dt_device as _dt_device_update,
    dt_device_implicit as _dt_device_update_implicit,
)


@partial(jax.jit, static_argnames=("rasters", "cuts", "combine", "bs"))
@jax.named_scope("CreateObstacles")
def _create_blocks(packs, slots, frames, given, xc, real, h_raw, tab,
                   rasters, cuts, combine, bs):
    """CreateObstacles on the single-device forest as ONE program: from
    the step's uploads to every field the step reads.

    Body ``i`` with a traced block rasterizer (``rasters[i]``, e.g.
    ``StefanFish.raster_blocks``) takes its ``cuts[i]`` = (rows, entries)
    of ``packs``, the host midlines of all such bodies in one upload, and
    of ``slots``, their candidate blocks in another, and writes its SDF
    and udef at ``xc``'s row count, bucket padding included.  A body
    without one (``rasters[i]`` None) brings ``given[i]``, the (sdf, udef
    or None) of its own ``rasterize()`` on the grid's real blocks, padded
    here.  Padding rows hold an all-zero SDF, on which the Towers chi is
    exactly 0 (ops/chi.py), so the bucket's invariants hold unmasked.

    Then the tail every body shares: Towers chi from the halo'd SDF
    (+-1h band), udef kept inside the band, and with ``combine`` the
    chi-weighted combined fields (the pipelined megastep recombines on
    device and passes False).  Returns the per-body (sdfs, chis, udefs)
    as tuples of separate outputs and (chi, udef) or (None, None):
    nothing is sliced or stacked afterwards."""
    rows = xc.shape[0]
    sdfs, chis, udefs = [], [], []
    p0 = s0 = 0
    for raster, (np_, ns), frame, own in zip(rasters, cuts, frames, given):
        if raster is None:
            sdf, udef = own
            sdf = bk.pad_field(sdf, rows)
            udef = (jnp.zeros(sdf.shape + (3,), sdf.dtype) if udef is None
                    else bk.pad_field(udef, rows))
        else:
            sdf, udef = raster(xc, real, slots[s0:s0 + ns],
                               packs[p0:p0 + np_], frame)
            p0, s0 = p0 + np_, s0 + ns
        chi = towers_chi(tab.assemble_scalar(sdf, bs), h_raw)
        sdfs.append(sdf)
        chis.append(chi)
        udefs.append(udef * (chi > 0)[..., None])
    combined = (
        combine_obstacle_fields(jnp.stack(chis), jnp.stack(udefs))
        if combine else (None, None)
    )
    return (tuple(sdfs), tuple(chis), tuple(udefs)) + combined


def _regrid_part(name: str):
    """One part of an adaptation pass that changes the mesh, in the
    profiler's trace (``cup3d:AdaptMesh.<name>``).  An annotation and no
    profiler section: the AdaptMesh section keeps the whole pass as its
    self time, which is what ``amr.adapt_host_ms_per_step`` reads."""
    return obs_trace.annotate(
        f"{obs_trace.ANNOTATION_PREFIX}AdaptMesh.{name}")


class AMRSimulation:
    """Adaptive driver.  With ``mesh`` (a 1-D jax Mesh) every block-axis
    field lives padded + sharded over the devices and all halo exchange /
    refluxing / Krylov work runs through the ShardedForest
    (parallel/forest.py) — the distributed execution mode of the
    reference's GridMPI.  Without it, single-device gather tables."""

    def __init__(self, cfg: SimulationConfig, tree: Optional[Octree] = None,
                 mesh=None):
        self.cfg = cfg
        self.mesh = mesh
        self.dtype = jnp.dtype(cfg.dtype)
        periodic = tuple(b == "periodic" for b in cfg.bc)
        if tree is None:
            tree = Octree(
                TreeConfig(
                    (cfg.bpdx, cfg.bpdy, cfg.bpdz), cfg.levelMax, periodic
                ),
                cfg.levelStart,
            )
        self.grid = BlockGrid(
            tree, cfg.extents, tuple(BC(b) for b in cfg.bc), cfg.block_size
        )
        self.state: Dict[str, jnp.ndarray] = {}
        self.obstacles: List = []
        self.time = 0.0
        self.step_idx = 0
        self.dt = 0.0
        self.uinf = np.asarray(cfg.uinf, np.float64)
        self._uinf_host_src = None    # identity key of the cached upload
        self._uinf_host_cache = None  # device mirror of self.uinf
        self.nu = cfg.nu
        self.lambda_penal = cfg.lambda_penalization
        # cached device mirror of the penalization coefficient
        # (_lambda_device): uploaded once per value (the old per-step
        # jnp.asarray(self.lambda_penal) was rule JX010)
        self._lambda_dev_cache = None
        self._lambda_dev_val = None
        self.logger = BufferedLogger(cfg.path4serialization)
        self.profiler = Profiler()
        from cup3d_tpu.io.dump import OutputCadence

        self._cadence = OutputCadence(cfg.tdump, cfg.fdump, cfg.saveFreq)
        # end-of-step packed QoI read (forces, penalization forces, max|u|):
        # one blocking transfer instead of one per quantity (each blocking
        # read stalls the dispatch queue; same scheme as sim/simulation.py)
        self._pending_parts: List = []
        self._umax_next = None
        # device-resident max|u| scalar (the dt chain's CFL scale; see
        # _use_device_dt) — sliced from the megastep pack, never fetched
        self._umax_dev = None
        # static-AMR mode: freeze the (converged) mesh — no tagging, no
        # re-layout, no recompiles (BASELINE config #3 is a static 2-level
        # run; dynamic runs leave this True)
        self.adapt_enabled = True
        # pipelined fast path (cfg.pipelined): grouped deferred reads
        # through the async host data-plane (stream/qoi.py; the uniform
        # driver's depth-2 scheme), plus a collision fallback latch that
        # reroutes to the host path while any stale overlap pre-check is
        # non-zero.  The pack policy slims 256^3-class configs to
        # scalars-only; every emitted pack here already is.
        from cup3d_tpu.stream.qoi import PackPolicy, QoIStream

        self._pack_reader = QoIStream(
            self._consume_entry,
            policy=PackPolicy.for_cells(self.grid.nb * self.grid.bs**3),
            profiler=self.profiler,
        )
        # off-critical-path output (stream/dump.py, stream/checkpoint.py)
        from cup3d_tpu.stream.checkpoint import AsyncCheckpointer
        from cup3d_tpu.stream.dump import AsyncDumper

        self._dumper = AsyncDumper()
        self._checkpointer = AsyncCheckpointer()
        # round-9 observability (cup3d_tpu/obs/): postmortem ring always
        # on; step traces under CUP3D_TRACE=1.  Solver stats ride the
        # packed QoI reads of the host path (the megastep pack layout is
        # unchanged — pipelined traces carry mesh/stream fields only).
        from cup3d_tpu.obs.flight import FlightRecorder

        obs_trace.TRACE.default_directory(cfg.path4serialization)
        self.flight = FlightRecorder(
            directory=cfg.path4serialization, run_config=cfg,
            state_probe=self._flight_state,
        )
        self._obs = obs_trace.StepObserver(
            self.profiler, flight=self.flight, stream=self._pack_reader,
            kind="amr",
        )
        # round-13 observability v2: capture windows at loop boundaries
        # (CUP3D_PROFILE=every:N) + the env-gated /metrics//health
        # exporter (CUP3D_METRICS_PORT); both disarmed by default
        from cup3d_tpu.obs import export as obs_export
        from cup3d_tpu.obs import profile as obs_profile

        obs_profile.CONTROLLER.default_directory(cfg.path4serialization)
        self._obs_profile = obs_profile.CONTROLLER
        obs_export.ensure_exporter()
        self._last_umax = None
        self._uinf_dev = None
        self._collision_hot = False
        # refinement scores dispatched one step EARLY in pipelined mode so
        # the device compute + transfer overlap the inter-step host work
        self._scores_prefetch = None
        # bucketed path binds the on-device tag decision in
        # _bind_bucket_executables; None = host tagging (forest)
        self._device_tags = None
        # capacity bucketing (module doc): single-device regrids reuse
        # compiled executables while the padded table shapes stay inside
        # a bucket
        self._table_memo: Dict = {}   # octree signature -> padded bundle
        self._exec_cache: Dict = {}   # bucket key -> jitted executables
        # octree signature -> the forest path's full executable bundle
        # (closure-style jits can only be reused for an IDENTICAL
        # topology, so the memo key is the signature, not the bucket);
        # round 18: the memo discipline lives in parallel/forest.py
        from cup3d_tpu.parallel.forest import ExecutableMemo

        self._forest_memo = ExecutableMemo(
            max_entries=4, name="forest.exec_memo")
        self._solver_core = None
        # round-10 resilience: simulate() installs a RecoveryEngine here
        # (CUP3D_RECOVER=1, the default); the Poisson escalation ladder
        # overrides these per driver (resilience/recovery.py)
        self._resilience = None
        self._poisson_two_level = None  # None = CUP3D_COARSE default
        self._poisson_maxiter = 1000
        self._rebuild()
        self._alloc_fields()

    def _flight_state(self) -> dict:
        """Driver + bucket/capacity state for a flight-recorder
        postmortem (called only at dump time)."""
        g = self.grid
        return {
            "driver": "amr",
            "blocks": int(g.nb),
            "bucket_capacity": int(getattr(self, "_cap", g.nb)),
            "levels": sorted(set(int(l) for l in np.asarray(g.level))),
            "table_memo_entries": len(self._table_memo),
            "exec_cache_entries": len(self._exec_cache),
            "step": self.step_idx,
            "time": self.time,
            "dt": self.dt,
            "collision_hot": bool(self._collision_hot),
            "obstacles": [type(ob).__name__ for ob in self.obstacles],
            "stream": self._pack_reader.snapshot(),
            # round 10: the async writers' health rides in postmortems
            # (latched background failures, drop counts)
            "checkpointer": self._checkpointer.health(),
            "dumper": self._dumper.health(),
        }

    # the obstacle classes address their host as `sim`; provide the same
    # attribute surface as SimulationData where they need it
    @property
    def sim(self):  # pragma: no cover - convenience alias
        return self

    @property
    def step(self) -> int:
        """SimulationData-compatible step counter (obstacle PID etc.)."""
        return self.step_idx

    def _alloc_fields(self):
        g = self.grid
        self.state = {
            "vel": self._pad(g.zeros(3, self.dtype)),
            "chi": self._pad(g.zeros(0, self.dtype)),
            "p": self._pad(g.zeros(0, self.dtype)),
            "udef": self._pad(g.zeros(3, self.dtype)),
        }

    def _pad(self, field):
        """Block-axis pad: shard padding on a device mesh, bucket-capacity
        padding on the single-device path (padding rows stay 0)."""
        if self.forest is not None:
            return self.forest.pad(field)
        return bk.pad_field(field, self._cap)

    def _unpad(self, field):
        if self.forest is not None:
            return self.forest.unpad(field)
        return field[: self.grid.nb]

    def uinf_device(self):
        # identity-keyed upload cache: uinf is only ever REASSIGNED (the
        # fixed-frame update in advance/_consume_step_pack), so `is`
        # tracks staleness and a constant uinf costs the step loop zero
        # host->device traffic (same contract as sim/data.uinf_device)
        if self._uinf_host_src is not self.uinf:
            with sanctioned_transfer("uinf-upload"):
                self._uinf_host_cache = jnp.asarray(self.uinf, self.dtype)
            self._uinf_host_src = self.uinf
        return self._uinf_host_cache

    def _lambda_device(self):
        """The penalization coefficient ``penalize_bodies`` is handed,
        device resident and uploaded once per value: DLM where cfg.DLM >
        0 (the kernel forms lambda = DLM / dt from the step's device dt
        scalar), else the static lambda.  The host ``lambda_penal``
        mirror keeps feeding logs/checkpoints."""
        val = self.cfg.DLM if self.cfg.DLM > 0 else self.lambda_penal
        if self._lambda_dev_val != val:
            with sanctioned_transfer("scalar-upload"):
                self._lambda_dev_cache = jnp.asarray(val, self.dtype)
            self._lambda_dev_val = val
        return self._lambda_dev_cache

    # -- jitted kernels (rebuilt per layout) -------------------------------

    def _aot_content_sig(self, octree_sig) -> tuple:
        """The persistent-store content key of this layout's forest
        executables (round 21): the octree signature plus every config
        knob the closures capture (tolerances, nu, dtype, extent, mesh
        layout).  Equal keys guarantee bitwise-equal bound tables (the
        ExecutableMemo contract), so a store hit is exact; anything
        that changes the compiled body changes the key."""
        cfg = self.cfg
        return (
            octree_sig,
            int(self.grid.bs),
            str(np.dtype(self.dtype)),
            float(self.nu),
            tuple(float(v) for v in self.grid.extent),
            float(cfg.poissonTol),
            float(cfg.poissonTolRel),
            bool(cfg.bMeanConstraint),
            bool(cfg.implicitDiffusion),
            float(cfg.diffusionTol),
            float(cfg.diffusionTolRel),
            bool(cfg.bFixMassFlux),
            int(cfg.step_2nd_start),
            (tuple(self.mesh.shape.items())
             if self.mesh is not None else None),
        )

    def _h_finest(self) -> float:
        """Spacing of the finest level the octree allows (constant over
        regrids): what the surface probe and its budgets are sized by."""
        g = self.grid
        return float(g.h0 / (1 << (len(g._slot_maps) - 1)))

    def _step_bodies(self, budgets=(), windows=()):
        """sim/amr_step.py's bodies for this run's configuration (and,
        for the kernels that hold the surface probe, the obstacles'
        static point ``budgets`` and the shapes of their ``windows``)."""
        cfg, g = self.cfg, self.grid
        helm = None
        if cfg.implicitDiffusion:
            from cup3d_tpu.ops import diffusion as dif

            # the captured tables are fallbacks only: the traced
            # tab1/ftab flow through helm's tab_arg/flux_arg at call time
            # (ADVICE r2), and the bucketed view hands it a traced geom
            helm = dif.build_amr_helmholtz_solver(
                g if self.forest is None else self._geom,
                tol_abs=cfg.diffusionTol, tol_rel=cfg.diffusionTolRel,
                tab=self._tab1, flux_tab=self._ftab,
            )
        return make_step_bodies(
            nu=self.nu, bs=g.bs, dtype=self.dtype, helm=helm,
            fix_mass_flux=cfg.bFixMassFlux, umax_forced=cfg.uMax_forced,
            tag_rule=(cfg.Rtol, cfg.Ctol, cfg.levelMax,
                      cfg.levelMaxVorticity, bool(cfg.bAdaptChiGradient)),
            h_fine=self._h_finest(), budgets=budgets, windows=windows,
            dlm=cfg.DLM > 0,
        )

    def _rebuild(self):
        """Tables, view and binding of the sharded forest (``mesh=``);
        single-device runs go to _rebuild_bucketed."""
        if self.mesh is None:
            return self._rebuild_bucketed()
        from cup3d_tpu.parallel.forest import (
            bind_step_executable,
            cached_forest,
        )

        # the forest keeps the host tagging decision
        self._device_tags = None
        g = self.grid
        cfg = self.cfg
        # within-signature regrids (the ping-pong A->B->A pattern)
        # rebind the memoized executable bundle: zero retraces, zero
        # table rebuilds (parallel/forest.py cached_forest shares
        # the key discipline)
        sig = g.signature
        memo = self._forest_memo.get(sig)
        if memo is not None:
            for k, v in memo.items():
                setattr(self, k, v)
            return
        self.forest = cached_forest(g, self.mesh)
        self._geom = self.forest.geom
        # round 4: mesh mode runs the face-slab fast path too
        # (parallel/faces.py; falls back to per-ghost lab tables only
        # on degenerate closed-boundary topologies)
        self._tab1 = self.forest.face_tables(1)
        self._tab3 = self.forest.face_tables(3)
        self._ftab = self.forest.flux_tables
        self._solver = self.forest.build_poisson_solver(
            tol_abs=cfg.poissonTol, tol_rel=cfg.poissonTolRel,
            mean_constraint=cfg.bMeanConstraint,
        )
        # padded geometry arrays; cell volume is 0 on padding blocks so
        # every volume-weighted reduction ignores them, and the padding
        # rows of all fields are kept at 0 (labs of padding blocks
        # assemble to zero, so operators never write garbage there)
        self._vol = jnp.asarray(self.forest.vol, self.dtype)
        self._h_col = self._pad(
            jnp.asarray(g.h.reshape(g.nb, 1, 1, 1), self.dtype)
        )
        self._xc = self._pad(jnp.asarray(g.cell_centers(self.dtype)))
        self._real_mask = jnp.asarray(self.forest.pmask, self.dtype)
        profile = vol_total = None
        if cfg.bFixMassFlux:
            vol_total = float(np.sum(g.h**3) * g.bs**3)
            eta = jnp.asarray(
                (self._xc[..., 1] / g.extent[1]), self.dtype
            )
            # (nb_pad,1,1,1) mask broadcasts over the (nb_pad,8,8,8)
            # profile; padding rows stay 0
            profile = 6.0 * eta * (1.0 - eta) * self._real_mask
        # The sharded forest's duck-typed tables are not pytrees, so the
        # view is built here, once per octree signature, and the bound
        # executables close over it (per-shard scale is bounded).  What
        # this binder has always computed differently from the bucketed
        # one stays in the view: host-float vol_total, the constant
        # acceleration unmasked, the stats-less solver, helm's own geom.
        self._view = GeomView(
            geom=self._geom, sol=self._solver, tab1=self._tab1,
            tab3=self._tab3, ftab=self._ftab, vol=self._vol, xc=self._xc,
            profile=profile, vol_total=vol_total,
        )
        # round 21: forest-bound executables persist in the AOT store
        # under (octree signature + closure-content) keys — equal keys
        # guarantee bitwise-equal bound tables, so a restarted process
        # reloads the serialized executable instead of retracing
        aot_sig = self._aot_content_sig(sig)
        bodies = self._step_bodies()
        self._forces_ex = {}  # _forces_kernel fills it, the memo keeps it

        def bind(name, donate=()):
            # the jit construction site lives in parallel/forest.py
            # (bind_step_executable), outside the adaptation path: a NEW
            # octree signature binds once and the bundle rides
            # _forest_memo after (zero steady-state retraces across the
            # regrid ping-pong — the JX007 burn-down)
            label = ("advdiff_imp" if name == "advdiff"
                     and cfg.implicitDiffusion else name)
            return bind_step_executable(
                getattr(bodies, name), self._view, donate=donate,
                name=label, store_sig=aot_sig)

        for name, donate in _STEP_KERNELS.items():
            setattr(self, "_" + name, bind(name, donate))
        if cfg.bFixMassFlux:
            self._fix_flux = bind("fix_flux")
        self._maxu = _maxu_j
        if cfg.pipelined:
            self._build_megastep()
        self._forest_memo.put(sig, {
            k: getattr(self, k) for k in _FOREST_EXEC_ATTRS
            if hasattr(self, k)
        })

    # -- capacity-bucketed rebuild (the single-device production path) -----

    def _rebuild_bucketed(self):
        """Tables and binding of the single device (module doc): pad
        every topology artifact to the capacity ladder, memoize the
        padded bundle by octree signature, and bind jitted executables
        from the compiled-step cache keyed on (capacity, table treedef +
        shapes, donation signature) — a regrid inside a bucket reuses
        them all."""
        g, cfg = self.grid, self.cfg
        self.forest = None
        from cup3d_tpu.grid.faces import pad_face_tables
        from cup3d_tpu.grid.flux import pad_flux_tables
        from cup3d_tpu.ops import krylov

        from cup3d_tpu.obs import metrics as obs_metrics

        sig = g.signature
        memo = self._table_memo.pop(sig, None)
        if memo is not None:
            self._table_memo[sig] = memo  # move-to-back (LRU)
        obs_metrics.counter(
            "bucket.table_memo_hits" if memo is not None
            else "bucket.table_memo_misses"
        ).inc()
        if memo is None:
            with _regrid_part("Tables"):
                cap = bk.capacity(g.nb)
                coarse = (krylov.use_coarse_correction()
                          if self._poisson_two_level is None
                          else bool(self._poisson_two_level))
                coarse = coarse and cfg.bMeanConstraint not in (1, 3)
                h = np.ones(cap, np.float64)
                h[: g.nb] = g.h
                vol = np.zeros((cap, 1, 1, 1), np.float64)
                vol[: g.nb, 0, 0, 0] = g.h**3
                mask = np.zeros((cap, 1, 1, 1), np.float32)
                mask[: g.nb] = 1.0
                xc = np.zeros((cap, g.bs, g.bs, g.bs, 3), np.float32)
                xc[: g.nb] = g.cell_centers(np.float32)
                # corner pin slot (mean_constraint 1/3) rides as a DYNAMIC
                # index so pin relocation across regrids never retraces
                slot0 = 0
                if cfg.bMeanConstraint in (1, 3):
                    slot0 = int(np.lexsort(
                        (g.ijk[:, 2], g.ijk[:, 1], g.ijk[:, 0])
                    )[0])
                # per-slot octree level for the on-device regrid decision
                # (padding slots carry level 0 -> device_tags emits 'L')
                level = np.zeros(cap, np.int32)
                level[: g.nb] = [k[0] for k in g.keys]
                tab1 = g.face_tables(1)
                memo = dict(
                    cap=cap,
                    # faces of leaves whose neighbour is coarser: the rows of
                    # the coarse-face halo tables, before the bucket's padding
                    cf_faces=sum(int(r.shape[0]) for r in tab1.cf_rows),
                    tab1=pad_face_tables(tab1, g, cap),
                    tab3=pad_face_tables(g.face_tables(3), g, cap),
                    ftab=pad_flux_tables(build_flux_tables(g), g.bs, cap),
                    graph=(krylov.block_graph_tables(g, cap=cap)
                           if coarse else None),
                    h=jnp.asarray(h, self.dtype),
                    vol=jnp.asarray(vol, self.dtype),
                    xc=jnp.asarray(xc, self.dtype),
                    mask=jnp.asarray(mask, self.dtype),
                    slot0=jnp.asarray(slot0, jnp.int32),
                    level=jnp.asarray(level),
                )
                self._table_memo[sig] = memo
                while len(self._table_memo) > 4:
                    self._table_memo.pop(next(iter(self._table_memo)))
        self._cap = memo["cap"]
        self._tab1, self._tab3 = memo["tab1"], memo["tab3"]
        self._ftab = memo["ftab"]
        self._graph = memo["graph"]
        self._h_arr = memo["h"]
        self._vol = memo["vol"]
        self._xc = memo["xc"]
        self._real_mask = memo["mask"]
        self._slot0_dev = memo["slot0"]
        self._level_arr = memo["level"]
        self._h_col = jnp.reshape(self._h_arr, (self._cap, 1, 1, 1))
        if cfg.bFixMassFlux:
            eta = self._xc[..., 1] / g.extent[1]
            self._profile = (6.0 * eta * (1.0 - eta)) * self._real_mask
        else:
            self._profile = jnp.zeros((), self.dtype)
        self._geom = _ArgGeom(g.bs, self._cap, self._h_arr, g.extent)
        if self._solver_core is None:
            self._solver_core = amr_ops.build_amr_poisson_solver_dynamic(
                g.bs, tol_abs=cfg.poissonTol, tol_rel=cfg.poissonTolRel,
                maxiter=self._poisson_maxiter,
                mean_constraint=cfg.bMeanConstraint,
            )

        def solver(rhs, x0=None, **kw):
            # eager convenience binding (init-time IC solve); the jitted
            # executables bind the traced geometry themselves
            kw.setdefault("geom", self._geom)
            kw.setdefault("vol", self._vol)
            kw.setdefault("pmask", self._real_mask)
            kw.setdefault("graph", self._graph)
            kw.setdefault("slot0", self._slot0_dev)
            return self._solver_core(rhs, x0, **kw)

        solver.supports_stats = True  # forwards with_stats to the core
        solver.maxiter = getattr(self._solver_core, "maxiter", None)
        self._solver = solver
        key = self._bucket_key()
        ex = self._exec_cache.get(key)
        obs_metrics.counter(
            "bucket.exec_cache_hits" if ex is not None
            else "bucket.exec_cache_misses"
        ).inc()
        obs_metrics.gauge("bucket.capacity").set(self._cap)
        obs_metrics.gauge("amr.blocks").set(g.nb)
        obs_metrics.gauge("amr.coarse_fine_faces").set(memo["cf_faces"])
        # 1: the bound graph carries the dense pseudo-inverse and the
        # preconditioner's coarse solve is one product; 0: the CG loop
        # (a forest above krylov.DENSE_COARSE_MAX, or no coarse level)
        obs_metrics.gauge("poisson.coarse_dense").set(int(
            self._graph is not None and self._graph.pinv is not None))
        with _regrid_part("Rebind"):
            if ex is None:
                ex = self._build_bucket_executables()
                self._exec_cache[key] = ex
            self._bind_bucket_executables(ex)
            if cfg.pipelined:
                self._build_megastep()

    def _geo_args(self):
        """The canonical traced-geometry bundle every bucketed
        executable takes as trailing args (unused entries are DCE'd by
        XLA): tables, spacing, volumes, centers, mask, coarse graph, pin
        slot, forcing profile."""
        return (self._tab1, self._tab3, self._ftab, self._h_arr,
                self._vol, self._xc, self._real_mask, self._graph,
                self._slot0_dev, self._profile)

    def _bucket_key(self):
        """(capacity, treedef, leaf shapes/dtypes) of the geometry
        bundle: equal keys <=> jax would reuse every compiled
        executable, which is the definition of 'same bucket'."""
        leaves, treedef = jax.tree_util.tree_flatten(self._geo_args())
        shapes = tuple((tuple(l.shape), str(l.dtype)) for l in leaves)
        return (self._cap, treedef, shapes)

    def _view_of(self):
        """``view_of(geo)``: the GeomView of one _geo_args bundle, for
        use INSIDE a trace — geometry (_ArgGeom) and Poisson solve are
        rebuilt from the traced arrays, so the compiled executables
        carry no topology constant and serve every regrid whose bucket
        key matches."""
        bs, cap, extent = self.grid.bs, self._cap, self.grid.extent
        solver_core = self._solver_core
        fix_mass_flux = self.cfg.bFixMassFlux

        def view_of(geo):
            tab1, tab3, ftab, h, vol, xc, mask, graph, slot0, profile = geo
            g_ = _ArgGeom(bs, cap, h, extent)
            return GeomView(
                geom=g_,
                sol=partial(solver_core, geom=g_, vol=vol, pmask=mask,
                            graph=graph, slot0=slot0),
                tab1=tab1, tab3=tab3, ftab=ftab, vol=vol, xc=xc, mask=mask,
                profile=profile,
                vol_total=jnp.sum(vol) * bs**3 if fix_mass_flux else None,
                helm_geom=g_,
            )

        return view_of

    def _geo_binder(self):
        """The bucketed binder: ``jit_geo(body, name, donate)`` jits
        ``body(*state, view_of(geo))`` with the _geo_args bundle as
        trailing traced arguments (unused entries are DCE'd by XLA).
        ``donate`` names the state argnums the caller rebinds from the
        return value (JX002 burn-down)."""
        view_of = self._view_of()
        n_geo = len(self._geo_args())

        def jit_geo(body, name, donate=(), **kw):
            def fn(*a):
                return body(*a[:-n_geo], view_of(a[-n_geo:]), **kw)

            fn.__name__ = name
            return jax.jit(fn, donate_argnums=donate)

        return jit_geo

    def _build_bucket_executables(self):
        """jit the step kernels ONCE per bucket."""
        bodies = self._step_bodies()
        jit_geo = self._geo_binder()
        ex = {
            name: jit_geo(getattr(bodies, name), name, donate)
            for name, donate in _STEP_KERNELS.items()
        }
        ex["tags"] = jit_geo(bodies.tags, "tags")
        if self.cfg.bFixMassFlux:
            ex["fix_flux"] = jit_geo(bodies.fix_flux, "fix_flux", (0,))
        return ex

    def _bind_bucket_executables(self, ex):
        geo = self._geo_args

        def bound(fn):
            return lambda *a: fn(*a, *geo())

        for name in _STEP_KERNELS:
            setattr(self, "_" + name, bound(ex[name]))
        self._forces_ex = ex.setdefault("forces_bodies", {})
        self._device_tags = (
            lambda vel, chi:
            ex["tags"](vel, chi, self._level_arr, *geo())
        )
        if self.cfg.bFixMassFlux:
            self._fix_flux = bound(ex["fix_flux"])
        self._maxu = _maxu_j

    def _forces_kernel(self, budgets, windows):
        """``forces_bodies`` bound like the step kernels, for these static
        point budgets and window shapes; kept with the kernels of its
        bucket (of its octree signature on a mesh)."""
        fn = self._forces_ex.get((budgets, windows))
        if fn is None:
            body = self._step_bodies(budgets, windows).forces_bodies
            if self.forest is None:
                jitted = self._geo_binder()(body, "forces_bodies")

                def fn(*a):  # the bucket's geometry of the day, as bound()
                    return jitted(*a, *self._geo_args())
            else:
                from cup3d_tpu.parallel.forest import bind_step_executable

                fn = bind_step_executable(
                    body, self._view, name="forces_bodies",
                    store_sig=self._aot_content_sig(self.grid.signature)
                    + (budgets, windows))
            self._forces_ex[(budgets, windows)] = fn
        return fn

    # -- pipelined megastep ------------------------------------------------

    def _build_megastep(self):
        """ONE jitted function for the whole obstacle step (amr_step.py
        ``mega``): advection -> vmapped device rigid update ->
        penalization -> projection -> force QoI -> packed read vector.
        The AMR twin of the uniform driver's device fast path
        (models/pipeline.py UpdateObstacles +
        models/base.rigid_update_device), generalized to MULTI-obstacle by
        vmapping the rigid update; collision response stays host-side via a
        stale overlap pre-check in the pack (see advance_pipelined).

        Motivation (VERDICT r2 item 5 / r3 profile): every jit dispatch
        has a host cost and every blocking device->host read stalls the
        dispatch queue; the non-pipelined AMR step pays ~15 dispatches +
        2 blocking reads of pure latency.  This path pays ~1 dispatch and
        reads one pack, one step late, on a worker thread.

        Single device: the jits live in the compiled-step cache keyed by
        (bucket, probe budgets, n_obs), with all topology data as traced
        args — regrids within a bucket reuse compiled executables.  Forest: bound to this signature's
        view through parallel/forest.py, once per pressure order."""
        from cup3d_tpu.ops.surface import obstacle_probe_budget

        g = self.grid
        # probe slot budgets are STATIC inside the trace
        hf0 = self._h_finest()
        budgets = tuple(
            obstacle_probe_budget(ob, hf0) for ob in self.obstacles
        )
        windows = self._probe_windows()[0]
        if self.forest is not None:
            from cup3d_tpu.parallel.forest import bind_order_executables

            bodies = self._step_bodies(budgets, windows)
            # (first, second pressure order) per body; vel, p donated
            jits, jits_free = (
                bind_order_executables(
                    body, (self._view,), donate=(0, 1),
                    store_sig=self._aot_content_sig(g.signature))
                for body in (bodies.mega, bodies.mega_free)
            )

            def geo():  # the view is closed over: no trailing args
                return ()
        else:
            key = ("mega", self._bucket_key(), budgets,
                   windows, bool(self.cfg.bFixMassFlux))
            ex = self._exec_cache.get(key)
            if ex is None:
                bodies = self._step_bodies(budgets, windows)
                jit_geo = self._geo_binder()
                ex = tuple(
                    tuple(jit_geo(body, name + ("_2nd" if so else ""),
                                  (0, 1), second_order=so)
                          for so in (False, True))
                    for name, body in (("mega", bodies.mega),
                                       ("mega_free", bodies.mega_free))
                )
                self._exec_cache[key] = ex
            jits, jits_free = ex
            geo = self._geo_args
        # the order switch is two cached executables, picked by step
        # index at call time
        self._megastep = lambda *a: jits[
            int(self.step_idx >= self.cfg.step_2nd_start)
        ](*a, *geo())
        self._megastep_free = lambda *a: jits_free[
            int(self.step_idx >= self.cfg.step_2nd_start)
        ](*a, *geo())

    # -- obstacles ---------------------------------------------------------

    def _add_obstacles(self):
        content = self.cfg.resolved_factory_content()
        if not content:
            return
        from cup3d_tpu.models.factory import make_obstacles

        self.obstacles = make_obstacles(self, parse_factory(content))

    def create_obstacles(self, dt: float = 0.0, combine: bool = True):
        """Reference CreateObstacles (main.cpp:13589-13621) on blocks.
        Single device: host work in NumPy, then two uploads at most and
        ONE program for all bodies (``_create_blocks``).
        advance_pipelined passes combine=False: the megastep recombines
        on device, so the combined-state write here would be dead work
        (every other caller needs it)."""
        if not self.obstacles:
            return
        fixed = [ob for ob in self.obstacles if ob.bFixFrameOfRef]
        if fixed:
            self.uinf = -np.mean([ob.transVel for ob in fixed], axis=0)
        if self.forest is None:
            return self._create_obstacles_single(dt, combine)
        sdfs, udefs = [], []
        for ob in self.obstacles:
            ob.update_shape(self.time, dt)
            sdf, udef = ob.rasterize(self.time)  # unpadded (nb, ...)
            if udef is None:
                udef = self.grid.zeros(3, self.dtype)
            sdfs.append(sdf)
            udefs.append(udef)
        # mesh mode: the Towers chi needs SDF halos, which live behind the
        # sharded forest's exchange — pad first, assemble, then combine
        # (same construction as the single-device path, so sharded-vs-
        # single trajectories stay comparable)
        chis_p, udefs_p = [], []
        for ob, sdf, ud in zip(self.obstacles, sdfs, udefs):
            sdf_p = self._pad(sdf)
            lab = self._tab1.assemble_scalar(sdf_p, self.grid.bs)
            chi_p = towers_chi(lab, self._h_col)
            ud_p = self._pad(ud) * (chi_p > 0)[..., None]
            ob.chi, ob.udef, ob.sdf = chi_p, ud_p, sdf_p
            chis_p.append(chi_p)
            udefs_p.append(ud_p)
        if not combine:
            return  # pipelined megastep recombines on device
        stack = jnp.stack(chis_p)
        self.state["chi"] = jnp.max(stack, axis=0)
        den = jnp.maximum(jnp.sum(stack, axis=0), _EPS)[..., None]
        self.state["udef"] = (
            sum(c[..., None] * u for c, u in zip(chis_p, udefs_p)) / den
        )

    def _create_obstacles_single(self, dt: float, combine: bool):
        """The host half of ``_create_blocks``.  A body that offers the
        traced block rasterizer (``raster_blocks`` with its NumPy
        ``block_inputs``) is rasterized inside the program; any other
        dispatches its own ``rasterize()`` first."""
        packs, slots, bodies = [], [], []  # (raster, cut, frame, given)
        for ob in self.obstacles:
            ob.update_shape(self.time, dt)
            raster = getattr(ob, "raster_blocks", None)
            if raster is None:
                bodies.append((None, (0, 0), None, ob.rasterize(self.time)))
            else:
                pack, frame, idx = ob.block_inputs()
                packs.append(pack)
                slots.append(idx)
                bodies.append((raster, (len(pack), len(idx)), frame, None))
        rasters, cuts, frames, given = zip(*bodies)
        if packs:
            # cast on the host: jnp.asarray(float64, float32) is an upload
            # AND a convert program
            packs = jnp.asarray(np.concatenate(packs).astype(self.dtype))
            slots = jnp.asarray(np.concatenate(slots))
        else:
            packs = slots = None
        sdfs, chis, udefs, chi, udef = _create_blocks(
            packs, slots, frames, given, self._xc, self._real_mask,
            self._h_col, self._tab1, rasters=rasters, cuts=cuts,
            combine=combine, bs=self.grid.bs,
        )
        for i, ob in enumerate(self.obstacles):
            # the SDF is kept for the surface-point force probe
            # (ops/surface.py)
            ob.sdf, ob.chi, ob.udef = sdfs[i], chis[i], udefs[i]
        if combine:
            self.state["chi"] = chi
            self.state["udef"] = udef

    def _obstacle_ubody(self, ob):
        """One body's velocity field, for the contact branch alone
        (``prevent_colliding_obstacles``): a step without contact builds
        every body's field inside ``penalize_bodies``."""
        return self._ubody(
            ob.udef,
            jnp.asarray(ob.centerOfMass, self.dtype),
            jnp.asarray(ob.transVel, self.dtype),
            jnp.asarray(ob.angVel, self.dtype),
        )

    def _rigid_rows(self):
        """The bodies' host mirrors as the body kernels read them: one
        upload of a row (transVel, angVel, centerOfMass) per body."""
        rows = np.stack([
            np.concatenate([ob.transVel, ob.angVel, ob.centerOfMass])
            for ob in self.obstacles
        ])
        # cast on the host: jnp.asarray(float64, float32) is an upload
        # AND a convert program
        return jnp.asarray(rows.astype(self.dtype))

    def _probe_windows(self):
        """The surface probe's host half for all bodies: the static
        shapes of their windows, in blocks of the finest level, and ONE
        int32 vector to upload, every body's window origin and then every
        body's block slots (amr_step.py ``forces_bodies``).  A shape
        depends on the body's length and the finest spacing alone, never
        on where the body is (ops/surface.py ``block_window_slots``)."""
        from cup3d_tpu.ops.surface import block_window_slots

        b0s, slots = [], []
        for ob in self.obstacles:
            slots_, b0, _ = block_window_slots(
                self.grid, np.asarray(ob.position), ob.length
            )
            b0s.append(b0)
            slots.append(slots_)
        win = np.concatenate([np.ravel(b0s)] + [s_.ravel() for s_ in slots])
        return tuple(s_.shape for s_ in slots), win.astype(np.int32)

    # -- adaptation --------------------------------------------------------

    def adapt_mesh(self):
        g = self.grid
        cfg = self.cfg
        pf, self._scores_prefetch = self._scores_prefetch, None
        if pf is not None and pf[1] != g.nb:
            pf = None  # layout changed since dispatch: recompute
        if self._device_tags is not None:
            # on-device decision (grid/adapt.py device_tags): the host
            # downloads only (cap,) tags — or decodes them from the
            # prefetch pack, where they ride as exact small floats
            if pf is not None and pf[2] == "tags":
                tags = np.rint(np.asarray(pf[0], np.float64))
            else:
                # the pass's one blocking read: it waits for the step's
                # device work in front of the tags
                tags = blocking_read("tags-read", self._device_tags(
                    self.state["vel"], self.state["chi"]
                ))
            states = ad.states_from_tags(g, tags[: g.nb])
            return self._apply_states(states)
        if pf is not None and pf[2] == "scores":
            vals = np.asarray(pf[0], np.float64)
            vort, near_body = vals[: vals.shape[0] // 2], (
                vals[vals.shape[0] // 2:] > 0.5
            )
        else:
            vort, near_body = blocking_read("tags-read", self._scores(
                self.state["vel"], self.state["chi"]
            ))
        score = np.asarray(vort, np.float64)[: g.nb]
        near = np.asarray(near_body)[: g.nb]
        if cfg.bAdaptChiGradient and near.any():
            score = np.where(near, np.inf, score)
        # per-block refinement cap: levelMaxVorticity away from bodies
        cap = np.where(near, cfg.levelMax - 1, cfg.levelMaxVorticity - 1)
        states = ad.tag_states(g, score, cfg.Rtol, cfg.Ctol, cap)
        return self._apply_states(states)

    def _apply_states(self, states) -> bool:
        """Adaptation tail (plan -> transfer -> rebuild -> repad), split
        from the tagging so tests can force arbitrary regrid cycles
        (the capacity-bucket tests drive refine->coarsen->refine through
        here and assert the compiled-step cache absorbs them)."""
        from cup3d_tpu.obs import metrics as obs_metrics

        g = self.grid
        if all(s == "L" for s in states.values()):
            # the steady-state pass: nothing asked for, no part opened
            plan = None
        else:
            with _regrid_part("Plan"):
                plan = ad.adapt(g, states)
        if plan is None:
            obs_metrics.counter("amr.regrid_noops").inc()
            return False
        obs_metrics.counter("amr.regrids").inc()
        with _regrid_part("Transfer"):
            for k in ("vel", "udef", "chi", "p"):
                self.state[k] = ad.transfer_field(
                    g, plan, self._unpad(self.state[k])
                )
        self.grid = plan.new_grid
        self._rebuild()  # its Tables and Rebind parts
        with _regrid_part("Transfer"):
            for k in self.state:
                self.state[k] = self._pad(self.state[k])
        return True

    # -- initialization ----------------------------------------------------

    def _ic(self):
        if self.cfg.initCond == "taylorGreen":
            from cup3d_tpu.utils.flows import taylor_green_2d

            vel = taylor_green_2d(self.grid, dtype=self.dtype)
        elif self.cfg.initCond == "vorticity":
            # coiled-vorticity IC (reference IC_vorticity,
            # main.cpp:12506-12668): omega from the coil, then
            # u_d = lap^-1(-(curl omega)_d) with the forest solver
            from cup3d_tpu.utils.flows import coil_vorticity

            g = self.grid
            om = coil_vorticity(jnp.asarray(g.cell_centers(self.dtype)))
            om = self._pad(om)
            vlab = self._tab1.assemble_vector(om, g.bs)
            curl = amr_ops.curl_blocks(self._geom, vlab, self._tab1.width)
            comps = [
                self._solver(
                    -curl[..., d], tab_arg=self._tab1, flux_arg=self._ftab
                )
                for d in range(3)
            ]
            self.state["vel"] = jnp.stack(comps, axis=-1)
            self.state["p"] = self._pad(self.grid.zeros(0, self.dtype))
            return
        else:
            vel = self.grid.zeros(3, self.dtype)
        self.state["vel"] = self._pad(vel)
        self.state["p"] = self._pad(self.grid.zeros(0, self.dtype))

    def init(self):
        """Reference init(): obstacles, IC, then 3*levelMax adaptation
        rounds to converge the initial grid (main.cpp:15163-15178)."""
        self._add_obstacles()
        if self.cfg.pipelined:
            for ob in self.obstacles:
                # stale-PID allowed (see sim/simulation.py init); roll
                # correction mutates the host rigid solve and is not
                if getattr(ob, "bCorrectRoll", False):
                    raise ValueError(
                        "pipelined mode cannot run roll-corrected "
                        "obstacles (host-side angVel mutation) — run "
                        "without -pipelined"
                    )
        self.create_obstacles()
        self._ic()
        for _ in range(3 * self.cfg.levelMax):
            changed = self.adapt_mesh()
            self.create_obstacles()
            self._ic()
            if not changed:
                break

    # -- stepping ----------------------------------------------------------

    def _use_device_dt(self) -> bool:
        """Device-resident dt chain (VERDICT r3 item 4): eligible for
        pipelined OBSTACLE-FREE runs (fish midline kinematics consume host
        time each step) terminated by step count, with no time-based dump
        cadence or mass-flux log rows that would force host reads."""
        cfg = self.cfg
        if not (cfg.pipelined and not self.obstacles and self.forest is None):
            return False
        if cfg.dt > 0 or cfg.tend > 0 or cfg.tdump > 0 or cfg.bFixMassFlux:
            return False
        if cfg.dtDevice == 0:
            return False
        return cfg.dtDevice == 1 or jax.default_backend() == "tpu"

    def _calc_dt_device(self):
        """CFL dt from the previous step's ON-DEVICE max|u| — exactly the
        non-pipelined one-step-lag policy (no staleness margin, no growth
        cap), with zero host transfers.  The runaway abort checks the
        freshest host MIRROR (stale by <= ~3*read_every steps — an abort
        tolerates lag; the dt itself never does)."""
        cfg = self.cfg
        if faults.fire("step.nan_velocity", self.step_idx):
            # injected fault: poison the host mirror so the existing
            # runaway/NaN abort below detects it (resilience/faults.py)
            self._umax_next = float("nan")
        um = self._umax_next
        if um is not None and (not np.isfinite(um) or um > cfg.uMax_allowed):
            self.logger.flush()
            reason = ("nan-velocity" if not np.isfinite(um)
                      else "runaway-velocity")
            extra = {"step": self.step_idx, "umax": um}
            # postmortem (or recovery interception) BEFORE the raise,
            # like the host-dt path below
            self.flight.trigger(reason, extra=extra)
            raise SimulationFailure(
                reason, f"runaway velocity: max|u|={um:.3g}", extra
            )
        if self._umax_dev is None:
            self._umax_dev = self._maxu(self.state["vel"], self.uinf_device())
        from cup3d_tpu.sim import dtpolicy

        cfl = dtpolicy.ramped_cfl(cfg.CFL, self.step_idx, cfg.rampup)
        hmin = float(self.grid.h.min())
        with sanctioned_transfer("scalar-upload"):
            if cfg.implicitDiffusion:
                dt = _dt_device_update_implicit(
                    self._umax_dev, jnp.asarray(cfl, self.dtype),
                    jnp.asarray(hmin, self.dtype),
                    jnp.asarray(self.nu, self.dtype),
                    jnp.asarray(self.step_idx > 10),
                )
            else:
                dt = _dt_device_update(
                    self._umax_dev, jnp.asarray(cfl, self.dtype),
                    jnp.asarray(hmin, self.dtype),
                    jnp.asarray(self.nu, self.dtype),
                )
        if self._resilience is not None:
            # retry dt halving: identity at scale 1.0, one eager device
            # multiply while recovering (no host sync either way)
            dt = self._resilience.scale_dt(dt)
        self.dt = dt
        if cfg.DLM > 0:
            self.lambda_penal = cfg.DLM / dt
        return dt

    def calc_max_timestep(self) -> float:
        cfg = self.cfg
        if self._use_device_dt():
            return self._calc_dt_device()
        hmin = float(self.grid.h.min())
        if faults.fire("step.nan_velocity", self.step_idx):
            # injected fault: poison the max|u| mirror so the EXISTING
            # NaN-umax abort below detects it (resilience/faults.py)
            self._umax_next = float("nan")
        if self._umax_next is not None:
            umax = self._umax_next
            if not cfg.pipelined:
                self._umax_next = None
            # pipelined: keep the latest consumed max|u| (the reader may
            # still be in flight), floored by the fresh host-side body
            # speed — a gait spin-up outruns the stale mirror (measured
            # blow-up at 256^3; see Obstacle.max_body_speed)
            if cfg.pipelined and self.obstacles:
                umax = max(
                    umax,
                    max(ob.max_body_speed(self.uinf)
                        for ob in self.obstacles),
                )
        else:
            # the designed once-per-step dt sync of the non-pipelined path
            # (dispatched in front of the read, which then only waits)
            maxima = (self._maxu(self.state["vel"], self.uinf_device()),)
            if self.obstacles:
                # body kinematics bound the CFL immediately (see
                # sim/simulation.py calc_max_timestep)
                maxima += (jnp.max(jnp.abs(self.state["udef"])),)
            umax = max(float(m) for m in blocking_read("umax-read", maxima))
        self._last_umax = umax  # host float already (both branches)
        if not np.isfinite(umax) or umax > cfg.uMax_allowed:
            # NaN must trip the abort too: `NaN > x` is False, and a NaN
            # umax would otherwise propagate into dt (code-review r4)
            self.logger.flush()
            # postmortem BEFORE the raise (obs/flight.py): ring, residual
            # history, bucket/capacity state, last-known-good step
            reason = ("nan-velocity" if not np.isfinite(umax)
                      else "runaway-velocity")
            extra = {"step": self.step_idx, "umax": umax}
            self.flight.trigger(reason, extra=extra)
            raise SimulationFailure(
                reason, f"runaway velocity: max|u|={umax:.3g}", extra
            )
        if cfg.dt > 0:
            self.dt = cfg.dt
        else:
            from cup3d_tpu.sim import dtpolicy

            prev_dt = self.dt
            if cfg.pipelined:
                # stale-umax margin: see sim/simulation.py calc_max_timestep
                umax = 1.5 * umax
            # reference combined advection-diffusion cap + 1e-3 CFL ramp
            # (main.cpp:15268-15281 via sim/dtpolicy.py)
            self.dt = dtpolicy.dt_host(hmin, self.nu, umax, cfg.CFL,
                                       self.step_idx, cfg.rampup,
                                       cfg.implicitDiffusion)
            if cfg.pipelined and prev_dt > 0:
                self.dt = min(self.dt, 1.03 * prev_dt)
            if cfg.tend > 0:
                self.dt = min(self.dt, cfg.tend - self.time)
        if self._resilience is not None:
            # retry dt halving (exact no-op at scale 1.0, so the armed
            # clean path stays bitwise-identical to CUP3D_RECOVER=0)
            self.dt = self._resilience.scale_dt(self.dt)
        if faults.fire("dt.collapse", self.step_idx):
            # injected fault: collapse dt so the existing abort trips
            self.dt = float("nan")
        if not np.isfinite(self.dt) or self.dt <= 0:
            # dt policy collapse -> postmortem + abort (obs/flight.py)
            extra = {"step": self.step_idx, "dt": self.dt, "umax": umax}
            self.flight.trigger("dt-collapse", extra=extra)
            raise SimulationFailure(
                "dt-collapse", f"dt policy collapse: dt={self.dt:.3g}",
                extra,
            )
        if cfg.DLM > 0:
            self.lambda_penal = cfg.DLM / self.dt
        return self.dt

    # -- output ------------------------------------------------------------

    def _maybe_dump_save(self):
        if self._cadence.dump_due(self.time, self.step_idx):
            self.flush_packs()  # host mirrors current before output
            self.dump_fields()
        if self._cadence.save_due(self.step_idx):
            self.flush_packs()
            with self.profiler("Checkpoint"):
                # async snapshot: fields stage via copy_to_host_async and
                # serialize on the writer thread (stream/checkpoint.py)
                self._save_checkpoint_guarded()

    def _save_checkpoint_guarded(self):
        """Round-10 degradation policy (see sim/simulation.py): under
        recovery a surfaced background-write failure falls back to one
        synchronous atomic write, then drops + counts — output must
        never kill the step loop.  Legacy behavior without recovery."""
        from cup3d_tpu.obs import metrics as obs_metrics

        try:
            self._checkpointer.save(self)
        except Exception:
            if self._resilience is None:
                raise
            obs_metrics.counter("resilience.ckpt_sync_fallbacks").inc()
            try:
                from cup3d_tpu.io.checkpoint import save_checkpoint

                save_checkpoint(self)
            except Exception:
                obs_metrics.counter("resilience.ckpt_dropped").inc()

    def dump_fields(self):
        import os

        from cup3d_tpu.io import dump as dmp

        state_view = {k: self._unpad(v) for k, v in self.state.items()}
        fields = dmp.collect_dump_fields_device(
            self.cfg, state_view,
            lambda _vel: self._unpad(self._omega_mag(self.state["vel"])),
        )
        if fields:
            prefix = os.path.join(
                self.cfg.path4serialization, f"dump_{self.step_idx:07d}"
            )
            with self.profiler("Dump"):
                # async staged handoff: the sharded multi-writer runs off
                # the step loop (stream/dump.py).  The grid object handed
                # over is this step's layout — adaptation replaces, never
                # mutates, the BlockGrid, so the snapshot stays coherent.
                self._dumper.submit(prefix, self.time, self.grid, fields,
                                    step=self.step_idx)

    def drain_streams(self):
        """Join all off-critical-path output (pending dumps/checkpoints,
        trace writer) — run end, and anything that must observe the files
        on disk."""

        self._dumper.wait()
        try:
            self._checkpointer.wait()
        except Exception:
            # under recovery a failed final checkpoint write must not
            # fail an otherwise-complete run: drop + count
            if self._resilience is None:
                raise
            from cup3d_tpu.obs import metrics as obs_metrics

            obs_metrics.counter("resilience.ckpt_dropped").inc()
        # close + harvest a still-open capture window before the trace
        # flush so its device-attribution record lands in this trace
        self._obs_profile.finish()
        obs_trace.TRACE.flush()

    def _log_diagnostics(self):
        """div.txt/energy.txt rows every freqDiagnostics steps — shared by
        all three advance paths.  Off the hot path by construction: the
        production configs run freqDiagnostics=0 (bench.py), so the two
        blocking reads here cost their round trips on diagnostic steps
        only."""
        freq = self.cfg.freqDiagnostics
        if freq <= 0 or self.step_idx % freq:
            return
        with self.profiler("Diagnostics"):
            total, peak = self._divnorms(self.state["vel"])
            self.logger.write(
                "div.txt",
                f"{self.step_idx} {self.time:.8e} {float(total):.8e}"
                f" {float(peak):.8e}\n",
            )
            d = self._dissipation(self.state["vel"])
            self.logger.write(
                "energy.txt",
                f"{self.time:.8e} {float(d['kinetic_energy']):.8e} "
                f"{float(d['enstrophy']):.8e}"
                f" {float(d['dissipation_rate']):.8e}\n",
            )

    def advance(self, dt: float):
        # step span + flight ring around whichever stepping path runs:
        # the record carries the pre-step topology (nb/bucket) so regrid
        # and bucket transitions are visible across consecutive records
        extra = {"nb": int(self.grid.nb)}
        if self.forest is None:
            extra["bucket_capacity"] = int(self._cap)
        if self._last_umax is not None:
            extra["umax"] = float(self._last_umax)
        with self._obs.step(self.step_idx, self.time, dt, **extra) as late:
            try:
                if self.cfg.pipelined and not self._collision_hot:
                    if self.obstacles:
                        return self.advance_pipelined(dt)
                    return self.advance_pipelined_free(dt)
                return self._advance_host(dt)
            finally:
                if int(self.grid.nb) != extra["nb"]:
                    late["regrid"] = True
                    late["nb_post"] = int(self.grid.nb)

    def _adapt_due(self, step: int) -> bool:
        """The adaptation cadence: each of the first 10 steps, then every
        ADAPT_EVERY (main.cpp:15314)."""
        return self.adapt_enabled and (
            step < 10 or step % ADAPT_EVERY == 0
        )

    def _prefetch_regrid_decision(self):
        """Pipelined advances, after their megastep: when the NEXT step
        adapts, dispatch its refinement decision now, so the compute and
        transfer overlap this step's pack read + host work (staged
        through the stream so its bytes are counted).  The bucketed path
        ships (cap,) device tags; the forest ships the raw score
        fields."""
        if not self._adapt_due(self.step_idx + 1):
            return
        s = self.state
        if self._device_tags is not None:
            t = self._device_tags(s["vel"], s["chi"])
            # -1/0/1 are exact in any float dtype
            packed = self._pack_reader.stage(t.astype(self.dtype))
            self._scores_prefetch = (packed, self.grid.nb, "tags")
        else:
            vort, near = self._scores(s["vel"], s["chi"])
            packed = self._pack_reader.stage(jnp.concatenate(
                [vort.astype(self.dtype), near.astype(self.dtype)]
            ))
            self._scores_prefetch = (packed, self.grid.nb, "scores")

    def _advance_host(self, dt: float):
        """Non-pipelined stepping (also the collision fallback path)."""
        if self._pack_reader:
            # entering the host path from pipelined mode (collision
            # fallback or mode switch): mirrors must be current and the
            # device chains dropped
            self.flush_packs()
            for ob in self.obstacles:
                ob._dev_rigid = None
            self._uinf_dev = None
        s = self.state
        dt_j = device_scalar(dt, self.dtype, tag="dt-upload")

        self._maybe_dump_save()
        if self._adapt_due(self.step_idx):
            with self.profiler("AdaptMesh"):
                self.adapt_mesh()

        with self.profiler("CreateObstacles"):
            self.create_obstacles(dt)
        # after create_obstacles: it refreshes the frame velocity from the
        # bodies that fix the frame, and this step advects with that one
        uinf = self.uinf_device()
        with self.profiler("AdvectionDiffusion"):
            s["vel"] = self._advdiff(s["vel"], dt_j, uinf)
        if self.obstacles:
            with self.profiler("UpdateObstacles"):
                n_obs = len(self.obstacles)
                chis = tuple(ob.chi for ob in self.obstacles)
                cms = jnp.asarray(
                    np.stack([ob.centerOfMass for ob in self.obstacles])
                    .astype(self.dtype)
                )
                # the collision pre-check (overlap cell count per pair)
                # rides the moments read: one program, one transfer
                pairs = [
                    (i, j) for i in range(n_obs) for j in range(i + 1, n_obs)
                ]
                # the designed once-per-step moments sync of the
                # non-pipelined obstacle path (the pipelined megastep
                # streams these rows through the QoI pack instead)
                vals = blocking_read(
                    "moments-read", self._moments_read(chis, s["vel"], cms),
                    np.float64,
                )
                precheck = dict(zip(pairs, vals[n_obs * 19:].tolist()))
                self._overlap_now = any(v > 0 for v in precheck.values())
                M = vals[: n_obs * 19].reshape(n_obs, 19)
                for ob, row in zip(self.obstacles, M):
                    ob.compute_velocities(unpack_moments(row))
                    ob.update(dt)
            with self.profiler("Penalization"):
                from cup3d_tpu.obs import metrics as obs_metrics

                if self._overlap_now:
                    # contact work, op by op: the bodies' velocity
                    # fields and chi gradients for the pairs that
                    # overlap; an impulse latches the mirrors' velocities
                    from cup3d_tpu.models.collisions import (
                        prevent_colliding_obstacles,
                    )

                    prevent_colliding_obstacles(
                        self.obstacles,
                        [self._obstacle_ubody(ob) for ob in self.obstacles],
                        self._gradchi,
                        self._xc,
                        dt,
                        precheck_counts=precheck,
                    )
                obs_metrics.counter(
                    "operators.body_steps_contact" if self._overlap_now
                    else "operators.body_steps_fused"
                ).inc()
                # the mirrors as update() and the contact branch left
                # them; ComputeForces reads the same upload
                rigid = self._rigid_rows()
                s["vel"], PF = self._penalize_bodies(
                    s["vel"], chis,
                    tuple(ob.udef for ob in self.obstacles), rigid, dt_j,
                    self._lambda_device(),
                )
                self._pending_parts.append(("penal", PF))
        if self.cfg.bFixMassFlux:
            with self.profiler("FixMassFlux"):
                self._fix_mass_flux()
        elif self.cfg.uMax_forced > 0:
            # constant streamwise acceleration (ExternalForcing,
            # main.cpp:10581-10596); padding rows stay 0
            H = self.grid.extent[1]
            accel = 8.0 * self.nu * self.cfg.uMax_forced / (H * H)
            add = accel * dt
            if self._real_mask is not None:
                add = add * self._real_mask
            s["vel"] = s["vel"].at[..., 0].add(
                add if np.ndim(add) else float(add)
            )
        with self.profiler("PressureProjection"):
            # warm-start the Krylov solve from the previous pressure; after
            # step_2nd_start use the reference's increment form
            # (main.cpp:15087-15100)
            proj = (
                self._project_2nd
                if self.step_idx >= self.cfg.step_2nd_start
                else self._project
            )
            s["vel"], s["p"], psolve = proj(
                s["vel"], dt_j, s["chi"], s["udef"], s["p"]
            )
            # [resid, iters] joins the end-of-step packed read: solver
            # telemetry for the obs layer, no extra transfer
            self._pending_parts.append(("psolve", psolve))
        if self.obstacles:
            with self.profiler("ComputeForces"):
                self._compute_forces(rigid)
        self._log_diagnostics()
        with self.profiler("SyncQoI"):
            self._consume_step_pack()
        # collision-fallback bookkeeping: the host path just measured fresh
        # overlap counts; resume the pipelined fast path once clear
        if self._collision_hot:
            latched = any(
                ob.collision_counter > 0 for ob in self.obstacles
            )
            if not latched and not getattr(self, "_overlap_now", False):
                self._collision_hot = False
        self.step_idx += 1
        self.time += dt

    # -- pipelined stepping (device megastep + depth-2 packed reads) -------

    def advance_pipelined(self, dt: float):
        """One device dispatch for the whole obstacle step; the packed QoI
        of step N is fetched by a worker thread during step N+1's device
        work (the uniform driver's depth-2 scheme, sim/simulation.py)."""
        s = self.state
        dt_j = device_scalar(dt, self.dtype, tag="dt-upload")
        self._maybe_dump_save()
        if self._adapt_due(self.step_idx):
            with self.profiler("AdaptMesh"):
                # no flush: packs are immutable device vectors (still
                # readable after re-layout) and the rigid chains are pure
                # kinematic state, independent of the field layout; the
                # no-change case (the steady-state common one) costs only
                # the prefetched scores read
                self.adapt_mesh()
        with self.profiler("CreateObstacles"):
            self.create_obstacles(dt, combine=False)
        with self.profiler("Megastep"):
            n = len(self.obstacles)
            chis = jnp.stack([ob.chi for ob in self.obstacles])
            udefs = jnp.stack([ob.udef for ob in self.obstacles])
            sdfs = jnp.stack([ob.sdf for ob in self.obstacles])
            win = jnp.asarray(self._probe_windows()[1])
            rigid = jnp.stack(
                [ob.rigid_state_dev(self.dtype) for ob in self.obstacles]
            )
            forced = jnp.asarray(
                np.stack([ob.bForcedInSimFrame for ob in self.obstacles])
            )
            blocked = jnp.asarray(
                np.stack([ob.bBlockRotation for ob in self.obstacles])
            )
            fixmask = jnp.asarray(
                [1.0 if ob.bFixFrameOfRef else 0.0 for ob in self.obstacles],
                self.dtype,
            )
            uinf = (
                self._uinf_dev
                if self._uinf_dev is not None
                else self.uinf_device()
            )
            vel, p, chi, udef, uinf_next, pack = self._megastep(
                s["vel"], s["p"], chis, udefs, sdfs, rigid, forced,
                blocked, fixmask, win, uinf, dt_j, self._lambda_device(),
            )
            s["vel"], s["p"], s["chi"], s["udef"] = vel, p, chi, udef
            self._uinf_dev = uinf_next
            for i, ob in enumerate(self.obstacles):
                row = pack[i * RIGID_PACK:(i + 1) * RIGID_PACK]
                ob._dev_rigid = {
                    "step": self.step_idx, "pack": row, "trans": row[0:3],
                    "ang": row[3:6], "cm": row[12:15],
                }
            self._prefetch_regrid_decision()
        self._log_diagnostics()
        with self.profiler("SyncQoI"):
            npairs = n * (n - 1) // 2
            layout = [("rigid", n * RIGID_PACK), ("penal", n * 6),
                      ("forces", n * FORCE_PACK), ("overlap", npairs),
                      ("flux", 1),
                      ("umax", 1)]
            # grouped deferred read (sim/pack.py): K packs -> one device
            # concat -> one worker-thread fetch, so no blocking read
            # stalls the dispatch queue; staleness bounded by ~2K steps
            self._pack_reader.emit(
                {"layout": layout, "pack": pack, "time": self.time,
                 "step": self.step_idx}
            )
            # collision staleness guard (ADVICE r3): the overlap pre-check
            # in the pack is consumed up to ~2*read_every steps late.  When
            # the (stale) host mirrors show two bodies' bounding boxes
            # within a few fine cells of contact, kick an immediate read so
            # _collision_hot latches with ~1-step staleness instead.
            if n > 1 and self._mirrors_near_contact():
                self._pack_reader.kick()
        self.step_idx += 1
        self.time += dt

    def _mirrors_near_contact(self, margin_cells: float = 6.0) -> bool:
        h_fine = float(self.grid.h.min())
        obs = self.obstacles
        for i in range(len(obs)):
            for j in range(i + 1, len(obs)):
                half = 0.5 * (obs[i].length + obs[j].length)
                d = np.abs(
                    np.asarray(obs[i].position) - np.asarray(obs[j].position)
                )
                if np.all(d < half + margin_cells * h_fine):
                    return True
        return False

    def advance_pipelined_free(self, dt: float):
        """Obstacle-free fused stepping (the amr_tgv/TGV regime): one
        dispatch per step, same grouped pack reads and scores prefetch."""
        s = self.state
        dt_j = device_scalar(dt, self.dtype, tag="dt-upload")
        self._maybe_dump_save()
        if self._adapt_due(self.step_idx):
            with self.profiler("AdaptMesh"):
                self.adapt_mesh()
        with self.profiler("Megastep"):
            uinf = (
                self._uinf_dev
                if self._uinf_dev is not None
                else self.uinf_device()
            )
            vel, p, pack = self._megastep_free(s["vel"], s["p"], uinf, dt_j)
            s["vel"], s["p"] = vel, p
            # device dt chain: next step's CFL scale, never read back
            self._umax_dev = pack[-1]
            self._prefetch_regrid_decision()
        self._log_diagnostics()
        with self.profiler("SyncQoI"):
            self._pack_reader.emit(
                {"layout": [("flux", 1), ("umax", 1)], "pack": pack,
                 "time": self.time, "step": self.step_idx}
            )
        self.step_idx += 1
        self.time += dt

    def flush_packs(self):
        """Drain in-flight reads + pending packs so host mirrors are
        current (dump/checkpoint/fallback boundaries)."""
        self._pack_reader.flush()

    def _consume_entry(self, entry: dict):
        vals = entry.get("vals")
        if vals is None:
            vals = blocking_read("qoi-read", entry["pack"], np.float64)
        off = 0
        for name, size in entry["layout"]:
            seg = vals[off:off + size]
            off += size
            if name == "rigid":
                for i, ob in enumerate(self.obstacles):
                    ob.apply_rigid_pack(
                        seg[RIGID_PACK * i:RIGID_PACK * (i + 1)],
                        clear_dev=False,
                    )
            elif name == "penal":
                for i, ob in enumerate(self.obstacles):
                    ob.penal_force = seg[6 * i:6 * i + 3]
                    ob.penal_torque = seg[6 * i + 3:6 * i + 6]
            elif name == "forces":
                for i, ob in enumerate(self.obstacles):
                    store_force_qoi(ob, unpack_forces(
                        seg[FORCE_PACK * i:FORCE_PACK * (i + 1)]))
                    log_forces(self.logger, i, entry["time"], ob)
            elif name == "overlap":
                if np.any(seg > 0):
                    # stale contact signal: reroute to the host path (fresh
                    # pre-check + collision impulse machinery) until clear;
                    # the fallback step flushes and clears device chains
                    self._collision_hot = True
            elif name == "flux":
                if self.cfg.bFixMassFlux:
                    u_target = 2.0 / 3.0 * self.cfg.uMax_forced
                    # the producing step's index, not the consuming one —
                    # host-path rows are "step time value target" too
                    self.logger.write(
                        "flux.txt",
                        f"{entry['step']} {entry['time']:.8e} "
                        f"{float(seg[0]):.8e} {u_target:.8e}\n",
                    )
            elif name == "umax":
                self._umax_next = float(seg[0])
            elif name == "psolve":
                # consumed up to ~2*read_every steps late: attribute the
                # stats to the PRODUCING step carried in the entry
                self._note_solve(int(entry.get("step", self.step_idx)), seg)
        # host frame velocity from the refreshed mirrors (logs/dumps)
        fixed = [ob for ob in self.obstacles if ob.bFixFrameOfRef]
        if fixed:
            self.uinf = -np.mean([ob.transVel for ob in fixed], axis=0)

    def _note_solve(self, step: int, seg):
        """One solve's ``[residual, iterations]`` as the packed read
        brought it: obs gauges + step trace + flight residual history
        (itercap trips a postmortem), and which coarse solve the bound
        preconditioner ran (host side: the graph's arm, no device read;
        a solver without a coarse level counts under neither)."""
        from cup3d_tpu.obs import metrics as obs_metrics

        self._obs.note_solver(
            step, seg[1], seg[0],
            cap=getattr(self._solver, "maxiter", None),
        )
        graph = getattr(self, "_graph", None)  # the sharded forest: none
        if graph is not None:
            obs_metrics.counter(
                "poisson.coarse_dense_solves" if graph.pinv is not None
                else "poisson.coarse_cg_solves"
            ).inc()

    def _consume_step_pack(self):
        """ONE blocking host read for everything the step produced
        (penalization forces, force QoI, next-dt max|u|) — the AMR twin of
        sim/simulation.py's packed read."""
        from cup3d_tpu.models.base import (
            log_forces, store_force_qoi, unpack_forces,
        )

        parts = self._pending_parts
        self._pending_parts = []
        umax_dev = self._maxu(self.state["vel"], self.uinf_device())
        if self.obstacles:
            umax_dev = jnp.maximum(
                umax_dev, jnp.max(jnp.abs(self.state["udef"]))
            )
        parts.append(("umax", umax_dev.reshape(1)))
        pack = jnp.concatenate([p[1].astype(self.dtype) for p in parts])
        # THE designed end-of-step packed QoI read of the host path: one
        # blocking transfer serves every consumer
        vals = blocking_read("qoi-read", pack, np.float64)
        off = 0
        for name, arr in parts:
            seg = vals[off:off + arr.shape[0]]
            off += arr.shape[0]
            if name == "penal":
                for i, ob in enumerate(self.obstacles):
                    ob.penal_force = seg[6 * i:6 * i + 3]
                    ob.penal_torque = seg[6 * i + 3:6 * i + 6]
            elif name == "forces":
                for i, ob in enumerate(self.obstacles):
                    store_force_qoi(ob, unpack_forces(
                        seg[FORCE_PACK * i:FORCE_PACK * (i + 1)]))
                    log_forces(self.logger, i, self.time, ob)
            elif name == "umax":
                self._umax_next = float(seg[0])
            elif name == "psolve":
                self._note_solve(self.step_idx, seg)

    def _fix_mass_flux(self):
        u_target = 2.0 / 3.0 * self.cfg.uMax_forced
        vel, u_msr = self._fix_flux(
            self.state["vel"],
            jnp.asarray(self.uinf[0], self.dtype),
            jnp.asarray(u_target, self.dtype),
        )
        self.state["vel"] = vel
        self.logger.write(
            "flux.txt",
            # jax-lint: allow(JX001, designed flux.txt sync on the host
            # path; the pipelined megastep streams this row instead)
            f"{self.step_idx} {self.time:.8e} {float(u_msr):.8e}"
            f" {u_target:.8e}\n",
        )

    def _compute_forces(self, rigid):
        """Per-obstacle force/torque/power QoI from the surface-point
        probe (ops/surface.py; reference ComputeForces,
        main.cpp:12250-12503): one upload, the probe windows, and one
        program for all bodies.  ``rigid``: the rows Penalization
        uploaded (the mirrors have not moved since)."""
        from cup3d_tpu.ops.surface import obstacle_probe_budget

        s, obs = self.state, self.obstacles
        h_fine = float(self.grid.h.min())
        budgets = tuple(obstacle_probe_budget(ob, h_fine) for ob in obs)
        windows, win = self._probe_windows()
        rows = self._forces_kernel(budgets, windows)(
            s["vel"], s["p"], tuple(ob.chi for ob in obs),
            tuple(ob.sdf for ob in obs), tuple(ob.udef for ob in obs),
            jnp.asarray(win), rigid,
        )
        # joins the end-of-step packed read (_consume_step_pack)
        self._pending_parts.append(("forces", rows))

    # -- resilience hooks (resilience/recovery.py driver contract) ---------

    def _resilience_restore(self, payload: dict):
        """In-place rollback to a ``build_payload``-shaped in-memory
        snapshot: rebuild the octree/grid from the snapshot's leaf keys
        (exactly ``io.checkpoint.load_checkpoint``'s AMR branch, minus
        the disk), rebind the compiled executables — a topology already
        seen hits the table memo and the bucketed exec cache, so the
        common rollback costs zero retraces — and restore fields/host
        scalars/obstacles."""
        import pickle

        from cup3d_tpu.grid.octree import Octree, TreeConfig

        cfg = self.cfg
        periodic = tuple(b == "periodic" for b in cfg.bc)
        tree = Octree(
            TreeConfig((cfg.bpdx, cfg.bpdy, cfg.bpdz), cfg.levelMax,
                       periodic),
            0,
        )
        tree.leaves.clear()
        for l, i, j, k in payload["leaves"]:
            tree.leaves[(int(l), int(i), int(j), int(k))] = None
        tree.assert_balanced()
        self.grid = BlockGrid(
            tree, cfg.extents, tuple(BC(b) for b in cfg.bc), cfg.block_size
        )
        self._scores_prefetch = None
        self._rebuild()
        # re-copy on the way in: the step jits donate these buffers and
        # the engine's snapshot must survive repeated restores
        self.state = {
            k: self._pad(jnp.copy(v)) for k, v in payload["fields"].items()
        }
        self.time = float(payload["time"])
        self.step_idx = int(payload["step"])
        self.dt = float(payload["dt"])
        self.uinf = np.asarray(payload["uinf"], np.float64)
        self.lambda_penal = float(payload["lambda_penal"])
        self._cadence.next_dump = float(payload["next_dump"])
        self.obstacles = pickle.loads(payload["obstacles"])
        for ob in self.obstacles:
            ob.sim = self
        self._pending_parts = []
        self._umax_next = None
        self._umax_dev = None
        self._uinf_dev = None
        self._last_umax = None
        self._collision_hot = False
        # mirrors queued from the abandoned trajectory must never apply
        self._pack_reader.abandon()
        if self.obstacles:
            self.create_obstacles(0.0)  # rebuild chi/udef/sdf on device

    def _resilience_zero_pressure(self):
        """Escalation stage 'zero-guess': the next solve warm-starts
        from p = 0 (projection warm-starts from the live p field)."""
        self.state["p"] = jnp.zeros_like(self.state["p"])

    def _resilience_rebuild_poisson(self, two_level=None,
                                    maxiter_mult: int = 1):
        """Escalation stages 'tile-only' / 'iter-bump': rebuild every
        solver-bearing executable with the two-level preconditioner
        dropped and/or a bumped iteration budget.  Clears the bucketed
        caches (the solver is baked into them) — a deliberate, counted
        retrace on the failure path only."""
        self._poisson_two_level = two_level
        self._poisson_maxiter = 1000 * int(maxiter_mult)
        self._solver_core = None
        self._exec_cache.clear()
        self._table_memo.clear()  # memo carries the coarse graph
        self._rebuild()

    def simulate(self):
        from cup3d_tpu.resilience.recovery import RecoveryEngine

        cfg = self.cfg
        eng = RecoveryEngine.install(self)
        try:
            while True:
                # capture-window hook at the loop top (disabled: one
                # branch; obs/profile.py)
                self._obs_profile.on_step(self.step_idx)
                if eng is not None and eng.on_loop_top():
                    continue  # rolled back: restart the iteration
                try:
                    dt = self.calc_max_timestep()
                    if cfg.verbose:
                        print(
                            f"cup3d_tpu[amr]: step: {self.step_idx},"
                            f" time: {self.time:f},"
                            f" dt: {dt:.3e}, blocks: {self.grid.nb}"
                        )
                    self.advance(dt)
                except Exception as e:
                    if eng is not None and eng.handle_failure(e):
                        continue  # rolled back: retry from the snapshot
                    raise
                done_t = cfg.tend > 0 and self.time >= cfg.tend - 1e-12
                done_n = cfg.nsteps > 0 and self.step_idx >= cfg.nsteps
                if done_t or done_n:
                    break
            self.flush_packs()
            self.drain_streams()
            self.logger.flush()
        finally:
            if eng is not None:
                eng.uninstall()


def make_amr_tgv_step(sim: "AMRSimulation"):
    """The obstacle-free bucketed-AMR scan body as a pure function
    ``one_step(carry, cfl_eff) -> (carry', row (TGV_ROW,))`` — the
    block-forest twin of sim/megaloop.make_tgv_step, so fleet/batch.py
    can ``vmap`` adaptive lanes exactly like uniform ones.

    The padded topology bundle (_geo_args) is frozen in the closure:
    every lane in a fleet bucket shares the template's (capacity,
    octree-signature) tables, and the body never regrids — fleet AMR
    tenants run on a frozen topology for the drain (fleet/server.py
    keys assembly on the signature, so mixed topologies land in
    different buckets).  The dt chain is the uniform policy on the
    FINEST level's spacing (the binding CFL constraint on a forest);
    no operation reduces across lanes, so the PR 9 isolation contract
    (per-lane NaN containment, bitwise freeze) carries over unchanged.
    """
    view = sim._view_of()(sim._geo_args())
    cfg, nu, dtype = sim.cfg, sim.nu, sim.dtype
    g = sim.grid
    # the forest's own advdiff / project bodies, explicit RK3 whatever
    # the configuration says (fleet/server.py admits no other)
    bodies = make_step_bodies(nu=nu, bs=g.bs, dtype=dtype)
    so = cfg.step_2nd_start == 0
    h_fine = float(np.min(g.h))
    uinf = sim.uinf_device()

    def one_step(carry, cfl_eff):
        vel, p = carry["vel"], carry["p"]
        umax, time, dtprev = carry["umax"], carry["time"], carry["dt"]
        cap_dt = (h_fine * h_fine / 6.0) / (nu + (h_fine / 6.0) * umax)
        dt = jnp.minimum(cfl_eff * h_fine / (umax + 1e-8), cap_dt)
        dt = jnp.where(dtprev > 0, jnp.minimum(dt, 1.03 * dtprev), dt)
        vel = bodies.advdiff(vel, dt, uinf, view)
        vel, p, stats = bodies.project(vel, dt, None, None, p, view,
                                       second_order=so)
        umax_new = jnp.max(jnp.abs(vel + uinf))
        time_new = time + dt
        out = {"vel": vel, "p": p, "umax": umax_new, "time": time_new,
               "dt": dt}
        row = jnp.concatenate([jnp.asarray(stats, dtype), umax_new[None],
                               dt[None], time_new[None]])
        return out, row

    return one_step
