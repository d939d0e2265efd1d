"""Multi-tenant fleet server: job queue -> bucketed batches -> dispatch.

The serving pipeline, one layer per concern:

1. **Queue** — ``submit(tenant, spec)`` validates a scenario spec and
   enqueues a :class:`FleetJob`; ``poll``/``cancel`` give tenants the
   usual lifecycle, ``drain`` runs the dispatch loop to completion.
2. **Capacity-bucketed assembly** — queued jobs group by their *static
   signature* (grid shape, dtype, solver, fish geometry: everything
   that changes the compiled executable) plus a ×1.25 ladder rung of
   their step budget (grid/bucket.py's ladder idea, re-applied to the
   lane and step axes), and each group is padded up the lane ladder —
   so mixed workloads share a small, bounded set of executables and the
   RecompileCounter budget is #buckets, not #jobs.
3. **Dispatch loop** — each batch advances all its lanes K steps per
   dispatch through the vmapped advance (fleet/batch.py), emitting one
   (B, K, ROW) QoI block per dispatch into a stream/qoi.py
   :class:`QoIStream` (async copy, bounded in-flight window).
4. **Fan-out** — the stream consumer splits rows per lane, runs the
   per-lane failure detection (fleet/isolate.py), and appends each
   tenant's rows in (step) order into that job's QoI buffer — a
   deterministic, byte-stable ordering per tenant.

Round 16 — the job-lifecycle observatory: every job carries a
monotonic-clock span timeline (``obs.trace.now()`` marks at the
lifecycle seams: submitted -> queued -> bucketed -> running ->
dispatched -> fanout -> rollback*/retire -> done/failed/cancelled).
Timestamps are host clock reads at seam transitions ONLY — the dispatch
loop itself never takes one per step, and nothing here reads a device
value.  At a job's terminal transition the server (1) observes
queue-wait / execution / end-to-end durations into per-tenant,
per-bucket ``fleet.job_*_s`` histograms (obs/metrics.py log buckets ->
p50/p95/p99), (2) tracks the per-tenant SLO window (target p99 +
rolling breach window -> burn-rate counters in ``health()``), and
(3) when tracing is on, emits one ``kind="job"`` aux record plus a
pid-3 lane-occupancy span into the Perfetto export (obs/trace.py).

Round 17 — continuous batching: with ``CUP3D_FLEET_CONTINUOUS`` on
(the default) the server is work-conserving at K-boundaries.  A lane
that retires (done, cancelled, or gave up) is immediately reseeded
with a compatible queued job — same static signature, so the cached
executable is reused with zero recompiles; the reseed is a per-lane
carry upload (fleet/batch.reseed_lane_carry, the same scan-carry
upload shape as the rollback path) plus a gait-row swap, leaving every
other lane bitwise untouched.  ``serve(feed)`` accepts ``submit()``
in-flight with admission control (per-tenant quota + max-queue-depth
backpressure, surfaced in ``health()["admission"]``), the scheduler
policy hook picks the reseed order (FIFO default, "srb" =
shortest-remaining-budget), and lane occupancy (busy-lane-steps /
total-lane-steps per drain window) lands in the
``fleet.lane_occupancy`` gauge plus idle spans on the pid-3 Perfetto
lane tracks.  ``CUP3D_FLEET_CONTINUOUS=0`` keeps the legacy
generation-drain path bitwise-unchanged.

Round 23 — durability: with ``CUP3D_FLEET_JOURNAL`` on (the default)
every job lifecycle transition (submit, lane placement, terminal) plus
a periodic settled K-boundary carry snapshot per lane lands in a
write-ahead journal (fleet/journal.py) under the server workdir; a
killed-and-restarted server replays it via :meth:`FleetServer.recover`
— terminal jobs remembered, queued jobs re-admitted, RUNNING jobs
resumed from their latest snapshot through the jitted reseed upload
INTO a batch rebuilt at the RECORDED (cap, K) so the same compiled
executable reproduces the never-crashed bytes.  fleet/migrate.py rides
the same checkpoint/resume seams for live migration and graceful
drains.  ``CUP3D_FLEET_JOURNAL=0`` keeps the serve loop bitwise-legacy
(no journal instance at all).

Env knobs: ``CUP3D_FLEET_LANES`` caps lanes per batch (default 64),
``CUP3D_FLEET_BUCKETS`` caps the executable cache (default 8, LRU),
``CUP3D_FLEET_MESH=1`` shards the lane axis over visible devices,
``CUP3D_FLEET_SLO_P99``/``CUP3D_FLEET_SLO_WINDOW`` set the completion
SLO (target p99 seconds, rolling job window), and ``CUP3D_SNAP_EVERY``/
``CUP3D_MAX_RETRIES`` carry their resilience meanings per lane.
Round 17 adds ``CUP3D_FLEET_CONTINUOUS`` (default 1),
``CUP3D_FLEET_POLICY`` (``fifo``/``srb``), ``CUP3D_FLEET_QUEUE_DEPTH``
(admission backpressure threshold, default 1024) and
``CUP3D_FLEET_TENANT_QUOTA`` (live jobs per tenant, 0 = unlimited).
Live servers surface in the obs /health payload (obs/export.py)
through the same weakref registry pattern as the flight recorders.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
import weakref
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from cup3d_tpu.config import SimulationConfig
from cup3d_tpu.fleet import batch as FB
from cup3d_tpu.fleet import isolate as ISO
from cup3d_tpu.fleet.journal import JobJournal
from cup3d_tpu.grid.bucket import count_capacity
from cup3d_tpu.obs import federate as FEDERATE
from cup3d_tpu.obs import flight as _flight
from cup3d_tpu.obs import metrics as M
from cup3d_tpu.obs import trace as OT
from cup3d_tpu.parallel import topology as topo
from cup3d_tpu.resilience import faults
from cup3d_tpu.sim.dtpolicy import ramped_cfl
from cup3d_tpu.sim.megaloop import (
    DEFAULT_SCAN_K,
    FISH_ROW,
    TGV_ROW,
    resolve_scan_k,
)
from cup3d_tpu.stream.qoi import QoIStream

# job lifecycle states
QUEUED = "queued"
RUNNING = "running"
DONE = "done"
FAILED = "failed"
CANCELLED = "cancelled"
#: round 23 — checkpointed off this server by fleet/migrate.py; the
#: receiving server finishes the job under the same id
MIGRATED = "migrated"

#: terminal statuses (the journal replays these verbatim; mirrored as
#: literals in fleet/journal.py TERMINAL_STATUSES)
TERMINALS = (DONE, FAILED, CANCELLED, MIGRATED)

#: lane-count ladder base: fleet batches start amortizing at 2 lanes
LANE_LADDER_BASE = 2

#: scheduler policies: FIFO (submit order) and shortest-remaining-budget
#: (smallest nsteps first, cutting p99 under skewed job lengths)
POLICIES = ("fifo", "srb")

#: sentinel so FleetServer(mesh=None) means "explicitly unsharded"
#: while omitting it means "resolve via fleet_mesh()/CUP3D_FLEET_MESH"
_MESH_DEFAULT = object()


class FleetAdmissionError(RuntimeError):
    """submit() rejected by admission control: the queue is at its
    backpressure depth, or the tenant is at its live-job quota.  The
    ``reason`` ("queue-full" / "quota") matches the
    ``fleet.admission_rejects`` counter label and the backpressure
    field in ``health()["admission"]``."""

    def __init__(self, reason: str, detail: str):
        super().__init__(detail)
        self.reason = reason


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    # jax-lint: allow(JX009, malformed env knob falls back to the
    # default; the effective value is visible in health()["knobs"])
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    # jax-lint: allow(JX009, malformed env knob falls back to the
    # default; the effective value is visible in health()["slo"])
    except ValueError:
        return default


@dataclass
class FleetJob:
    """One tenant scenario: spec in, per-step QoI rows + final lane
    state out."""

    job_id: str
    tenant: str
    spec: dict
    status: str = QUEUED
    nsteps: int = 0
    steps_done: int = 0
    time: float = 0.0
    error: Optional[str] = None
    rows: Optional[np.ndarray] = None  # (nsteps, ROW) float64, step order
    lane: int = -1
    batch: Optional["FleetBatch"] = None
    cfg: Optional[SimulationConfig] = None
    #: bucket-signature label for the SLO histograms (set at assembly)
    sig_label: str = ""
    #: the monotonic span timeline: (event, obs.trace.now()) appends at
    #: lifecycle seams — never inside the per-step hot loop
    events: List[Tuple[str, float]] = field(default_factory=list)
    _seen: Set[str] = field(default_factory=set, repr=False)
    #: round 23 — _job_terminal ran for this job (idempotence guard:
    #: cancel of a mid-migration or journal-replayed job must resolve
    #: to exactly one terminal, never a double fold into the SLO state)
    _terminal_done: bool = field(default=False, repr=False)

    def mark(self, event: str, once: bool = False,
             collapse: bool = False) -> None:
        """Append one lifecycle event at the current monotonic time.
        ``once`` drops repeats (dispatched/fanout fire per dispatch
        otherwise); ``collapse`` drops a repeat only when it would
        IMMEDIATELY follow itself (compile_wait/reseed_wait re-fire
        every scheduling pass while the job stays parked — one event
        per parked stretch is the provenance-correct timeline).  The
        timestamp is clamped non-decreasing: marks may arrive from the
        dispatch thread and the QoI consumer thread, and the timeline
        is validated monotone (obs/trace.py)."""
        if once and event in self._seen:
            return
        if collapse and self.events and self.events[-1][0] == event:
            return
        self._seen.add(event)
        t = OT.now()
        if self.events and t < self.events[-1][1]:
            t = self.events[-1][1]
        self.events.append((event, t))

    def event_time(self, event: str) -> Optional[float]:
        """First occurrence time of ``event`` (None when absent)."""
        for n, t in self.events:
            if n == event:
                return t
        return None

    def durations(self) -> Dict[str, float]:
        """The SLO-relevant durations derivable from the timeline:
        queue-wait (queued -> running), execution (running -> terminal)
        and end-to-end (submitted -> terminal) — all on the monotonic
        clock, present only when both endpoints were marked.

        CAVEAT (round 22): ``queue_wait_s`` is kept for schema
        compatibility but since the round-21 AOT path it CONFLATES two
        remediable-by-different-means waits — capacity wait (fix:
        scale out) and background compile wait (fix: warm the store).
        The split rides alongside as ``capacity_wait_s`` +
        ``compile_wait_s`` (from :meth:`phases`); prefer those and the
        full :meth:`phases` decomposition for attribution."""
        out: Dict[str, float] = {}
        if not self.events:
            return out
        t_end = self.events[-1][1]
        t_q = self.event_time("queued")
        t_run = self.event_time("running")
        t_sub = self.event_time("submitted")
        if t_q is not None and t_run is not None:
            out["queue_wait_s"] = t_run - t_q
            ph = self.phases()
            out["capacity_wait_s"] = ph.get("capacity_wait", 0.0)
            out["compile_wait_s"] = ph.get("compile_wait", 0.0)
        if t_run is not None:
            out["exec_s"] = t_end - t_run
        if t_sub is not None:
            out["e2e_s"] = t_end - t_sub
        return out

    def phases(self) -> Dict[str, float]:
        """Exact latency-provenance decomposition of the timeline
        (:func:`cup3d_tpu.obs.trace.phase_decomposition`): exclusive
        per-phase seconds that sum to end-to-end by construction."""
        return OT.phase_decomposition(self.events)

    def record(self, step: int, row: np.ndarray, t: float) -> None:
        """Append (or re-apply, after a lane rollback replay) the QoI
        row for ``step``; keyed by step index, so the final buffer is a
        clean, gap-free, byte-stable sequence per tenant."""
        if 0 <= step < self.nsteps:
            self.rows[step] = row
            self.steps_done = max(self.steps_done, step + 1)
            self.time = t

    def summary(self) -> dict:
        return {
            "job_id": self.job_id,
            "tenant": self.tenant,
            "status": self.status,
            "steps_done": int(self.steps_done),
            "nsteps": int(self.nsteps),
            "time": float(self.time),
            "error": self.error,
        }

    def qoi_bytes(self) -> bytes:
        """The tenant's QoI block as bytes (ordering-stability tests)."""
        return b"" if self.rows is None else self.rows.tobytes()


def _job_config(spec: dict, workdir: str) -> Tuple[str, SimulationConfig]:
    """Scenario spec -> (kind, SimulationConfig) for one pipelined
    lane.  Only scan-eligible configs are expressible: free dt,
    step-budget termination, <= 1 frozen-gait obstacle; "amr_tgv"
    lanes run the bucketed block-forest body on a topology frozen
    after init (see _AMRLaneDriver)."""
    kind = str(spec.get("kind", "fish"))
    nsteps = int(spec.get("nsteps", 0))
    if nsteps <= 0:
        raise ValueError("fleet scenario needs nsteps > 0")
    n = int(spec.get("n", 32))
    common = dict(
        nsteps=nsteps, tend=0.0,
        CFL=float(spec.get("cfl", 0.3)),
        rampup=int(spec.get("rampup", 0)),
        dtype=str(spec.get("dtype", "float32")),
        pipelined=True, verbose=False, freqDiagnostics=0,
        path4serialization=workdir,
    )
    uniform = dict(
        bpdx=1, bpdy=1, bpdz=1, block_size=n,
        levelMax=1, levelStart=0,
    )
    if kind == "tgv":
        cfg = SimulationConfig(
            extent=float(spec.get("extent", 2.0 * np.pi)),
            nu=float(spec.get("nu", 0.02)),
            initCond=str(spec.get("initCond", "taylorGreen")),
            **uniform, **common,
        )
    elif kind == "amr_tgv":
        bpd = int(spec.get("bpd", 2))
        lm = int(spec.get("levelMax", 2))
        cfg = SimulationConfig(
            bpdx=bpd, bpdy=bpd, bpdz=bpd,
            levelMax=lm, levelStart=int(spec.get("levelStart", lm - 1)),
            Rtol=float(spec.get("rtol", 1e9)),
            Ctol=float(spec.get("ctol", -1.0)),
            extent=float(spec.get("extent", 2.0 * np.pi)),
            nu=float(spec.get("nu", 0.02)),
            initCond=str(spec.get("initCond", "taylorGreen")),
            step_2nd_start=int(spec.get("step_2nd_start", 0)),
            **common,
        )
    elif kind == "fish":
        L = float(spec.get("L", 0.3))
        T = float(spec.get("T", 1.0))
        xpos = float(spec.get("xpos", 0.5))
        factory = f"stefanfish L={L} T={T} xpos={xpos}"
        for k in ("ypos", "zpos"):
            if k in spec:
                factory += f" {k}={float(spec[k])}"
        cfg = SimulationConfig(
            extent=float(spec.get("extent", 1.0)),
            nu=float(spec.get("nu", 1e-4)),
            factory_content=factory,
            **uniform, **common,
        )
    else:
        raise ValueError(f"unknown fleet scenario kind {kind!r}")
    return kind, cfg


class _AMRLaneDriver:
    """Adapter giving an obstacle-free AMRSimulation the driver surface
    assemble()/FleetBatch expect (.sim/.cfg/init/_megaloop_eligible).
    init runs the usual 3*levelMax adaptation rounds, then FREEZES the
    topology: the fleet scan body never regrids, so every lane keeps
    the (capacity, topology-signature) it bucketed on for the whole
    drain — the zero-retrace contract inside a bucket."""

    def __init__(self, sim):
        self.sim = sim
        self.cfg = sim.cfg

    def init(self):
        self.sim.init()
        self.sim.adapt_enabled = False

    def _megaloop_eligible(self) -> bool:
        s, cfg = self.sim, self.cfg
        return (not s.obstacles and s.forest is None
                and not cfg.implicitDiffusion and not cfg.bFixMassFlux
                and cfg.uMax_forced <= 0)


def _static_signature(drv, kind: str) -> tuple:
    """Everything that changes the compiled lane body: jobs sharing a
    signature (and a lane/step rung) share one executable.  Adaptive
    tenants key on (capacity, octree topology-signature): equal keys
    <=> the vmapped bucketed step's compiled shapes AND its frozen
    padded tables match, so lanes can share the closure-captured
    geometry bundle without retracing."""
    s = drv.sim
    if kind == "amr_tgv":
        return (
            kind,
            int(s.grid.bs),
            int(s._cap),
            s.grid.signature,
            str(np.dtype(s.dtype)),
            float(s.nu),
            tuple(float(v) for v in s.grid.extent),
            int(drv.cfg.step_2nd_start),
        )
    sig = (
        kind,
        tuple(int(v) for v in np.asarray(s.grid.shape)),
        str(np.dtype(s.dtype)),
        float(s.grid.h),
        float(s.nu),
        type(s.poisson_solver).__name__,
    )
    if s.obstacles:
        ob = s.obstacles[0]
        sig += (
            float(ob.length),
            bool(ob.bFixFrameOfRef),
            tuple(int(v) for v in ob._window_shape),
            ob._raster_box,
            tuple(np.asarray(ob.forced_mask_dev()).astype(float).tolist()),
            tuple(np.asarray(ob.block_mask_dev()).astype(float).tolist()),
            float(drv.cfg.DLM),
            float(drv.cfg.lambda_penalization),
        )
    return sig


def _lane_payload(kind: str, drv, label: str):
    """One lane's device payload from an initialized driver: the solo
    carry plus the frozen gait (fish only) — shared by first assembly
    (stacked into the batched carry) and reseeding (per-lane upload)."""
    if kind == "fish":
        ob = drv.sim.obstacles[0]
        from cup3d_tpu.models.fish.device_midline import freeze_gait

        gait = freeze_gait(ob, drv.sim.time, drv.sim.dtype)
        if gait is None:
            raise ValueError(f"{label}: gait not freezable for fleet")
        return FB.init_body_carry(drv.sim, ob), gait
    if kind == "amr_tgv":
        return FB.init_amr_carry(drv.sim), None
    return FB.init_tgv_carry(drv.sim), None


class FleetBatch:
    """B lanes sharing one compiled executable: the batched carry, the
    host step/budget mirrors, the lane guard, and the QoI stream."""

    def __init__(self, server: "FleetServer", batch_id: int, kind: str,
                 jobs: List[FleetJob], drivers: list, K: int, cap: int):
        self.server = server
        self.batch_id = batch_id
        #: cross-restart-unique batch id for journal place/snapshot
        #: records — a restarted server reuses small batch_ids, and a
        #: replayed record must never alias a live batch's lanes
        self.uid = f"{os.getpid():x}.{batch_id}"
        self.kind = kind
        self.K = int(K)
        self.B = int(cap)
        self.row_w = FISH_ROW if kind == "fish" else TGV_ROW
        # row offsets of the per-lane (umax, dt, time) chain
        self.off_umax = self.row_w - 3
        self.off_dt = self.row_w - 2
        self.off_time = self.row_w - 1

        template = drivers[0]
        self.template = template
        s = template.sim
        self.np_dtype = np.dtype(s.dtype)

        # lane assembly: per-job solo carries + frozen gaits, padded up
        # the lane ladder with inert clones of lane 0 (left = 0 from
        # step 0, so the gated body freezes them; they are never
        # consumed because jobs[lane] is None there)
        carries, gaits, targets = [], [], []
        for job, drv in zip(jobs, drivers):
            carry, gait = _lane_payload(kind, drv, job.job_id)
            carries.append(carry)
            if gait is not None:
                gaits.append(gait)
            targets.append(job.nsteps)
        while len(carries) < self.B:
            carries.append(carries[0])
            targets.append(0)
            if gaits:
                gaits.append(gaits[0])
        self.jobs: List[Optional[FleetJob]] = list(jobs) + [None] * (
            self.B - len(jobs))
        for lane, job in enumerate(jobs):
            job.lane = lane
            job.batch = self
            job.status = RUNNING
            job.mark("running")
            job.rows = np.zeros((job.nsteps, self.row_w), np.float64)
            # jax-lint: allow(JX013, journal append is host-side file
            # I/O — no device dispatch per lane; the place record is
            # inherently per-lane)
            server._journal(
                "place", job_id=job.job_id, batch_uid=self.uid,
                lane=lane, cap=self.B, K=self.K, kind=kind)
        #: lanes whose job has not had its first dispatch marked yet —
        #: steady-state dispatch() pays one empty-set truth test
        self._undispatched: Set[int] = {
            lane for lane, j in enumerate(self.jobs) if j is not None}

        self.carry = FB.stack_carries(carries, targets)
        self.gaits = FB.stack_gaits(gaits, s.dtype) if gaits else None
        ob = s.obstacles[0] if kind == "fish" else None
        # the batch's actual mesh: the server's, downgraded loudly to
        # None when B does not divide over it (fleet.mesh_fallbacks) —
        # health()/the CLI report THIS, the shard state really running
        self.mesh = FB.resolve_fleet_mesh(self.B, server.mesh)
        #: lanes on failed mesh slices (resilience/elastic.fail_shard):
        #: never reseed targets again, frozen at zero budget
        self.dead_lanes: Set[int] = set()
        #: the static bucket signature — reseed compatibility is THIS
        #: (the step-budget rung only shapes first assembly; it does
        #: not enter the executable key, so cross-rung reseeds still
        #: hit the compiled-advance cache)
        self.sig = _static_signature(template, kind)
        self.advance = server.executable(
            self.sig, s, ob, self.B, self.K, kind=kind, mesh=self.mesh)

        self.step_h = np.zeros(self.B, np.int64)
        self.left_h = np.asarray(targets, np.int64)
        self.snap_dispatches = max(1, server.snap_steps // self.K)
        self.guard = ISO.LaneGuard(self.B, server.max_retries)
        self.guard.snapshot(self.carry, self.step_h, self.left_h)
        self._since_snap = 0
        self.dispatches = 0
        # lane-occupancy accounting: busy = budget-gated lane-steps
        # actually advanced, total = B*K per dispatch (frozen and
        # padding lanes count against the denominator — that is the
        # waste continuous batching reclaims)
        self.busy_steps = 0
        self.total_steps = 0
        #: monotonic time each idle lane last went free (padding lanes
        #: at construction, retired lanes at their terminal mark) —
        #: the start of the pid-3 idle span the next reseed closes
        self._lane_free_since: Dict[int, float] = {
            lane: OT.now() for lane in range(self.B)
            if self.jobs[lane] is None}
        self.stream = QoIStream(
            self._consume, read_every=1, max_inflight=2,
            name=f"fleet-b{batch_id}")
        M.counter("fleet.batches").inc()
        M.counter("fleet.lanes", kind=kind).inc(len(jobs))

    # -- dispatch ----------------------------------------------------------

    def active(self) -> bool:
        return bool(
            (self.left_h > 0).any()
            or self.stream
            or any(j is not None and j.status == RUNNING for j in self.jobs)
        )

    def _cfl_block(self) -> np.ndarray:
        """Host-precomputed per-lane CFL ramp for the next K steps —
        the same dtpolicy.ramped_cfl chain the solo megaloop feeds, per
        lane (host fan-out loop: no device work here)."""
        cfl = np.empty((self.B, self.K), self.np_dtype)
        for lane in range(self.B):
            job = self.jobs[lane]
            base = float(job.cfg.CFL) if job is not None else 0.1
            ramp = int(job.cfg.rampup) if job is not None else 0
            step0 = int(self.step_h[lane])
            for k in range(self.K):
                cfl[lane, k] = ramped_cfl(base, step0 + k, ramp)
        return cfl

    def nshards(self) -> int:
        """Mesh slices this batch spans (1 when unsharded)."""
        return (int(self.mesh.devices.size)
                if self.mesh is not None else 1)

    def lane_shard(self, lane: int) -> int:
        """The mesh slice owning ``lane`` (occupancy/SLO shard labels;
        0 when unsharded)."""
        from cup3d_tpu.resilience import elastic as EL

        return EL.shard_of_lane(self.B, self.nshards(), lane)

    def fail_shard(self, shard: int) -> List[str]:
        """Drop one mesh slice: freeze its lane block, requeue its
        running jobs onto the queue for surviving shards (per-slice
        elastic recovery, resilience/elastic.py)."""
        from cup3d_tpu.resilience import elastic as EL

        return EL.fail_shard(self, shard)

    def dispatch(self) -> None:
        """One batched advance: every live lane moves K steps, one QoI
        block goes onto the stream."""
        # the shard-loss seam fires per mesh slice at the K-boundary
        # (shard index in the step slot, the fleet.lane_nan idiom one
        # level up); the dead slice's lanes drop out of this dispatch
        for shard in range(self.nshards()):
            if shard in {self.lane_shard(d) for d in self.dead_lanes}:
                continue
            if faults.fire("fleet.shard_loss", step=shard):
                self.fail_shard(shard)
        valid = np.minimum(self.left_h, self.K).astype(np.int64)
        if self._undispatched:
            for lane in sorted(self._undispatched):
                if valid[lane] > 0:
                    job = self.jobs[lane]
                    if job is not None:
                        job.mark("dispatched", once=True)
                    self._undispatched.discard(lane)
        carry, rows = self.advance(self.carry, self._cfl_block(), self.gaits)
        self.carry = carry
        entry = self.stream.pack_parts(
            [("scan", rows.reshape(self.B * self.K * self.row_w))],
            self.template.sim.dtype,
            step0=self.step_h.copy(), valid=valid,
            epochs=self.guard.epochs.copy(),
            step=int(self.dispatches),
        )
        self.stream.emit(entry)
        self.step_h += valid
        self.left_h -= valid
        self.dispatches += 1
        self._since_snap += 1
        busy = int(valid.sum())
        self.busy_steps += busy
        self.total_steps += self.B * self.K
        M.counter("fleet.dispatches").inc()
        M.counter("fleet.busy_lane_steps").inc(busy)
        M.counter("fleet.total_lane_steps").inc(self.B * self.K)
        ns = self.nshards()
        if ns > 1:
            # shard-labeled occupancy (round 18): which mesh slice the
            # busy lane-steps ran on, additive next to the totals
            bl = self.B // ns
            for shard in range(ns):
                sb = int(valid[shard * bl:(shard + 1) * bl].sum())
                M.counter("fleet.shard_busy_lane_steps",
                          shard=str(shard)).inc(sb)
                M.counter("fleet.shard_total_lane_steps",
                          shard=str(shard)).inc(bl * self.K)
        # round-19 observatory seam: per-shard K-boundary walls + skew
        # detection + the federation snapshot refresh.  Host scalars
        # only (the mark is obs.trace.now()); both calls collapse to
        # one bool/len test when nothing is armed or the batch is
        # unsharded, so the solo-lane hot path pays nothing.
        if ns > 1:
            FEDERATE.STRAGGLER.boundary(
                range(ns), source="fleet", sink=OT.TRACE,
                step=int(self.dispatches))
        FEDERATE.FED.on_k_boundary()
        if self._since_snap >= self.snap_dispatches:
            self.settle()
            self.guard.snapshot(self.carry, self.step_h, self.left_h)
            self.journal_snapshots()
            self._since_snap = 0
        # the crash drill's kill switch (round 23): hard process death
        # at a K-boundary, armed with the dispatch count in the step
        # slot — recovery may lose at most the work since the last
        # journaled snapshot, never a job
        if faults.fire("server.crash", step=int(self.dispatches)):
            os._exit(23)

    def settle(self) -> None:
        """Drain the stream: every emitted row is consumed (and every
        lane fault handled) before the caller proceeds.  Required
        before snapshots — only a validated state may become a rollback
        target."""
        self.stream.flush()

    def tick(self) -> None:
        """One dispatch-loop turn: advance if any lane has budget, else
        drain the stream (which may resurrect budget via rollback)."""
        if (self.left_h > 0).any():
            self.dispatch()
        else:
            self.settle()

    # -- fan-out + isolation ----------------------------------------------

    def _consume(self, entry: dict) -> None:
        vals = entry.get("vals")
        if vals is None:
            vals = np.asarray(entry["pack"], np.float64)
        rows = np.asarray(vals, np.float64).reshape(
            self.B, self.K, self.row_w)
        step0, valid = entry["step0"], entry["valid"]
        epochs = entry["epochs"]
        for lane in range(self.B):
            job = self.jobs[lane]
            if job is None or job.status != RUNNING:
                continue
            if epochs[lane] != self.guard.epochs[lane]:
                continue  # stale rows from an abandoned lane trajectory
            if valid[lane] > 0:
                job.mark("fanout", once=True)
            for k in range(int(valid[lane])):
                step = int(step0[lane]) + k
                row = rows[lane, k]
                reason = self.guard.check_row(
                    lane, step, float(row[self.off_umax]),
                    float(row[self.off_dt]))
                if reason is not None:
                    self.lane_fault(lane, step, reason)
                    break
                self.guard.note_progress(lane, step)
                job.record(step, row, float(row[self.off_time]))
                if job.steps_done >= job.nsteps:
                    self.retire(lane, DONE, "done")
                    break

    def lane_fault(self, lane: int, step: int, reason: str) -> None:
        """Contain one lane's failure: rollback with dt-halving while
        the retry budget lasts, retire the lane after."""
        M.counter("fleet.lane_faults", reason=reason).inc()
        if self.guard.exhausted(lane):
            self.carry = self.guard.give_up(self.carry, lane, reason)
            self.left_h[lane] = 0
            job = self.jobs[lane]
            job.error = reason
            self.retire(lane, FAILED, "failed")
            return
        job = self.jobs[lane]
        if job is not None:
            job.mark("rollback")
        self.carry, snap_step, snap_left = self.guard.rollback(
            self.carry, lane, step, reason)
        self.step_h[lane] = snap_step
        self.left_h[lane] = snap_left

    def retire(self, lane: int, status: str, reason: str) -> None:
        job = self.jobs[lane]
        if job is None or job.status not in (RUNNING,):
            return
        job.status = status
        job.mark("retire")
        job.mark(status)
        M.counter("fleet.lane_retires", reason=reason).inc()
        self.server.update_lane_gauge()
        # the lane goes idle exactly where the job's occupancy span
        # ends (the terminal mark), so the idle span a later reseed
        # emits touches it without overlapping
        self._lane_free_since[lane] = job.events[-1][1]
        self.server._job_terminal(job, batch=self, lane=lane)

    def cancel_lane(self, lane: int) -> None:
        """Freeze the lane (bits of every other lane untouched) and
        drop its in-flight rows."""
        self.carry = ISO.retire_lanes(
            self.carry, np.arange(self.B) == lane)
        self.left_h[lane] = 0
        self.guard.epochs[lane] += 1
        self.retire(lane, CANCELLED, "cancelled")

    def free_lanes(self) -> List[int]:
        """Lanes holding no RUNNING job — padding or retired — i.e.
        reseed targets for the continuous scheduler.  Callers settle
        the stream first so pending retirements are visible.  Lanes on
        a lost mesh slice (``dead_lanes``) are never reseed targets."""
        return [lane for lane in range(self.B)
                if lane not in self.dead_lanes
                and (self.jobs[lane] is None
                     or self.jobs[lane].status != RUNNING)]

    def reseed_lane(self, lane: int, job: FleetJob, drv) -> None:
        """Splice a queued job into a freed lane at a K-boundary: a
        per-lane carry upload + gait-row swap (fleet/batch.py), fresh
        host mirrors, and a guard reset (epoch bump + full retry budget
        + snapshot-row refresh, fleet/isolate.py).  Every other lane's
        carry bits are untouched, and the previous occupant's in-flight
        rows drop on the epoch bump."""
        solo, gait = _lane_payload(self.kind, drv, job.job_id)
        self.carry = FB.reseed_lane_carry(
            self.carry, lane, solo, job.nsteps, mesh=self.mesh)
        if self.gaits is not None:
            self.gaits = FB.reseed_lane_gaits(
                self.gaits, lane, gait, mesh=self.mesh)
        self.step_h[lane] = 0
        self.left_h[lane] = job.nsteps
        self.guard.reseed(self.carry, lane, job.nsteps)
        self.jobs[lane] = job
        job.lane = lane
        job.batch = self
        job.status = RUNNING
        job.mark("reseeded")
        job.mark("running")
        job.rows = np.zeros((job.nsteps, self.row_w), np.float64)
        self._undispatched.add(lane)
        self.server._journal(
            "place", job_id=job.job_id, batch_uid=self.uid,
            lane=lane, cap=self.B, K=self.K, kind=self.kind)
        M.counter("fleet.reseeds", kind=self.kind).inc()
        M.counter("fleet.lanes", kind=self.kind).inc()
        self.server.update_lane_gauge()
        t_free = self._lane_free_since.pop(lane, None)
        sink = OT.TRACE
        if sink.enabled and t_free is not None:
            t_run = job.event_time("running")
            if t_run is not None and t_run > t_free:
                sink.lane_span(
                    FB.lane_track_id(self.batch_id, lane), "idle",
                    t_free, t_run - t_free, args={"job_id": "<idle>"})

    # -- durability (round 23) ---------------------------------------------

    def journal_snapshots(self) -> None:
        """Journal one carry snapshot per RUNNING lane.  Called at the
        same settled K-boundary as the rollback snapshot, so the
        recorded state is always validated: every row up to it consumed
        clean, ``steps_done == step_h`` per lane.  The recorded (cap,
        K) let recovery rebuild the SAME compiled executable, which is
        what makes a resumed trajectory bitwise."""
        if self.server.journal is None:
            return
        for lane in range(self.B):
            job = self.jobs[lane]
            if job is None or job.status != RUNNING:
                continue
            steps = int(job.steps_done)
            self.server._journal(
                "snapshot", job_id=job.job_id, batch_uid=self.uid,
                cap=self.B, K=self.K, kind=self.kind, lane=lane,
                step=int(self.step_h[lane]),
                left=int(self.left_h[lane]),
                steps_done=steps, time=float(job.time),
                rows=job.rows[:steps].copy(),
                carry=FB.lane_carry_host(self.carry, lane))

    def resume_lane(self, lane: int, job: FleetJob, snap: dict) -> None:
        """Upload one journaled/migrated lane checkpoint into ``lane``:
        the round-23 resume splice.  The batch was just built with the
        checkpoint's recorded (cap, K) and ``job`` occupies ``lane``
        from first assembly (RUNNING, zeroed rows); this re-enters the
        checkpointed carry through the same jitted per-lane upload as a
        reseed, restores the recorded rows, and points the guard's host
        mirrors at the resumed position."""
        solo = {k: np.asarray(v) for k, v in snap["carry"].items()}
        step, left = int(snap["step"]), int(snap["left"])
        self.carry = FB.reseed_lane_carry(
            self.carry, lane, solo, left, mesh=self.mesh)
        self.step_h[lane] = step
        self.left_h[lane] = left
        self.guard.resume(self.carry, lane, step, left)
        rows = snap.get("rows")
        if rows is not None and len(rows):
            rows = np.asarray(rows, np.float64)
            job.rows[:rows.shape[0]] = rows
        job.steps_done = int(snap.get("steps_done", step))
        job.time = float(snap.get("time", 0.0))
        M.counter("fleet.lane_resumes", kind=self.kind).inc()

    def release_for_migration(self, lane: int) -> dict:
        """Checkpoint one RUNNING lane off this batch for live
        migration (fleet/migrate.py): settle so the lane state is
        validated, host-serialize the carry + rows, then freeze the
        lane and retire its job MIGRATED.  Every other lane's bits are
        untouched (the same lane-wise selects as a cancel).  The
        returned payload is exactly a journal snapshot view, so the
        receiving server resumes it through ``resume_lane``."""
        self.settle()
        job = self.jobs[lane]
        if job is None or job.status != RUNNING:
            raise ValueError(f"lane {lane} holds no RUNNING job")
        steps = int(job.steps_done)
        ckpt = {
            "job_id": job.job_id, "tenant": job.tenant,
            "spec": dict(job.spec), "nsteps": int(job.nsteps),
            "kind": self.kind, "cap": self.B, "K": self.K,
            "step": int(self.step_h[lane]),
            "left": int(self.left_h[lane]),
            "steps_done": steps, "time": float(job.time),
            "rows": job.rows[:steps].copy(),
            "carry": FB.lane_carry_host(self.carry, lane),
        }
        self.carry = ISO.retire_lanes(
            self.carry, np.arange(self.B) == lane)
        self.left_h[lane] = 0
        self.guard.epochs[lane] += 1
        self.retire(lane, MIGRATED, "migrated")
        return ckpt

    def lane_state(self, lane: int) -> Dict[str, np.ndarray]:
        """Host copies of one lane's carry leaves (tests, summaries)."""
        return {k: np.asarray(v[lane]) for k, v in self.carry.items()}

    def running_lanes(self) -> int:
        return sum(
            1 for j in self.jobs if j is not None and j.status == RUNNING)


#: weakrefs of live servers, for the obs /health payload
_LIVE: List["weakref.ReferenceType[FleetServer]"] = []


def live_servers() -> List["FleetServer"]:
    out = []
    for ref in list(_LIVE):
        srv = ref()
        if srv is None:
            _LIVE.remove(ref)
        else:
            out.append(srv)
    return out


class FleetServer:
    """The multi-tenant front door: queue, assembly, dispatch, fan-out."""

    #: SLO error budget matching a p99 target: 1% of jobs may breach
    SLO_ERROR_BUDGET = 0.01

    def __init__(self, max_lanes: Optional[int] = None,
                 max_buckets: Optional[int] = None,
                 snap_every: Optional[int] = None,
                 max_retries: Optional[int] = None,
                 workdir: Optional[str] = None,
                 slo_p99_s: Optional[float] = None,
                 slo_window: Optional[int] = None,
                 continuous: Optional[bool] = None,
                 policy: Optional[str] = None,
                 max_queue_depth: Optional[int] = None,
                 tenant_quota: Optional[int] = None,
                 provenance: Optional[bool] = None,
                 journal: Optional[bool] = None,
                 mesh=_MESH_DEFAULT):
        # the chaos sites (server.crash, journal.write_fail, ...) are
        # armable from the environment in drill subprocesses
        # (tools/chaosdrill.py); the solo path loads CUP3D_FAULT at
        # RecoveryEngine.install, the fleet path loads it here
        faults.load_env()
        self.max_lanes = int(
            max_lanes if max_lanes is not None
            else _env_int("CUP3D_FLEET_LANES", 64))
        self.max_buckets = int(
            max_buckets if max_buckets is not None
            else _env_int("CUP3D_FLEET_BUCKETS", 8))
        snap_steps = (
            snap_every if snap_every is not None
            else _env_int("CUP3D_SNAP_EVERY", 16))
        self.snap_steps = max(1, int(snap_steps))
        self.max_retries = max_retries
        self.workdir = workdir or tempfile.mkdtemp(prefix="cup3d-fleet-")
        self._jobs: "OrderedDict[str, FleetJob]" = OrderedDict()
        self._execs: "OrderedDict[tuple, object]" = OrderedDict()
        self.batches: List[FleetBatch] = []
        self._next_job = 0
        self._next_batch = 0
        self.mesh = FB.fleet_mesh() if mesh is _MESH_DEFAULT else mesh
        # completion SLO: target p99 end-to-end seconds + rolling
        # per-tenant breach window (health()["slo"], fleet slo CLI)
        self.slo_p99_s = float(
            slo_p99_s if slo_p99_s is not None
            else _env_float("CUP3D_FLEET_SLO_P99", 60.0))
        self.slo_window = max(1, int(
            slo_window if slo_window is not None
            else _env_int("CUP3D_FLEET_SLO_WINDOW", 100)))
        self._slo_windows: Dict[str, deque] = {}
        # round 17 — continuous-batching knobs + scheduler state
        self.continuous = bool(
            continuous if continuous is not None
            else _env_int("CUP3D_FLEET_CONTINUOUS", 1))
        self.policy = str(
            policy if policy is not None
            else os.environ.get("CUP3D_FLEET_POLICY", "fifo"))
        if self.policy not in POLICIES:
            raise ValueError(
                f"unknown scheduler policy {self.policy!r} "
                f"(expected one of {POLICIES})")
        self.max_queue_depth = max(1, int(
            max_queue_depth if max_queue_depth is not None
            else _env_int("CUP3D_FLEET_QUEUE_DEPTH", 1024)))
        self.tenant_quota = int(
            tenant_quota if tenant_quota is not None
            else _env_int("CUP3D_FLEET_TENANT_QUOTA", 0))
        self.reseeds = 0
        self.last_occupancy: Optional[float] = None
        #: prepared-but-waiting queued jobs: job_id -> (kind, driver,
        #: sig, bucket key) — a job waiting for a compatible lane is
        #: not re-inited at every K-boundary
        self._prepared: Dict[str, tuple] = {}
        #: round 21 — background compile service (aot/compiler.py),
        #: created lazily iff the persistent store is active; with
        #: CUP3D_AOT_STORE unset the whole AOT path is inert
        self._aot_service = None
        # round 22 — latency provenance: per-job phase decomposition,
        # fleet.latency_phase_s histograms, flow events, and SLO burn
        # attribution.  CUP3D_FLEET_PROVENANCE=0 reverts _job_terminal
        # to the round-16 aggregate-only bookkeeping (the bench.py
        # _provenance_overhead gate measures exactly this delta).
        self.provenance = bool(
            provenance if provenance is not None
            else _env_int("CUP3D_FLEET_PROVENANCE", 1))
        #: per-tenant rolling history of per-job phase SHARES (phase
        #: seconds / e2e), newest last — the burn-attribution baseline
        self._phase_share_history: Dict[str, deque] = {}
        # round 23 — write-ahead durability.  CUP3D_FLEET_JOURNAL=0
        # keeps the serve loop bitwise-legacy: no journal instance, no
        # appends, no recovery — every _journal call is one None test.
        use_journal = bool(
            journal if journal is not None
            else _env_int("CUP3D_FLEET_JOURNAL", 1))
        self.journal = (
            JobJournal(os.path.join(self.workdir, "journal"))
            if use_journal else None)
        #: admission closed for drain_for_shutdown (fleet/migrate.py)
        self.draining = False
        #: the last recover() outcome (health()["durability"])
        self.last_recovery: Optional[dict] = None
        self.migrations = 0
        _LIVE.append(weakref.ref(self))

    # -- AOT store / background compile (round 21) -------------------------

    def _aot(self):
        """(store, service) when ``CUP3D_AOT_STORE`` is set, else
        (None, None): the whole zero-cold-start machinery keys off the
        active store."""
        from cup3d_tpu.aot import store as aot_store

        st = aot_store.active_store()
        if st is None:
            return None, None
        if self._aot_service is None:
            from cup3d_tpu.aot.compiler import CompileService

            self._aot_service = CompileService()
        return st, self._aot_service

    @staticmethod
    def _mesh_key(mesh):
        return tuple(mesh.shape.items()) if mesh is not None else None

    @staticmethod
    def _store_sig(sig: tuple, cap: int, K: int, mesh_key) -> tuple:
        """The cross-process store key for one fleet advance: the
        content-addressed static signature plus the shapes that enter
        the compiled executable (lane rung, scan K, mesh layout)."""
        return ("fleet.advance", sig, int(cap), int(K), mesh_key)

    def _bind_advance(self, s, ob, cap: int, K: int, kind, mesh,
                      sig: tuple, store):
        """Build the vmapped advance and, with a store active, wrap it
        store-backed: first use loads the serialized executable (zero
        compiles) or AOT-compiles and writes back."""
        fn = FB.build_fleet_advance(s, ob, mesh=mesh, kind=kind)
        if store is not None:
            from cup3d_tpu.aot import store as aot_store

            skey = self._store_sig(sig, cap, K, self._mesh_key(mesh))
            fn = aot_store.StoreBackedExecutable(
                fn, skey,
                name=f"fleet.advance-{aot_store.sig_label(skey)}",
                store=store)
        return fn

    def _background_key(self, sig: tuple, cap: int, K: int, mesh):
        return (sig, int(cap), int(K), self._mesh_key(mesh))

    def _batch_shape(self, members) -> Tuple[int, int, object]:
        """(cap, K, mesh) the assembly of ``members`` will use — must
        mirror _build_batches so background-compiled executables land
        on the exact LRU key assembly asks for."""
        cap = self.lane_capacity(len(members))
        K = resolve_scan_k(members[0][2].cfg)
        if K <= 1:
            K = DEFAULT_SCAN_K
        mesh = FB.resolve_fleet_mesh(cap, self.mesh)
        return cap, K, mesh

    def _maybe_background_compile(self, leftovers):
        """Split fresh-assembly groups into assemble-now vs wait-for-
        compile.  With the service active, a group whose executable is
        neither LRU-cached nor in the store is submitted as a
        background build and its jobs stay QUEUED (preps cached) —
        the dispatch thread keeps serving warm signatures meanwhile.
        Returns the groups to assemble on this pass."""
        st, svc = self._aot()
        if svc is None:
            return leftovers
        ready: "OrderedDict[tuple, list]" = OrderedDict()
        for key, members in leftovers.items():
            sig = key[0]
            kind, job, drv = members[0]
            cap, K, mesh = self._batch_shape(members)
            ekey = self._background_key(sig, cap, K, mesh)
            if ekey in self._execs:
                self._mark_compile_ready(members)
                ready[key] = members
                continue
            status = svc.status(ekey)
            if status == "done":
                fn = svc.take(ekey)
                if fn is not None:
                    self._execs[ekey] = fn
                    M.counter("aot.background_installs").inc()
                self._mark_compile_ready(members)
                ready[key] = members
                continue
            if status in ("pending", "running"):
                svc.attach(ekey, [job_m.job_id for _, job_m, _ in members])
                for kind_m, job_m, drv_m in members:
                    job_m.mark("compile_wait", collapse=True)
                    self._prepared[job_m.job_id] = (
                        kind_m, drv_m, sig, key)
                continue
            if status == "failed" or st.contains(
                    self._store_sig(sig, cap, K, self._mesh_key(mesh))):
                # failed background build -> synchronous fallback;
                # store present -> assembling now is a disk read
                self._mark_compile_ready(members)
                ready[key] = members
                continue
            self._submit_background(svc, st, sig, cap, K, kind, mesh,
                                    drv, job, members, ekey, key)
        return ready

    @staticmethod
    def _mark_compile_ready(members) -> None:
        """Close the compile_wait interval on every member that opened
        one: the group's executable is now installable, so from here the
        timeline is back in "assembly" (round-22 provenance).  Members
        that never waited (warm signature) are untouched."""
        for _kind, job_m, _drv in members:
            if job_m.event_time("compile_wait") is not None:
                job_m.mark("compile_ready", collapse=True)

    def _submit_background(self, svc, st, sig, cap, K, kind, mesh,
                           drv, job, members, ekey, bucket_key) -> None:
        """Queue one demand build (plus the speculative ±1 ladder
        rungs) and park the group's jobs as prepared-but-waiting."""
        from cup3d_tpu.aot import compiler as aot_compiler

        s = drv.sim
        ob = s.obstacles[0] if kind == "fish" else None
        carry, gait = _lane_payload(kind, drv, job.job_id)
        label = "fleet.advance-" + hashlib.blake2s(
            repr(sig).encode()).hexdigest()[:8]

        def demand_build(cap=cap, K=K, mesh=mesh):
            fn = self._bind_advance(s, ob, cap, K, kind, mesh, sig, st)
            avals = FB.abstract_advance_args(
                carry, gait, cap, K, s.dtype)
            warm = getattr(fn, "warm", None)
            if warm is not None:
                warm(*avals)
            return fn

        # the demand build is causally linked to the jobs that wait on
        # it (round 22): their ids ride the compile task into the pid-5
        # Perfetto span + flow events, and each job's timeline opens a
        # compile_wait interval here
        svc.submit(ekey, demand_build, name=label,
                   priority=aot_compiler.PRIORITY_DEMAND,
                   jobs=[job_m.job_id for _, job_m, _ in members])
        for kind_m, job_m, drv_m in members:
            job_m.mark("compile_wait", collapse=True)
            self._prepared[job_m.job_id] = (kind_m, drv_m, sig,
                                            bucket_key)
        if not aot_compiler.speculate_enabled():
            return
        for rung in self._neighbor_rungs(cap):
            rkey = self._background_key(sig, rung, K, mesh)
            if rkey in self._execs or svc.status(rkey) is not None:
                continue

            def spec_build(rung=rung, K=K, mesh=mesh):
                fn = self._bind_advance(s, ob, rung, K, kind, mesh,
                                        sig, st)
                avals = FB.abstract_advance_args(
                    carry, gait, rung, K, s.dtype)
                warm = getattr(fn, "warm", None)
                if warm is not None:
                    warm(*avals)
                return fn

            if svc.submit(rkey, spec_build, name=label,
                          priority=aot_compiler.PRIORITY_SPECULATIVE):
                M.counter("aot.speculative_compiles").inc()

    def _neighbor_rungs(self, cap: int) -> List[int]:
        """The ±1 rungs of the ×1.25 lane ladder around ``cap``
        (mesh-rounded, max-lanes-clamped, deduplicated)."""
        rungs = []
        down = None
        c = LANE_LADDER_BASE
        while c < cap:
            down = c
            c = max(c + 1, int(np.ceil(c * 1.25)))
        if down is not None:
            down = self.lane_capacity(down)
            if 0 < down != cap:
                rungs.append(down)
        up = self.lane_capacity(cap + 1)
        if cap < up <= self.max_lanes and up not in rungs:
            rungs.append(up)
        return rungs

    # -- tenant lifecycle --------------------------------------------------

    def submit(self, tenant: str, spec: dict) -> str:
        """Validate + enqueue one scenario; returns the job id.
        Admission control (round 17): a queue at its backpressure
        depth, or a tenant at its live-job quota, raises
        :class:`FleetAdmissionError` instead of enqueueing — both
        rejection counts and the backpressure flag surface in
        ``health()["admission"]``."""
        kind = str(spec.get("kind", "fish"))
        if kind not in ("fish", "tgv", "amr_tgv"):
            raise ValueError(f"unknown fleet scenario kind {kind!r}")
        if int(spec.get("nsteps", 0)) <= 0:
            raise ValueError("fleet scenario needs nsteps > 0")
        if self.draining:
            M.counter("fleet.admission_rejects", reason="draining").inc()
            raise FleetAdmissionError(
                "draining", "server is draining for shutdown")
        depth = self.queue_depth()
        if depth >= self.max_queue_depth:
            M.counter("fleet.admission_rejects", reason="queue-full").inc()
            raise FleetAdmissionError(
                "queue-full",
                f"queue depth {depth} at backpressure threshold "
                f"{self.max_queue_depth}")
        if self.tenant_quota > 0:
            live = sum(
                1 for j in self._jobs.values()
                if j.tenant == str(tenant)
                and j.status in (QUEUED, RUNNING))
            if live >= self.tenant_quota:
                M.counter("fleet.admission_rejects", reason="quota").inc()
                raise FleetAdmissionError(
                    "quota",
                    f"tenant {tenant!r} at live-job quota "
                    f"{self.tenant_quota}")
        job_id = f"job-{self._next_job:04d}"
        self._next_job += 1
        job = FleetJob(job_id=job_id, tenant=str(tenant), spec=dict(spec),
                       nsteps=int(spec["nsteps"]))
        job.mark("submitted")
        job.mark("queued")
        self._jobs[job_id] = job
        self._journal("submit", job_id=job_id, tenant=job.tenant,
                      spec=dict(spec), nsteps=job.nsteps)
        M.counter("fleet.submits").inc()
        return job_id

    def poll(self, job_id: str) -> dict:
        return self._jobs[job_id].summary()

    def cancel(self, job_id: str) -> bool:
        """Cancel a queued or running job; terminal jobs are left
        alone.  Returns True when the job state changed."""
        job = self._jobs[job_id]
        if job.status == QUEUED:
            job.status = CANCELLED
            job.mark("cancelled")
            self._prepared.pop(job_id, None)
            M.counter("fleet.lane_retires", reason="cancelled").inc()
            self._job_terminal(job)
            return True
        if job.status == RUNNING and job.batch is not None:
            job.batch.cancel_lane(job.lane)
            # cancel_lane retires through the batch's guarded retire()
            # — a lane already swapped or terminal in the batch is a
            # no-op there, so verify the state actually changed rather
            # than reporting success unconditionally
            return job.status == CANCELLED
        return False

    def drain(self) -> Dict[str, dict]:
        """Run everything queued to completion and return the per-
        tenant summary.  Continuous mode (the default) runs the work-
        conserving serve() loop with admission closed;
        ``CUP3D_FLEET_CONTINUOUS=0`` keeps the legacy generation-drain
        (assemble the queue once, run every batch to completion)
        bitwise-unchanged as the occupancy baseline."""
        if self.continuous:
            return self.serve()
        busy0, total0 = self._occupancy_totals()
        self.assemble()
        while True:
            live = [b for b in self.batches if b.active()]
            if not live:
                break
            for b in live:
                b.tick()
        for b in self.batches:
            b.settle()
        self._aot_quiesce()
        self._close_occupancy_window(busy0, total0)
        self.update_lane_gauge()
        return self.tenant_summary()

    def serve(self, feed=None) -> Dict[str, dict]:
        """The continuous-batching dispatch loop: one scheduling pass
        (reseed freed lanes, assemble what cannot wait) plus one round-
        robin tick per K-boundary.  ``feed(server, tick)``, when given,
        is called at each boundary and may ``submit()`` in-flight
        (admission control applies); it returns False to close
        admission.  The loop ends when admission is closed and every
        admitted job is terminal.  Returns the tenant summary."""
        busy0, total0 = self._occupancy_totals()
        admitting = feed is not None
        tick = 0
        while True:
            if admitting:
                # settle first so pending retirements are visible to
                # the feed's poll()-driven admission decisions; with no
                # feed there is nothing to decide and the dispatch
                # pipeline keeps its full in-flight overlap
                for b in self.batches:
                    if b.active():
                        b.settle()
                admitting = bool(feed(self, tick))
            self._schedule()
            live = [b for b in self.batches if b.active()]
            for b in live:
                b.tick()
            tick += 1
            queued = any(
                j.status == QUEUED for j in self._jobs.values())
            if (not live and queued and self._aot_service is not None
                    and self._aot_service.depth() > 0):
                # death-path (round 23): a dead compile worker can
                # never finish its orphaned builds — reap them FAILED
                # (aot.service_fallbacks) so the next scheduling pass
                # compiles inline, instead of parking forever below
                if self._aot_service.fail_orphans():
                    continue
                # every queued job waits on a background compile and
                # nothing is dispatchable: park on the service instead
                # of busy-spinning the scheduler
                self._aot_service.wait(0.05)
            if not admitting and not live and not queued:
                break
        for b in self.batches:
            b.settle()
        self._aot_quiesce()
        self._close_occupancy_window(busy0, total0)
        self.update_lane_gauge()
        return self.tenant_summary()

    def _aot_quiesce(self) -> None:
        """Let in-flight background builds finish before the serve/drain
        window closes: speculative executables land in the store (warm
        for the next boot), and the process never exits mid-XLA-compile
        (a daemon thread inside the compiler at interpreter teardown
        aborts the process)."""
        if self._aot_service is not None:
            self._aot_service.drain(timeout=600.0)

    def queue_depth(self) -> int:
        return sum(1 for j in self._jobs.values() if j.status == QUEUED)

    # -- durability (round 23) ---------------------------------------------

    def _journal(self, rtype: str, **fields) -> None:
        """Best-effort journal append (no-op with the journal off)."""
        if self.journal is not None:
            self.journal.append(rtype, **fields)

    def close_admission(self) -> None:
        """Stop accepting new jobs (drain-for-shutdown seam,
        fleet/migrate.py): submit() rejects with reason "draining"."""
        self.draining = True

    def _note_job_id(self, job_id: str) -> None:
        """Keep the job-id counter ahead of a replayed id so a
        recovered server never mints a colliding fresh id."""
        try:
            n = int(job_id.rsplit("-", 1)[-1])
        # jax-lint: allow(JX009, foreign-format replayed ids cannot
        # collide with the server's job-%04d mint, so there is nothing
        # to advance past; journal.orphan_records covers the taxonomy)
        except ValueError:
            return
        self._next_job = max(self._next_job, n + 1)

    def recover(self) -> dict:
        """Replay the write-ahead journal into this server (boot-time;
        idempotent — job ids already known are skipped, so replaying
        twice, or a journal extended by this server's own appends, is a
        no-op).  Terminal jobs are remembered with their recorded rows
        (QoI bytes intact, nothing re-runs); queued jobs re-enter the
        queue; RUNNING jobs with a snapshot resume mid-flight in a
        batch rebuilt at the recorded (cap, K) — same executable, same
        bytes; RUNNING jobs that never reached a snapshot restart from
        step 0, which recomputes the identical trajectory.  Returns
        ``{replayed, remembered, requeued, resumed}``."""
        stats = {"replayed": 0, "remembered": 0, "requeued": 0,
                 "resumed": 0}
        if self.journal is None:
            self.last_recovery = dict(stats)
            return self.last_recovery
        pending: List[Tuple[FleetJob, dict]] = []
        for job_id, view in self.journal.replay().items():
            if job_id in self._jobs:
                continue
            stats["replayed"] += 1
            job = FleetJob(
                job_id=job_id, tenant=str(view["tenant"]),
                spec=dict(view["spec"]), nsteps=int(view["nsteps"]))
            self._note_job_id(job_id)
            self._jobs[job_id] = job
            snap = self._install_replayed_job(job, view)
            if job.status in TERMINALS:
                stats["remembered"] += 1
            elif snap is not None:
                pending.append((job, snap))
                stats["resumed"] += 1
            else:
                stats["requeued"] += 1
        if pending:
            self._resume_batches(pending)
        self.update_lane_gauge()
        self.last_recovery = dict(stats)
        return self.last_recovery

    def _install_replayed_job(self, job: FleetJob,
                              view: dict) -> Optional[dict]:
        """Install one replayed journal view onto a fresh FleetJob.
        Returns the snapshot record to resume from (RUNNING jobs with a
        journaled snapshot), else None.  Terminal replays keep their
        recorded rows/steps and set the ``_terminal_done`` guard — the
        crashed server already folded them into its SLO bookkeeping, so
        this server only REMEMBERS them (poll/summaries/QoI bytes),
        it does not re-observe them."""
        status = view["status"]
        if status in TERMINALS:
            job.status = status
            job.error = view.get("error")
            job.steps_done = int(view.get("steps_done", 0))
            job.time = float(view.get("time", 0.0))
            rows = view.get("rows")
            if rows is not None:
                job.rows = np.asarray(rows, np.float64).copy()
            job.mark(status)
            job._terminal_done = True
            M.counter("fleet.recovered_jobs", outcome="remembered").inc()
            return None
        job.status = QUEUED
        job.mark("submitted")
        job.mark("queued")
        job.mark("recovered")
        snap = view.get("snapshot") if status == RUNNING else None
        M.counter("fleet.recovered_jobs",
                  outcome="resumed" if snap is not None
                  else "requeued").inc()
        return snap

    def _resume_batches(self, pending) -> int:
        """Rebuild one batch per crashed batch_uid at its RECORDED
        (cap, K) and splice every resumed job back in at its journaled
        position.  Forcing the recorded shape — rather than re-deriving
        the rung from the (smaller) survivor count — is what keeps
        recovery bitwise: the lane count enters the compiled
        executable, and only the crashed server's own executable
        reproduces the control bytes (with a warm AOT store it loads
        from disk, zero recompiles)."""
        groups: "OrderedDict[object, list]" = OrderedDict()
        for job, snap in pending:
            prep = self._prepare(job)
            if prep is None:
                continue
            kind, drv, _sig, _key = prep
            groups.setdefault(snap.get("batch_uid"), []).append(
                (kind, job, drv, snap))
        resumed = 0
        for members in groups.values():
            kind = members[0][0]
            snap0 = members[0][3]
            cap, K = int(snap0["cap"]), int(snap0["K"])
            jobs = [job for _, job, _, _ in members]
            drivers = [drv for _, _, drv, _ in members]
            b = FleetBatch(self, self._next_batch, kind, jobs,
                           drivers, K, cap)
            self._next_batch += 1
            self.batches.append(b)
            for lane, (_, job, _, snap) in enumerate(members):
                b.resume_lane(lane, job, snap)
                resumed += 1
        return resumed

    # -- assembly ----------------------------------------------------------

    def lane_capacity(self, njobs: int) -> int:
        """Lane-count ladder rung for a batch of ``njobs``, clamped to
        the max-lanes knob and rounded to the mesh multiple."""
        cap = min(
            count_capacity(njobs, base=LANE_LADDER_BASE), self.max_lanes)
        cap = max(cap, njobs)
        mult = FB.mesh_lane_multiple(self.mesh)
        if cap % mult:
            cap += mult - cap % mult
        return cap

    def _prepare(self, job: FleetJob) -> Optional[tuple]:
        """Build + init one queued job's lane driver and bucket key,
        consuming the prepared-job cache when the scheduler already did
        the work on an earlier pass.  Returns (kind, driver, sig,
        bucket_key), or None after failing an ineligible job."""
        prep = self._prepared.pop(job.job_id, None)
        if prep is not None:
            return prep
        kind, cfg = _job_config(job.spec, self.workdir)
        job.cfg = cfg
        if kind == "amr_tgv":
            from cup3d_tpu.sim.amr import AMRSimulation

            drv = _AMRLaneDriver(AMRSimulation(cfg))
        else:
            from cup3d_tpu.sim.simulation import Simulation

            drv = Simulation(cfg)
        drv.init()
        if not drv._megaloop_eligible():
            job.status = FAILED
            job.error = "scenario not scan-eligible"
            job.mark("failed")
            M.counter("fleet.lane_retires", reason="ineligible").inc()
            self._job_terminal(job)
            return None
        sig = _static_signature(drv, kind)
        key = (sig, count_capacity(job.nsteps, base=1))
        # deterministic bucket-signature label for the SLO
        # histograms (hash(), being per-process salted, would split
        # one bucket's series across restarts)
        job.sig_label = "{}-{}".format(
            kind,
            hashlib.blake2s(repr(key).encode()).hexdigest()[:8])
        job.mark("bucketed")
        return kind, drv, sig, key

    def _build_batches(self, buckets) -> List[FleetBatch]:
        """Bucketed (kind, job, driver) groups -> FleetBatches: each
        bucket splits into chunks of <= max_lanes and pads up the lane
        ladder."""
        built = []
        for (sig, _rung), members in buckets.items():
            for i in range(0, len(members), self.max_lanes):
                chunk = members[i:i + self.max_lanes]
                kind = chunk[0][0]
                jobs = [job for _, job, _ in chunk]
                drivers = [drv for _, _, drv in chunk]
                K = resolve_scan_k(drivers[0].cfg)
                if K <= 1:
                    K = DEFAULT_SCAN_K
                b = FleetBatch(self, self._next_batch, kind, jobs,
                               drivers, K, self.lane_capacity(len(jobs)))
                self._next_batch += 1
                self.batches.append(b)
                built.append(b)
        return built

    def assemble(self) -> List[FleetBatch]:
        """Queued jobs -> bucketed batches.  Buckets key on the static
        signature plus the ×1.25 step-budget rung; each bucket splits
        into chunks of <= max_lanes and pads up the lane ladder."""
        queued = [j for j in self._jobs.values() if j.status == QUEUED]
        if not queued:
            return []
        buckets: "OrderedDict[tuple, list]" = OrderedDict()
        for job in queued:
            prep = self._prepare(job)
            if prep is None:
                continue
            kind, drv, _sig, key = prep
            buckets.setdefault(key, []).append((kind, job, drv))
        built = self._build_batches(buckets)
        self.update_lane_gauge()
        return built

    def _schedule(self) -> int:
        """One K-boundary scheduling pass (continuous batching): settle
        the live batches so pending retirements are visible, reseed
        freed lanes with compatible queued jobs (same static signature
        -> the cached executable is reused with zero recompiles), and
        assemble fresh batches only for jobs with no compatible live
        batch to wait on.  Returns the number of lanes reseeded."""
        queued = [j for j in self._jobs.values() if j.status == QUEUED]
        if not queued:
            return 0
        for b in self.batches:
            if b.active():
                b.settle()
        if self.policy == "srb":
            # shortest-remaining-budget: stable sort, FIFO within ties
            queued.sort(key=lambda j: j.nsteps)
        reseeded = 0
        leftovers: "OrderedDict[tuple, list]" = OrderedDict()
        waiting: "OrderedDict[tuple, list]" = OrderedDict()
        for job in queued:
            prep = self._prepare(job)
            if prep is None:
                continue
            kind, drv, sig, key = prep
            placed = blocked = False
            for b in self.batches:
                # only LIVE batches are reseed targets: once a batch
                # fully drains, fresh assembly (which serves the same
                # executable from the LRU cache) is just as work-
                # conserving and keeps the generation semantics of an
                # idle server unchanged
                if b.kind != kind or b.sig != sig or not b.active():
                    continue
                free = b.free_lanes()
                if free:
                    b.reseed_lane(free[0], job, drv)
                    self.reseeds += 1
                    reseeded += 1
                    placed = True
                    break
                blocked = True
            if placed:
                continue
            if blocked:
                # a live compatible batch will free a lane at a coming
                # K-boundary; waiting beats padding out a fresh batch.
                # The wait is a distinct provenance phase (reseed_wait):
                # neither capacity (lanes exist) nor compile (executable
                # is warm) — collapse keeps one event per parked stretch
                job.mark("reseed_wait", collapse=True)
                self._prepared[job.job_id] = prep
                waiting.setdefault(key, []).append((kind, job, drv))
                continue
            leftovers.setdefault(key, []).append((kind, job, drv))
        for key, members in waiting.items():
            # enough blocked same-rung jobs to FILL a batch beats
            # waiting: zero padding lanes, so assembling now is a
            # strict occupancy win over a reseed slot later
            if (len(members) > 1
                    and self.lane_capacity(len(members)) == len(members)):
                for _, job, _ in members:
                    self._prepared.pop(job.job_id, None)
                leftovers.setdefault(key, []).extend(members)
        if leftovers:
            # round 21: cold signatures may compile off-thread — the
            # service keeps their jobs queued and this pass assembles
            # only what is warm (LRU, store, or finished build)
            leftovers = self._maybe_background_compile(leftovers)
        if leftovers:
            self._build_batches(leftovers)
        if reseeded or leftovers:
            self.update_lane_gauge()
        return reseeded

    def executable(self, sig: tuple, s, ob, cap: int, K: int,
                   kind: Optional[str] = None, mesh=None):
        """The compiled-advance cache, LRU-capped by the buckets knob:
        one vmapped executable per (signature, lane rung, K, mesh).
        Round 21: with ``CUP3D_AOT_STORE`` set a miss first consults
        the background compile service, then binds a store-backed
        executable — a previously-seen signature loads its serialized
        XLA executable instead of compiling (zero-cold-start boot)."""
        key = (sig, int(cap), int(K), self._mesh_key(mesh))
        hit = self._execs.pop(key, None)
        if hit is not None:
            self._execs[key] = hit
            M.counter("fleet.executable_hits").inc()
            return hit
        st, svc = self._aot()
        fn = svc.take(key) if svc is not None else None
        if fn is not None:
            M.counter("aot.background_installs").inc()
        else:
            fn = self._bind_advance(s, ob, cap, K, kind, mesh, sig, st)
        self._execs[key] = fn
        M.counter("fleet.executable_builds").inc()
        while len(self._execs) > self.max_buckets:
            self._execs.popitem(last=False)
            M.counter("fleet.executable_evictions").inc()
        return fn

    # -- observability -----------------------------------------------------

    def _occupancy_totals(self) -> Tuple[int, int]:
        return (sum(b.busy_steps for b in self.batches),
                sum(b.total_steps for b in self.batches))

    def _close_occupancy_window(self, busy0: int,
                                total0: int) -> Optional[float]:
        """Fold one drain/serve window into the ``fleet.lane_occupancy``
        gauge: busy-lane-steps / total-lane-steps over the window's
        dispatches.  Frozen and padding lanes count against the
        denominator — that is exactly the waste continuous batching
        reclaims, so the gauge is the bench gate's metric
        (bench.py fleet_skew, gates.fleet_occupancy)."""
        busy, total = self._occupancy_totals()
        dbusy, dtotal = busy - busy0, total - total0
        if dtotal <= 0:
            return None
        occ = dbusy / dtotal
        self.last_occupancy = occ
        M.gauge("fleet.lane_occupancy").set(occ)
        return occ

    def _job_terminal(self, job: FleetJob, batch: Optional[FleetBatch]
                      = None, lane: Optional[int] = None) -> None:
        """One job reached done/failed/cancelled: fold its timeline into
        the SLO histograms + breach window, notify the flight recorders,
        and (tracing on) emit the kind="job" aux record and the pid-3
        lane-occupancy span.  Called exactly once per job — every
        terminal transition funnels through here, and the
        ``_terminal_done`` guard (round 23) makes a second arrival — a
        cancel racing a migration, or a replayed-from-journal terminal
        — a counted no-op instead of a double SLO fold."""
        if job._terminal_done:
            M.counter("fleet.duplicate_terminals").inc()
            return
        job._terminal_done = True
        self._journal(
            "terminal", job_id=job.job_id, status=job.status,
            error=job.error, steps_done=int(job.steps_done),
            time=float(job.time), nsteps=int(job.nsteps),
            rows=None if job.rows is None else job.rows.copy())
        durs = job.durations()
        bucket = job.sig_label or "unbucketed"
        if "queue_wait_s" in durs:
            M.histogram("fleet.job_queue_wait_s", tenant=job.tenant,
                        bucket=bucket).observe(durs["queue_wait_s"])
        if "exec_s" in durs:
            M.histogram("fleet.job_exec_s", tenant=job.tenant,
                        bucket=bucket).observe(durs["exec_s"])
        # round 22 — latency provenance: the exact phase decomposition
        # (sums to e2e by construction) feeds the federation-mergeable
        # per-phase histograms and the burn-attribution share history.
        # CUP3D_FLEET_PROVENANCE=0 skips all of it (overhead gate).
        phases = job.phases() if self.provenance else None
        if phases:
            for ph, v in phases.items():
                M.histogram("fleet.latency_phase_s", phase=ph,
                            tenant=job.tenant).observe(v)
            total = sum(phases.values())
            if total > 0:
                self._phase_share_history.setdefault(
                    job.tenant, deque(maxlen=64)).append(
                        {ph: v / total for ph, v in phases.items()})
        e2e = durs.get("e2e_s")
        if e2e is not None:
            M.histogram("fleet.job_e2e_s", tenant=job.tenant,
                        bucket=bucket).observe(e2e)
            # shard-labeled companion (round 18): which mesh slice the
            # job finished on — a separate family so the existing
            # tenant/bucket label sets (and their quantile merges) are
            # untouched by sharding
            if batch is not None and lane is not None \
                    and batch.nshards() > 1:
                M.histogram(
                    "fleet.shard_job_e2e_s",
                    shard=str(batch.lane_shard(lane))).observe(e2e)
            wnd = self._slo_windows.setdefault(
                job.tenant, deque(maxlen=self.slo_window))
            breached = e2e > self.slo_p99_s
            wnd.append(bool(breached))
            if breached:
                M.counter("fleet.slo_breaches", tenant=job.tenant).inc()
        for fr in _flight.live_recorders():
            fr.note_job({"job_id": job.job_id, "tenant": job.tenant,
                         "status": job.status,
                         "steps_done": int(job.steps_done),
                         **{k: round(v, 6) for k, v in durs.items()}})
        sink = OT.TRACE
        if not sink.enabled:
            return
        rec = OT.job_record(
            job.job_id, job.tenant, job.status, job.steps_done,
            job.events, bucket=bucket,
            durations={k: round(v, 6) for k, v in durs.items()})
        if phases:
            # unrounded: trace_check asserts the partition invariant to
            # float eps against the event-timeline span
            rec["phases"] = phases
        if batch is not None and lane is not None:
            rec["batch"] = int(batch.batch_id)
            rec["lane"] = int(lane)
        sink.aux(rec)
        t_run = job.event_time("running")
        if batch is not None and lane is not None and t_run is not None:
            tid = FB.lane_track_id(batch.batch_id, lane)
            t_end = job.events[-1][1]
            sink.lane_span(
                tid, job.job_id, t_run, t_end - t_run,
                args={"job_id": job.job_id, "tenant": job.tenant,
                      "status": job.status, "bucket": bucket,
                      "steps_done": int(job.steps_done)})
            for name, t in job.events:
                if name == "rollback":
                    sink.lane_instant(tid, "rollback", t,
                                      args={"job_id": job.job_id})
            if (self.provenance
                    and job.event_time("compile_wait") is not None):
                # terminate the flow arrow the compile service opened:
                # the arrow lands inside this job's lane-occupancy span,
                # tying cold-start wait to its build in the trace UI
                sink.flow_finish(job.job_id, "compile->lane", t_run,
                                 OT.LANE_PID, tid)

    def latency_quantiles(self, name: str = "fleet.job_e2e_s",
                          tenant: Optional[str] = None,
                          qs: Tuple[float, ...] = (0.5, 0.95, 0.99)
                          ) -> Dict[str, Optional[float]]:
        """Aggregate quantiles over one job-latency histogram family
        (optionally one tenant's slice), merging bucket counts across
        label sets — the PromQL ``histogram_quantile(sum by (le))``
        computed in-process.  Values are None until a first job lands.
        Note the registry is process-global: the family aggregates over
        every server in the process, exactly like a scrape would."""
        hists = [h for h in M.histograms(name)
                 if tenant is None or h.labels.get("tenant") == tenant]
        return {f"p{int(round(q * 100))}": M.merged_quantile(hists, q)
                for q in qs}

    def phase_quantiles(self, tenant: Optional[str] = None,
                        qs: Tuple[float, ...] = (0.5, 0.99)
                        ) -> Dict[str, Dict[str, Optional[float]]]:
        """Per-phase latency quantiles over the round-22
        ``fleet.latency_phase_s`` family (optionally one tenant's
        slice), bucket counts merged across label sets exactly like
        :meth:`latency_quantiles`.  Only phases that observed at least
        one job appear."""
        out: Dict[str, Dict[str, Optional[float]]] = {}
        fam = M.histograms("fleet.latency_phase_s")
        for ph in OT.JOB_PHASES:
            hists = [h for h in fam
                     if h.labels.get("phase") == ph
                     and (tenant is None
                          or h.labels.get("tenant") == tenant)]
            if hists:
                out[ph] = {
                    f"p{int(round(q * 100))}": M.merged_quantile(
                        hists, q)
                    for q in qs}
        return out

    def phase_attribution(self, tenant: str) -> Optional[dict]:
        """SLO burn attribution for one tenant: which phase dominates
        the current latency window, and which phase's SHARE of
        end-to-end grew against the rolling baseline (the
        obs/history.py median machinery).  Shares are per-job
        phase-seconds / e2e, so they are scale-free: a fleet that got
        uniformly slower shows zero deltas, while a compile storm shows
        compile_wait's share growing.  None until a first job retires
        (or with provenance off)."""
        from cup3d_tpu.obs import history as obs_history

        shares = self._phase_share_history.get(tenant)
        if not shares:
            return None
        recent = list(shares)[-8:]
        quantiles = self.phase_quantiles(tenant=tenant, qs=(0.99,))
        phases: Dict[str, dict] = {}
        dominant = grew = None
        dom_share = grew_delta = 0.0
        for ph in OT.JOB_PHASES:
            series = [s.get(ph, 0.0) for s in shares]
            share = sum(s.get(ph, 0.0) for s in recent) / len(recent)
            base = obs_history.rolling_baseline(series, window=32)
            delta = share - base
            phases[ph] = {
                "p99_s": quantiles.get(ph, {}).get("p99"),
                "share": round(share, 4),
                "baseline_share": round(base, 4),
                "delta": round(delta, 4),
            }
            if dominant is None or share > dom_share:
                dominant, dom_share = ph, share
            if grew is None or delta > grew_delta:
                grew, grew_delta = ph, delta
        return {"dominant_phase": dominant, "grew_phase": grew,
                "phases": phases}

    def slo_status(self) -> dict:
        """The per-tenant SLO view (health()["slo"], fleet slo CLI):
        target, rolling-window breach fraction, and the burn rate —
        breach fraction over the 1% error budget a p99 target implies
        (burn 1.0 = exactly on budget, >1 = burning ahead of it)."""
        tenants = {}
        for tenant, wnd in sorted(self._slo_windows.items()):
            n = len(wnd)
            b = int(sum(wnd))
            frac = (b / n) if n else 0.0
            tenants[tenant] = {
                "jobs": n,
                "breaches": b,
                "breach_fraction": round(frac, 4),
                "burn_rate": round(frac / self.SLO_ERROR_BUDGET, 2),
                "quantiles": self.latency_quantiles(tenant=tenant),
            }
            if frac > self.SLO_ERROR_BUDGET and self.provenance:
                # the budget is burning ahead of plan: attach the
                # round-22 phase attribution so /health names the
                # phase to remediate (capacity vs compile vs reseed)
                tenants[tenant]["attribution"] = \
                    self.phase_attribution(tenant)
        return {
            "target_p99_s": self.slo_p99_s,
            "window": self.slo_window,
            "error_budget": self.SLO_ERROR_BUDGET,
            "tenants": tenants,
        }

    def shard_loss(self, shard: int) -> List[str]:
        """Per-slice elastic recovery entry point: drop mesh slice
        ``shard`` of every live sharded batch.  The lost lanes' RUNNING
        jobs go back to the queue (from step 0) and land on surviving
        shards at the next K-boundary; every surviving lane's carry
        bits are untouched (resilience/elastic.py).  Returns the
        requeued job ids."""
        requeued: List[str] = []
        for b in self.batches:
            if b.nshards() > 1 and shard < b.nshards():
                requeued.extend(b.fail_shard(shard))
        # the jobs are back in the implicit queue (status == QUEUED in
        # self._jobs); the next _schedule() pass reseeds them onto
        # surviving-shard lanes
        return requeued

    def update_lane_gauge(self) -> None:
        M.gauge("fleet.lanes_active").set(
            float(sum(b.running_lanes() for b in self.batches)))

    def jobs_by_status(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for job in self._jobs.values():
            out[job.status] = out.get(job.status, 0) + 1
        return out

    def tenant_summary(self) -> Dict[str, dict]:
        out: Dict[str, dict] = {}
        for job in self._jobs.values():
            t = out.setdefault(
                job.tenant, {"jobs": [], "steps_done": 0, "statuses": {}})
            t["jobs"].append(job.summary())
            t["steps_done"] += int(job.steps_done)
            st = t["statuses"]
            st[job.status] = st.get(job.status, 0) + 1
        return out

    def lane_state(self, job_id: str) -> Dict[str, np.ndarray]:
        job = self._jobs[job_id]
        if job.batch is None:
            raise ValueError(f"{job_id} was never assembled into a batch")
        return job.batch.lane_state(job.lane)

    def _aot_health(self) -> Optional[dict]:
        """Store + compile-service state, or None when inert."""
        from cup3d_tpu.aot import store as aot_store

        st = aot_store.active_store()
        if st is None and self._aot_service is None:
            return None
        return {
            "store": st.state() if st is not None else None,
            "service": (self._aot_service.state()
                        if self._aot_service is not None else None),
        }

    def health(self) -> dict:
        """Fleet state for the obs /health endpoint."""
        depth = self.queue_depth()
        return {
            "jobs": self.jobs_by_status(),
            "lanes_active": int(
                sum(b.running_lanes() for b in self.batches)),
            "batches": len(self.batches),
            "dispatches": int(sum(b.dispatches for b in self.batches)),
            "rollbacks": int(sum(b.guard.rollbacks for b in self.batches)),
            "executables": len(self._execs),
            "aot": self._aot_health(),
            "slo": self.slo_status(),
            "admission": {
                "queue_depth": depth,
                "max_queue_depth": self.max_queue_depth,
                "backpressure": depth >= self.max_queue_depth,
                "tenant_quota": self.tenant_quota,
            },
            "scheduler": {
                "continuous": self.continuous,
                "policy": self.policy,
                "reseeds": int(self.reseeds),
                "lane_occupancy": self.last_occupancy,
            },
            "mesh": {
                **topo.mesh_state(
                    self.mesh,
                    fallbacks=int(
                        M.counter("fleet.mesh_fallbacks").value)),
                "dead_lanes": sorted(
                    int(lane) for b in self.batches
                    for lane in b.dead_lanes),
                "shard_losses": int(
                    M.counter("fleet.shard_losses").value),
            },
            "durability": {
                "journal": (None if self.journal is None
                            else self.journal.state()),
                "draining": bool(self.draining),
                "recovered": self.last_recovery,
                "migrations": int(self.migrations),
            },
            "knobs": {
                "max_lanes": self.max_lanes,
                "max_buckets": self.max_buckets,
                "snap_steps": self.snap_steps,
                "mesh": (int(self.mesh.devices.size)
                         if self.mesh is not None else 0),
            },
        }


def summary_json(summary: Dict[str, dict]) -> str:
    return json.dumps(summary, indent=2, sort_keys=True)
