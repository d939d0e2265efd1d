"""Simulation driver: init + timestep loop (reference Simulation,
main.cpp:15161-15326).

``simulate()`` = loop { calcMaxTimestep; advance }, with the reference's
CFL advective/diffusive dt policy, 100-step logarithmic ramp-up, runaway-
velocity abort, and heartbeat print (main.cpp:15247-15305).
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional

import jax
import numpy as np

from cup3d_tpu.analysis.runtime import (
    blocking_read,
    device_scalar,
    sanctioned_transfer,
)
from cup3d_tpu.config import SimulationConfig, parse_factory
from cup3d_tpu.obs import trace as obs_trace
from cup3d_tpu.obs.flight import FlightRecorder
from cup3d_tpu.ops import diagnostics as diag
from cup3d_tpu.resilience import faults
from cup3d_tpu.resilience.recovery import SimulationFailure
from cup3d_tpu.sim import operators as ops
from cup3d_tpu.sim.data import SimulationData


class Simulation:
    def __init__(self, cfg: SimulationConfig):
        self.cfg = cfg
        self.sim = SimulationData(cfg)
        self.pipeline: List[ops.Operator] = []
        self._max_u = jax.jit(diag.max_velocity)
        # max|u| fetched in the previous step's packed read (fast path):
        # saves the blocking read at the top of calc_max_timestep
        self._umax_next: float | None = None
        # pipelined mode: grouped deferred reads through the async host
        # data-plane (stream/qoi.py) — K packs concatenate on device into
        # ONE async fetch (a blocking device->host read stalls the
        # dispatch queue, so reads are grouped and taken off the step);
        # non-pipelined runs consume each pack at the end of its own step.
        # The pack policy slims 256^3-class configs to scalars-only.
        from cup3d_tpu.stream.qoi import PackPolicy, QoIStream

        ncells = int(np.prod(self.sim.grid.shape))
        self._pack_reader = QoIStream(
            self._consume_pack, policy=PackPolicy.for_cells(ncells),
            profiler=self.sim.profiler,
        )
        # off-critical-path output (stream/dump.py, stream/checkpoint.py)
        from cup3d_tpu.stream.checkpoint import AsyncCheckpointer
        from cup3d_tpu.stream.dump import AsyncDumper

        self._dumper = AsyncDumper()
        self._checkpointer = AsyncCheckpointer()
        # round-9 observability (cup3d_tpu/obs/): the flight recorder's
        # ring runs ALWAYS (O(1) host appends — postmortems need history
        # from before the failure); step traces only under CUP3D_TRACE=1.
        # Solver iteration counts ride the packed QoI read (see
        # PressureProjection), never a dedicated sync.
        obs_trace.TRACE.default_directory(cfg.path4serialization)
        self.flight = FlightRecorder(
            directory=cfg.path4serialization, run_config=cfg,
            state_probe=self._flight_state,
        )
        self._obs = obs_trace.StepObserver(
            self.sim.profiler, flight=self.flight,
            stream=self._pack_reader, kind="uniform",
        )
        # round-13 observability v2 (obs/profile.py, obs/export.py):
        # device-time capture windows at loop/K boundaries under
        # CUP3D_PROFILE=every:N, and the env-gated /metrics//health
        # exporter (CUP3D_METRICS_PORT) — both no-ops when disarmed,
        # neither ever touches a device value on the step loop.
        from cup3d_tpu.obs import export as obs_export
        from cup3d_tpu.obs import profile as obs_profile

        obs_profile.CONTROLLER.default_directory(cfg.path4serialization)
        self._obs_profile = obs_profile.CONTROLLER
        obs_export.ensure_exporter()
        self._last_umax: Optional[float] = None
        # round-10 resilience: simulate() installs a RecoveryEngine here
        # (CUP3D_RECOVER=1, the default); None = legacy crash-on-fault
        self._resilience = None
        # round-11 scan megaloop (sim/megaloop.py): K whole steps per
        # jitted lax.scan dispatch.  _scan_k resolves at init() (0 =
        # off, the seed per-step loop); the compiled loop and its
        # device carry build lazily on first eligible iteration.
        self._scan_k = 0
        self._megaloop = None  # (jitted scan fn, row width) once built
        self._scan_carry = None  # device carry dict between megaloops
        self._scan_mesh = None  # round-18 x-slab mesh when sharded

    # -- setup (reference init(), main.cpp:15163-15178) --------------------

    def init(self) -> None:
        self._setup_operators()
        self._add_obstacles()
        if self.cfg.pipelined:
            if len(self.sim.obstacles) > 1:
                raise ValueError(
                    "pipelined mode requires a single obstacle (the device "
                    "rigid chain has no multi-body collision path) — run "
                    "without -pipelined"
                )
            for ob in self.sim.obstacles:
                # stale-PID: position/depth controllers read host mirrors
                # that lag ~2x the grouped-read cadence; they are gentle,
                # clipped controllers and tolerate the lag (tested in
                # tests/test_amr_pipelined.py).  Roll correction instead
                # MUTATES angVel right after the 6x6 solve on host and
                # cannot ride the device rigid chain.
                if getattr(ob, "bCorrectRoll", False):
                    raise ValueError(
                        "pipelined mode cannot run roll-corrected "
                        "obstacles (host-side angVel mutation) — run "
                        "without -pipelined"
                    )
        ops.initial_conditions(self.sim)
        from cup3d_tpu.sim.megaloop import resolve_scan_k

        k = resolve_scan_k(self.cfg)
        self._scan_k = k if (k >= 1 and self._megaloop_eligible()) else 0

    def _megaloop_eligible(self) -> bool:
        """Static gate for the K-step scan megaloop (config + obstacle
        shape); the dynamic parts — gait freezability, the step budget
        tail, a recovery retreat in progress — are re-checked each
        iteration by :meth:`_scan_ready`."""
        cfg, s = self.cfg, self.sim
        if not cfg.pipelined or cfg.dt > 0 or cfg.implicitDiffusion:
            return False
        if cfg.tend > 0 or cfg.nsteps <= 0:
            # done-by-time needs a fresh s.time every step; inside the
            # scan the host time mirror lags by up to the stream window
            return False
        if cfg.freqDiagnostics:
            return False  # the diagnostics operators are per-step
        if not s.obstacles:
            return True  # forced or not: make_tgv_step forces in the scan
        if ops.forced(cfg):
            return False  # the single-body scan has no forcing stage
        if len(s.obstacles) != 1:
            return False
        # the body makes its shape inside the scan: a steady StefanFish
        # (the frozen-gait device midline) or a Sphere; a Naca runs per
        # step
        return s.obstacles[0].offers_scan_stage()

    def _scan_ready(self) -> bool:
        """True when the next simulate iteration should run as one
        K-step megaloop: scan enabled, the compiled loop buildable
        (the body's gait freezable), a full K inside the step budget, and no
        recovery retreat in progress (the per-step path owns the
        halved-dt re-advance; the scan resumes once the engine retires
        the attempt)."""
        K = self._scan_k
        if K < 1:
            return False
        s = self.sim
        if s.step + K > self.cfg.nsteps:
            return False  # per-step tail keeps nsteps exact
        if (self._resilience is not None
                and self._resilience.dt_scale != 1.0):
            return False
        if self._megaloop is None:
            from cup3d_tpu.parallel import topology as topo
            from cup3d_tpu.sim import megaloop as ml

            # CUP3D_MESH_X asks for the x-slab sharded scan body
            # (round 18).  A mesh that cannot be had — too few devices,
            # a solver or an nx that cannot slab — raises in
            # megaloop_mesh / the sharded builders; there is no solo
            # stand-in for a run that asked to be sharded
            mesh = topo.megaloop_mesh()
            if s.obstacles:
                ob = s.obstacles[0]
                fn = (ml.build_body_megaloop(s, ob) if mesh is None
                      else ml.build_fish_megaloop_sharded(s, ob, mesh))
                row_w = ml.FISH_ROW
            else:
                fn = (ml.build_tgv_megaloop(s) if mesh is None
                      else ml.build_tgv_megaloop_sharded(s, mesh))
                row_w = ml.tgv_row_width(self.cfg)
            if fn is None:
                # gait not freezable after all: scan off for the run
                self._scan_k = 0
                return False
            self._scan_mesh = mesh
            self._megaloop = (fn, row_w)
        return True

    def _setup_operators(self) -> None:
        """Pipeline order is the reference's (main.cpp:15229-15246)."""
        s = self.sim
        cfg = self.cfg
        with_bodies = bool(s.obstacles or cfg.factory_content)
        if with_bodies:
            from cup3d_tpu.models import pipeline as body_ops

        pipe: List[ops.Operator] = []
        if with_bodies:
            pipe.append(body_ops.CreateObstacles(s))
        if cfg.implicitDiffusion:
            pipe.append(ops.AdvectionDiffusionImplicit(s))
        else:
            pipe.append(ops.AdvectionDiffusion(s))
        if cfg.uMax_forced > 0 and not cfg.bFixMassFlux:
            pipe.append(ops.ExternalForcing(s))
        if cfg.bFixMassFlux:
            pipe.append(ops.FixMassFlux(s))
        if with_bodies:
            pipe.append(body_ops.UpdateObstacles(s))
            pipe.append(body_ops.Penalization(s))
        pipe.append(ops.PressureProjection(s))
        if with_bodies:
            pipe.append(body_ops.ComputeForces(s))
        pipe.append(ops.ComputeDissipation(s))
        pipe.append(ops.ComputeDivergence(s))
        self.pipeline = pipe

    def _add_obstacles(self) -> None:
        content = self.cfg.resolved_factory_content()
        if not content:
            return
        from cup3d_tpu.models.factory import make_obstacles

        self.sim.obstacles = make_obstacles(self.sim, parse_factory(content))

    # -- observability -----------------------------------------------------

    def _flight_state(self) -> dict:
        """Driver state for a flight-recorder postmortem (called only at
        dump time, so the host reads here are free to be thorough)."""
        s = self.sim
        return {
            "driver": "uniform",
            "shape": list(s.grid.shape),
            "step": s.step,
            "time": s.time,
            "dt": s.dt,
            "uinf": [float(v) for v in s.uinf],
            "obstacles": [type(ob).__name__ for ob in s.obstacles],
            "stream": self._pack_reader.snapshot(),
            # round 10: the async writers' health rides in postmortems
            # (latched background failures, drop counts)
            "checkpointer": self._checkpointer.health(),
            "dumper": self._dumper.health(),
        }

    # -- time stepping -----------------------------------------------------

    def calc_max_timestep(self) -> float:
        """CFL dt with diffusive cap and log ramp-up (main.cpp:15254-15305)."""
        s, cfg = self.sim, self.cfg
        h = s.grid.h
        if faults.fire("step.nan_velocity", s.step):
            # injected fault (resilience/faults.py): poison the max|u|
            # mirror so the EXISTING NaN-umax abort below detects it
            self._umax_next = float("nan")
        if self._umax_next is not None:
            umax = self._umax_next
            if not self.cfg.pipelined:
                self._umax_next = None
            # pipelined: keep the latest consumed max|u| — staleness is
            # bounded by ~2x the grouped-read cadence (sim/pack.py) — and
            # FLOOR it with the fresh host-side body speed: a gait
            # spin-up outruns the stale mirror while dt sits at the
            # diffusive cap (measured blow-up at 256^3; see
            # Obstacle.max_body_speed)
            if self.cfg.pipelined and s.obstacles:
                umax = max(
                    umax,
                    max(ob.max_body_speed(s.uinf) for ob in s.obstacles),
                )
        else:
            # the designed once-per-step dt sync of the non-pipelined
            # path (the ONLY device->host read its steady-state step pays)
            # (dispatched in front of the read, which then only waits)
            maxima = (self._max_u(s.state["vel"], s.uinf_device()),)
            if s.obstacles:
                # the CFL scale must see the BODY kinematics
                # immediately: at full gait amplitude the tail's
                # deformation velocity reaches the advective limit one
                # step before it imprints on the measured fluid field
                # (blow-up observed at the diffusive-cap dt otherwise)
                import jax.numpy as _jnp

                maxima += (_jnp.max(_jnp.abs(s.state["udef"])),)
            umax = max(float(m) for m in blocking_read("umax-read", maxima))
        self._last_umax = umax  # host float already (both branches)
        if not np.isfinite(umax) or umax > cfg.uMax_allowed:
            # NaN must trip the abort too (`NaN > x` is False; code-review r4)
            s.logger.flush()
            # postmortem BEFORE the raise: ring contents, residual
            # history, last-known-good step (obs/flight.py)
            reason = ("nan-velocity" if not np.isfinite(umax)
                      else "runaway-velocity")
            extra = {"step": s.step, "umax": umax}
            self.flight.trigger(reason, extra=extra)
            raise SimulationFailure(
                reason,
                f"runaway velocity: max|u|={umax:.3g} > uMax_allowed={cfg.uMax_allowed}",
                extra,
            )
        if cfg.dt > 0:
            s.dt = cfg.dt
        else:
            from cup3d_tpu.sim import dtpolicy

            prev_dt = s.dt
            if cfg.pipelined:
                # max|u| may be (1 + max_inflight) * read_every ~ 12 steps
                # stale with the round-4 non-blocking reader (sim/pack.py):
                # assume it can have grown 1.5x since measured (the dt
                # growth bound below limits it to 1.03^12 ~ 1.43) so the
                # EFFECTIVE CFL never exceeds the configured value — a
                # sharp-chi fish at full gait measurably blows up without
                # this margin while the fresh-umax host path is stable
                umax = 1.5 * umax
            # reference dt = min(combined diffusion cap, ramped CFL * h/umax)
            # (main.cpp:15268-15281 via sim/dtpolicy.py — the combined cap
            # is the upwind3 stability boundary; the pure 0.25 h^2/nu cap
            # blew up the 256^3 fish, see dtpolicy docstring)
            s.dt = dtpolicy.dt_host(h, s.nu, umax, cfg.CFL, s.step,
                                    cfg.rampup, cfg.implicitDiffusion)
            if cfg.pipelined and prev_dt > 0:
                s.dt = min(s.dt, 1.03 * prev_dt)
            if cfg.tend > 0:
                s.dt = min(s.dt, cfg.tend - s.time)
        if self._resilience is not None:
            # retry dt halving (exact no-op at scale 1.0, so the armed
            # clean path stays bitwise-identical to CUP3D_RECOVER=0)
            s.dt = self._resilience.scale_dt(s.dt)
        if faults.fire("dt.collapse", s.step):
            # injected fault: collapse dt so the existing abort trips
            s.dt = float("nan")
        if not np.isfinite(s.dt) or s.dt <= 0:
            # dt policy collapse: a non-finite or non-positive dt would
            # loop forever / poison every field — dump and abort
            extra = {"step": s.step, "dt": s.dt, "umax": umax}
            self.flight.trigger("dt-collapse", extra=extra)
            raise SimulationFailure(
                "dt-collapse", f"dt policy collapse: dt={s.dt:.3g}", extra
            )
        # lambda = DLM/dt each step (main.cpp:15302-15303)
        if cfg.DLM > 0:
            s.lambda_penal = cfg.DLM / s.dt
        return s.dt

    # -- output ------------------------------------------------------------

    def _maybe_dump_save(self) -> None:
        s = self.sim
        if s.cadence.dump_due(s.time, s.step):
            self.flush_packs()  # host mirrors current before output
            self.dump_fields()
        if s.cadence.save_due(s.step):
            self.flush_packs()
            with s.profiler("Checkpoint"):
                # async snapshot: fields stage via copy_to_host_async and
                # serialize on the writer thread (stream/checkpoint.py)
                self._save_checkpoint_guarded()

    def _save_checkpoint_guarded(self) -> None:
        """Async checkpoint with the round-10 degradation policy: under
        recovery, a failed background write (surfaced by the
        AsyncCheckpointer on the NEXT save) falls back to ONE synchronous
        atomic write; if that fails too the checkpoint is dropped +
        counted — output must never kill the step loop.  Without
        recovery the failure propagates (the legacy baseline)."""
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

    def dump_fields(self) -> None:
        import os

        import jax.numpy as jnp

        from cup3d_tpu.io import dump as dmp

        s, cfg = self.sim, self.cfg

        def omega_mag(vel):
            om = diag.vorticity(s.grid, vel)
            return jnp.sqrt(jnp.sum(om * om, axis=-1))

        fields = dmp.collect_dump_fields_device(cfg, s.state, omega_mag)
        if fields:
            prefix = os.path.join(cfg.path4serialization, f"dump_{s.step:07d}")
            with s.profiler("Dump"):
                # async staged handoff: the sharded multi-writer runs off
                # the step loop (stream/dump.py)
                self._dumper.submit(prefix, s.time, s.grid, fields,
                                    step=s.step)

    def drain_streams(self) -> None:
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

    def advance(self, dt: float) -> None:
        s = self.sim
        # step span + flight ring: wall/sections/solver-iters land in the
        # trace record (CUP3D_TRACE=1) and the postmortem ring (always)
        with self._obs.step(s.step, s.time, dt, umax=self._last_umax):
            self._maybe_dump_save()
            # the step's dt lives twice.  ``s.dt``: the Python float the
            # obstacles' host kinematics take (update_shape, update, the
            # collision latch).  And ONE sanctioned upload: every
            # operator receives dt as the same device scalar.  What the
            # tests prove under jax.transfer_guard: the obstacle-free
            # loop makes no other transfer (tests/test_analysis.py, the
            # sanitizer contract in VALIDATION.md); with bodies,
            # update_shape touches no device and CreateObstacles makes
            # one upload per body (tests/test_create_obstacles_dispatch
            # .py) — the other obstacle operators still stage their
            # rigid mirrors per step
            s.dt = float(dt)
            dt_dev = device_scalar(dt, s.dtype, tag="dt-upload")
            for op in self.pipeline:
                with s.profiler(op.name):
                    op(dt_dev)
            if s.pending_parts:
                with s.profiler("SyncQoI"):
                    entry = self._emit_step_pack()
                    if self.cfg.pipelined:
                        # grouped deferred read (sim/pack.py): the
                        # transfer of K packs overlaps later steps' device
                        # work; mirrors are applied strictly FIFO on the
                        # main thread
                        self._pack_reader.emit(entry)
                    else:
                        self._consume_pack(entry)
            elif self._pack_reader:
                # a pack-less step (ADVICE r2: unreachable today in
                # pipelined mode, but the coupling is fragile): keep
                # draining so queued reads and the stale-umax chain still
                # make progress
                self._pack_reader.flush()
            s.step += 1
            s.time += dt

    def advance_megaloop(self) -> None:
        """One K-step scan dispatch (sim/megaloop.py): the whole
        per-step pipeline — dt policy, the body's shape, rasterization,
        rigid update, penalization, projection, force probe — runs
        inside one jitted ``lax.scan``; the host only precomputes the
        CFL ramp, dispatches, and emits the (K, ROW) QoI block into the
        stream.  Host mirrors, logs, and failure detection are applied
        row by row at consumption (:meth:`_consume_scan_rows`), so the
        step loop's externally visible sequence is the per-step one, K
        steps late."""
        import jax.numpy as jnp

        from cup3d_tpu.obs import metrics as obs_metrics
        from cup3d_tpu.sim import dtpolicy
        from cup3d_tpu.sim import megaloop as ml

        s, cfg = self.sim, self.cfg
        K = self._scan_k
        fn, row_w = self._megaloop
        base_step = s.step
        with self._obs.step(base_step, s.time, s.dt,
                            umax=self._last_umax, scan_k=K):
            self._maybe_dump_save()
            if self._scan_carry is None:
                # carry (re)seed from the host mirrors: one sanctioned
                # upload at scan entry (cold start, post-rollback,
                # post-fallback), never per step
                with sanctioned_transfer("scan-carry-upload"):
                    self._scan_carry = (
                        ml.init_body_carry(s, s.obstacles[0])
                        if s.obstacles else ml.init_tgv_carry(s))
                    if self._scan_mesh is not None:
                        from cup3d_tpu.parallel import topology as topo

                        self._scan_carry = topo.shard_carry(
                            self._scan_carry, self._scan_mesh)
            # the CFL ramp is a pure function of the step index: host
            # precompute, shipped once per megaloop
            # jax-lint: allow(JX016, host list of Python floats in, host
            # ndarray out — no shard-resident array is gathered)
            cfl = np.asarray([
                dtpolicy.ramped_cfl(cfg.CFL, base_step + k, cfg.rampup)
                for k in range(K)
            ], dtype=s.dtype)
            with sanctioned_transfer("scan-carry-upload"):
                cfl_dev = jnp.asarray(cfl)
            with s.profiler("Megaloop"):
                carry, rows = fn(self._scan_carry, cfl_dev)
            obs_metrics.counter("megaloop.dispatches").inc()
            ops.note_poisson_solves(s.poisson_solver, K)
            if s.obstacles:  # the single body rasterizes every step
                s.obstacles[0].note_raster_work(K, scan=True)
            elif ops.forced(cfg):  # make_tgv_step forces every step
                obs_metrics.counter("operators.flux_scan_steps").inc(K)
            self._scan_carry = carry
            # the megaloop donates its carry: rebind the field state to
            # the carried arrays so dumps/snapshots/fallback see live
            # buffers, never donated ones
            s.state["vel"] = carry["vel"]
            s.state["p"] = carry["p"]
            if "chi" in carry:
                s.state["chi"] = carry["chi"]
                s.state["udef"] = carry["udef"]
            with s.profiler("SyncQoI"):
                entry = self._pack_reader.pack_parts(
                    [("scan", rows.reshape(K * row_w))], s.dtype,
                    time=s.time, step=base_step, scan_k=K)
                self._pack_reader.emit(entry)
            s.step += K
        # round-19 observatory seam: attribute the K-boundary wall to
        # every x-slab shard + refresh the federation snapshot.  Host
        # scalars only (the mark is obs.trace.now()); both calls are a
        # bool/None test when unsharded and unfederated.
        from cup3d_tpu.obs import federate as FEDERATE

        if self._scan_mesh is not None:
            FEDERATE.STRAGGLER.boundary(
                range(int(self._scan_mesh.devices.size)),
                source="megaloop", sink=obs_trace.TRACE, step=base_step)
        FEDERATE.FED.on_k_boundary()

    def _emit_step_pack(self) -> dict:
        """Concatenate every device QoI the step produced (rigid state,
        forces, penalization forces) plus max|u| for a later dt into ONE
        device vector (fast path; see models/base.rigid_update_device).
        Non-pipelined runs read the entry back immediately (advance);
        pipelined runs hand it to the grouped reader."""
        import jax.numpy as jnp

        s = self.sim
        parts = s.pending_parts
        s.pending_parts = []
        umax_dev = self._max_u(s.state["vel"], s.uinf_device())
        if s.obstacles:
            # include body kinematics in the CFL scale (see
            # calc_max_timestep)
            umax_dev = jnp.maximum(
                umax_dev, jnp.max(jnp.abs(s.state["udef"]))
            )
        parts.append(("umax", umax_dev.reshape(1)))
        # pack in the solver dtype (a forced f32 cast would silently
        # truncate the rigid trajectory in a float64 configuration); the
        # stream applies its slimming policy before the device concat
        return self._pack_reader.pack_parts(parts, s.dtype, time=s.time,
                                            step=s.step)

    def _consume_pack(self, entry: dict) -> None:
        """Read one emitted pack (or reuse the worker's fetch) and refresh
        host mirrors — always called from the main thread."""
        from cup3d_tpu.models.base import (
            log_forces, store_force_qoi, unpack_forces,
        )

        s = self.sim
        vals = entry.get("vals")
        if vals is None:
            # the designed end-of-step QoI sync of the non-pipelined path
            vals = blocking_read("qoi-read", entry["pack"], np.float64)
        ob = s.obstacles[0] if s.obstacles else None
        off = 0
        for name, size in entry["layout"]:
            seg = vals[off:off + size]
            off += size
            if name == "rigid":
                # pipelined mode chains the rigid state on device across
                # steps: the (trailing) mirrors must not clobber it
                ob.apply_rigid_pack(seg, clear_dev=not self.cfg.pipelined)
            elif name == "penal":
                ob.penal_force = seg[:3]
                ob.penal_torque = seg[3:]
            elif name == "forces":
                store_force_qoi(ob, unpack_forces(seg))
                log_forces(s.logger, 0, entry["time"], ob)
            elif name == "umax":
                self._umax_next = float(seg[0])
            elif name == "psolve":
                # [residual, iterations] from PressureProjection — the
                # consumed values feed the obs gauges, the step trace,
                # and the flight recorder's residual history (itercap
                # trips a postmortem there)
                self._obs.note_solver(
                    int(entry.get("step", s.step)), seg[1], seg[0],
                    cap=getattr(s.poisson_solver, "maxiter", None),
                )
            elif name == "scan":
                self._consume_scan_rows(entry, seg)

    def _consume_scan_rows(self, entry: dict, seg: np.ndarray) -> None:
        """Apply one megaloop's (K, ROW) packed QoI block row by row.
        Each row is one full step's QoI — rigid mirrors, penalization
        forces, surface forces, solver stats, umax/dt/t — so the host
        mirrors, force logs, flight ring and failure detection see the
        SAME per-step sequence the per-step path produces, K steps
        late (row layouts: sim/megaloop.py FISH_ROW / TGV_ROW; a forced
        flow's bulk velocity at TGV_BULK feeds flux.txt as FixMassFlux
        writes it per step)."""
        from cup3d_tpu.models.base import (
            log_forces, store_force_qoi, unpack_forces,
        )
        from cup3d_tpu.sim import megaloop as ml

        s, cfg = self.sim, self.cfg
        ob = s.obstacles[0] if s.obstacles else None
        row_w = ml.FISH_ROW if ob is not None else ml.tgv_row_width(cfg)
        rows = seg.reshape(-1, row_w)
        base_step = int(entry.get("step", s.step))
        for k in range(rows.shape[0]):
            row = rows[k]
            step_k = base_step + k
            if ob is not None:
                resid, iters = float(row[52]), float(row[53])
                umax, dt_k, t_k = (float(row[58]), float(row[59]),
                                   float(row[60]))
            else:
                resid, iters = float(row[0]), float(row[1])
                umax, dt_k, t_k = (float(row[-3]), float(row[-2]),
                                   float(row[-1]))
                if cfg.bFixMassFlux:
                    s.logger.write("flux.txt", ops.flux_line(
                        step_k, s.time, float(row[ml.TGV_BULK]),
                        ops.bulk_target(cfg)))
            # fault seams replay PER STEP at consumption: the injected
            # poisons land on the host copies, so the whole detection
            # -> trigger -> rollback chain runs exactly as it does on a
            # real mid-megaloop failure (resilience/faults.py)
            if faults.fire("step.nan_velocity", step_k):
                umax = float("nan")
            if not np.isfinite(umax) or umax > cfg.uMax_allowed:
                s.logger.flush()
                reason = ("nan-velocity" if not np.isfinite(umax)
                          else "runaway-velocity")
                extra = {"step": step_k, "umax": umax,
                         "scan_k": rows.shape[0]}
                self.flight.trigger(reason, extra=extra)
                raise SimulationFailure(
                    reason,
                    f"runaway velocity: max|u|={umax:.3g} > "
                    f"uMax_allowed={cfg.uMax_allowed}", extra)
            if faults.fire("dt.collapse", step_k):
                dt_k = float("nan")
            if not np.isfinite(dt_k) or dt_k <= 0:
                extra = {"step": step_k, "dt": dt_k, "umax": umax,
                         "scan_k": rows.shape[0]}
                self.flight.trigger("dt-collapse", extra=extra)
                raise SimulationFailure(
                    "dt-collapse",
                    f"dt policy collapse: dt={dt_k:.3g}", extra)
            if ob is not None:
                ob.apply_rigid_pack(row[0:29])
                ob.apply_scan_state(row[54:58])
                ob.penal_force = row[29:32]
                ob.penal_torque = row[32:35]
                store_force_qoi(ob, unpack_forces(row[35:52]))
                log_forces(s.logger, 0, t_k, ob)
                if ob.bFixFrameOfRef:
                    # jax-lint: allow(JX010, host-mirror copy: transVel
                    # is the numpy mirror apply_rigid_pack just wrote —
                    # no device value crosses here)
                    s.uinf = -np.asarray(ob.transVel, np.float64)
                    s._uinf_dev = None
            if iters >= 0:  # -1 = the solver packs no stats
                self._obs.note_solver(
                    step_k, iters, resid,
                    cap=getattr(s.poisson_solver, "maxiter", None))
            # per-step flight ring records: the postmortem sees every
            # scan step, not one blurred megaloop
            self.flight.record_step({
                "step": step_k, "t": t_k, "dt": dt_k, "umax": umax,
                "wall_s": 0.0, "scan": True,
            })
            s.time = t_k
            s.dt = dt_k
            if cfg.DLM > 0:
                s.lambda_penal = cfg.DLM / dt_k
            self._umax_next = umax
            self._last_umax = umax

    def flush_packs(self) -> None:
        """Drain pending QoI packs so host mirrors are current — called
        before dumps, checkpoints, and at run end (pipelined mode)."""
        self._pack_reader.flush()

    # -- resilience hooks (resilience/recovery.py driver contract) ---------

    def _resilience_restore(self, payload: dict) -> None:
        """In-place rollback to a ``build_payload``-shaped in-memory
        snapshot (the uniform twin of ``io.checkpoint.load_checkpoint``,
        reusing the live pipeline/jits so the retry costs zero
        retraces).  Fields are re-copied on the way in: the step jits
        donate them, and the engine's snapshot must survive repeated
        restores."""
        import pickle

        import jax.numpy as jnp

        s = self.sim
        s.state = {k: jnp.copy(v) for k, v in payload["fields"].items()}
        s.time = float(payload["time"])
        s.step = int(payload["step"])
        s.dt = float(payload["dt"])
        s.uinf = np.asarray(payload["uinf"], np.float64)
        s.lambda_penal = float(payload["lambda_penal"])
        s.cadence.next_dump = float(payload["next_dump"])
        s.obstacles = pickle.loads(payload["obstacles"])
        for ob in s.obstacles:
            ob.sim = s
        s.pending_parts = []
        s._uinf_dev = None
        self._umax_next = None
        self._last_umax = None
        # the scan carry references the abandoned trajectory (and its
        # donated buffers): reseed from the restored mirrors on the
        # next megaloop entry
        self._scan_carry = None
        # mirrors queued from the abandoned trajectory must never apply
        self._pack_reader.abandon()
        if s.obstacles:
            self.pipeline[0].rebuild()  # CreateObstacles: chi/udef

    def _resilience_zero_pressure(self) -> None:
        """Escalation stage 'zero-guess': the warm start restarts from
        p = 0 (the solvers warm-start from the live pressure field)."""
        import jax.numpy as jnp

        self.sim.state["p"] = jnp.zeros_like(self.sim.state["p"])

    def _resilience_rebuild_poisson(self, two_level=None,
                                    maxiter_mult: int = 1) -> None:
        """Escalation stages 'tile-only' / 'iter-bump': rebuild the
        Poisson solve with the two-level preconditioner dropped and/or a
        bumped iteration budget.  A deliberate one-off retrace on the
        failure path (the spectral solver is direct and ignores both)."""
        from cup3d_tpu.ops.poisson import make_poisson_solver

        s, cfg = self.sim, self.cfg
        s.poisson_solver = make_poisson_solver(
            s.grid, cfg.poissonSolver, s.dtype, tol_abs=cfg.poissonTol,
            tol_rel=cfg.poissonTolRel, maxiter=1000 * int(maxiter_mult),
            mean_constraint=cfg.bMeanConstraint, two_level=two_level,
        )
        for i, op in enumerate(self.pipeline):
            if isinstance(op, ops.PressureProjection):
                self.pipeline[i] = ops.PressureProjection(s)
        # the megaloop closed over the replaced solver: rebuild it too
        # (a second deliberate retrace, failure path only)
        self._megaloop = None
        self._scan_carry = None

    def simulate(self) -> None:
        from cup3d_tpu.resilience.recovery import RecoveryEngine

        s, cfg = self.sim, self.cfg
        eng = RecoveryEngine.install(self)
        try:
            while True:
                # capture-window hook at the loop top: for the megaloop
                # this is a K boundary, so a profiler window brackets
                # whole scan dispatches (disabled: one branch)
                self._obs_profile.on_step(s.step)
                try:
                    scan_now = self._scan_ready()
                    if scan_now:
                        if eng is not None and eng.snapshot_due(s.step):
                            # K-boundary snapshot consistency: the
                            # engine pickles host obstacle mirrors, so
                            # they must be current (equal to the carry)
                            # before the cadence snapshot fires
                            self.flush_packs()
                    elif self._scan_carry is not None:
                        # leaving scan mode (step-budget tail, recovery
                        # retreat): drain the stream so mirrors, time
                        # and dt are current for the per-step path
                        self.flush_packs()
                        self._scan_carry = None
                except Exception as e:
                    # a flush consumes queued scan rows and can surface
                    # a latched in-flight failure — same recovery path
                    if eng is not None and eng.handle_failure(e):
                        continue
                    raise
                if eng is not None and eng.on_loop_top():
                    continue  # rolled back: restart the iteration
                try:
                    if scan_now:
                        if cfg.verbose:
                            print(f"cup3d_tpu: steps {s.step}.."
                                  f"{s.step + self._scan_k - 1} "
                                  f"(scan K={self._scan_k}), "
                                  f"time: {s.time:f}")
                        self.advance_megaloop()
                    else:
                        dt = self.calc_max_timestep()
                        if cfg.verbose:
                            print(f"cup3d_tpu: step: {s.step}, "
                                  f"time: {s.time:f}, dt: {dt:.3e}")
                        self.advance(dt)
                except Exception as e:
                    if eng is not None and eng.handle_failure(e):
                        continue  # rolled back: retry from the snapshot
                    raise
                done_t = cfg.tend > 0 and s.time >= cfg.tend - 1e-12
                done_n = cfg.nsteps > 0 and s.step >= cfg.nsteps
                if done_t or done_n:
                    break
            self.flush_packs()
            self.drain_streams()
            s.logger.flush()
        finally:
            if eng is not None:
                eng.uninstall()
