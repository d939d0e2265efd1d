"""Rollback/retry recovery for the step loop (ISSUE 5 tentpole b).

The :class:`RecoveryEngine` turns the flight recorder's terminal
conditions (nan-velocity, runaway-velocity, dt-collapse,
poisson-itercap, poisson-nan-residual) from crashes into bounded
recovery, following the elastic-training pattern (periodic in-memory
snapshots + rollback/retry, as in Orbax-style emergency checkpointing):

- every ``CUP3D_SNAP_EVERY`` steps the engine takes a **rolling
  in-memory snapshot**: ``io.checkpoint.build_payload`` (the exact
  restart payload) with every device field re-staged into a FRESH
  device buffer (``jnp.copy`` — the step jits donate their state, so
  holding live references would hand the engine deleted arrays) and the
  host-mutable obstacle state deep-frozen via a pickle round trip.  The
  snapshot never leaves the device on the hot path — no host sync, no
  retrace (``jnp.copy`` is an eager op, not a jit);
- on a flight-recorder trigger the engine **rolls back** to the last
  snapshot (``driver._resilience_restore``), **halves dt** for the
  re-advance (``0.5**attempt``, floored at ``CUP3D_DT_FLOOR``, reset
  once the run progresses past the failure), and for Poisson failures
  walks the **escalation ladder**: warm-restart (restored pressure) ->
  zero initial guess -> tile-only preconditioner -> 4x iteration
  budget (the last two rebuild the solver — a deliberate, counted
  retrace on the failure path only);
- after ``CUP3D_MAX_RETRIES`` failed attempts it restores the last good
  snapshot, writes the postmortem (interception bypassed) plus a
  restartable on-disk checkpoint, and re-raises — a clean, resumable
  exit instead of a poisoned trajectory.

``CUP3D_RECOVER=0`` (or a sharded ``mesh`` driver, whose topology has no
in-place restore) disables installation entirely: the drivers then
behave exactly as before this subsystem existed — that is the bitwise
equivalence baseline the bench overhead gate compares against.

Every rollback/retry lands in the obs registry
(``resilience.rollbacks``, ``resilience.retries{stage=...}``,
``resilience.snapshots``, ``resilience.giveups``) and in the flight
recorder's ``recovery_events`` ring (part of any later postmortem).
"""

from __future__ import annotations

import os
import pickle
from typing import Optional

from cup3d_tpu.obs import metrics as _metrics
from cup3d_tpu.resilience import faults

#: flight-recorder reasons the engine knows how to recover from
RECOVERABLE = frozenset((
    "nan-velocity",
    "runaway-velocity",
    "dt-collapse",
    "poisson-itercap",
    "poisson-nan-residual",
))

#: reasons that walk the Poisson escalation ladder on retry
_POISSON = frozenset(("poisson-itercap", "poisson-nan-residual"))

#: ladder stage per attempt number for Poisson failures
_LADDER = {1: "warm-restart", 2: "zero-guess", 3: "tile-only"}


def recovery_enabled() -> bool:
    """Default ON; ``CUP3D_RECOVER=0`` keeps the legacy crash-on-fault
    behavior (the equivalence baseline)."""
    return os.environ.get("CUP3D_RECOVER", "1") != "0"


class SimulationFailure(RuntimeError):
    """A detected terminal condition, carrying its flight-recorder
    ``reason`` so the recovery engine can classify it.  Subclasses
    RuntimeError: callers (and tests) that match the legacy abort
    messages keep working unchanged."""

    def __init__(self, reason: str, message: str,
                 extra: Optional[dict] = None):
        super().__init__(message)
        self.reason = reason
        self.extra = dict(extra or {})


class RecoveryEngine:
    """Snapshot / rollback / retry state machine for one driver run.

    The driver contract (implemented by ``sim/simulation.py`` and
    ``sim/amr.py``):

    - ``driver.flight``                        flight recorder
    - ``driver._resilience``                   engine backref (dt scale)
    - ``driver._resilience_restore(payload)``  in-place restore of a
      ``build_payload``-shaped snapshot
    - ``driver._resilience_zero_pressure()``   zero the pressure field
    - ``driver._resilience_rebuild_poisson(two_level=, maxiter_mult=)``
      rebuild the Poisson solve (escalation; retraces by design)
    """

    def __init__(self, driver, snapshot_every: Optional[int] = None,
                 max_retries: Optional[int] = None,
                 dt_floor: Optional[float] = None):
        env = os.environ.get
        self.driver = driver
        self.flight = driver.flight
        self.snapshot_every = int(
            snapshot_every if snapshot_every is not None
            else env("CUP3D_SNAP_EVERY", "16")
        )
        self.max_retries = int(
            max_retries if max_retries is not None
            else env("CUP3D_MAX_RETRIES", "4")
        )
        self.dt_floor = float(
            dt_floor if dt_floor is not None else env("CUP3D_DT_FLOOR", "1e-9")
        )
        self.dt_scale = 1.0
        self.attempts = 0
        self._snap: Optional[dict] = None
        self._snap_step: Optional[int] = None
        self._pending: Optional[tuple] = None
        self._recovering_until = -1
        self._c_snap = _metrics.counter("resilience.snapshots")
        self._c_roll = _metrics.counter("resilience.rollbacks")
        self._c_give = _metrics.counter("resilience.giveups")
        # the one bound-method object installed as the flight hook
        # (bound methods are created per access, so identity checks in
        # uninstall need a stable reference)
        self._hook = self._intercept

    # -- lifecycle ---------------------------------------------------------

    @classmethod
    def install(cls, driver, force: bool = False,
                **kw) -> Optional["RecoveryEngine"]:
        """Attach an engine to ``driver`` for the duration of a
        ``simulate()`` loop (None when disabled).  Sharded (mesh) runs
        are excluded: their topology has no in-place restore path."""
        if not (force or recovery_enabled()):
            return None
        if getattr(driver, "mesh", None) is not None:
            return None
        faults.load_env()
        eng = cls(driver, **kw)
        driver._resilience = eng
        eng.flight.recovery_intercept = eng._hook
        return eng

    def uninstall(self) -> None:
        if getattr(self.driver, "_resilience", None) is self:
            self.driver._resilience = None
        if self.flight.recovery_intercept is self._hook:
            self.flight.recovery_intercept = None
        # the engine refers to itself (``_hook``), so only the cyclic
        # collector frees it: an engine taken off would keep its device
        # copy of every field until then, one more for each
        # ``simulate()`` call (0.54 GB each at 256^3)
        self._snap = None

    # -- flight-recorder interception --------------------------------------

    def _intercept(self, reason: str, extra: dict) -> bool:
        """Called INSIDE ``flight.trigger``: claim the failure (skip the
        postmortem dump) when it is recoverable and a snapshot exists;
        the actual rollback runs from the simulate loop."""
        if reason not in RECOVERABLE or self._snap is None:
            return False
        self._pending = (reason, dict(extra))
        return True

    # -- simulate-loop hooks -----------------------------------------------

    def _step(self) -> int:
        d = self.driver
        if hasattr(d, "step_idx"):  # AMR driver
            return int(d.step_idx)
        return int(d.sim.step)

    def snapshot_due(self, step: Optional[int] = None) -> bool:
        """True when :meth:`on_loop_top` will take its cadence snapshot.
        Scan-megaloop drivers ask BEFORE the loop top and flush their
        QoI stream first, so the pickled obstacle mirrors match the
        device carry at the K boundary (VALIDATION.md round 11)."""
        if step is None:
            step = self._step()
        return (self._snap is None
                or step - self._snap_step >= self.snapshot_every)

    def on_loop_top(self) -> bool:
        """Top of every simulate iteration.  Handles failures latched by
        the async pack consumption (returns True after a rollback so the
        loop re-enters), retires recovery state once the run progressed
        past the failure, and takes the cadence snapshot."""
        if self._pending is not None:
            reason, extra = self._pending
            self._pending = None
            if not self._recover(reason, extra):
                self._give_up(reason, extra)  # raises
            return True
        step = self._step()
        if self.attempts and step > self._recovering_until:
            self.attempts = 0
            self.dt_scale = 1.0
        if self.snapshot_due(step):
            try:
                self.snapshot()
            except Exception:
                # best-effort: a snapshot that cannot be taken (e.g. an
                # unpicklable monkeypatched obstacle) must never kill a
                # healthy run — the rollback point just stays staler,
                # and the drop is counted
                _metrics.counter("resilience.snapshot_failures").inc()
        return False

    def handle_failure(self, exc: BaseException) -> bool:
        """Exception filter for the simulate loop: True after a
        successful rollback (retry the iteration), False when the
        failure is not ours / not recoverable (re-raise)."""
        self._pending = None  # the raise supersedes any latched trigger
        reason = getattr(exc, "reason", None)
        if reason is None or reason not in RECOVERABLE:
            return False
        if self._snap is None:
            # nothing to roll back to: the trigger already wrote its
            # postmortem (interception declines without a snapshot)
            return False
        if not self._recover(reason, getattr(exc, "extra", {})):
            self._give_up(reason, getattr(exc, "extra", {}), exc)  # raises
        return True

    # -- snapshot / restore ------------------------------------------------

    def snapshot(self) -> None:
        """Rolling in-memory snapshot: the restart payload with every
        device field re-staged into a fresh buffer and obstacles frozen
        to bytes.  Device-staged — the hot path pays eager device copies
        and host pickling of small kinematic state, never a field
        read."""
        import jax.numpy as jnp

        from cup3d_tpu.io.checkpoint import build_payload

        if hasattr(getattr(self.driver, "dt", 0.0), "block_until_ready"):
            # device-dt chain: the payload's float(dt) is a real sync —
            # a designed once-per-cadence read (VALIDATION.md round 10)
            from cup3d_tpu.analysis.runtime import sanctioned_transfer

            with sanctioned_transfer("resilience-snapshot"):
                payload = build_payload(self.driver)
        else:
            payload = build_payload(self.driver)
        payload["obstacles"] = pickle.dumps(
            payload["obstacles"], protocol=pickle.HIGHEST_PROTOCOL
        )
        payload["fields"] = {
            k: (jnp.copy(v) if hasattr(v, "block_until_ready") else v)
            for k, v in payload["fields"].items()
        }
        self._snap = payload
        self._snap_step = int(payload["step"])
        self._c_snap.inc()

    def _restore(self) -> None:
        self.driver._resilience_restore(self._snap)

    # -- rollback / escalation ---------------------------------------------

    def _stage(self, reason: str) -> str:
        if reason in _POISSON:
            return _LADDER.get(self.attempts, "iter-bump")
        return "dt-halve"

    def _recover(self, reason: str, extra: dict) -> bool:
        """One rollback attempt; False when the retry budget is spent."""
        self.attempts += 1
        if self.attempts > self.max_retries:
            return False
        failed_at = int(extra.get("step", self._step()))
        stage = self._stage(reason)
        self._restore()
        self.dt_scale = 0.5 ** self.attempts
        if reason in _POISSON:
            if stage == "zero-guess":
                self.driver._resilience_zero_pressure()
            elif stage == "tile-only":
                self.driver._resilience_zero_pressure()
                self.driver._resilience_rebuild_poisson(two_level=False)
            elif stage == "iter-bump":
                self.driver._resilience_zero_pressure()
                self.driver._resilience_rebuild_poisson(
                    two_level=False, maxiter_mult=4
                )
        # recovery state retires once the run is safely past the failure
        # (a short grace: dt returns to policy quickly, and a recurrence
        # simply re-enters with attempts already counted up)
        self._recovering_until = failed_at + 4
        self._c_roll.inc()
        _metrics.counter("resilience.retries", stage=stage).inc()
        self.flight.note_recovery({
            "reason": reason, "stage": stage, "attempt": self.attempts,
            "failed_at_step": failed_at, "rolled_back_to": self._snap_step,
            "dt_scale": self.dt_scale,
        })
        return True

    def _give_up(self, reason: str, extra: dict,
                 exc: Optional[BaseException] = None) -> None:
        """Retries exhausted: postmortem (interception bypassed) + a
        restartable checkpoint from the last good snapshot, then raise —
        the exit is clean and resumable, never a poisoned trajectory."""
        self._c_give.inc()
        icpt, self.flight.recovery_intercept = (
            self.flight.recovery_intercept, None,
        )
        try:
            self.flight.trigger(reason, extra={
                **extra, "recovery": "exhausted",
                "attempts": self.attempts,
                "rolled_back_to": self._snap_step,
            })
        finally:
            self.flight.recovery_intercept = icpt
        try:
            self._restore()
            from cup3d_tpu.io.checkpoint import save_checkpoint

            path = save_checkpoint(self.driver)
            _metrics.counter("resilience.restart_checkpoints").inc()
            self.flight.note_recovery({
                "reason": reason, "stage": "give-up",
                "restart_checkpoint": path,
            })
        except Exception:
            # the give-up path must reach the raise even when the disk
            # (or an armed ckpt.write_fail) refuses the restart file
            _metrics.counter("resilience.restart_ckpt_failures").inc()
        if exc is not None:
            raise exc
        raise SimulationFailure(
            reason,
            f"recovery exhausted after {self.attempts - 1} retries: "
            f"{reason}", extra,
        )

    # -- dt policy hook ----------------------------------------------------

    def scale_dt(self, dt):
        """Retry dt halving.  Exact identity (same object) at scale 1.0,
        so the armed-but-clean path is bitwise-equivalent to
        CUP3D_RECOVER=0; host floats are floored at ``dt_floor`` (device
        dt chains scale unfloored — a probe-free multiply)."""
        if self.dt_scale == 1.0:
            return dt
        scaled = dt * self.dt_scale
        if isinstance(dt, float):
            return max(scaled, min(dt, self.dt_floor))
        return scaled
