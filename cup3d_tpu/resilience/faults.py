"""Deterministic fault injection at named seams (ISSUE 5 tentpole a).

Every injectable failure the flight recorder already knows how to detect
gets a stable SITE name; arming one makes the existing seam misbehave in
a controlled, reproducible way so the recovery machinery (and its tests)
exercise the REAL detection and rollback paths instead of mocks:

==================== ======================================================
site                 seam (where ``fire`` is consulted)
==================== ======================================================
step.nan_velocity    drivers' ``calc_max_timestep``: poisons the max|u|
                     mirror, tripping the existing NaN-umax abort
dt.collapse          drivers' ``calc_max_timestep``: poisons the computed
                     dt, tripping the existing dt-collapse abort
solver.nan_residual  ``obs.trace.StepObserver.note_solver``: the consumed
                     packed solver residual becomes NaN
solver.itercap       ``obs.trace.StepObserver.note_solver``: the consumed
                     iteration count hits the solver's cap
ckpt.write_fail      ``io.checkpoint.write_payload``: the checkpoint
                     write raises (every retry re-fires while armed)
dump.write_fail      ``stream.dump.AsyncDumper._write``: the dump write
                     raises (retried, then dropped + counted)
stream.stall         ``stream.qoi.QoIStream.emit``: a simulated transfer
                     stall (sleep) before the pack is queued
==================== ======================================================

Arming is via ``CUP3D_FAULT="site@step[:count]"`` (``;``-separated for
several; ``step`` may be ``*`` for "any step") or the :func:`arm` API.
A site fires at most ``count`` times, once armed-and-reached; every
firing lands in the obs registry as ``faults.injected{site=...}``.  An
empty plan is one tuple iteration per probe — the unarmed hot path pays
nothing measurable.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import List, Optional

from cup3d_tpu.obs import metrics as _metrics

#: the fault-site catalog (README "Resilience" documents each seam)
SITES = (
    "solver.nan_residual",
    "solver.itercap",
    "step.nan_velocity",
    "dt.collapse",
    "ckpt.write_fail",
    "dump.write_fail",
    "stream.stall",
    # lane-addressed fleet seam: armed with the LANE index in the step
    # slot, it poisons exactly one chosen lane's QoI chain at its next
    # consumed row (fleet/isolate.py check_row)
    "fleet.lane_nan",
    # shard-addressed fleet seam (round 18): armed with the SHARD index
    # in the step slot, it drops that mesh slice of every live batch at
    # the next dispatch boundary (resilience/elastic.fail_shard via
    # fleet/server.FleetBatch.dispatch)
    "fleet.shard_loss",
    # round 23 — durability chaos sites:
    # journal segment write raises inside the writeguard seam (one-shot
    # arms are absorbed by the retry; wildcard arms exhaust it and the
    # append is counted-dropped, never raised to the serve loop)
    "journal.write_fail",
    # hard process death (os._exit) at a dispatch K-boundary of
    # fleet/server.FleetBatch.dispatch, armed with the DISPATCH count in
    # the step slot — the crash-restart drill's kill switch
    "server.crash",
    # flips bytes mid-artifact before an aot/store.py load, driving the
    # read down the checksum-reject path (transparent recompile)
    "aot.store_corrupt",
    # kills the background compile worker thread mid-task
    # (aot/compiler.py _run), leaving its build orphaned RUNNING — the
    # death-path serve() must fall back from, not park on
    "compile.service_die",
)

ENV_VAR = "CUP3D_FAULT"

#: simulated transfer stall for the stream.stall site (seconds)
STALL_S = 0.02


class InjectedFault(IOError):
    """The exception raised at write-path seams when their site fires."""

    def __init__(self, site: str, step):
        super().__init__(f"injected fault {site!r} at step {step}")
        self.site = site
        self.step = step


@dataclass
class _Arm:
    site: str
    step: Optional[int]  # None = any step ('*')
    count: int = 1
    fired: int = 0

    def matches(self, step) -> bool:
        if self.fired >= self.count:
            return False
        if self.step is None:
            return True
        return step is not None and int(step) >= self.step


class FaultPlan:
    """A deterministic, ordered set of armed fault sites."""

    def __init__(self) -> None:
        self.arms: List[_Arm] = []

    def arm(self, site: str, step="*", count: int = 1) -> None:
        if site not in SITES:
            raise ValueError(
                f"unknown fault site {site!r}; known: {', '.join(SITES)}"
            )
        step_i = None if step in ("*", None) else int(step)
        self.arms.append(_Arm(site, step_i, int(count)))

    def clear(self) -> None:
        self.arms = []

    def parse(self, spec: str) -> None:
        """``site@step[:count]`` entries separated by ``;`` or ``,``."""
        for part in spec.replace(",", ";").split(";"):
            part = part.strip()
            if not part:
                continue
            if "@" not in part:
                raise ValueError(
                    f"bad CUP3D_FAULT entry {part!r}: want site@step[:count]"
                )
            site, rest = part.split("@", 1)
            count = 1
            if ":" in rest:
                rest, cnt = rest.rsplit(":", 1)
                count = int(cnt)
            self.arm(site.strip(), rest.strip(), count)

    def fire(self, site: str, step=None) -> bool:
        """True exactly when an armed entry for ``site`` fires at
        ``step`` (counted, so a ``count``-shot arm exhausts itself)."""
        for a in self.arms:
            if a.site == site and a.matches(step):
                a.fired += 1
                _metrics.counter("faults.injected", site=site).inc()
                return True
        return False

    def snapshot(self) -> List[dict]:
        """Armed-state view for postmortems / tests."""
        return [
            {"site": a.site, "step": a.step, "count": a.count,
             "fired": a.fired}
            for a in self.arms
        ]


#: the process-global plan every seam consults
PLAN = FaultPlan()

_env_src: str = ""


def load_env(force: bool = False) -> FaultPlan:
    """(Re)load ``CUP3D_FAULT`` into the global plan.  Idempotent while
    the env value is unchanged, so drivers call it at every
    ``simulate()`` entry; API-armed entries survive only until the env
    value CHANGES (tests monkeypatching the env get a fresh plan)."""
    global _env_src
    spec = os.environ.get(ENV_VAR, "")
    if not force and spec == _env_src:
        return PLAN
    _env_src = spec
    PLAN.clear()
    if spec:
        PLAN.parse(spec)
    return PLAN


def arm(site: str, step="*", count: int = 1) -> None:
    PLAN.arm(site, step, count)


def clear() -> None:
    """Disarm everything (tests)."""
    global _env_src
    PLAN.clear()
    _env_src = ""


def fire(site: str, step=None) -> bool:
    return PLAN.fire(site, step)


def maybe_raise(site: str, step=None) -> None:
    """Raise :class:`InjectedFault` when ``site`` fires (write seams)."""
    if PLAN.fire(site, step):
        raise InjectedFault(site, step)


def maybe_stall(site: str = "stream.stall", step=None) -> None:
    """Sleep :data:`STALL_S` when ``site`` fires (stream seams)."""
    if PLAN.fire(site, step):
        time.sleep(STALL_S)
