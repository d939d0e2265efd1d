"""Background compile service (ISSUE 18 tentpole c).

A cold signature hitting the fleet's admission path used to stall the
dispatch thread for the whole trace+compile; with the service the
scheduler submits the build here, keeps the job queued, and goes on
dispatching warm signatures.  The worker thread runs the build (which
for a :class:`~cup3d_tpu.aot.store.StoreBackedExecutable` means store
probe, then AOT compile + write-back), the scheduler installs the
result into its LRU at the next pass, and the job assembles with zero
compile time on the dispatch thread.

Speculative pre-compiles (the ±1 rungs of the ×1.25 capacity ladder)
ride the same queue at low priority: demand builds always pop first.

Tasks are keyed and deduplicated; a failed build parks the key in
``failed`` state so the scheduler falls back to a synchronous compile
(transparent degradation, counted in ``aot.compile_failures``) —
exactly one thread, daemonized, nothing to shut down.

XLA compilation is thread-safe and the builds touch no interpreter
state beyond the store, so the only shared-state discipline needed is
the condition variable around the task table.
"""

from __future__ import annotations

import heapq
import os
import threading
from typing import Callable, Dict, Optional

from cup3d_tpu.obs import metrics as M
from cup3d_tpu.obs import trace as OT
from cup3d_tpu.resilience import faults

PENDING, RUNNING, DONE, FAILED = "pending", "running", "done", "failed"

#: demand builds beat speculative ones in the priority heap
PRIORITY_DEMAND = 0
PRIORITY_SPECULATIVE = 10


def speculate_enabled() -> bool:
    """``CUP3D_AOT_SPECULATE`` (default on — speculation only spends
    background-thread time and store bytes, never dispatch time)."""
    return os.environ.get("CUP3D_AOT_SPECULATE", "1") not in ("0", "")


class CompileService:
    """One daemon worker draining a keyed priority queue of builds."""

    def __init__(self, name: str = "aot-compile"):
        self.name = str(name)
        self._cv = threading.Condition()
        self._heap = []  # (priority, seq, key)
        self._seq = 0
        self._tasks: Dict[object, dict] = {}
        self._thread: Optional[threading.Thread] = None

    # -- submission ----------------------------------------------------------

    def submit(self, key, build: Callable[[], object],
               name: str = "exec",
               priority: int = PRIORITY_DEMAND,
               jobs=None) -> bool:
        """Enqueue ``build`` under ``key`` (dedup: a key already
        pending/running/done is left alone; a failed key may be
        resubmitted).  ``jobs`` — the FleetJob ids parked on this build
        (round-22 causal link): they ride the task into the pid-5
        Perfetto compile span and its flow arrows.  Returns True when
        actually enqueued."""
        with self._cv:
            task = self._tasks.get(key)
            if task is not None and task["status"] != FAILED:
                return False
            self._tasks[key] = {"status": PENDING, "build": build,
                                "name": str(name), "result": None,
                                "priority": int(priority),
                                "jobs": list(jobs or [])}
            heapq.heappush(self._heap, (int(priority), self._seq, key))
            self._seq += 1
            self._ensure_worker()
            self._cv.notify_all()
        M.counter(
            "aot.compile_submits",
            kind="speculative" if priority >= PRIORITY_SPECULATIVE
            else "demand").inc()
        self._update_depth()
        return True

    def attach(self, key, jobs) -> None:
        """Merge more waiting-job ids onto an in-flight build: jobs
        that hit the same cold signature on a LATER scheduling pass
        still want their flow arrow from the one shared compile span."""
        with self._cv:
            task = self._tasks.get(key)
            if task is None or task["status"] in (DONE, FAILED):
                return
            have = task.setdefault("jobs", [])
            for j in jobs:
                if j not in have:
                    have.append(j)

    def _ensure_worker(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, name=self.name, daemon=True)
            self._thread.start()

    # -- queries -------------------------------------------------------------

    def status(self, key) -> Optional[str]:
        with self._cv:
            task = self._tasks.get(key)
            return None if task is None else task["status"]

    def take(self, key):
        """Pop and return a DONE build's result (None otherwise; the
        task record stays so dedup keeps holding the key)."""
        with self._cv:
            task = self._tasks.get(key)
            if task is None or task["status"] != DONE:
                return None
            result, task["result"] = task["result"], None
            return result

    def depth(self) -> int:
        """Builds not yet finished (queued + running)."""
        with self._cv:
            return sum(1 for t in self._tasks.values()
                       if t["status"] in (PENDING, RUNNING))

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until some build finishes (or timeout); True when the
        queue is fully drained.  The serve loop parks here instead of
        busy-spinning when every queued job waits on a compile."""
        with self._cv:
            if self.depth_locked() == 0:
                return True
            self._cv.wait(timeout)
            return self.depth_locked() == 0

    def depth_locked(self) -> int:
        return sum(1 for t in self._tasks.values()
                   if t["status"] in (PENDING, RUNNING))

    def drain(self, timeout: float = 60.0) -> bool:
        """Wait until every submitted build finished (tests/CLI).
        Death-path (round 23): a dead worker can never finish its
        orphaned RUNNING task, so each wait iteration reaps orphans —
        without it, ``_aot_quiesce`` would park for the full timeout on
        a queue that cannot drain."""
        deadline = OT.now() + float(timeout)
        while True:
            self.fail_orphans()
            with self._cv:
                if self.depth_locked() == 0:
                    return True
                remaining = deadline - OT.now()
                if remaining <= 0:
                    return False
                self._cv.wait(min(remaining, 0.25))

    @staticmethod
    def _orphaned(task: dict) -> bool:
        """A task is orphaned when the thread that took it is dead."""
        return task["status"] == RUNNING and not task["owner"].is_alive()

    def fail_orphans(self) -> int:
        """Death-path recovery (round 23): when a worker thread died
        (``compile.service_die``, or any uncatchable thread death), its
        popped-but-unfinished build is stuck RUNNING forever — nothing
        requeues it, so ``depth()`` never reaches zero and every waiter
        parks.  Mark such orphans FAILED (the schedulers' existing
        failed-build path then compiles inline, a transparent
        degradation counted ``aot.service_fallbacks``) and restart the
        worker for any still-PENDING queue entries.  Orphans are judged
        by the thread that took them, not by the service's current
        thread: a later ``submit`` may already have started a new
        worker, alive and idle beside the dead one's task.  Returns the
        number of orphans failed."""
        with self._cv:
            n = 0
            for task in self._tasks.values():
                if self._orphaned(task):
                    task["status"] = FAILED
                    task["build"] = None
                    n += 1
            if any(t["status"] == PENDING for t in self._tasks.values()):
                self._ensure_worker()
            if n:
                self._cv.notify_all()
        if n:
            M.counter("aot.service_fallbacks").inc(n)
            self._update_depth()
        return n

    def state(self) -> dict:
        """The /health payload.  A RUNNING task whose owner died counts
        under ``orphaned``, not ``running``: the state that parks every
        waiter shows before ``fail_orphans`` reaps it."""
        with self._cv:
            counts: Dict[str, int] = {}
            for t in self._tasks.values():
                kind = "orphaned" if self._orphaned(t) else t["status"]
                counts[kind] = counts.get(kind, 0) + 1
            return {"queue_depth": self.depth_locked(),
                    "tasks": counts,
                    "worker_alive": bool(
                        self._thread is not None
                        and self._thread.is_alive())}

    def _update_depth(self) -> None:
        M.gauge("aot.compile_queue_depth").set(float(self.depth()))

    # -- the worker ----------------------------------------------------------

    def _run(self) -> None:
        while True:
            with self._cv:
                while True:
                    key = None
                    while self._heap:
                        _, _, cand = heapq.heappop(self._heap)
                        task = self._tasks.get(cand)
                        if task is not None and task["status"] == PENDING:
                            key = cand
                            break
                    if key is not None:
                        break
                    self._cv.wait()
                task = self._tasks[key]
                task["status"] = RUNNING
                task["owner"] = threading.current_thread()
                build, name = task["build"], task["name"]
            # the chaos seam: the worker dies mid-task, leaving this
            # build orphaned RUNNING — exactly the state fail_orphans()
            # and the serve() death-path fallback must recover from
            if faults.fire("compile.service_die"):
                return
            t0 = OT.now()
            try:
                result = build()
                status = DONE
                M.counter("aot.background_compiles").inc()
            except Exception:
                result, status = None, FAILED
                M.counter("aot.compile_failures", executable=name).inc()
            t1 = OT.now()
            M.histogram("aot.background_compile_s",
                        executable=name).observe(t1 - t0)
            with self._cv:
                task = self._tasks.get(key)
                jobs = list(task.get("jobs") or ()) if task else []
                if task is not None:
                    task["status"] = status
                    task["result"] = result
                    task["build"] = None
                self._cv.notify_all()
            self._update_depth()
            self._trace_build(name, status, jobs, t0, t1)

    @staticmethod
    def _trace_build(name: str, status: str, jobs, t0: float,
                     t1: float) -> None:
        """Round-22 provenance: one pid-5 compile span per build, plus
        a flow arrow opened per waiting job (terminated by that job's
        lane span in fleet/server.py _job_terminal) — a cold-start job
        reads as one causal chain in the Perfetto UI."""
        sink = OT.TRACE
        if not sink.enabled:
            return
        sink.compile_span(
            1, name, t0, t1 - t0,
            args={"outcome": status, "jobs": list(jobs)})
        for job_id in jobs:
            sink.flow_start(job_id, "compile->lane", t1,
                            OT.COMPILE_PID, 1)
