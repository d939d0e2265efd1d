"""Durable fleet acceptance (round 23; VALIDATION.md "Round 23"):

- Journal mechanics: record round-trip, replay folding, per-defect-
  class corrupt-segment skip (io/magic/truncated/checksum/unpickle/
  schema — each counted ``journal.rejects{reason}``, every healthy
  segment kept), and the write seam (a one-shot ``journal.write_fail``
  is absorbed by the writeguard retry; a persistent one degrades to a
  counted ``journal.append_failures`` without touching the serve loop).
- Crash-restart recovery: a journaled server abandoned mid-flight is
  resumed by a fresh server on the same workdir — zero lost jobs and
  QoI bytes BITWISE-identical to an unfaulted journal-off control;
  replay is idempotent (a second ``recover()`` is a no-op); unplaced
  queued jobs re-queue; fully-drained jobs are remembered from their
  terminal records without re-running.
- Terminal idempotence (regression): a second terminal arrival — a
  cancel racing a migration, or a replayed-from-journal terminal —
  is a counted no-op (``fleet.duplicate_terminals``), never a double
  SLO fold.
- Live migration: ``migrate_job`` moves a RUNNING lane between servers
  bitwise; ``drain_for_shutdown`` closes admission and either migrates
  or journals every running lane.
- Journal-off legacy: ``CUP3D_FLEET_JOURNAL=0`` serves bitwise-
  identically with no journal directory.
- Compile-service death path: a dead background compile worker is
  reaped (``aot.service_fallbacks``) and serve() falls back to inline
  compiles instead of parking forever.
- Slow: the full subprocess drill — hard-killed serve (``os._exit(23)``
  via the ``server.crash`` chaos site), CLI restart, bitwise QoI vs
  control with ZERO advance recompiles against the warm AOT store.
"""

import functools
import hashlib
import json
import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

from cup3d_tpu.fleet.journal import MAGIC, JobJournal
from cup3d_tpu.fleet.migrate import (
    drain_for_shutdown,
    migrate_job,
)
from cup3d_tpu.fleet.server import (
    CANCELLED,
    DONE,
    MIGRATED,
    QUEUED,
    RUNNING,
    FleetAdmissionError,
    FleetServer,
)
from cup3d_tpu.obs import metrics as M
from cup3d_tpu.resilience import faults
from tests._cases import delta, tgv_spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: three K-boundaries, so a crash drill can stop between them
_tgv_spec = functools.partial(tgv_spec, nsteps=24)


pytestmark = pytest.mark.usefixtures("clean_faults")


def _qoi(server, ids):
    return {j: server._jobs[j].qoi_bytes() for j in ids}


def _server(tmp, tag, journal, **kw):
    kw.setdefault("max_lanes", 4)
    kw.setdefault("snap_every", 8)
    return FleetServer(workdir=str(tmp / tag), journal=journal, **kw)


def _control(tmp, specs):
    """Journal-off drain: the bitwise-legacy baseline."""
    ctl = _server(tmp, "ctl", journal=False)
    ids = [ctl.submit(f"t{i}", sc) for i, sc in enumerate(specs)]
    ctl.drain()
    assert all(ctl._jobs[j].status == DONE for j in ids)
    return ctl, ids


def _run_two_boundaries(server):
    """Advance every batch two K-boundaries (snapshots land, nsteps=24
    jobs do not finish) and settle — the abandon-point of the crash
    drills."""
    server._schedule()
    for _ in range(2):
        for b in server.batches:
            b.tick()
    for b in server.batches:
        b.settle()


# -- journal mechanics ------------------------------------------------------


def test_journal_roundtrip_and_replay(tmp_path):
    j = JobJournal(str(tmp_path / "j"))
    rows = np.arange(12, dtype=np.float64).reshape(2, 6)
    assert j.append("submit", job_id="job-0000", tenant="a",
                    spec={"kind": "tgv", "n": 16}, nsteps=8)
    assert j.append("place", job_id="job-0000", batch_uid="x.0",
                    lane=1, cap=2, K=8, kind="tgv")
    assert j.append("submit", job_id="job-0001", tenant="b",
                    spec={"kind": "tgv"}, nsteps=8)
    assert j.append("terminal", job_id="job-0000", status="done",
                    error=None, steps_done=8, time=0.5, nsteps=8,
                    rows=rows)
    view = JobJournal(str(tmp_path / "j")).replay()
    assert list(view) == ["job-0000", "job-0001"]
    a, b = view["job-0000"], view["job-0001"]
    assert a["status"] == "done" and a["steps_done"] == 8
    assert a["tenant"] == "a" and a["cap"] == 2 and a["K"] == 8
    np.testing.assert_array_equal(a["rows"], rows)
    assert b["status"] == "queued" and b["snapshot"] is None
    # a recovered journal appends AFTER what it replayed
    assert JobJournal(str(tmp_path / "j"))._seq == 4


def test_journal_defect_classes_skipped(tmp_path):
    """One corrupt segment per reject class: counted and skipped,
    every healthy record kept, replay never raises."""
    j = JobJournal(str(tmp_path / "j"))
    paths = [j.append("submit", job_id=f"job-{i:04d}", tenant="t",
                      spec={}, nsteps=8) for i in range(6)]
    with open(paths[1], "r+b") as f:          # magic
        f.write(b"XXXX")
    with open(paths[2], "r+b") as f:          # truncated
        f.truncate(len(MAGIC) + 4)
    blob = open(paths[3], "rb").read()        # checksum
    with open(paths[3], "wb") as f:
        f.write(blob[:-1] + bytes([blob[-1] ^ 0xFF]))
    inner = b"\x80\x04 not a pickle"          # unpickle
    with open(paths[4], "wb") as f:
        f.write(MAGIC + hashlib.blake2s(inner).hexdigest().encode()
                + b"\n" + inner)
    inner = pickle.dumps({"schema": 999, "type": "submit", "seq": 5})
    with open(paths[5], "wb") as f:           # schema (wrong era)
        f.write(MAGIC + hashlib.blake2s(inner).hexdigest().encode()
                + b"\n" + inner)
    os.makedirs(j.path_for(99))               # io (unreadable entry)

    before = M.snapshot()
    view = JobJournal(str(tmp_path / "j")).replay()
    assert set(view) == {"job-0000"}
    for reason in ("magic", "truncated", "checksum", "unpickle",
                   "schema", "io"):
        key = "journal.rejects{reason=%s}" % reason
        assert delta(before, key) == 1, reason


def test_journal_write_fail_absorbed_and_degrades(tmp_path):
    """The chaos site fires INSIDE the writeguard seam: a one-shot
    fault is absorbed by the retry (segment still promoted); a
    persistent fault exhausts the retries and degrades to a counted
    drop — append never raises."""
    j = JobJournal(str(tmp_path / "j"))
    faults.arm("journal.write_fail", "*", 1)
    before = M.snapshot()
    path = j.append("submit", job_id="job-0000", tenant="t",
                    spec={}, nsteps=1)
    assert path is not None and os.path.exists(path)
    assert delta(
        before, "resilience.write_retries{site=fleet-journal}") >= 1
    assert delta(before, "journal.append_failures{type=submit}") == 0

    faults.clear()
    faults.arm("journal.write_fail", "*", 99)
    before = M.snapshot()
    assert j.append("submit", job_id="job-0001", tenant="t",
                    spec={}, nsteps=1) is None
    assert delta(before, "journal.append_failures{type=submit}") == 1
    faults.clear()
    # the healthy record survives, the dropped one never landed
    assert set(JobJournal(str(tmp_path / "j")).replay()) == {"job-0000"}


# -- crash-restart recovery -------------------------------------------------


def test_crash_restart_recovery_bitwise_and_idempotent(tmp_path):
    """A journaled server abandoned mid-flight resumes on a fresh
    server with bitwise-identical QoI; a second recover() is a no-op."""
    specs = [_tgv_spec(), _tgv_spec(cfl=0.28)]
    ctl, ids = _control(tmp_path, specs)
    ctl_qoi = _qoi(ctl, ids)

    crashy = _server(tmp_path, "crash", journal=True)
    got = [crashy.submit(f"t{i}", sc) for i, sc in enumerate(specs)]
    assert got == ids
    _run_two_boundaries(crashy)
    assert all(crashy._jobs[j].status == RUNNING for j in ids)

    fresh = _server(tmp_path, "crash", journal=True)
    before = M.snapshot()
    rec = fresh.recover()
    assert rec == {"replayed": 2, "remembered": 0, "requeued": 0,
                   "resumed": 2}
    assert delta(
        before, "fleet.recovered_jobs{outcome=resumed}") == 2
    fresh.drain()
    assert all(fresh._jobs[j].status == DONE for j in ids)
    for j in ids:
        assert fresh._jobs[j].qoi_bytes() == ctl_qoi[j], j
    # idempotent: the journal now also holds the terminal records, and
    # every id is known — a second replay changes nothing
    again = fresh.recover()
    assert again == {"replayed": 0, "remembered": 0, "requeued": 0,
                     "resumed": 0}
    dur = fresh.health()["durability"]
    assert dur["journal"]["segments"] >= 4
    assert dur["recovered"] == again


def test_recover_requeues_unplaced_jobs(tmp_path):
    """Jobs journaled at submit but never placed (no snapshot) restart
    from step 0 — still bitwise (same executable, same init)."""
    specs = [_tgv_spec(nsteps=8)]
    ctl, ids = _control(tmp_path, specs)
    crashy = _server(tmp_path, "crash", journal=True)
    assert [crashy.submit("t0", specs[0])] == ids
    # abandoned before any scheduling pass: only the submit record

    fresh = _server(tmp_path, "crash", journal=True)
    rec = fresh.recover()
    assert rec["requeued"] == 1 and rec["resumed"] == 0
    assert fresh._jobs[ids[0]].status == QUEUED
    fresh.drain()
    assert fresh._jobs[ids[0]].qoi_bytes() == ctl._jobs[ids[0]].qoi_bytes()


def test_recover_remembers_terminal_jobs(tmp_path):
    """A fully-drained journal replays as remembered terminals: rows
    restored from the terminal record, nothing re-runs, no duplicate
    SLO fold."""
    specs = [_tgv_spec(nsteps=8), _tgv_spec(nsteps=8, cfl=0.28)]
    srv1 = _server(tmp_path, "wd", journal=True)
    ids = [srv1.submit(f"t{i}", sc) for i, sc in enumerate(specs)]
    srv1.drain()
    qoi = _qoi(srv1, ids)

    srv2 = _server(tmp_path, "wd", journal=True)
    before = M.snapshot()
    rec = srv2.recover()
    assert rec["remembered"] == 2 and rec["resumed"] == 0
    assert delta(
        before, "fleet.recovered_jobs{outcome=remembered}") == 2
    assert delta(before, "fleet.duplicate_terminals") == 0
    for j in ids:
        assert srv2._jobs[j].status == DONE
        assert srv2._jobs[j].qoi_bytes() == qoi[j]
    # a remembered terminal is settled state: cancel() leaves it alone
    assert srv2.cancel(ids[0]) is False
    assert srv2._jobs[ids[0]].status == DONE


# -- terminal idempotence (regression) --------------------------------------


def test_job_terminal_idempotent(tmp_path):
    """The _terminal_done guard: a second terminal arrival is a
    counted no-op, never a double SLO fold or journal record."""
    srv = _server(tmp_path, "wd", journal=True)
    jid = srv.submit("t0", _tgv_spec())
    assert srv.cancel(jid) is True
    job = srv._jobs[jid]
    assert job.status == CANCELLED
    e2e_key = "fleet.job_e2e_s{tenant=t0}.count"
    before = M.snapshot()
    srv._job_terminal(job)  # the double-arrival seam, forced
    assert delta(before, "fleet.duplicate_terminals") == 1
    assert delta(before, e2e_key) == 0
    # a second cancel of a terminal job reports no state change
    assert srv.cancel(jid) is False
    assert job.status == CANCELLED


def test_cancel_after_migration_single_terminal(tmp_path):
    """Cancel racing a migration resolves to exactly one terminal
    state per server: MIGRATED on the source wins, the destination's
    copy cancels independently."""
    specs = [_tgv_spec(), _tgv_spec(cfl=0.28)]
    src = _server(tmp_path, "src", journal=True)
    ids = [src.submit(f"t{i}", sc) for i, sc in enumerate(specs)]
    _run_two_boundaries(src)
    dst = _server(tmp_path, "dst", journal=True)

    before = M.snapshot()
    migrate_job(src, dst, ids[0])
    assert src._jobs[ids[0]].status == MIGRATED
    # the source's copy is terminal: cancel is a no-op, not a second
    # terminal transition
    assert src.cancel(ids[0]) is False
    assert src._jobs[ids[0]].status == MIGRATED
    # the destination's copy is live and cancels exactly once
    assert dst._jobs[ids[0]].status == RUNNING
    assert dst.cancel(ids[0]) is True
    assert dst._jobs[ids[0]].status == CANCELLED
    assert dst.cancel(ids[0]) is False
    assert delta(before, "fleet.duplicate_terminals") == 0
    src.drain()
    assert src._jobs[ids[1]].status == DONE


# -- live migration ---------------------------------------------------------


def test_migrate_job_bitwise(tmp_path):
    """A RUNNING lane checkpointed off server A and finished on server
    B reproduces the control's QoI bytes exactly."""
    specs = [_tgv_spec(), _tgv_spec(cfl=0.28)]
    ctl, ids = _control(tmp_path, specs)
    src = _server(tmp_path, "src", journal=True)
    assert [src.submit(f"t{i}", sc)
            for i, sc in enumerate(specs)] == ids
    _run_two_boundaries(src)
    dst = _server(tmp_path, "dst", journal=True)

    before = M.snapshot()
    assert migrate_job(src, dst, ids[0]) == ids[0]
    assert delta(before, "fleet.migrations") == 1
    assert src.migrations == 0 and dst.migrations == 1
    dst.drain()
    src.drain()
    assert dst._jobs[ids[0]].qoi_bytes() == ctl._jobs[ids[0]].qoi_bytes()
    assert src._jobs[ids[1]].qoi_bytes() == ctl._jobs[ids[1]].qoi_bytes()


def test_drain_for_shutdown_migrates_and_closes_admission(tmp_path):
    specs = [_tgv_spec(), _tgv_spec(cfl=0.28)]
    ctl, ids = _control(tmp_path, specs)
    src = _server(tmp_path, "src", journal=True)
    assert [src.submit(f"t{i}", sc)
            for i, sc in enumerate(specs)] == ids
    _run_two_boundaries(src)
    dst = _server(tmp_path, "dst", journal=True)
    report = drain_for_shutdown(src, target=dst)
    assert sorted(report["migrated"]) == sorted(ids)
    assert report["journaled"] == [] and report["queued"] == []
    with pytest.raises(FleetAdmissionError) as exc:
        src.submit("late", _tgv_spec())
    assert exc.value.reason == "draining"
    dst.drain()
    for j in ids:
        assert dst._jobs[j].qoi_bytes() == ctl._jobs[j].qoi_bytes()


def test_drain_for_shutdown_journals_without_target(tmp_path):
    """No target: every RUNNING lane gets a final settled snapshot, so
    a later restart resumes it — the scale-in handoff to recover()."""
    specs = [_tgv_spec()]
    ctl, ids = _control(tmp_path, specs)
    src = _server(tmp_path, "wd", journal=True)
    assert [src.submit("t0", specs[0])] == ids
    _run_two_boundaries(src)
    report = drain_for_shutdown(src)
    assert report["journaled"] == ids and report["migrated"] == []

    fresh = _server(tmp_path, "wd", journal=True)
    rec = fresh.recover()
    assert rec["resumed"] == 1
    fresh.drain()
    assert fresh._jobs[ids[0]].qoi_bytes() == ctl._jobs[ids[0]].qoi_bytes()


# -- journal-off legacy -----------------------------------------------------


def test_journal_off_bitwise_legacy(tmp_path, monkeypatch):
    """CUP3D_FLEET_JOURNAL=0 serves bitwise-identically to the
    journaled path and writes no journal directory."""
    specs = [_tgv_spec(nsteps=8), _tgv_spec(nsteps=8, cfl=0.28)]
    on = _server(tmp_path, "on", journal=True)
    ids = [on.submit(f"t{i}", sc) for i, sc in enumerate(specs)]
    on.drain()
    assert os.path.isdir(os.path.join(on.workdir, "journal"))

    monkeypatch.setenv("CUP3D_FLEET_JOURNAL", "0")
    off = _server(tmp_path, "off", journal=None)
    assert off.journal is None
    assert [off.submit(f"t{i}", sc)
            for i, sc in enumerate(specs)] == ids
    off.drain()
    assert not os.path.isdir(os.path.join(off.workdir, "journal"))
    for j in ids:
        assert off._jobs[j].qoi_bytes() == on._jobs[j].qoi_bytes()
    assert off.health()["durability"]["journal"] is None


# -- compile-service death path ---------------------------------------------


@pytest.mark.parametrize("submit_beside", [False, True])
def test_compile_service_death_reaped_and_restartable(submit_beside):
    """A worker killed mid-build leaves its task orphaned RUNNING;
    fail_orphans marks it FAILED (counted), drain() stops parking, and
    a resubmit restarts the worker and succeeds.  ``submit_beside`` is
    the fleet's race: a later submit starts a NEW worker before anyone
    reaps, and the orphan is judged by the thread that took it, so the
    live one does not hide it."""
    from cup3d_tpu.aot.compiler import CompileService

    svc = CompileService("test-die")
    faults.arm("compile.service_die", "*", 1)
    before = M.snapshot()
    assert svc.submit(("k", 1), lambda: "built", name="probe")
    if submit_beside:
        svc._thread.join(timeout=10.0)
        assert svc.submit(("k", 2), lambda: "built", name="probe")
        # what /health shows of the state that parked the suite
        health = svc.state()
        assert health["worker_alive"] is True
        assert health["tasks"].get("orphaned") == 1, health
    assert svc.drain(timeout=10.0), svc.state()
    assert svc.status(("k", 1)) == "failed"
    assert delta(before, "aot.service_fallbacks") == 1
    assert svc.state()["worker_alive"] is submit_beside
    if submit_beside:
        assert svc.take(("k", 2)) == "built"
    # a failed key may be resubmitted: the worker restarts and builds
    assert svc.submit(("k", 1), lambda: "built", name="probe")
    assert svc.drain(timeout=10.0)
    assert svc.take(("k", 1)) == "built"


def test_serve_falls_back_inline_when_service_dies(tmp_path, monkeypatch):
    """The round-23 satellite: with the background compile worker dead,
    serve() reaps the orphaned build and compiles inline instead of
    parking on service.wait() forever — the job still finishes."""
    monkeypatch.setenv("CUP3D_AOT_STORE", str(tmp_path / "store"))
    faults.arm("compile.service_die", "*", 1)
    before = M.snapshot()
    srv = FleetServer(workdir=str(tmp_path / "wd"))
    ids = [srv.submit(f"t{i}", _tgv_spec(nsteps=8)) for i in range(2)]
    srv.drain()
    assert all(srv._jobs[j].status == DONE for j in ids)
    assert delta(before, "aot.service_fallbacks") >= 1


# -- the full subprocess drill (slow) ---------------------------------------


@pytest.mark.slow
def test_crash_restart_drill_subprocess(tmp_path):
    """Kill -9-grade death (os._exit(23) via the server.crash chaos
    site) of a serving subprocess; a ``fleet recover`` CLI restart
    against the same workdir finishes every job with QoI bytes bitwise
    equal to an unfaulted control and ZERO advance compiles against
    the store the crashed run warmed (RecompileCounter + aot.compile_s
    counted in the recover report)."""
    spec_path = str(tmp_path / "spec.json")
    with open(spec_path, "w") as f:
        json.dump([_tgv_spec(tenant=f"drill-{i}") for i in range(2)], f)
    drill = os.path.join(REPO, "tools", "chaosdrill.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CUP3D_AOT_STORE=str(tmp_path / "store"),
               CUP3D_SNAP_EVERY="8")
    env.pop("CUP3D_FAULT", None)

    def serve(tag, journal, fault=None):
        e = dict(env)
        if fault:
            e["CUP3D_FAULT"] = fault
        return subprocess.run(
            [sys.executable, drill, "_serve",
             "--workdir", str(tmp_path / tag), "--spec", spec_path,
             "--lanes", "4", "--snap-every", "8",
             "--journal", "1" if journal else "0"],
            capture_output=True, text=True, env=e, timeout=120)

    ctl = serve("ctl", journal=False)
    assert ctl.returncode == 0, ctl.stderr[-400:]
    ctl_rep = json.loads(ctl.stdout)

    crash = serve("crash", journal=True, fault="server.crash@1")
    assert crash.returncode == 23, (crash.returncode, crash.stderr[-400:])

    rec = subprocess.run(
        [sys.executable, "-m", "cup3d_tpu", "fleet", "recover",
         "--workdir", str(tmp_path / "crash"), "--lanes", "4"],
        capture_output=True, text=True, env=env, timeout=120)
    assert rec.returncode == 0, rec.stderr[-400:]
    report = json.loads(rec.stdout)

    assert set(report["jobs"]) == set(ctl_rep["jobs"])  # zero lost
    assert all(st == "done" for st in report["jobs"].values())
    assert report["recovery"]["resumed"] == 2
    assert report["rows_blake2s"] == ctl_rep["rows_blake2s"]  # bitwise
    assert report["advance_compiles"] == 0  # warm store: no recompile
    assert report["recover_restart_s"] is not None
