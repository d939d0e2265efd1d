"""Fleet serving acceptance (cup3d_tpu/fleet/; VALIDATION.md "Round 14"):

- Batch-vs-solo equivalence: each fleet lane reproduces its solo
  megaloop run (same grid, same CFL chain) to the vmap-lowering
  tolerance — <= 1e-4 relative KE (observed ~5e-6 f32), positions to
  1e-5 — for both the TGV and the stefanfish pipelines.
- Isolation: a NaN injected into ONE lane leaves every other lane
  bitwise identical to the unfaulted batch while the faulted lane rolls
  back, recovers, and completes (the Round-14 acceptance criterion).
- Bucketed assembly: mixed workloads share executables — compiled
  vmapped advances <= #buckets, and a re-drain of the same signature
  recompiles nothing.
- Lifecycle: submit/poll/cancel/drain, padding lanes stay inert, the
  per-tenant summary and obs /health fleet state are coherent.
- Byte-stable fan-out: two identical drains produce bitwise-identical
  per-tenant QoI buffers.
- Continuous batching (round 17): work-conserving lane reseeding at
  K-boundaries — reseeds are bitwise non-interfering and compile-free,
  serve() admits submissions in-flight under quota/backpressure
  control, a failed lane reseeds with a fresh retry budget, and the
  CUP3D_FLEET_CONTINUOUS=0 generation-drain baseline stays
  bitwise-unchanged.
"""

import json

import numpy as np
import pytest

from cup3d_tpu.config import SimulationConfig
from cup3d_tpu.fleet.server import (
    CANCELLED,
    DONE,
    FAILED,
    QUEUED,
    FleetServer,
)
from cup3d_tpu.obs import metrics as M
from cup3d_tpu.resilience import faults
from cup3d_tpu.sim.simulation import Simulation
from tests._cases import mean_ke, tgv_spec


pytestmark = pytest.mark.usefixtures("clean_faults")


def _fish_spec(**kw):
    spec = dict(kind="fish", n=32, nsteps=8, cfl=0.3, L=0.3, T=1.0,
                xpos=0.5)
    spec.update(kw)
    return spec


def _solo_tgv(tmp, spec):
    """The solo-megaloop twin of a TGV lane: same grid, same CFL chain,
    scan path forced on (nsteps must be a multiple of K=8 so the solo
    run takes the scan path the fleet lane replicates)."""
    cfg = SimulationConfig(
        bpdx=1, bpdy=1, bpdz=1, block_size=spec["n"], levelMax=1,
        levelStart=0, extent=2 * np.pi, nu=0.02, CFL=spec["cfl"],
        nsteps=spec["nsteps"], tend=0.0, rampup=0, scan_k=8,
        initCond="taylorGreen", pipelined=True, verbose=False,
        freqDiagnostics=0, path4serialization=str(tmp),
    )
    sim = Simulation(cfg)
    sim.init()
    sim.simulate()
    return sim


def _solo_fish(tmp, spec):
    cfg = SimulationConfig(
        bpdx=1, bpdy=1, bpdz=1, block_size=spec["n"], levelMax=1,
        levelStart=0, extent=1.0, nu=1e-4, CFL=spec["cfl"],
        nsteps=spec["nsteps"], tend=0.0, rampup=0, scan_k=8,
        factory_content=(
            f"stefanfish L={spec['L']} T={spec['T']} xpos={spec['xpos']}"),
        dtype="float32", pipelined=True, verbose=False,
        freqDiagnostics=0, path4serialization=str(tmp),
    )
    sim = Simulation(cfg)
    sim.init()
    sim.simulate()
    return sim


def _drain(tmp, specs, **srv_kw):
    """Fresh server, one tenant per spec; returns (server, job_ids)."""
    srv = FleetServer(workdir=str(tmp), **srv_kw)
    ids = [srv.submit(f"tenant-{i}", sp) for i, sp in enumerate(specs)]
    srv.drain()
    return srv, ids


# -- batch-vs-solo equivalence ---------------------------------------------


def test_tgv_lanes_match_solo_scan(tmp_path):
    """Two TGV lanes with different CFL each reproduce their solo
    scan-path run; the only divergence allowed is vmap lowering."""
    specs = [tgv_spec(cfl=0.3), tgv_spec(cfl=0.25)]
    srv, ids = _drain(tmp_path / "fleet", specs)
    for i, (job_id, spec) in enumerate(zip(ids, specs)):
        assert srv.poll(job_id)["status"] == DONE
        solo = _solo_tgv(tmp_path / f"solo{i}", spec)
        lane = srv.lane_state(job_id)
        vel_f, vel_s = lane["vel"], np.asarray(solo.sim.state["vel"])
        ke_f, ke_s = mean_ke(vel_f), mean_ke(vel_s)
        assert abs(ke_f - ke_s) <= 1e-4 * max(abs(ke_s), 1e-12)
        np.testing.assert_allclose(vel_f, vel_s, rtol=0, atol=1e-4)
        assert np.isclose(float(lane["time"]), solo.sim.time, rtol=1e-4)
        assert np.isclose(float(lane["dt"]), solo.sim.dt, rtol=1e-4)
    # the two lanes really ran different dt chains
    t0 = srv.poll(ids[0])["time"]
    t1 = srv.poll(ids[1])["time"]
    assert t0 != t1


def test_fish_lanes_match_solo_scan(tmp_path):
    """Two stefanfish lanes swimming DIFFERENT gaits (T) in one
    executable each reproduce their solo run: KE to 1e-4 relative,
    positions to 1e-5."""
    specs = [_fish_spec(T=1.0), _fish_spec(T=0.9)]
    srv, ids = _drain(tmp_path / "fleet", specs)
    positions = []
    for i, (job_id, spec) in enumerate(zip(ids, specs)):
        assert srv.poll(job_id)["status"] == DONE
        solo = _solo_fish(tmp_path / f"solo{i}", spec)
        lane = srv.lane_state(job_id)
        ke_f, ke_s = mean_ke(lane["vel"]), mean_ke(solo.sim.state["vel"])
        assert abs(ke_f - ke_s) <= 1e-4 * max(abs(ke_s), 1e-12)
        pos_f = np.asarray(lane["rigid"][6:9], np.float64)
        pos_s = np.asarray(solo.sim.obstacles[0].position, np.float64)
        np.testing.assert_allclose(pos_f, pos_s, rtol=0, atol=1e-5)
        positions.append(pos_f)
    # distinct gaits -> distinct trajectories inside one executable
    assert not np.allclose(positions[0], positions[1], atol=1e-9)


# -- per-lane fault isolation ----------------------------------------------


def test_lane_nan_isolated_bitwise_and_recovers(tmp_path):
    """The Round-14 acceptance criterion: a NaN injected into lane 1
    leaves lanes 0 and 2 BITWISE identical to the unfaulted batch,
    while lane 1 rolls back to its snapshot, halves dt, and completes."""
    specs = [tgv_spec(cfl=0.3, nsteps=12), tgv_spec(cfl=0.28, nsteps=12),
             tgv_spec(cfl=0.25, nsteps=12)]
    ref, ref_ids = _drain(tmp_path / "ref", specs, snap_every=4)
    ref_lanes = [ref.lane_state(j) for j in ref_ids]

    faults.arm("fleet.lane_nan", 1, 1)  # poison lane 1's row chain once
    s0 = M.snapshot()
    flt, flt_ids = _drain(tmp_path / "flt", specs, snap_every=4)
    d = M.delta(s0)

    for lane in (0, 2):
        a, b = ref_lanes[lane], flt.lane_state(flt_ids[lane])
        assert sorted(a) == sorted(b)
        for key in a:
            np.testing.assert_array_equal(a[key], b[key], err_msg=key)
    # the faulted lane recovered: job done, budget spent, fields finite
    assert flt.poll(flt_ids[1])["status"] == DONE
    faulted = flt.lane_state(flt_ids[1])
    assert np.isfinite(faulted["vel"]).all()
    assert d["fleet.lane_faults{reason=nan-velocity}"] == 1
    assert d["fleet.lane_rollbacks{reason=nan-velocity}"] == 1
    assert d["fleet.lane_retires{reason=done}"] == 3
    assert d.get("fleet.lane_giveups{reason=nan-velocity}", 0) == 0
    assert flt.poll(flt_ids[1])["steps_done"] == 12


def test_step_nan_fault_recovers_without_collateral(tmp_path):
    """The solo seam (step.nan_velocity) fires inside the fleet
    consumer too: the lane that consumes the armed step first rolls
    back; every job still completes."""
    specs = [tgv_spec(cfl=0.3, nsteps=8), tgv_spec(cfl=0.25, nsteps=8)]
    faults.arm("step.nan_velocity", 2, 1)
    s0 = M.snapshot()
    srv, ids = _drain(tmp_path, specs, snap_every=4)
    d = M.delta(s0)
    assert d["fleet.lane_rollbacks{reason=nan-velocity}"] == 1
    for job_id in ids:
        assert srv.poll(job_id)["status"] == DONE
        assert np.isfinite(srv.lane_state(job_id)["vel"]).all()


def test_exhausted_lane_fails_alone(tmp_path):
    """A lane that faults past its retry budget is retired FAILED; the
    other tenants finish untouched."""
    specs = [tgv_spec(cfl=0.3), tgv_spec(cfl=0.25)]
    # the seam fires at lane >= armed, so poison the LAST lane to keep
    # the injection single-lane; every consumed row of lane 1 faults
    faults.arm("fleet.lane_nan", 1, 99)
    s0 = M.snapshot()
    srv, ids = _drain(tmp_path, specs, max_retries=2)
    d = M.delta(s0)
    assert srv.poll(ids[1])["status"] == FAILED
    assert srv.poll(ids[1])["error"] == "nan-velocity"
    assert srv.poll(ids[0])["status"] == DONE
    assert d["fleet.lane_giveups{reason=nan-velocity}"] == 1
    assert d["fleet.lane_retires{reason=failed}"] == 1
    summary = srv.tenant_summary()
    assert summary["tenant-1"]["statuses"] == {FAILED: 1}
    assert summary["tenant-0"]["statuses"] == {DONE: 1}


# -- bucketed assembly ------------------------------------------------------


def test_bucketed_assembly_bounds_compiles(tmp_path):
    """Four jobs in two shape classes -> two batches, and the compiled
    vmapped advance count is <= #buckets, not #jobs; a re-drain of the
    same signature serves from the executable cache with ZERO new
    compiles."""
    from cup3d_tpu.analysis import runtime as R

    srv = FleetServer(workdir=str(tmp_path))
    for spec in (tgv_spec(n=16, cfl=0.3), tgv_spec(n=16, cfl=0.25),
                 tgv_spec(n=24, cfl=0.3), tgv_spec(n=24, cfl=0.25)):
        srv.submit("t", spec)
    s0 = M.snapshot()
    with R.RecompileCounter() as rc:
        srv.drain()
    d = M.delta(s0)
    assert len(srv.batches) == 2
    assert rc.compiles.get("advance", 0) <= 2
    assert d["fleet.executable_builds"] == 2
    assert srv.jobs_by_status() == {DONE: 4}

    # same signature again: the cache serves the jit, nothing recompiles
    srv.submit("t", tgv_spec(n=16, cfl=0.28))
    srv.submit("t", tgv_spec(n=16, cfl=0.27))
    s0 = M.snapshot()
    with R.RecompileCounter() as rc2:
        srv.drain()
    d = M.delta(s0)
    assert rc2.compiles.get("advance", 0) == 0
    assert d["fleet.executable_hits"] == 1
    assert srv.jobs_by_status() == {DONE: 6}


# -- lifecycle + padding ----------------------------------------------------


def test_lifecycle_submit_poll_cancel_and_padding(tmp_path):
    """The tenant lifecycle end to end; cancelling one of 7 jobs leaves
    6, whose lane rung (7) carries one inert padding lane."""
    srv = FleetServer(workdir=str(tmp_path))
    with pytest.raises(ValueError):
        srv.submit("t", dict(kind="warp-drive", nsteps=4))
    with pytest.raises(ValueError):
        srv.submit("t", dict(kind="tgv"))  # no step budget
    ids = [srv.submit(f"t{i}", tgv_spec(cfl=0.3 - 0.01 * i))
           for i in range(7)]
    assert srv.poll(ids[0])["status"] == QUEUED
    assert srv.cancel(ids[3]) is True
    assert srv.poll(ids[3])["status"] == CANCELLED
    srv.drain()
    assert srv.jobs_by_status() == {DONE: 6, CANCELLED: 1}
    (batch,) = srv.batches
    assert batch.B == 7 and batch.running_lanes() == 0
    assert batch.jobs[6] is None  # the padding lane never had a tenant
    # terminal jobs are left alone
    assert srv.cancel(ids[0]) is False
    assert srv.poll(ids[0])["status"] == DONE
    health = srv.health()
    assert health["jobs"] == {DONE: 6, CANCELLED: 1}
    assert health["lanes_active"] == 0
    assert health["batches"] == 1 and health["executables"] == 1
    with pytest.raises(KeyError):
        srv.poll("job-9999")


# -- byte-stable per-tenant QoI ---------------------------------------------


def test_qoi_fanout_is_byte_stable(tmp_path):
    """Two identical drains produce bitwise-identical per-tenant QoI
    buffers: the fan-out ordering is deterministic, keyed by step."""
    specs = [tgv_spec(cfl=0.3), tgv_spec(cfl=0.25)]
    a_srv, a_ids = _drain(tmp_path / "a", specs)
    b_srv, b_ids = _drain(tmp_path / "b", specs)
    for a_id, b_id in zip(a_ids, b_ids):
        a_job, b_job = a_srv._jobs[a_id], b_srv._jobs[b_id]
        assert a_job.rows.shape == (8, a_job.batch.row_w)
        assert np.isfinite(a_job.rows).all()
        assert a_job.steps_done == a_job.nsteps
        assert a_job.qoi_bytes() == b_job.qoi_bytes()
    # distinct CFL -> distinct payloads (the bytes are not trivially 0)
    assert a_srv._jobs[a_ids[0]].qoi_bytes() != \
        a_srv._jobs[a_ids[1]].qoi_bytes()


# -- CLI + /health ----------------------------------------------------------


def test_fleet_cli_and_health_payload(tmp_path, capsys):
    """`python -m cup3d_tpu fleet --scenarios spec.json` drains the
    queue, prints the per-tenant summary JSON, and the live server
    surfaces in the obs /health payload."""
    from cup3d_tpu.__main__ import main as pkg_main
    from cup3d_tpu.obs.export import health_payload

    spec_path = tmp_path / "spec.json"
    spec_path.write_text(json.dumps({
        "scenarios": [dict(tgv_spec(cfl=0.3), tenant="acme"),
                      dict(tgv_spec(cfl=0.25))],
        "lanes": 8,
    }))
    with pytest.raises(SystemExit) as exc:
        pkg_main(["fleet", "--scenarios", str(spec_path),
                  "--workdir", str(tmp_path / "wd")])
    assert exc.value.code == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["acme"]["statuses"] == {DONE: 1}
    assert summary["tenant-1"]["statuses"] == {DONE: 1}
    assert summary["acme"]["steps_done"] == 8

    payload = health_payload()
    assert any(h["jobs"].get(DONE, 0) >= 1 and h["batches"] >= 1
               for h in payload["fleet"])
    assert any(k.startswith("fleet.") for k in payload["recovery_counters"])


# -- round 17: continuous batching ------------------------------------------


def test_legacy_drain_matches_continuous_no_arrivals(tmp_path):
    """With nothing submitted mid-flight the continuous serve loop is
    observationally identical to the legacy generation-drain: same
    statuses, byte-identical per-tenant QoI, zero reseeds — the
    CUP3D_FLEET_CONTINUOUS=0 baseline stays bitwise-unchanged."""
    specs = [tgv_spec(cfl=0.3), tgv_spec(cfl=0.25),
             tgv_spec(cfl=0.28, nsteps=16)]
    legacy, lid = _drain(tmp_path / "legacy", specs, continuous=False)
    cont, cid = _drain(tmp_path / "cont", specs, continuous=True)
    assert cont.reseeds == 0
    assert legacy.jobs_by_status() == cont.jobs_by_status() == {DONE: 3}
    for j1, j2 in zip(lid, cid):
        assert legacy._jobs[j1].qoi_bytes() == cont._jobs[j2].qoi_bytes()


def test_reseed_bitwise_non_interference(tmp_path):
    """Reseeding a freed lane leaves every OTHER lane leaf-for-leaf
    identical to a serve that never reseeds — the round-14 isolation
    contract extended to reseeding — and the spliced-in tenant
    completes on the reused lane."""
    # one bucket (nsteps 8 and 9 share the ×1.25 step rung): lane 0
    # retires after a single dispatch while lanes 1-2 still run
    specs = [tgv_spec(nsteps=8, cfl=0.3), tgv_spec(nsteps=9, cfl=0.25),
             tgv_spec(nsteps=9, cfl=0.28)]
    ref, rid = _drain(tmp_path / "ref", specs)

    srv = FleetServer(workdir=str(tmp_path / "srv"))
    ids = [srv.submit(f"tenant-{i}", sp) for i, sp in enumerate(specs)]
    late = {}

    def feed(server, tick):
        if "id" not in late and server.poll(ids[0])["status"] == DONE:
            late["id"] = server.submit(
                "late", tgv_spec(nsteps=8, cfl=0.2))
        return "id" not in late

    srv.serve(feed)
    assert srv.reseeds == 1
    assert srv.poll(late["id"])["status"] == DONE
    assert srv._jobs[late["id"]].lane == srv._jobs[ids[0]].lane == 0
    for jid, ref_jid in zip(ids[1:], rid[1:]):
        assert srv.poll(jid)["status"] == DONE
        mine, theirs = srv.lane_state(jid), ref.lane_state(ref_jid)
        assert sorted(mine) == sorted(theirs)
        for k in mine:
            np.testing.assert_array_equal(mine[k], theirs[k])
        assert (srv._jobs[jid].qoi_bytes()
                == ref._jobs[ref_jid].qoi_bytes())


def test_submit_during_serve_admission(tmp_path):
    """serve() accepts submissions in-flight: late jobs land in freed
    lanes of the live batch (cross-rung, so no new batch and no new
    executable) and the occupancy window closes into the gauge."""
    srv = FleetServer(workdir=str(tmp_path))
    srv.submit("t0", tgv_spec(nsteps=8))
    srv.submit("t0", tgv_spec(nsteps=32))
    stream = [tgv_spec(nsteps=8), tgv_spec(nsteps=8)]

    def feed(server, tick):
        if stream and server.queue_depth() == 0:
            server.submit("late", stream.pop(0))
        return bool(stream)

    s0 = M.snapshot()
    srv.serve(feed)
    d = M.delta(s0)
    assert srv.jobs_by_status() == {DONE: 4}
    assert srv.reseeds == 2
    assert d["fleet.reseeds{kind=tgv}"] == 2
    # rungs differ but (sig, cap, K) match: one executable, one build
    assert d["fleet.executable_builds"] == 1
    health = srv.health()
    assert health["scheduler"]["reseeds"] == 2
    assert health["scheduler"]["continuous"] is True
    assert health["admission"]["backpressure"] is False
    assert 0.0 < srv.last_occupancy <= 1.0
    assert d["fleet.busy_lane_steps"] <= d["fleet.total_lane_steps"]


def test_reseed_zero_recompile(tmp_path):
    """Reseeds are compile-free: a serve window with three reseeds
    compiles the vmapped advance exactly once (the single bucket) and
    the per-lane upload path traces once — steady-state reseeds touch
    neither."""
    from cup3d_tpu.analysis import runtime as R

    srv = FleetServer(workdir=str(tmp_path))
    srv.submit("t", tgv_spec(nsteps=8))
    srv.submit("t", tgv_spec(nsteps=32))
    stream = [tgv_spec(nsteps=8, cfl=0.3 - 0.01 * i) for i in range(3)]

    def feed(server, tick):
        if stream and server.queue_depth() == 0:
            server.submit("late", stream.pop(0))
        return bool(stream)

    s0 = M.snapshot()
    with R.RecompileCounter() as rc:
        srv.serve(feed)
    d = M.delta(s0)
    assert srv.jobs_by_status() == {DONE: 5}
    assert srv.reseeds == 3
    assert rc.compiles.get("advance", 0) == 1
    assert d["fleet.executable_builds"] == 1


def test_lane_nan_fault_then_reseed_same_lane(tmp_path):
    """A lane whose tenant exhausts its retry budget retires FAILED,
    then is reseeded with fresh work on the SAME lane: the new tenant
    starts with a full retry budget and completes cleanly."""
    srv = FleetServer(workdir=str(tmp_path), max_retries=0)
    # one bucket (8 and 9 share the step rung): the batch stays live
    # on lane 1 while lane 0 fails and is reseeded
    doomed = srv.submit("t", tgv_spec(nsteps=8, cfl=0.3))
    other = srv.submit("t", tgv_spec(nsteps=9, cfl=0.25))
    faults.arm("fleet.lane_nan", 0, 1)
    late = {}

    def feed(server, tick):
        if "id" not in late and server.poll(doomed)["status"] == FAILED:
            late["id"] = server.submit(
                "late", tgv_spec(nsteps=8, cfl=0.2))
        return "id" not in late

    s0 = M.snapshot()
    srv.serve(feed)
    d = M.delta(s0)
    assert srv.poll(doomed)["status"] == FAILED
    assert srv.poll(other)["status"] == DONE
    assert srv.poll(late["id"])["status"] == DONE
    assert d["fleet.lane_giveups{reason=nan-velocity}"] == 1
    job = srv._jobs[late["id"]]
    assert job.lane == srv._jobs[doomed].lane == 0
    assert job.batch is srv._jobs[doomed].batch
    assert job.steps_done == job.nsteps
    # fresh retry budget on the reseeded lane
    assert job.batch.guard.attempts[0] == 0
    assert job.batch.guard.fail_step[0] == -1


def test_admission_quota_and_backpressure(tmp_path):
    """Per-tenant quota and max-queue-depth backpressure reject at
    submit() with typed reasons, count into fleet.admission_rejects,
    and surface in health()["admission"]."""
    from cup3d_tpu.fleet.server import FleetAdmissionError

    srv = FleetServer(workdir=str(tmp_path), tenant_quota=2)
    srv.submit("a", tgv_spec())
    srv.submit("a", tgv_spec())
    s0 = M.snapshot()
    with pytest.raises(FleetAdmissionError) as exc:
        srv.submit("a", tgv_spec())
    assert exc.value.reason == "quota"
    srv.submit("b", tgv_spec())  # other tenants unaffected
    assert M.delta(s0)["fleet.admission_rejects{reason=quota}"] == 1

    srv2 = FleetServer(workdir=str(tmp_path), max_queue_depth=2)
    srv2.submit("a", tgv_spec())
    srv2.submit("b", tgv_spec())
    assert srv2.health()["admission"]["backpressure"] is True
    s0 = M.snapshot()
    with pytest.raises(FleetAdmissionError) as exc:
        srv2.submit("c", tgv_spec())
    assert exc.value.reason == "queue-full"
    assert M.delta(s0)["fleet.admission_rejects{reason=queue-full}"] == 1


def test_cancel_running_verifies_lane_state(tmp_path):
    """cancel() on a RUNNING job reports whether cancel_lane actually
    changed lane state: a lane that no longer holds the job returns
    False instead of the old unconditional True."""
    srv = FleetServer(workdir=str(tmp_path), continuous=False)
    jid = srv.submit("t", tgv_spec(nsteps=64))
    srv.assemble()
    assert srv.poll(jid)["status"] == "running"
    assert srv.cancel(jid) is True
    assert srv.poll(jid)["status"] == CANCELLED
    assert srv.cancel(jid) is False

    # a stale handle: the batch lane no longer holds the job (as after
    # a swap), so the guarded retire is a no-op and cancel must say so
    jid2 = srv.submit("t", tgv_spec(nsteps=64))
    srv.assemble()
    job2 = srv._jobs[jid2]
    job2.batch.jobs[job2.lane] = None
    assert srv.cancel(jid2) is False
