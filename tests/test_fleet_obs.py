"""Fleet serving observatory acceptance (VALIDATION.md "Round 16"):

- Job-lifecycle timelines: every drained job leaves a kind="job" trace
  record whose event sequence is ordered and monotonic across the
  submit, cancel, and fault paths, plus a pid-3 lane-occupancy span in
  the Perfetto export carrying the job id.
- Fault isolation in the observatory: a NaN-faulted lane emits rollback
  events on ITS timeline; the other lanes' timelines are unchanged.
- Streaming quantiles: the fixed log-bucket histogram estimates p50/p95
  within one bucket width (~33%) of the exact sample quantile.
- Live /metrics: a real HTTP scrape exposes per-tenant cumulative
  ``_bucket{le=...}`` lines that parse back as conformant histograms.
- SLO burn rate: a job whose end-to-end latency exceeds the target p99
  bumps the per-tenant breach counter and a nonzero burn rate.
"""

import json
import os
import tempfile
import urllib.request

import numpy as np
import pytest

from cup3d_tpu.fleet.server import DONE, FleetServer
from cup3d_tpu.obs import export as E
from cup3d_tpu.obs import metrics as M
from cup3d_tpu.obs import trace as OT
from cup3d_tpu.resilience import faults
from tests._cases import tgv_spec


pytestmark = pytest.mark.usefixtures("clean_faults")


def _job_records(trace_dir):
    path = os.path.join(trace_dir, "trace.jsonl")
    with open(path) as f:
        records = [json.loads(line) for line in f if line.strip()]
    for rec in records:
        assert not OT.validate_step_record(rec), rec
    return [r for r in records if r.get("kind") == "job"]


@pytest.fixture(scope="module")
def drained():
    """One traced drain shared by the timeline + scrape tests: two done
    tenants, one job cancelled while queued."""
    td = tempfile.mkdtemp(prefix="cup3d-fleetobs-")
    OT.TRACE.configure(enabled=True, directory=td)
    try:
        srv = FleetServer(workdir=os.path.join(td, "wd"))
        done_ids = [srv.submit("acme", tgv_spec(cfl=0.3)),
                    srv.submit("zeta", tgv_spec(cfl=0.25))]
        cancel_id = srv.submit("acme", tgv_spec(cfl=0.28))
        assert srv.cancel(cancel_id) is True
        srv.drain()
        OT.TRACE.close()  # flush trace.jsonl + write trace.pfto.json
        yield srv, done_ids, cancel_id, td
    finally:
        OT.TRACE.configure(enabled=False)


# -- job-lifecycle timelines ------------------------------------------------


def test_job_timelines_ordered_and_monotonic(drained):
    """Done jobs carry the full lifecycle in order; the cancelled job
    stops at submitted -> queued -> cancelled; timestamps never
    decrease within a timeline."""
    srv, done_ids, cancel_id, td = drained
    jobs = {r["job_id"]: r for r in _job_records(td)}
    assert set(jobs) == set(done_ids) | {cancel_id}
    for job_id in done_ids:
        rec = jobs[job_id]
        assert rec["status"] == DONE and rec["step"] == 8
        names = [n for n, _ in rec["events"]]
        assert names == ["submitted", "queued", "bucketed", "running",
                         "dispatched", "fanout", "retire", "done"]
        times = [t for _, t in rec["events"]]
        assert times == sorted(times)
        assert rec["bucket"].startswith("tgv-")
        assert rec["durations"]["e2e_s"] >= rec["durations"]["exec_s"] >= 0
    cancelled = jobs[cancel_id]
    assert [n for n, _ in cancelled["events"]] == [
        "submitted", "queued", "cancelled"]


def test_lane_occupancy_tracks_in_perfetto_export(drained):
    """The merged export grows pid-3 lane tracks: a process_name
    metadata event, one occupancy span per done job carrying its
    job id, spans non-overlapping per track — and the trace_check
    validator accepts the whole artifact."""
    import subprocess
    import sys

    srv, done_ids, cancel_id, td = drained
    with open(os.path.join(td, "trace.pfto.json")) as f:
        events = json.load(f)["traceEvents"]
    lane = [e for e in events if e.get("pid") == OT.LANE_PID]
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in lane)
    spans = [e for e in lane if e["ph"] == "X"]
    assert {e["args"]["job_id"] for e in spans} == set(done_ids)
    for e in spans:
        assert e["dur"] >= 0 and e["args"]["status"] == DONE
    # the cancelled job never occupied a lane -> no span for it
    assert cancel_id not in {e["args"]["job_id"] for e in spans}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "trace_check.py"),
         os.path.join(td, "trace.jsonl")],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "job-lifecycle records" in proc.stdout


def test_faulted_lane_rolls_back_alone(tmp_path):
    """A NaN injected into lane 1 puts rollback events on THAT job's
    timeline; lane 0's timeline shows none and both jobs complete."""
    td = str(tmp_path)
    OT.TRACE.configure(enabled=True, directory=td)
    try:
        faults.arm("fleet.lane_nan", 1, 1)
        srv = FleetServer(workdir=os.path.join(td, "wd"), snap_every=4)
        ids = [srv.submit("t0", tgv_spec(cfl=0.3, nsteps=12)),
               srv.submit("t1", tgv_spec(cfl=0.28, nsteps=12))]
        srv.drain()
        OT.TRACE.close()
    finally:
        OT.TRACE.configure(enabled=False)
    jobs = {r["job_id"]: r for r in _job_records(td)}
    clean = [n for n, _ in jobs[ids[0]]["events"]]
    faulted = [n for n, _ in jobs[ids[1]]["events"]]
    assert "rollback" in faulted and faulted[-1] == DONE
    assert "rollback" not in clean
    assert clean == ["submitted", "queued", "bucketed", "running",
                     "dispatched", "fanout", "retire", "done"]
    assert jobs[ids[1]]["step"] == 12  # recovered and finished


# -- streaming quantiles ----------------------------------------------------


def test_quantile_estimates_within_one_bucket_width():
    """The log-ladder guarantee: 8 buckets/decade puts any estimate
    within one bucket width (a 10^(1/8) ~ 1.33x factor) of the exact
    sample quantile; min/max are exact at the extremes."""
    h = M.histogram("t16.quant", case="ladder")
    vals = [0.0013 * (i + 1) for i in range(1000)]  # 1.3 ms .. 1.3 s
    for v in vals:
        h.observe(v)
    width = 10.0 ** (1.0 / M.BUCKETS_PER_DECADE)
    for q in (0.5, 0.9, 0.95, 0.99):
        exact = float(np.quantile(vals, q))
        est = h.quantile(q)
        assert exact / width <= est <= exact * width, (q, est, exact)
    assert min(vals) <= h.quantile(0.0) <= min(vals) * width
    assert max(vals) / width <= h.quantile(1.0) <= max(vals)


# -- live /metrics scrape ---------------------------------------------------


def test_metrics_scrape_exposes_per_tenant_buckets(drained):
    """A real HTTP scrape: per-tenant fleet.job_e2e_s renders as a
    conformant histogram family (cumulative le buckets, _sum, _count)
    and round-trips through parse_histograms."""
    srv, done_ids, _, _ = drained
    ex = E.MetricsExporter(port=0).start()
    try:
        body = urllib.request.urlopen(ex.url + "/metrics").read().decode()
    finally:
        ex.stop()
    assert 'le="+Inf"' in body
    fams = E.parse_histograms(body)
    for tenant in ("acme", "zeta"):
        keys = [k for k in fams
                if k[0] == "cup3d_fleet_job_e2e_s"
                and ("tenant", tenant) in k[1]]
        assert keys, (tenant, sorted(fams))
        fam = fams[keys[0]]
        assert fam["count"] >= 1 and fam["sum"] >= 0
        cums = [c for _, c in fam["buckets"]]
        assert cums == sorted(cums)  # cumulative, ending at +Inf=count
        assert fam["buckets"][-1][0] == float("inf")
        assert fam["buckets"][-1][1] == fam["count"]
    # the legacy flat keys stay in snapshot() for existing consumers
    snap = M.snapshot()
    assert any(k.startswith("fleet.job_e2e_s{") and k.endswith(".count")
               for k in snap)


# -- SLO burn rate ----------------------------------------------------------


def test_burn_rate_fires_when_latency_exceeds_slo(tmp_path):
    """With the target p99 forced below any real drain latency, every
    job breaches: the per-tenant breach counter fires and slo_status
    reports a nonzero burn rate; /health carries the block."""
    s0 = M.snapshot()
    srv = FleetServer(workdir=str(tmp_path), slo_p99_s=1e-6,
                      slo_window=10)
    srv.submit("burny", tgv_spec(cfl=0.3))
    srv.drain()
    d = M.delta(s0)
    assert d.get("fleet.slo_breaches{tenant=burny}", 0) == 1
    slo = srv.slo_status()
    assert slo["target_p99_s"] == pytest.approx(1e-6)
    burny = slo["tenants"]["burny"]
    assert burny["jobs"] == 1 and burny["breaches"] == 1
    assert burny["breach_fraction"] == 1.0
    assert burny["burn_rate"] == pytest.approx(1.0 / srv.SLO_ERROR_BUDGET)
    assert burny["quantiles"]["p99"] > 1e-6
    health = srv.health()
    assert health["slo"]["tenants"]["burny"]["breaches"] == 1


# -- latency provenance (round 22) ------------------------------------------


def _assert_partition(rec):
    """The partition invariant: the phases block uses only catalog
    phases, is non-negative, and sums to the event span exactly (float
    eps) — no leftover, no double counting."""
    phases = rec["phases"]
    assert phases and set(phases) <= set(OT.JOB_PHASES)
    assert all(v >= 0.0 for v in phases.values())
    times = [t for _, t in rec["events"]]
    span = times[-1] - times[0]
    assert sum(phases.values()) == pytest.approx(span, rel=1e-9, abs=1e-12)


def test_phase_decomposition_partitions_e2e(drained):
    """Every terminal job record carries a phases block summing to its
    event span — done and cancelled fates alike; a job that never ran
    has no dispatch mass."""
    srv, done_ids, cancel_id, td = drained
    jobs = {r["job_id"]: r for r in _job_records(td)}
    for job_id in done_ids:
        _assert_partition(jobs[job_id])
        assert jobs[job_id]["phases"]["dispatch"] > 0
    cancelled = jobs[cancel_id]
    _assert_partition(cancelled)
    assert "dispatch" not in cancelled["phases"]
    # the live-server view agrees with the trace record
    for job_id in done_ids:
        live = srv._jobs[job_id].phases()
        assert live == pytest.approx(jobs[job_id]["phases"])


def test_phase_decomposition_requeue_and_unknown_events():
    """The pure decomposition on a requeued-after-shard-loss timeline:
    the loss->requeue gap lands in rollback_retry, the second queue
    stretch back in capacity_wait, and the partition still closes.
    Unknown event names degrade to the retire bucket, never crash."""
    events = [("submitted", 0.0), ("queued", 0.5), ("bucketed", 1.0),
              ("running", 1.5), ("shard_lost", 2.0), ("queued", 2.25),
              ("running", 3.0), ("retire", 3.5), ("done", 3.75)]
    ph = OT.phase_decomposition(events)
    assert sum(ph.values()) == pytest.approx(3.75)
    assert ph["rollback_retry"] == pytest.approx(0.25)
    assert ph["capacity_wait"] == pytest.approx(1.25)  # both waits
    assert ph["dispatch"] == pytest.approx(1.0)        # both runs
    assert ph["admission"] == pytest.approx(0.5)
    assert ph["assembly"] == pytest.approx(0.5)
    assert ph["retire"] == pytest.approx(0.25)
    weird = OT.phase_decomposition(
        [("submitted", 0.0), ("comet_strike", 1.0), ("done", 2.0)])
    assert weird["retire"] == pytest.approx(1.0)
    assert sum(weird.values()) == pytest.approx(2.0)


def test_failed_job_partitions_with_rollback_mass(tmp_path):
    """A lane that faults past its retry budget retires FAILED with a
    phases block whose rollback_retry mass is nonzero — and the
    partition invariant holds on the failed fate too."""
    td = str(tmp_path)
    OT.TRACE.configure(enabled=True, directory=td)
    try:
        faults.arm("fleet.lane_nan", 1, 99)
        srv = FleetServer(workdir=os.path.join(td, "wd"),
                          max_retries=2, snap_every=4)
        ids = [srv.submit("t0", tgv_spec(cfl=0.3, nsteps=12)),
               srv.submit("t1", tgv_spec(cfl=0.28, nsteps=12))]
        srv.drain()
        OT.TRACE.close()
    finally:
        OT.TRACE.configure(enabled=False)
    jobs = {r["job_id"]: r for r in _job_records(td)}
    assert jobs[ids[1]]["status"] == "failed"
    _assert_partition(jobs[ids[1]])
    assert jobs[ids[1]]["phases"]["rollback_retry"] > 0
    _assert_partition(jobs[ids[0]])
    assert "rollback_retry" not in jobs[ids[0]]["phases"]


def test_burn_attribution_names_dominant_phase(tmp_path):
    """With every job breaching, slo_status attaches the per-tenant
    burn attribution: phase shares sum to 1, the dominant phase is a
    catalog phase, and the per-phase quantiles are coherent."""
    srv = FleetServer(workdir=str(tmp_path), slo_p99_s=1e-6,
                      slo_window=10)
    # warm the signature under a throwaway tenant so the measured
    # job's assembly phase is a cache hit — otherwise the XLA compile
    # lands in assembly and can out-weigh dispatch on a loaded machine
    srv.submit("warmup", tgv_spec(cfl=0.3))
    srv.drain()
    # 64 steps, not 8: with a few ms of dispatch a hiccup between submit
    # and the scheduling pass (capacity_wait) won a third of 48 runs
    # under load, on this tree and its parent alike; at 64, none
    srv.submit("burny", tgv_spec(cfl=0.3, nsteps=64))
    srv.drain()
    attr = srv.slo_status()["tenants"]["burny"]["attribution"]
    assert attr["dominant_phase"] in OT.JOB_PHASES
    shares = {ph: d["share"] for ph, d in attr["phases"].items()}
    # shares are reported rounded to 4 decimals — allow one rounding
    # ulp per phase in the sum
    assert sum(shares.values()) == pytest.approx(
        1.0, abs=5e-4 * len(OT.JOB_PHASES))
    assert attr["dominant_phase"] == max(shares, key=shares.get)
    for ph, d in attr["phases"].items():
        assert ph in OT.JOB_PHASES
        assert 0 <= d["share"] <= 1
        # a phase with window mass has a quantile; unseen phases (share
        # 0) report None, not a fabricated number
        if d["share"] > 0:
            assert d["p99_s"] >= 0
    # the dispatch phase dominates a healthy single-job drain (the
    # compute IS the latency here)
    assert attr["dominant_phase"] == "dispatch"
    pq = srv.phase_quantiles(tenant="burny")
    assert pq["dispatch"]["p99"] > 0
    assert set(pq) <= set(OT.JOB_PHASES)


def test_provenance_knob_disables_phase_records(tmp_path):
    """CUP3D_FLEET_PROVENANCE=0 / provenance=False: no phase
    histograms, no share history — the decomposition stays available
    on demand via job.phases()."""
    s0 = M.snapshot()
    srv = FleetServer(workdir=str(tmp_path), provenance=False)
    jid = srv.submit("quiet", tgv_spec())
    srv.drain()
    d = M.delta(s0)
    assert not any(v for k, v in d.items()
                   if k.startswith("fleet.latency_phase_s"))
    assert srv._phase_share_history == {}
    assert sum(srv._jobs[jid].phases().values()) > 0
