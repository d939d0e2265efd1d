"""Observability subsystem (cup3d_tpu/obs/): metrics registry, span
tracer + step traces, and the flight recorder — unit tests plus the
ISSUE 4 acceptance paths on live drivers:

- a traced uniform run produces a schema-valid JSONL trace and a
  Perfetto-loadable export whose step spans carry solver iteration
  counts and stream-wait time;
- an injected-NaN run (uniform AND AMR) produces a postmortem with the
  correct last-known-good step and a non-empty residual history; a
  clean run produces none;
- the metrics/trace hot path is sync-free under
  ``no_implicit_transfers`` (the zero-device-sync guarantee pinned in
  VALIDATION.md round 9).
"""

import functools
import itertools
import json
import os
import time

import numpy as np
import pytest

from cup3d_tpu.obs import flight as F
from cup3d_tpu.obs import metrics as M
from cup3d_tpu.obs import trace as T
from tests._cases import flight_files, iterative_tgv_cfg


# -- metrics registry ------------------------------------------------------


def test_metrics_get_or_create_identity_and_labels():
    r = M.MetricsRegistry()
    c1 = r.counter("ev", site="a")
    c2 = r.counter("ev", site="a")
    c3 = r.counter("ev", site="b")
    assert c1 is c2 and c1 is not c3
    c1.inc()
    c1.inc(2.5)
    c3.inc()
    snap = r.snapshot()
    assert snap["ev{site=a}"] == 3.5 and snap["ev{site=b}"] == 1
    with pytest.raises(TypeError):
        r.gauge("ev", site="a")  # kind mismatch on the same key


def test_metrics_gauge_histogram_snapshot_delta_reset():
    r = M.MetricsRegistry()
    r.gauge("cap").set(69)
    h = r.histogram("iters")
    for v in (12, 3, 30):
        h.observe(v)
    s0 = r.snapshot()
    assert s0["cap"] == 69
    assert s0["iters.count"] == 3 and s0["iters.sum"] == 45
    assert s0["iters.min"] == 3 and s0["iters.max"] == 30
    assert s0["iters.last"] == 30
    h.observe(5)
    d = r.delta(s0)
    assert d["iters.count"] == 1 and d["iters.sum"] == 5
    r.reset()
    assert r.snapshot()["cap"] == 0
    assert "iters.min" not in r.snapshot()  # empty hist drops extrema


def test_metrics_collector_merges_and_weakref_drops():
    r = M.MetricsRegistry()

    class Holder:
        stats = {"x": 2}

    h = Holder()
    r.register_collector(lambda: dict(h.stats), owner=h)
    r.counter("x").inc(1)  # metric + collector with the same key SUM
    assert r.snapshot()["x"] == 3
    del h
    import gc

    gc.collect()
    assert r.snapshot()["x"] == 1  # dead owner dropped the collector


def test_stream_stats_reach_global_registry():
    from cup3d_tpu.stream.qoi import QoIStream

    st = QoIStream(lambda e: None, name="obs-test-stream")
    st.stats["packs_emitted"] = 7
    snap = M.snapshot()
    assert snap["stream.packs_emitted{stream=obs-test-stream}"] == 7


# -- span timer (Profiler engine) ------------------------------------------


def _fake_clock(monkeypatch, ticks):
    seq = iter(ticks)
    monkeypatch.setattr(time, "perf_counter", lambda: next(seq))


def test_spans_self_time_partitions_nesting(monkeypatch):
    """The StreamWait-inside-SyncQoI case: inner wall excluded from the
    outer section, totals partition the measured wall."""
    p = T.SpanTimer(sink=T.TraceSink(enabled=False))
    _fake_clock(monkeypatch, [0.0, 2.0, 5.0, 10.0])
    with p("SyncQoI"):
        with p("StreamWait"):
            pass
    assert p.totals["StreamWait"] == 3.0
    assert p.totals["SyncQoI"] == 7.0  # 10 - 3: self time only
    assert p.counts["SyncQoI"] == 1 and p.counts["StreamWait"] == 1


def test_spans_recursive_same_name_counts_once(monkeypatch):
    """Round-9 recursion fix: a section nesting within ITSELF is one
    logical call — totals still sum to the outer wall (no double count,
    no double subtraction) and counts no longer inflate (the old
    profiler counted 2, halving totals/counts means)."""
    p = T.SpanTimer(sink=T.TraceSink(enabled=False))
    # sink constructed BEFORE the fake clock (its epoch reads the clock)
    p2 = T.SpanTimer(sink=T.TraceSink(enabled=False))
    _fake_clock(monkeypatch, [0.0, 1.0, 3.0, 10.0])
    with p("A"):
        with p("A"):
            pass
    assert p.totals["A"] == 10.0
    assert p.counts["A"] == 1
    # ...including indirect recursion A{B{A}}
    _fake_clock(monkeypatch, [0.0, 1.0, 2.0, 4.0, 8.0, 9.0])
    with p2("A"):
        with p2("B"):
            with p2("A"):
                pass
    assert p2.totals["A"] + p2.totals["B"] == 9.0
    assert p2.counts["A"] == 1 and p2.counts["B"] == 1


def test_io_logging_profiler_is_the_span_shim():
    from cup3d_tpu.io.logging import Profiler

    p = Profiler()
    assert isinstance(p, T.SpanTimer)
    with p("X"):
        pass
    assert p.counts["X"] == 1 and "X" in p.report()


# -- trace sink ------------------------------------------------------------


def test_trace_sink_jsonl_and_perfetto_roundtrip(tmp_path):
    sink = T.TraceSink(enabled=True, directory=str(tmp_path), max_steps=50)
    timer = T.SpanTimer(sink=sink)
    obs = T.StepObserver(timer, kind="t1")
    for i in range(4):
        with obs.step(i, i * 0.5, 0.5, nb=12):
            with timer("Megastep"):
                pass
        obs.note_solver(i, iters=10 + i, resid=1e-6)
    sink.close()
    # JSONL: schema-valid, step-monotonic, solver stats present
    recs = [json.loads(l) for l in open(tmp_path / "trace.jsonl")]
    assert len(recs) == 4
    for rec in recs:
        assert T.validate_step_record(rec) == []
    assert recs[-1]["solver"]["iters"] == 12.0  # consumed before step 3
    assert recs[-1]["nb"] == 12
    assert "Megastep" in recs[-1]["sections"]
    # Perfetto export loads and step spans carry the record as args
    pf = json.load(open(tmp_path / "trace.pfto.json"))
    steps = [e for e in pf["traceEvents"] if e["name"] == "step"]
    assert len(steps) == 4
    assert all({"name", "ph", "ts", "dur"} <= set(e) for e in steps)
    assert steps[-1]["args"]["solver"]["iters"] == 12.0


def test_trace_sink_bounded_and_disabled_is_noop(tmp_path):
    sink = T.TraceSink(enabled=True, directory=str(tmp_path), max_steps=2)
    obs = T.StepObserver(T.SpanTimer(sink=sink), kind="t2")
    for i in range(5):
        with obs.step(i, 0.0, 0.1):
            pass
    sink.close()
    assert len(open(tmp_path / "trace.jsonl").readlines()) == 2
    assert sink.steps_dropped == 3
    off = T.TraceSink(enabled=False, directory=str(tmp_path / "off"))
    obs2 = T.StepObserver(T.SpanTimer(sink=off), kind="t3")
    with obs2.step(0, 0.0, 0.1):
        pass
    off.close()
    assert not (tmp_path / "off").exists()  # nothing written


def test_validate_step_record_rejects_bad_records():
    good = {"schema": T.SCHEMA_VERSION, "step": 1, "t": 0.1, "dt": 0.1,
            "wall_s": 0.01}
    assert T.validate_step_record(good) == []
    assert T.validate_step_record({}) != []
    assert T.validate_step_record({**good, "schema": 99}) != []
    assert T.validate_step_record({**good, "step": -1}) != []
    assert T.validate_step_record({**good, "solver": {"resid": 1.0}}) != []


# -- flight recorder -------------------------------------------------------


def test_flight_recorder_ring_last_good_and_postmortem(tmp_path):
    fr = F.FlightRecorder(capacity=3, directory=str(tmp_path),
                          run_config={"cfg": 1})
    for i in range(5):
        fr.record_step({"step": i, "dt": 0.1, "t": i * 0.1,
                        "wall_s": 0.01})
        fr.note_solver(i, iters=20, resid=1e-5)
    fr.record_step({"step": 5, "dt": float("nan"), "t": 0.5,
                    "wall_s": 0.01})
    assert fr.last_known_good_step == 4
    path = fr.trigger("nan-velocity", extra={"step": 5, "umax": 1e9})
    pm = F.load_postmortem(path)
    assert pm["reason"] == "nan-velocity"
    assert pm["last_known_good_step"] == 4
    assert pm["triggered_at_step"] == 5
    assert len(pm["steps"]) == 3  # ring capacity, oldest dropped
    assert pm["residual_history"][-1]["iters"] == 20
    assert pm["config"] == {"cfg": 1}
    # one-dump latch: the second failure does not spam the disk
    assert fr.trigger("nan-velocity") is None


def test_flight_recorder_itercap_triggers(tmp_path):
    fr = F.FlightRecorder(directory=str(tmp_path))
    fr.note_solver(3, iters=17, resid=1e-5, cap=1000)
    assert not fr.dumps_written
    fr.note_solver(4, iters=1000, resid=0.2, cap=1000)
    assert len(fr.dumps_written) == 1
    pm = F.load_postmortem(fr.dumps_written[0])
    assert pm["reason"] == "poisson-itercap"
    assert pm["extra"]["iters"] == 1000


# -- live drivers ----------------------------------------------------------


_uniform_cfg = functools.partial(iterative_tgv_cfg, nsteps=3)


def test_uniform_traced_run_and_clean_flight(tmp_path):
    """Acceptance: a traced uniform run writes a schema-valid trace with
    per-step solver iteration counts + stream-wait time, and a CLEAN run
    leaves no flight-recorder dump."""
    from cup3d_tpu.sim.simulation import Simulation

    T.TRACE.configure(enabled=True, directory=str(tmp_path))
    try:
        sim = Simulation(_uniform_cfg(tmp_path))
        sim.init()
        sim.simulate()
        T.TRACE.close()
    finally:
        T.TRACE.configure(enabled=False)
    recs = [json.loads(l) for l in open(tmp_path / "trace.jsonl")]
    assert len(recs) == 3
    for rec in recs:
        assert T.validate_step_record(rec) == []
        assert "stream_wait_s" in rec
    # the non-pipelined pack consumes within the step: iters per record
    assert all(rec["solver"]["iters"] >= 1 for rec in recs)
    pf = json.load(open(tmp_path / "trace.pfto.json"))
    steps = [e for e in pf["traceEvents"] if e["name"] == "step"]
    assert steps and "solver" in steps[-1]["args"]
    assert flight_files(tmp_path) == []  # clean run: no postmortem
    # solver gauges reached the process-global registry
    assert M.snapshot()["poisson.iters{driver=uniform}"] >= 1


def test_uniform_nan_injection_dumps_postmortem(tmp_path):
    import jax.numpy as jnp

    from cup3d_tpu.sim.simulation import Simulation

    sim = Simulation(_uniform_cfg(tmp_path, nsteps=10**9))
    sim.init()
    for _ in range(3):
        sim.advance(sim.calc_max_timestep())
    sim.sim.state["vel"] = sim.sim.state["vel"].at[0].set(jnp.nan)
    with pytest.raises(RuntimeError):
        # the poisoned step may die at the solver-residual consume or at
        # the next dt's NaN-umax abort — both are flight triggers
        for _ in range(2):
            sim.advance(sim.calc_max_timestep())
    files = flight_files(tmp_path)
    assert len(files) == 1, files
    pm = F.load_postmortem(os.path.join(tmp_path, files[0]))
    assert pm["reason"] in ("nan-velocity", "poisson-nan-residual")
    # steps 0..2 ran clean and step 2's record is finite
    assert pm["last_known_good_step"] >= 2
    assert len(pm["residual_history"]) >= 3
    assert any(np.isfinite(r["resid"]) for r in pm["residual_history"])
    assert pm["state"]["driver"] == "uniform"
    assert pm["metrics"], "postmortem must embed a metrics snapshot"


def test_uniform_obs_hot_path_is_transfer_clean(tmp_path):
    """The round-9 zero-device-sync guarantee: stepping WITH tracing
    enabled stays clean under jax.transfer_guard('disallow') + the
    documented allowlist — telemetry adds no hidden syncs."""
    from cup3d_tpu.analysis.runtime import no_implicit_transfers
    from cup3d_tpu.sim.simulation import Simulation

    T.TRACE.configure(enabled=True, directory=str(tmp_path))
    try:
        sim = Simulation(_uniform_cfg(tmp_path, nsteps=10**9))
        sim.init()
        sim.advance(sim.calc_max_timestep())  # compiles outside the guard
        with no_implicit_transfers(allow=[
            "umax-read", "dt-upload", "uinf-upload", "qoi-read",
            "scalar-upload",
        ]):
            for _ in range(3):
                sim.advance(sim.calc_max_timestep())
        T.TRACE.flush()
    finally:
        T.TRACE.configure(enabled=False)
    assert os.path.exists(tmp_path / "trace.jsonl")


def test_amr_nan_injection_dumps_postmortem(tmp_path):
    """AMR acceptance twin: host-path AMR run, NaN injected mid-run ->
    postmortem with bucket/capacity state and residual history."""
    import jax.numpy as jnp

    from cup3d_tpu.config import SimulationConfig
    from cup3d_tpu.sim.amr import AMRSimulation

    cfg = SimulationConfig(
        bpdx=2, bpdy=2, bpdz=2, levelMax=2, levelStart=0,
        extent=2 * np.pi, CFL=0.3, nu=0.02, nsteps=10**9, rampup=0,
        Rtol=1.8, Ctol=0.05, initCond="taylorGreen",
        poissonSolver="iterative", poissonTol=1e-6, poissonTolRel=1e-4,
        verbose=False, freqDiagnostics=0,
        path4serialization=str(tmp_path),
    )
    sim = AMRSimulation(cfg)
    sim.init()
    for _ in range(2):
        sim.advance(sim.calc_max_timestep())
    sim.state["vel"] = sim.state["vel"].at[0].set(jnp.nan)
    with pytest.raises(RuntimeError):
        for _ in range(2):
            sim.advance(sim.calc_max_timestep())
    files = flight_files(tmp_path)
    assert len(files) == 1, files
    pm = F.load_postmortem(os.path.join(tmp_path, files[0]))
    assert pm["reason"] in ("nan-velocity", "poisson-nan-residual")
    assert pm["last_known_good_step"] >= 1
    assert len(pm["residual_history"]) >= 2
    # the dump is self-contained: bucket/capacity state + config
    assert pm["state"]["driver"] == "amr"
    assert pm["state"]["blocks"] >= 8
    assert pm["state"]["bucket_capacity"] >= pm["state"]["blocks"]
    assert pm["config"]["levelMax"] == 2


def test_dt_collapse_triggers_postmortem(tmp_path):
    from cup3d_tpu.sim.simulation import Simulation

    sim = Simulation(_uniform_cfg(tmp_path, nsteps=10**9))
    sim.init()
    sim.advance(sim.calc_max_timestep())
    # a stale tend BEHIND the current time drives the end-of-run clamp
    # negative: the dt policy collapses without any NaN in sight
    sim.cfg.tend = max(sim.sim.time * 0.5, 1e-9)
    with pytest.raises(RuntimeError, match="dt policy collapse"):
        sim.calc_max_timestep()
    files = flight_files(tmp_path)
    assert len(files) == 1
    assert F.load_postmortem(
        os.path.join(tmp_path, files[0])
    )["reason"] == "dt-collapse"


# -- device peak table (obs/costs.py) ----------------------------------------


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform = platform
        self.device_kind = device_kind


def test_unknown_tpu_device_kind_raises():
    """A TPU the peak table does not know is an error, never another
    chip's ceilings; the kinds it does know resolve (v5 lite = v5e)."""
    from cup3d_tpu.obs import costs

    v5e = costs.device_peaks(_FakeDevice("tpu", "TPU v5 lite"))
    assert v5e.kind == "TPU v5e" and v5e.hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError, match="PEAK_TABLE"):
        costs.device_peaks(_FakeDevice("tpu", "TPU v99 imaginary"))


def test_cpu_device_has_no_ceiling():
    """Off the TPU there is no roofline denominator: the live (CPU)
    backend and any CPU device resolve to None, and the bench's roofline
    shares read None ("not measured") instead of a share of v5e peaks."""
    import bench
    from cup3d_tpu.obs import costs

    assert costs.device_peaks() is None  # conftest pins the CPU backend
    assert costs.device_peaks(_FakeDevice("cpu", "cpu")) is None
    r = bench._roofline_dict(1e-3, 4096, 100.0, 50.0)
    assert r["peaks"] is None
    assert r["mfu_vs_bf16_peak"] is None and r["hbm_fraction"] is None
    assert r["cell_iters_per_s"] > 0  # counts and rates still reported
