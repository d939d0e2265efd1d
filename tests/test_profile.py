"""Device-time attribution + perf observatory (ISSUE 9): the trace-
event parser on the checked-in synthetic capture fixture, capture-
window cadence on the injected test seam, the Prometheus/health
exporter round trip, bench-history regression detection, and the
zero-sync guarantee with profiling armed but idle.

Everything here runs on CPU with no profiler session: the parser eats
the gzipped Chrome-JSON fixture ``tests/data/synthetic_profile
.trace.json.gz`` (regenerate with
``python -c "from cup3d_tpu.obs import profile;
profile.write_synthetic_capture('tests/data/...')"`` — byte-stable,
gzip mtime=0), and the CaptureController takes ``start_fn``/``stop_fn``
so cadence is tested without jax.profiler."""

import json
import os
import urllib.error
import urllib.request

import numpy as np
import pytest

from cup3d_tpu.obs import export as E
from cup3d_tpu.obs import flight as F
from cup3d_tpu.obs import history as H
from cup3d_tpu.obs import metrics as M
from cup3d_tpu.obs import profile as P
from cup3d_tpu.obs import trace as T

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "synthetic_profile.trace.json.gz")


# -- trace-event parser on the checked-in fixture ---------------------------


def test_fixture_attribution_sums_and_sections():
    """Attribution by scope over the fixture (two steps of a TPU-shaped
    capture): device self time by outermost operator scope, one level
    below for the split operators, the op with no scope under ``other``,
    and the invariant sum(sections)+other == total == busy time."""
    attr = P.attribute(P.load_chrome_trace(FIXTURE), source=FIXTURE)
    assert set(attr.sections) == {"AdvectionDiffusion",
                                  "PressureProjection"}
    assert attr.sections["AdvectionDiffusion"] == pytest.approx(2.6)
    # the while's own 0.3 ms a step + its nested body, counted once
    assert attr.sections["PressureProjection"] == pytest.approx(7.8)
    assert attr.other_ms == pytest.approx(1.2)  # copy.9, twice
    assert abs(sum(attr.sections.values()) + attr.other_ms
               - attr.total_ms) < 1e-9
    assert attr.children("PressureProjection") == pytest.approx(
        {"PoissonRHS": 0.6, "PoissonSolve": 6.0, "Gradient": 1.2})
    assert attr.children("AdvectionDiffusion") == pytest.approx(
        {"self": 1.8, "Halo": 0.8})
    assert attr.paths[
        "PressureProjection/PoissonSolve/Preconditioner/TileSolve"
    ] == pytest.approx(2.4)
    # every device op is bucketed exactly once
    assert len(attr.events) == 18
    assert [e["name"] for e in attr.events
            if e["section"] is None] == ["copy.9", "copy.9"]
    assert attr.programs == {"jit_step(1)": pytest.approx([11.6, 1.2])}
    # busy + idle = the window, and each idle instant has one owner
    assert attr.total_ms + sum(attr.gaps.values()) == pytest.approx(
        attr.window_ms)
    assert attr.gaps["cup3d:read:qoi-read"] == pytest.approx(1.8)
    assert attr.gaps["cup3d:CreateObstacles"] == pytest.approx(3.8)
    assert attr.gaps[P.NO_SPAN] == pytest.approx(1.0)
    # where the host thread was, device busy or not: each span's self time
    assert attr.host == pytest.approx({
        "cup3d:step": 1.0, "cup3d:CreateObstacles": 3.8,
        "cup3d:AdvectionDiffusion": 0.4, "cup3d:SyncQoI": 0.4,
        "cup3d:read:qoi-read": 12.4})


def test_fixture_matches_generator():
    """The checked-in fixture IS write_synthetic_capture's output —
    drift between the repo fixture and the generator fails here."""
    with open(FIXTURE, "rb") as f:
        checked_in = f.read()
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        fresh = P.write_synthetic_capture(os.path.join(td, "f.gz"))
        with open(fresh, "rb") as f:
            assert f.read() == checked_in


def test_attribute_reads_the_scope_from_the_event_or_joins_the_hlo():
    """Where the trace carries the op_name (a TPU's) it is read from the
    event; where it names only module and instruction (the CPU's
    executor threads) it is joined from the program's optimised HLO
    text; an op that neither names goes to ``other``."""
    hlo = (
        "HloModule jit_step, entry_computation_layout={()->f32[]}\n\n"
        "ENTRY %main () -> f32[] {\n"
        '  %fusion.3 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name='
        '"jit(step)/Penalization/Penalization/mul" source_file="x.py"}\n'
        '  ROOT %dot.7 = f32[] dot(), metadata={op_name='
        '"jit(step)/PressureProjection/PoissonSolve/while/body/Dots/'
        'reduce_sum"}\n}\n')
    names = P.hlo_op_names(hlo)
    assert names[("jit_step", "fusion.3")].endswith("Penalization/mul")
    trace = {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 3, "ts": 0,
         "args": {"name": "/host:CPU"}},
        {"name": "thread_name", "ph": "M", "pid": 3, "tid": 20,
         "ts": 0, "args": {"name": "tf_XLAPjRtCpuClient/12345"}},
        {"name": "fusion.9", "ph": "X", "pid": 3, "tid": 20, "ts": 0.0,
         "dur": 10.0, "args": {"hlo_op": "fusion.9", "hlo_module": "jit_x",
                               "tf_op": "jit(x)/ComputeForces/gather"}},
        {"name": "fusion.3", "ph": "X", "pid": 3, "tid": 20, "ts": 20.0,
         "dur": 30.0, "args": {"hlo_op": "fusion.3",
                               "hlo_module": "jit_step"}},
        {"name": "dot.7", "ph": "X", "pid": 3, "tid": 20, "ts": 60.0,
         "dur": 5.0, "args": {"hlo_op": "dot.7", "hlo_module": "jit_step"}},
        {"name": "mystery", "ph": "X", "pid": 3, "tid": 20, "ts": 70.0,
         "dur": 5.0, "args": {"hlo_op": "mystery", "hlo_module": "jit_y"}},
        # a python frame is neither a device op nor a span of the program
        {"name": "$contextlib.py", "ph": "X", "pid": 3, "tid": 10,
         "ts": 0.0, "dur": 1000.0},
    ]}
    attr = P.attribute(trace, op_names=names)
    assert attr.sections == {
        "ComputeForces": pytest.approx(0.010),
        "Penalization": pytest.approx(0.030),
        "PressureProjection": pytest.approx(0.005)}
    assert attr.paths["PressureProjection/PoissonSolve/Dots"] == (
        pytest.approx(0.005))
    assert attr.other_ms == pytest.approx(0.005)
    assert attr.total_ms == pytest.approx(0.050)
    assert attr.programs["jit_y"] == pytest.approx([0.005, 0.005])
    # without the join the two ops of jit_step have no owner
    assert P.attribute(trace).other_ms == pytest.approx(0.040)


@pytest.mark.parametrize("op_name, path", [
    ("jit(megaloop)/while/body/closed_call/PressureProjection/PoissonSolve/"
     "while/body/Dots/reduce_sum", "PressureProjection/PoissonSolve/Dots"),
    # a scope entered again right inside itself counts once
    ("jit(megaloop)/while/body/closed_call/CreateObstacles/"
     "jit(rasterize_midline)/CreateObstacles/jit(rasterize_points)/"
     "CreateObstacles/while/body/closed_call/lt", "CreateObstacles"),
    # the v5e's trace ends an op_name with a colon and may write a nested
    # computation's whole path again behind its caller's
    ("jit(project_2nd)/PressureProjection/PoissonSolve/while/body/"
     "Preconditioner/jit(project_2nd)/PressureProjection/PoissonSolve/"
     "Preconditioner/Halo/concatenate:",
     "PressureProjection/PoissonSolve/Preconditioner/Halo"),
    # a child alone, in a program that is no step (a solve probe)
    ("jit(solve)/PoissonSolve/while/body/Laplacian/add",
     "PoissonSolve/Laplacian"),
    ("jit(multiply)/mul", ""), ("", ""),
])
def test_scope_path_reads_the_vocabulary_out_of_an_op_name(op_name, path):
    assert "/".join(P.scope_path(op_name)) == path


def test_attribute_gives_each_idle_instant_to_the_innermost_span_open():
    """The device idles from 100 to 900 us: the part under the blocking
    read is the read's, the rest of the section's wall the section's,
    what no section covers the step's, and what follows the step
    nobody's."""
    trace = {"traceEvents": [
        {"name": "process_name", "ph": "M", "pid": 9, "ts": 0,
         "args": {"name": "/device:TPU:1"}},
        {"name": "thread_name", "ph": "M", "pid": 9, "tid": 0, "ts": 0,
         "args": {"name": "XLA Ops"}},
        {"name": "thread_name", "ph": "M", "pid": 9, "tid": 1, "ts": 0,
         "args": {"name": "Steps"}},
        {"name": "cup3d:step", "ph": "X", "pid": 1, "tid": 1,
         "ts": 0.0, "dur": 700.0},
        {"name": "cup3d:SyncQoI", "ph": "X", "pid": 1, "tid": 1,
         "ts": 200.0, "dur": 400.0},
        {"name": "cup3d:read:qoi-read", "ph": "X", "pid": 1, "tid": 1,
         "ts": 250.0, "dur": 300.0},
        {"name": "bench:advance", "ph": "X", "pid": 1, "tid": 1,
         "ts": 0.0, "dur": 1000.0},  # not the program's: no owner
        {"name": "fusion.1", "ph": "X", "pid": 9, "tid": 0,
         "ts": 0.0, "dur": 100.0,
         "args": {"tf_op": "jit(a)/AdvectionDiffusion/add"}},
        {"name": "fusion.2", "ph": "X", "pid": 9, "tid": 0,
         "ts": 900.0, "dur": 100.0,
         "args": {"tf_op": "jit(b)/DtPolicy/reduce_max"}},
        # another line of the device plane is no operation
        {"name": "7", "ph": "X", "pid": 9, "tid": 1,
         "ts": 0.0, "dur": 1000.0},
    ]}
    attr = P.attribute(trace)
    assert attr.total_ms == pytest.approx(0.2)
    assert attr.window_ms == pytest.approx(1.0)
    assert attr.gaps == {
        "cup3d:step": pytest.approx(0.2),            # 100-200, 600-700
        "cup3d:SyncQoI": pytest.approx(0.1),         # 200-250, 550-600
        "cup3d:read:qoi-read": pytest.approx(0.3),   # 250-550
        P.NO_SPAN: pytest.approx(0.2),               # 700-900
    }


def test_parse_plan_specs_and_bad_plan_counted():
    assert P.parse_plan(None) is None
    assert P.parse_plan("") is None
    assert P.parse_plan("off") is None
    assert P.parse_plan("every:5") == {"mode": "every", "n": 5}
    assert P.parse_plan("once") == {"mode": "once", "at": 0}
    assert P.parse_plan("once:40") == {"mode": "once", "at": 40}
    before = M.snapshot().get("profile.bad_plan", 0.0)
    assert P.parse_plan("every:zero") is None
    assert P.parse_plan("sometimes") is None
    assert M.snapshot()["profile.bad_plan"] == before + 2


# -- capture-window cadence (injected start/stop seam) ----------------------


def _ctl(tmp_path, plan, **kw):
    calls = []
    ctl = P.CaptureController(
        plan=plan, directory=str(tmp_path),
        sink=T.TraceSink(enabled=False),
        start_fn=lambda d: calls.append(("start", d)),
        stop_fn=lambda: calls.append(("stop",)),
        **kw,
    )
    return ctl, calls


def test_every_n_cadence_and_window_length(tmp_path):
    ctl, calls = _ctl(tmp_path, "every:4", window_steps=2)
    for s in range(12):
        ctl.on_step(s)
    # windows [4,6) and [8,10); step 12 would open the next
    assert ctl.windows == 2
    assert [c[0] for c in calls] == ["start", "stop", "start", "stop"]
    assert "window_0000004" in calls[0][1]
    assert not ctl.capturing


def test_once_mode_single_window_and_finish_closes(tmp_path):
    ctl, calls = _ctl(tmp_path, "once:3", window_steps=100)
    for s in range(6):
        ctl.on_step(s)
    assert ctl.capturing  # window still open (100 steps long)
    ctl.finish()
    assert not ctl.capturing and ctl.windows == 1
    assert [c[0] for c in calls] == ["start", "stop"]
    # once means once: more steps never reopen
    for s in range(6, 20):
        ctl.on_step(s)
    assert ctl.windows == 1


def test_start_failure_disables_plan_not_run(tmp_path):
    def boom(d):
        raise RuntimeError("no profiler on this backend")

    before = M.snapshot().get("profile.capture_errors", 0.0)
    ctl = P.CaptureController(plan="every:2", directory=str(tmp_path),
                              sink=T.TraceSink(enabled=False),
                              start_fn=boom, stop_fn=lambda: None)
    for s in range(10):
        ctl.on_step(s)  # must not raise, must not retry every step
    assert ctl.plan is None and ctl.windows == 0
    assert M.snapshot()["profile.capture_errors"] == before + 1


def test_harvest_merges_fixture_into_sink(tmp_path):
    """End-to-end minus jax.profiler: a controller window over a logdir
    holding the fixture lands gauges, the kind="device" JSONL record,
    and pid-2 device ops in the Perfetto export."""
    logdir = tmp_path / "window"
    os.makedirs(logdir / "plugins" / "profile" / "run")
    import shutil

    shutil.copy(FIXTURE,
                logdir / "plugins" / "profile" / "run" / "x.trace.json.gz")
    sink = T.TraceSink(enabled=True, directory=str(tmp_path))
    ctl = P.CaptureController(plan=None, directory=str(tmp_path), sink=sink)
    attr = ctl.harvest(str(logdir), window=(8, 10))
    assert attr is not None and ctl.last_attribution is attr
    snap = M.snapshot()
    for sect, ms in attr.sections.items():
        assert snap[f"profile.device_ms{{section={sect}}}"] == (
            pytest.approx(ms))
    assert snap["profile.device_total_ms"] == pytest.approx(attr.total_ms)
    sink.close()
    recs = [json.loads(l) for l in open(tmp_path / "trace.jsonl")]
    dev = [r for r in recs if r.get("kind") == "device"]
    assert len(dev) == 1 and dev[0]["step"] == 10
    assert dev[0]["window"] == [8, 10]
    assert T.validate_step_record(dev[0]) == []
    assert dev[0]["device_sections"]["PressureProjection"] > 0
    assert dev[0]["idle_gaps_ms"]["cup3d:read:qoi-read"] > 0
    assert snap["profile.idle_ms{span=cup3d:read:qoi-read}"] == (
        pytest.approx(attr.gaps["cup3d:read:qoi-read"]))
    pf = json.load(open(tmp_path / "trace.pfto.json"))
    dev_ops = [e for e in pf["traceEvents"]
               if e.get("pid") == P.DEVICE_PID and e["ph"] == "X"]
    assert len(dev_ops) == len(attr.events)
    assert all("section" in e["args"] for e in dev_ops)


def test_harvest_empty_logdir_counts_not_raises(tmp_path):
    before = M.snapshot().get("profile.empty_captures", 0.0)
    ctl = P.CaptureController(plan=None, directory=str(tmp_path),
                              sink=T.TraceSink(enabled=False))
    assert ctl.harvest(str(tmp_path / "nothing")) is None
    assert M.snapshot()["profile.empty_captures"] == before + 1


# -- exporter: /metrics Prometheus round trip, /health ----------------------


def test_prometheus_render_parse_round_trip():
    """Every flat snapshot key survives render -> parse with its value;
    special float values included."""
    M.counter("t9.scrapes", driver="fish").inc(3)
    M.gauge("t9.device_ms", section="halo.ring").set(1.25)
    M.histogram("t9.wall").observe(0.5)
    snap = dict(M.snapshot())
    snap['t9.weird{msg=a "quoted\\path"}'] = float("nan")
    snap["t9.inf"] = float("inf")
    text = E.render_prometheus(snap)
    parsed = E.parse_prometheus_text(text)
    assert len(parsed) == len(snap)
    for flat, val in snap.items():
        name, labels = E.prometheus_key(flat)
        got = parsed[(name, frozenset(labels.items()))]
        if np.isnan(val):
            assert np.isnan(got)
        else:
            assert got == pytest.approx(val)
    # the parser has teeth
    with pytest.raises(ValueError):
        E.parse_prometheus_text("not a sample line at all{")


def test_http_metrics_and_health_reflect_flight_event(tmp_path):
    """A live exporter on an ephemeral port: /metrics parses as
    Prometheus text and carries registry values; /health reports the
    injected flight-recorder dump (armed flips false, last-known-good
    pinned)."""
    fr = F.FlightRecorder(capacity=4, directory=str(tmp_path))
    for i in range(3):
        fr.record_step({"step": i, "dt": 0.1, "t": i * 0.1,
                        "wall_s": 0.01})
    M.counter("t9.http", driver="uniform").inc()
    ex = E.MetricsExporter(port=0).start()
    try:
        body = urllib.request.urlopen(ex.url + "/metrics").read().decode()
        parsed = E.parse_prometheus_text(body)
        assert parsed[("cup3d_t9_http",
                       frozenset({("driver", "uniform")}))] >= 1.0
        health = json.loads(
            urllib.request.urlopen(ex.url + "/health").read())
        mine = [h for h in health["flight_recorders"]
                if h["directory"] == str(tmp_path)]
        assert len(mine) == 1
        assert mine[0]["armed"] is True
        assert mine[0]["last_known_good_step"] == 2
        # inject a failure: the next scrape must see the dump
        fr.trigger("nan-velocity", extra={"step": 3})
        health = json.loads(
            urllib.request.urlopen(ex.url + "/health").read())
        mine = [h for h in health["flight_recorders"]
                if h["directory"] == str(tmp_path)][0]
        assert mine["armed"] is False
        assert len(mine["dumps_written"]) == 1
        assert health["recovery_counters"]["flight.dumps"] >= 1.0
        assert "profile" in health and "trace" in health
        # unknown path: 404, not a crash
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(ex.url + "/nope")
    finally:
        ex.stop()


def test_ensure_exporter_off_by_default(monkeypatch):
    monkeypatch.delenv("CUP3D_METRICS_PORT", raising=False)
    monkeypatch.setattr(E, "EXPORTER", None)
    assert E.ensure_exporter() is None
    monkeypatch.setenv("CUP3D_METRICS_PORT", "0")
    assert E.ensure_exporter() is None


# -- bench history: regression detection ------------------------------------


def _summary(cells, iter_ms, p95):
    return {"value": cells, "unit": "cells/s",
            "fish": {"wall_per_step_p95_s": p95,
                     "roofline": {"bicgstab_iter_device_ms": iter_ms}}}


def test_history_regression_fires_on_slowdown_quiet_in_noise(tmp_path):
    store = H.HistoryStore(str(tmp_path / "hist.jsonl"))
    for cells, ms, p95 in ((1.00e6, 2.00, 0.100), (1.02e6, 1.97, 0.098),
                           (0.98e6, 2.03, 0.102), (1.01e6, 2.01, 0.101),
                           (0.99e6, 1.99, 0.099)):
        store.append(_summary(cells, ms, p95))
    reports = H.detect_regressions(store.summaries())
    assert not H.any_regressed(reports), reports
    # a 20% slowdown fires on all three tracked metrics
    store.append(_summary(0.80e6, 2.40, 0.120))
    by = {r["metric"]: r for r in
          H.detect_regressions(store.summaries())}
    for name in ("cells_per_s", "bicgstab_iter_device_ms",
                 "wall_per_step_p95_s"):
        assert by[name]["regressed"], (name, by[name])
    # direction matters: a 20% SPEEDUP is not a regression
    store2 = H.HistoryStore(str(tmp_path / "hist2.jsonl"))
    for _ in range(4):
        store2.append(_summary(1.0e6, 2.0, 0.1))
    store2.append(_summary(1.2e6, 1.6, 0.08))
    assert not H.any_regressed(H.detect_regressions(store2.summaries()))


def test_history_store_skips_bad_lines_and_partial_summaries(tmp_path):
    store = H.HistoryStore(str(tmp_path / "hist.jsonl"))
    store.append(_summary(1.0e6, 2.0, 0.1))
    # a summary missing the fish block contributes no point for the
    # fish metrics but still counts for cells_per_s
    store.append({"value": 1.0e6})
    with open(store.path, "a") as f:
        f.write('{"cut mid-jso\n')
        f.write('"not a wrapper"\n')
    assert len(store.load()) == 2
    reports = H.detect_regressions(store.summaries())
    by = {r["metric"]: r for r in reports}
    assert by["cells_per_s"]["n"] == 2
    assert by["wall_per_step_p95_s"].get("reason")  # <2 points -> skip
    assert not H.any_regressed(reports)


def test_extract_first_path_wins_and_rejects_bools():
    spec = H.MetricSpec("m", (("fish", "x"), ("detail", "x")))
    assert H.extract({"detail": {"x": 2.0}}, spec) == 2.0
    assert H.extract({"fish": {"x": 1.0}, "detail": {"x": 2.0}}, spec) == 1.0
    assert H.extract({"fish": {"x": True}}, spec) is None
    assert H.extract({}, spec) is None


# -- zero-sync guarantee: profiling armed but idle --------------------------


def test_armed_idle_profile_hook_is_transfer_clean(tmp_path):
    """The round-13 overhead contract's test half: a controller that is
    ARMED (plan set, window far in the future) adds no device sync or
    transfer to the step loop — on_step is pure host bookkeeping."""
    from cup3d_tpu.analysis.runtime import no_implicit_transfers
    from cup3d_tpu.config import SimulationConfig
    from cup3d_tpu.sim.simulation import Simulation

    cfg = SimulationConfig(
        bpdx=2, bpdy=2, bpdz=2, levelMax=1, levelStart=0,
        extent=2 * np.pi, CFL=0.3, nu=0.02, nsteps=3, rampup=0,
        initCond="taylorGreen", poissonSolver="iterative",
        poissonTol=1e-6, poissonTolRel=1e-4,
        verbose=False, freqDiagnostics=0,
        path4serialization=str(tmp_path),
    )
    ctl = P.CaptureController(
        plan="every:1000000", directory=str(tmp_path),
        sink=T.TraceSink(enabled=False),
        start_fn=lambda d: (_ for _ in ()).throw(
            AssertionError("armed-idle window must never open")),
        stop_fn=lambda: None,
    )
    sim = Simulation(cfg)
    sim.init()
    sim.advance(sim.calc_max_timestep())  # compiles outside the guard
    with no_implicit_transfers(allow=[
        "umax-read", "dt-upload", "uinf-upload", "qoi-read",
        "scalar-upload",
    ]):
        for i in range(3):
            ctl.on_step(i)  # the driver hook, armed but idle
            sim.advance(sim.calc_max_timestep())
    assert ctl.windows == 0 and not ctl.capturing


def test_disabled_controller_on_step_is_noop():
    ctl = P.CaptureController(plan=None, sink=T.TraceSink(enabled=False),
                              start_fn=lambda d: 1 / 0,
                              stop_fn=lambda: 1 / 0)
    for s in range(1000):
        ctl.on_step(s)
    ctl.finish()
    assert ctl.windows == 0 and not ctl.capturing
