"""Resilience subsystem (cup3d_tpu/resilience/): deterministic fault
injection, rollback/retry recovery on both drivers, and the hardened
host data-plane (ISSUE 5).

The acceptance paths:

- a one-shot ``step.nan_velocity`` on uniform AND AMR TGV configs
  completes via rollback (one rollback, <= 3 retries, no postmortem) and
  the final QoI match the unfaulted run within the documented tolerance
  (VALIDATION.md round 10: 5% on kinetic energy — the retry halves dt
  over a short window, so trajectories differ by time-discretization
  only);
- recovery armed with NO faults is bitwise-identical to CUP3D_RECOVER=0;
- retries exhausted -> postmortem + restartable checkpoint + raise;
- crash-restart: an injected ``ckpt.write_fail`` kills the legacy run
  mid-save, the restart resumes from the latest VALID checkpoint and
  runs to the end (uniform + AMR);
- a seeded chaos arm on a short fish run either completes via recovery
  or exits gracefully with a postmortem.
"""

import functools
import os
import pickle
import random
import time

import numpy as np
import pytest

from cup3d_tpu.config import SimulationConfig
from cup3d_tpu.obs import metrics as M
from cup3d_tpu.resilience import faults
from cup3d_tpu.resilience.recovery import RecoveryEngine, SimulationFailure
from tests._cases import flight_files, iterative_tgv_cfg, mean_ke


pytestmark = pytest.mark.usefixtures("clean_faults")


_uniform_cfg = functools.partial(iterative_tgv_cfg, tend=0.5, nsteps=0)


def _amr_cfg(tmp, **kw):
    base = dict(
        bpdx=2, bpdy=2, bpdz=2, levelMax=2, levelStart=0,
        extent=float(2 * np.pi), CFL=0.3, nu=0.02, tend=0.4, nsteps=0,
        rampup=0, Rtol=1.8, Ctol=0.05, initCond="taylorGreen",
        poissonSolver="iterative", poissonTol=1e-6, poissonTolRel=1e-4,
        verbose=False, freqDiagnostics=0, path4serialization=str(tmp),
    )
    base.update(kw)
    return SimulationConfig(**base)


def _run_uniform(tmp, **kw):
    from cup3d_tpu.sim.simulation import Simulation

    sim = Simulation(_uniform_cfg(tmp, **kw))
    sim.init()
    sim.simulate()
    return sim


# -- fault plan ------------------------------------------------------------


def test_fault_plan_parse_arm_fire_counts():
    p = faults.FaultPlan()
    p.parse("step.nan_velocity@3:2; ckpt.write_fail@*")
    assert p.snapshot() == [
        {"site": "step.nan_velocity", "step": 3, "count": 2, "fired": 0},
        {"site": "ckpt.write_fail", "step": None, "count": 1, "fired": 0},
    ]
    # step-armed: silent before the step, fires exactly `count` times
    assert not p.fire("step.nan_velocity", 2)
    assert p.fire("step.nan_velocity", 3)
    assert p.fire("step.nan_velocity", 4)
    assert not p.fire("step.nan_velocity", 5)
    # wildcard: any step (including None), one shot
    assert p.fire("ckpt.write_fail", None)
    assert not p.fire("ckpt.write_fail", 99)
    # unarmed site never fires
    assert not p.fire("dump.write_fail", 3)
    with pytest.raises(ValueError, match="unknown fault site"):
        p.arm("bogus.site")
    with pytest.raises(ValueError, match="site@step"):
        p.parse("step.nan_velocity")


def test_fault_firings_reach_registry_and_env_reloads(monkeypatch):
    s0 = M.snapshot()
    faults.arm("dt.collapse", 5, 1)
    assert faults.fire("dt.collapse", 7)
    d = M.delta(s0)
    assert d["faults.injected{site=dt.collapse}"] == 1
    # env arming: load_env reparses when the env VALUE changes, and the
    # API-armed entries survive while it does not
    faults.clear()
    faults.arm("dump.write_fail")
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    faults.load_env()
    assert faults.PLAN.snapshot()[0]["site"] == "dump.write_fail"
    monkeypatch.setenv(faults.ENV_VAR, "solver.itercap@2:3")
    faults.load_env()
    assert faults.PLAN.snapshot() == [
        {"site": "solver.itercap", "step": 2, "count": 3, "fired": 0}
    ]


def test_maybe_raise_and_injected_fault_type():
    faults.arm("ckpt.write_fail", "*", 1)
    with pytest.raises(faults.InjectedFault) as ei:
        faults.maybe_raise("ckpt.write_fail", 7)
    assert isinstance(ei.value, IOError) and ei.value.site == "ckpt.write_fail"
    faults.maybe_raise("ckpt.write_fail", 8)  # exhausted: no raise


# -- rollback / retry on live drivers --------------------------------------


def test_uniform_nan_fault_recovers_and_matches_qoi(tmp_path):
    """Acceptance: step.nan_velocity@2:1 on the uniform TGV completes via
    rollback — one rollback, <= 3 retries, no postmortem — and the final
    kinetic energy matches the unfaulted run within 5%."""
    ref = _run_uniform(tmp_path / "ref")
    ke_ref = mean_ke(ref.sim.state["vel"])

    faults.arm("step.nan_velocity", 2, 1)
    s0 = M.snapshot()
    sim = _run_uniform(tmp_path / "flt")
    d = M.delta(s0)
    assert sim.sim.time >= sim.cfg.tend - 1e-9
    assert d["resilience.rollbacks"] == 1
    assert d.get("resilience.giveups", 0) == 0
    assert sum(v for k, v in d.items()
               if k.startswith("resilience.retries")) <= 3
    assert flight_files(tmp_path / "flt") == []  # recovered: no postmortem
    ev = list(sim.flight.recovery_events)
    assert any(e.get("reason") == "nan-velocity" and e.get("stage")
               for e in ev)
    ke = mean_ke(sim.sim.state["vel"])
    assert abs(ke - ke_ref) <= 0.05 * abs(ke_ref)


def test_uniform_recover_armed_is_bitwise_vs_legacy(tmp_path, monkeypatch):
    """Recovery armed + no faults must be bitwise-identical to the
    CUP3D_RECOVER=0 legacy loop; and the legacy loop + a fault keeps the
    old crash semantics (RuntimeError + postmortem on disk)."""
    armed = _run_uniform(tmp_path / "armed")
    monkeypatch.setenv("CUP3D_RECOVER", "0")
    legacy = _run_uniform(tmp_path / "legacy")
    np.testing.assert_array_equal(
        np.asarray(armed.sim.state["vel"]), np.asarray(legacy.sim.state["vel"])
    )
    # legacy crash-on-fault baseline
    from cup3d_tpu.sim.simulation import Simulation

    faults.arm("step.nan_velocity", 2, 1)
    sim = Simulation(_uniform_cfg(tmp_path / "crash"))
    sim.init()
    with pytest.raises(RuntimeError, match="runaway"):
        sim.simulate()
    files = flight_files(tmp_path / "crash")
    assert len(files) == 1 and "nan-velocity" in files[0]


def test_amr_nan_fault_recovers_and_matches_qoi(tmp_path):
    """AMR acceptance twin (the amr_tgv-class config): rollback across
    the bucketed driver restores topology + fields in place."""
    from cup3d_tpu.sim.amr import AMRSimulation

    ref = AMRSimulation(_amr_cfg(tmp_path / "ref"))
    ref.init()
    ref.simulate()
    ke_ref = mean_ke(ref._unpad(ref.state["vel"]))

    faults.arm("step.nan_velocity", 2, 1)
    s0 = M.snapshot()
    sim = AMRSimulation(_amr_cfg(tmp_path / "flt"))
    sim.init()
    sim.simulate()
    d = M.delta(s0)
    assert sim.time >= sim.cfg.tend - 1e-9
    assert d["resilience.rollbacks"] == 1
    assert flight_files(tmp_path / "flt") == []
    ke = mean_ke(sim._unpad(sim.state["vel"]))
    assert abs(ke - ke_ref) <= 0.05 * abs(ke_ref)


def test_poisson_itercap_fault_walks_the_ladder(tmp_path):
    """solver.itercap is detected at the ASYNC pack-consumption seam
    (no exception at the site): the latched trigger rolls back at the
    next loop top with the Poisson escalation ladder's first stage."""
    faults.arm("solver.itercap", 2, 1)
    s0 = M.snapshot()
    sim = _run_uniform(tmp_path)
    d = M.delta(s0)
    assert sim.sim.time >= sim.cfg.tend - 1e-9
    assert d["resilience.rollbacks"] == 1
    assert d["resilience.retries{stage=warm-restart}"] == 1
    assert flight_files(tmp_path) == []
    ev = list(sim.flight.recovery_events)
    assert any(e.get("reason") == "poisson-itercap" for e in ev)


def test_poisson_ladder_escalates_to_solver_rebuild(tmp_path):
    """A PERSISTENT poisson-nan-residual walks warm-restart ->
    zero-guess -> tile-only -> iter-bump and rebuilds the solver with
    the two-level preconditioner dropped and a 4x iteration budget."""
    from cup3d_tpu.sim.simulation import Simulation

    faults.arm("solver.nan_residual", 2, 99)
    s0 = M.snapshot()
    sim = Simulation(_uniform_cfg(tmp_path))
    sim.init()
    with pytest.raises(RuntimeError):
        sim.simulate()
    d = M.delta(s0)
    stages = {k.split("stage=")[1].rstrip("}"): v for k, v in d.items()
              if k.startswith("resilience.retries") and v}
    assert set(stages) == {"warm-restart", "zero-guess", "tile-only",
                           "iter-bump"}
    assert d["resilience.giveups"] == 1
    # the escalation really rebuilt the solve: bumped budget, postmortem
    # carries the recovery ring
    assert sim.sim.poisson_solver.maxiter == 4000
    files = flight_files(tmp_path)
    assert len(files) == 1
    from cup3d_tpu.obs.flight import load_postmortem

    pm = load_postmortem(os.path.join(tmp_path, files[0]))
    assert pm["reason"] == "poisson-nan-residual"
    assert len(pm["recovery_events"]) >= 4


def test_give_up_writes_postmortem_and_restartable_checkpoint(tmp_path):
    """Retries exhausted -> postmortem + a restartable checkpoint from
    the last good snapshot + re-raise; the restart completes."""
    from cup3d_tpu.io.checkpoint import (
        latest_valid_checkpoint, load_checkpoint,
    )
    from cup3d_tpu.sim.simulation import Simulation

    faults.arm("step.nan_velocity", 2, 99)  # persistent: every retry dies
    s0 = M.snapshot()
    sim = Simulation(_uniform_cfg(tmp_path))
    sim.init()
    with pytest.raises(RuntimeError, match="runaway"):
        sim.simulate()
    d = M.delta(s0)
    assert d["resilience.giveups"] == 1
    assert d["resilience.rollbacks"] >= 1
    files = flight_files(tmp_path)
    assert len(files) == 1
    faults.clear()
    path = latest_valid_checkpoint(str(tmp_path))
    assert path is not None
    res = load_checkpoint(path)
    res.simulate()
    assert res.sim.time >= res.cfg.tend - 1e-9


# -- crash-restart through the data plane ----------------------------------


def _await_bg_failure(ckpt, deadline_s: float = 5.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        if not ckpt.health()["ok"]:
            return
        time.sleep(0.01)
    raise AssertionError("background write failure never surfaced")


def test_crash_restart_uniform(tmp_path):
    """ckpt.write_fail mid-run kills the legacy loop (the satellite fix
    propagates the background failure on the NEXT save); restart resumes
    from the latest VALID checkpoint and runs to the end."""
    from cup3d_tpu.io.checkpoint import (
        latest_valid_checkpoint, load_checkpoint,
    )
    from cup3d_tpu.sim.simulation import Simulation

    os.environ["CUP3D_RECOVER"] = "0"  # legacy: failures crash the run
    try:
        # saves at steps 2/4/6; every write attempt from step 4 on fails
        faults.arm("ckpt.write_fail", 4, 99)
        cfg = _uniform_cfg(tmp_path, tend=0.0, nsteps=8, saveFreq=2)
        sim = Simulation(cfg)
        sim.init()
        with pytest.raises(Exception) as ei:
            sim.simulate()
            # the step-4 failure lands in the background; if the loop
            # finishes first, drain_streams/wait re-raises it instead
        assert isinstance(ei.value, faults.InjectedFault)
    finally:
        os.environ.pop("CUP3D_RECOVER", None)
    faults.clear()
    # the kill left no partial files, and the newest VALID checkpoint is
    # the pre-fault one
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    path = latest_valid_checkpoint(str(tmp_path))
    assert path is not None and path.endswith("ckpt_0000002.pkl")
    res = load_checkpoint(path)
    assert res.sim.step == 2
    res.simulate()
    assert res.sim.step == 8


def test_crash_restart_amr(tmp_path):
    """AMR twin of the crash-restart path: octree topology + fields
    restore from the latest valid checkpoint and continue to the end."""
    from cup3d_tpu.io.checkpoint import (
        latest_valid_checkpoint, load_checkpoint,
    )
    from cup3d_tpu.sim.amr import AMRSimulation

    os.environ["CUP3D_RECOVER"] = "0"
    try:
        faults.arm("ckpt.write_fail", 4, 99)
        cfg = _amr_cfg(tmp_path, tend=0.0, nsteps=6, saveFreq=2)
        sim = AMRSimulation(cfg)
        sim.init()
        with pytest.raises(Exception) as ei:
            sim.simulate()
        assert isinstance(ei.value, faults.InjectedFault)
    finally:
        os.environ.pop("CUP3D_RECOVER", None)
    faults.clear()
    path = latest_valid_checkpoint(str(tmp_path))
    assert path is not None and path.endswith("ckpt_0000002.pkl")
    res = load_checkpoint(path)
    assert res.step_idx == 2
    res.simulate()
    assert res.step_idx == 6
    assert np.all(np.isfinite(np.asarray(res._unpad(res.state["vel"]))))


def test_chaos_seeded_site_recovers_or_exits_gracefully(tmp_path):
    """Seeded chaos: a random site armed on a short fish run must either
    complete (recovery swallowed it) or exit with a RuntimeError AND a
    postmortem on disk — never a hang, never an unexplained traceback
    with no artifact."""
    from cup3d_tpu.sim.simulation import Simulation

    site = random.Random(7).choice(faults.SITES)
    faults.arm(site, 2, 1)
    cfg = SimulationConfig(
        bpdx=1, bpdy=1, bpdz=1, levelMax=1, levelStart=0, block_size=32,
        extent=1.0, CFL=0.3, nu=1e-4, tend=0.0, nsteps=6, rampup=0,
        factory_content="stefanfish L=0.3 T=1.0 xpos=0.5",
        verbose=False, freqDiagnostics=0, fdump=3, saveFreq=3,
        dumpChi=True, path4serialization=str(tmp_path), dtype="float32",
    )
    sim = Simulation(cfg)
    sim.init()
    try:
        sim.simulate()
        completed = True
    except RuntimeError:
        completed = False
    if completed:
        assert sim.sim.step >= cfg.nsteps
        assert np.all(np.isfinite(np.asarray(sim.sim.state["vel"])))
    else:
        assert flight_files(tmp_path), (
            f"graceful exit for site {site!r} must leave a postmortem"
        )


# -- hardened data plane ---------------------------------------------------


def test_async_checkpointer_propagates_bg_failure(tmp_path, monkeypatch):
    """Satellite regression: a background write exception must surface
    on the NEXT save()/wait() and through health() — never vanish."""
    from cup3d_tpu.sim.simulation import Simulation
    from cup3d_tpu.stream import checkpoint as sc

    sim = Simulation(_uniform_cfg(tmp_path, nsteps=1, tend=0.0))
    sim.init()
    ckpt = sc.AsyncCheckpointer()

    boom = RuntimeError("disk on fire")

    def bad_write(payload, path):
        raise boom

    monkeypatch.setattr(sc, "write_payload", bad_write)
    ckpt.save(sim)  # background write fails
    _await_bg_failure(ckpt)
    h = ckpt.health()
    assert not h["ok"] and "disk on fire" in h["error"]
    assert h["write_failures"] == 1
    with pytest.raises(RuntimeError, match="disk on fire"):
        ckpt.save(sim)  # the NEXT save propagates (and clears) it
    assert ckpt.health()["ok"]
    # wait() path: a still-pending failed write re-raises there too
    monkeypatch.setattr(sc, "write_payload", bad_write)
    ckpt.save(sim)
    with pytest.raises(RuntimeError, match="disk on fire"):
        ckpt.wait()
    assert ckpt.health()["ok"]


def test_checkpoint_atomic_write_and_corrupt_rejection(tmp_path):
    """Satellite: writes are tmp + os.replace (no partial file ever
    lands) and load_checkpoint rejects corruption with a clear error."""
    from cup3d_tpu.io.checkpoint import (
        latest_valid_checkpoint, load_checkpoint, save_checkpoint,
    )
    from cup3d_tpu.sim.simulation import Simulation

    sim = Simulation(_uniform_cfg(tmp_path, nsteps=1, tend=0.0))
    sim.init()
    sim.advance(sim.calc_max_timestep())
    good = save_checkpoint(sim)

    # a truncated copy is rejected with a clear message
    trunc = str(tmp_path / "ckpt_0000009.pkl")
    with open(good, "rb") as f:
        blob = f.read()
    with open(trunc, "wb") as f:
        f.write(blob[: len(blob) // 2])
    with pytest.raises(ValueError, match="corrupt or truncated"):
        load_checkpoint(trunc)
    # not-a-checkpoint pickles are rejected too
    junk = str(tmp_path / "ckpt_0000010.pkl")
    with open(junk, "wb") as f:
        pickle.dump(["not", "a", "payload"], f)
    with pytest.raises(ValueError, match="not a cup3d_tpu checkpoint"):
        load_checkpoint(junk)
    # discovery skips both invalid candidates (newer steps) and returns
    # the valid one
    assert latest_valid_checkpoint(str(tmp_path)) == good

    # an injected persistent write failure leaves NOTHING behind
    faults.arm("ckpt.write_fail", "*", 99)
    target = str(tmp_path / "sub" / "ckpt_0000042.pkl")
    with pytest.raises(faults.InjectedFault):
        save_checkpoint(sim, target)
    assert not os.path.exists(target)
    assert not os.path.exists(target + ".tmp")


def test_dump_write_failure_retries_then_drops(tmp_path):
    """Tentpole hardening: a transient dump failure retries (backoff +
    jitter) and succeeds; a persistent one drops + counts — wait()
    never raises into the step loop."""
    from cup3d_tpu.grid.uniform import BC, UniformGrid
    from cup3d_tpu.stream.dump import AsyncDumper

    g = UniformGrid((8, 8, 8), (1.0, 1.0, 1.0), (BC.periodic,) * 3)
    chi = np.random.default_rng(0).random((8, 8, 8)).astype(np.float32)

    # transient: one armed firing, the retry lands the file
    faults.arm("dump.write_fail", "*", 1)
    d = AsyncDumper(nshards=2)
    d.submit(str(tmp_path / "ok"), 0.0, g, {"chi": chi}, step=3)
    d.wait()
    assert d.stats["write_failures"] == 1 and d.stats["dropped"] == 0
    assert os.path.exists(tmp_path / "ok.chi.attr.raw")
    assert d.health()["ok"]

    # persistent: retries exhaust, the dump is dropped + counted
    s0 = M.snapshot()
    faults.clear()
    faults.arm("dump.write_fail", "*", 99)
    d.submit(str(tmp_path / "bad"), 0.0, g, {"chi": chi}, step=4)
    d.wait()  # must NOT raise
    assert d.stats["dropped"] == 1
    assert not d.health()["ok"]
    assert not os.path.exists(tmp_path / "bad.chi.attr.raw")
    assert M.delta(s0)["dump.write_dropped"] == 1


def test_stream_stall_site_and_abandon():
    """stream.stall fires at the emit seam; abandon() drops queued work
    without consuming it (rollback semantics)."""
    import jax.numpy as jnp

    from cup3d_tpu.stream.qoi import QoIStream

    seen = []
    st = QoIStream(lambda e: seen.append(e), read_every=100,
                   name="resilience-test")
    s0 = M.snapshot()
    faults.arm("stream.stall", 2, 1)
    for i in range(4):
        st.emit({"layout": [("x", 1)], "pack": jnp.ones(1), "step": i})
    assert M.delta(s0)["faults.injected{site=stream.stall}"] == 1
    assert len(st.queue) == 4 and not seen
    st.abandon()
    assert not st.queue and not seen
    assert st.stats["packs_abandoned"] == 4
    st.flush()
    assert not seen  # abandoned packs never reach the consumer


def test_recovery_engine_dt_scale_and_floor(tmp_path):
    """scale_dt is the identity object at scale 1.0 (bitwise guarantee)
    and floors host dt at dt_floor while recovering."""
    from cup3d_tpu.sim.simulation import Simulation

    sim = Simulation(_uniform_cfg(tmp_path, nsteps=1, tend=0.0))
    sim.init()
    eng = RecoveryEngine.install(sim, force=True, dt_floor=1e-3)
    try:
        dt = 0.123
        assert eng.scale_dt(dt) is dt
        eng.dt_scale = 0.5
        assert eng.scale_dt(0.2) == 0.1
        assert eng.scale_dt(1e-4) == 1e-4  # already below floor: unscaled
        assert eng.scale_dt(4e-3) == 2e-3
        assert eng.scale_dt(1.5e-3) == 1e-3  # floored
    finally:
        eng.uninstall()
    assert sim._resilience is None
    assert sim.flight.recovery_intercept is None


def test_simulate_called_again_leaves_no_snapshot_behind(tmp_path):
    """A caller that runs ``simulate()`` in chunks (the benchmark's
    window does) gets a new engine a call.  The engine refers to itself,
    so only the cyclic collector frees it: taken off, it has to let go
    of its device copy of the fields, or every call leaves one on the
    device (0.54 GB each at 256^3)."""
    import gc

    import jax

    from cup3d_tpu.sim.simulation import Simulation

    sim = Simulation(_uniform_cfg(tmp_path, tend=0.0, nsteps=2))
    sim.init()
    cells = int(np.prod(sim.sim.grid.shape))
    fields = lambda: sum(a.size >= cells for a in jax.live_arrays())
    gc.collect()
    gc.disable()
    try:
        counts = []
        for _ in range(4):
            sim.cfg.nsteps = sim.sim.step + 2
            sim.simulate()
            counts.append(fields())
    finally:
        gc.enable()
    assert M.counter("resilience.snapshots").value >= 4
    assert counts[1:] == counts[:-1], counts


def test_recovery_armed_adds_zero_steady_state_retraces(tmp_path):
    """Acceptance: the armed recovery path (snapshots every 2 steps
    here) adds NO steady-state retraces — jnp.copy snapshots are eager
    ops, never fresh jits."""
    from cup3d_tpu.analysis.runtime import RecompileCounter
    from cup3d_tpu.sim.simulation import Simulation

    with RecompileCounter() as rc:
        sim = Simulation(_uniform_cfg(tmp_path, tend=0.0, nsteps=10**9))
        sim.init()
        sim.advance(sim.calc_max_timestep())  # first step compiles
        eng = RecoveryEngine.install(sim, force=True, snapshot_every=2)
        try:
            for _ in range(5):
                eng.on_loop_top()
                sim.advance(sim.calc_max_timestep())
        finally:
            eng.uninstall()
    assert rc.compiles, "counter saw no jitted functions"
    rc.assert_steady_state(budget=1)


def test_simulation_failure_carries_reason():
    e = SimulationFailure("dt-collapse", "dt policy collapse: dt=nan",
                         {"step": 3})
    assert isinstance(e, RuntimeError)
    assert e.reason == "dt-collapse" and e.extra["step"] == 3
