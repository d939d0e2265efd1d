"""K-step scan megaloop acceptance (sim/megaloop.py; VALIDATION.md
"Round 11"):

- K-equivalence: the scan trajectory is a pure function of the carry, so
  K=1 vs K=8 must agree bitwise on the uniform TGV and to <= 1e-6 KE on
  the fish (empirically bitwise too: same compiled one_step body).
- Device- vs host-midline chi/udef equivalence at several gait phases
  (the frozen-gait port of models/fish/device_midline.py against the
  NumPy pipeline), f32-vs-f64 tolerances.
- Resilience: a fault landing mid-megaloop rolls back to a K-aligned
  snapshot and completes; recovery armed with no faults stays bitwise
  vs the CUP3D_RECOVER=0 legacy loop.
- Zero steady-state retraces: the compiled megaloop serves every
  dispatch of the run from one trace (RecompileCounter budget 1).
- Gating: CUP3D_SCAN_K resolution, static eligibility, per-step tail.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cup3d_tpu.obs import metrics as M
from cup3d_tpu.resilience import faults
from cup3d_tpu.sim.simulation import Simulation
from tests._cases import fish_cfg, mean_ke, simulate, tgv_cfg


pytestmark = pytest.mark.usefixtures("clean_faults")


# -- K-equivalence ---------------------------------------------------------


def test_tgv_scan_k1_vs_k8_bitwise(tmp_path):
    """One compiled one_step body serves both: only the scan length
    differs, so the trajectories must agree BITWISE."""
    a = simulate(tgv_cfg(tmp_path / "k1", scan_k=1))
    b = simulate(tgv_cfg(tmp_path / "k8", scan_k=8))
    assert a._scan_k == 1 and b._scan_k == 8
    assert a.sim.step == b.sim.step == 16
    np.testing.assert_array_equal(
        np.asarray(a.sim.state["vel"]), np.asarray(b.sim.state["vel"]))
    np.testing.assert_array_equal(
        np.asarray(a.sim.state["p"]), np.asarray(b.sim.state["p"]))
    assert a.sim.time == b.sim.time
    assert a.sim.dt == b.sim.dt


def test_fish_scan_k1_vs_k8_ke(tmp_path):
    """Fish carry adds rigid/qint/chi/udef; K must still not change the
    physics (<= 1e-6 relative KE, the ISSUE tolerance)."""
    a = simulate(fish_cfg(tmp_path / "k1", scan_k=1))
    b = simulate(fish_cfg(tmp_path / "k8", scan_k=8))
    assert a._scan_k == 1 and b._scan_k == 8
    assert a.sim.step == b.sim.step == 8
    ke_a, ke_b = mean_ke(a.sim.state["vel"]), mean_ke(b.sim.state["vel"])
    assert abs(ke_a - ke_b) <= 1e-6 * max(abs(ke_a), 1e-12)
    np.testing.assert_allclose(
        a.sim.obstacles[0].position, b.sim.obstacles[0].position,
        rtol=0, atol=1e-6)
    np.testing.assert_allclose(
        a.sim.obstacles[0].transVel, b.sim.obstacles[0].transVel,
        rtol=0, atol=1e-6)


# -- device- vs host-midline chi/udef --------------------------------------


def test_device_midline_chi_udef_matches_host(tmp_path):
    """The frozen-gait device midline, rasterized exactly as the scan
    body does, reproduces the host CreateObstacles chi/udef at several
    gait phases (f32 device vs f64 host tolerances)."""
    from cup3d_tpu.models.base import quat_to_rot_dev
    from cup3d_tpu.models.fish.device_midline import (
        device_midline_eligible,
        freeze_gait,
        midline_state_device,
    )
    from cup3d_tpu.models.fish.rasterize import rasterize_midline
    from cup3d_tpu.ops.chi import towers_chi

    # y/z offset by h/2: centers the (sub-cell-thin) body on cell
    # centers so the resting fish still owns interior cells at 32^3
    sim = Simulation(fish_cfg(tmp_path, factory_content=(
        "stefanfish L=0.3 T=1.0 xpos=0.5 ypos=0.515625 zpos=0.515625")))
    sim.init()
    s = sim.sim
    ob = s.obstacles[0]
    assert device_midline_eligible(ob)
    gait = freeze_gait(ob, 0.0, s.dtype)
    assert gait is not None

    grid = s.grid
    h = float(grid.h)
    n = np.asarray(grid.shape)
    grid_shape = tuple(int(v) for v in n)
    window_shape = tuple(ob._window_shape)
    half_win = 0.5 * np.asarray(window_shape) * h
    lim_win = n - np.asarray(window_shape)
    dt = 1e-3
    for t in (0.0, 0.25, 0.55, 0.8):  # gait phases t/T of the T=1 fish
        qint0 = np.asarray(ob.myFish.quaternion_internal, np.float64)
        # host path: NumPy midline -> rasterization (CreateObstacles)
        ob.update_shape(t, dt)
        ob.create(t)
        chi_h = np.asarray(ob.chi, np.float64)
        udef_h = np.asarray(ob.udef, np.float64)
        # device twin from the SAME pre-step state, the scan-body code
        mid, _ = midline_state_device(
            gait, jnp.asarray(t, s.dtype), jnp.asarray(dt, s.dtype),
            jnp.asarray(qint0, s.dtype))
        rigid = jnp.asarray(ob.rigid_state_vec(), s.dtype)
        pos, rot = rigid[6:9], quat_to_rot_dev(rigid[15:19])
        idx0 = np.clip(
            np.floor((np.asarray(pos, np.float64) - half_win) / h)
            .astype(np.int64), 0, lim_win)
        origin = jnp.asarray(idx0 * h, s.dtype)
        sdf_w, udef_w = rasterize_midline(
            origin, jnp.asarray(h, s.dtype), window_shape, ob._raster_box,
            mid, pos, rot)
        sdf = jnp.full(grid_shape, -1.0, s.dtype)
        sdf = jax.lax.dynamic_update_slice(
            sdf, sdf_w, tuple(int(v) for v in idx0))
        udef_d = jnp.zeros(grid_shape + (3,), s.dtype)
        udef_d = jax.lax.dynamic_update_slice(
            udef_d, udef_w, tuple(int(v) for v in idx0) + (0,))
        chi_d = towers_chi(grid.pad_scalar(sdf, 1), grid.h)
        udef_d = udef_d * (chi_d > 0)[..., None]

        chi_d = np.asarray(chi_d, np.float64)
        udef_d = np.asarray(udef_d, np.float64)
        # the bodies overlap almost perfectly: mismatched cells are
        # confined to the one-cell mollification band of the f32 SDF
        vol_h, vol_d = chi_h.sum(), chi_d.sum()
        assert vol_h > 0 and abs(vol_d - vol_h) <= 2e-3 * vol_h, t
        assert np.abs(chi_d - chi_h).mean() <= 1e-4, t
        # chi-weighted udef is what penalization consumes: compare the
        # weighted field pointwise (the sub-cell-thin body never reaches
        # chi ~ 1, so an unweighted core mask would be empty)
        wh = chi_h[..., None] * udef_h
        wd = chi_d[..., None] * udef_d
        scale = max(np.abs(wh).max(), 1e-6)
        assert np.abs(wd - wh).max() <= 2e-2 * scale, t
        if np.abs(wh).max() > 1e-6:  # phases past the rest state
            np.testing.assert_allclose(
                wd.sum(axis=(0, 1, 2)), wh.sum(axis=(0, 1, 2)),
                rtol=0, atol=2e-2 * float(np.abs(wh.sum(axis=(0, 1, 2)))
                                          .max() + 1e-9), err_msg=str(t))


# -- resilience across the megaloop ---------------------------------------


def test_scan_fault_mid_megaloop_rolls_back_and_completes(tmp_path,
                                                          monkeypatch):
    """step.nan_velocity armed INSIDE a K=4 megaloop (step 6, the third
    row of the second dispatch): detection rides the row consumption,
    rollback lands on the K-aligned cadence snapshot, the run completes
    with a clean decaying field."""
    monkeypatch.setenv("CUP3D_SNAP_EVERY", "4")
    ref = simulate(tgv_cfg(tmp_path / "ref", scan_k=4))
    ke_ref = mean_ke(ref.sim.state["vel"])

    faults.arm("step.nan_velocity", 6, 1)
    s0 = M.snapshot()
    sim = simulate(tgv_cfg(tmp_path / "flt", scan_k=4))
    d = M.delta(s0)
    assert sim.sim.step == 16
    assert d["resilience.rollbacks"] == 1
    assert d.get("resilience.giveups", 0) == 0
    vel = np.asarray(sim.sim.state["vel"], np.float64)
    assert np.isfinite(vel).all()
    ke = mean_ke(vel)
    # the retreat shrinks dt for the retried steps, so the faulted run
    # reaches step 16 at an earlier physical time than the reference:
    # demand a sane decaying-TGV energy, not a matched trajectory
    assert ke_ref <= ke <= 0.26  # initial mean KE of TGV is 0.25
    assert sim.sim.time <= ref.sim.time
    # the recovery retreat is temporary: the megaloop resumed after the
    # retried steps (scan-flagged flight records past the fault step)
    scans = [r["step"] for r in sim.flight.steps if r.get("scan")]
    assert scans and max(scans) == 15


def test_scan_recover_armed_idle_is_bitwise_vs_legacy(tmp_path,
                                                      monkeypatch):
    """Recovery armed + no faults must not perturb the scan trajectory:
    bitwise vs the CUP3D_RECOVER=0 legacy loop at the same K."""
    armed = simulate(tgv_cfg(tmp_path / "armed", scan_k=4))
    monkeypatch.setenv("CUP3D_RECOVER", "0")
    legacy = simulate(tgv_cfg(tmp_path / "legacy", scan_k=4))
    assert armed._scan_k == legacy._scan_k == 4
    np.testing.assert_array_equal(
        np.asarray(armed.sim.state["vel"]),
        np.asarray(legacy.sim.state["vel"]))
    assert armed.sim.time == legacy.sim.time


# -- steady-state retrace freedom ------------------------------------------


def test_scan_zero_steady_state_retraces(tmp_path):
    """Every megaloop dispatch of the run reuses ONE trace (the frozen
    probe budget / window geometry never retrace mid-run)."""
    from cup3d_tpu.analysis import runtime as R

    with R.RecompileCounter() as rc:
        sim = simulate(tgv_cfg(tmp_path, scan_k=4))
    assert sim._scan_k == 4
    assert "megaloop" in rc.compiles
    rc.assert_steady_state(budget=1)
    # 16 steps / K=4 -> the compiled loop actually served 4 dispatches
    assert rc.calls["megaloop"] == 4


# -- gating ----------------------------------------------------------------


def test_scan_k_resolution_and_eligibility(tmp_path, monkeypatch):
    def scan_k_of(cfg):
        sim = Simulation(cfg)
        sim.init()
        return sim._scan_k

    # env knob overrides config; malformed env falls back to config
    monkeypatch.setenv("CUP3D_SCAN_K", "5")
    assert scan_k_of(tgv_cfg(tmp_path / "env", scan_k=2)) == 5
    monkeypatch.setenv("CUP3D_SCAN_K", "bogus")
    assert scan_k_of(tgv_cfg(tmp_path / "bad", scan_k=2)) == 2
    monkeypatch.delenv("CUP3D_SCAN_K")
    # static gates: pipelined only, step-budget runs only
    assert scan_k_of(tgv_cfg(tmp_path / "np", scan_k=4,
                              pipelined=False)) == 0
    assert scan_k_of(tgv_cfg(tmp_path / "tend", scan_k=4, tend=0.5,
                              nsteps=0)) == 0
    assert scan_k_of(tgv_cfg(tmp_path / "fixed", scan_k=4,
                              dt=1e-3)) == 0


def test_scan_tail_steps_fall_back_to_host(tmp_path):
    """nsteps not divisible by K: the tail runs per-step so the step
    budget stays exact; flight records flag the scan steps."""
    sim = simulate(tgv_cfg(tmp_path, scan_k=4, nsteps=10))
    assert sim.sim.step == 10
    recs = list(sim.flight.steps)
    # scan rows cover steps 0..7; the host tail covers 8..9 (megaloop
    # dispatch records carry scan_k and ride alongside, not instead)
    assert [r["step"] for r in recs if r.get("scan")] == list(range(8))
    host = [r["step"] for r in recs
            if not r.get("scan") and "scan_k" not in r]
    assert host == [8, 9]
