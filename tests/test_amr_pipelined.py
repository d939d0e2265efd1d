"""Pipelined AMR stepping (sim/amr.py advance_pipelined): the fused device
megastep + depth-2 packed QoI reads must reproduce the per-operator host
path's physics on the two-fish acceptance topology."""

import numpy as np
import pytest

from cup3d_tpu.config import SimulationConfig
from cup3d_tpu.sim.amr import AMRSimulation

TWO_FISH = (
    "StefanFish L=0.4 T=1.0 xpos=0.3 ypos=0.5 zpos=0.5 planarAngle=180 "
    "heightProfile=danio widthProfile=stefan bFixFrameOfRef=1\n"
    "StefanFish L=0.4 T=1.0 xpos=0.7 ypos=0.5 zpos=0.5 "
    "heightProfile=danio widthProfile=stefan"
)
# resolvable at levelMax=2 (the Towers chi vanishes sub-cell bodies, so
# the fast A/B equality cases use spheres; the fish case runs at its
# resolvable levelMax=4 below)
TWO_SPHERES = (
    "Sphere radius=0.12 xpos=0.35 ypos=0.5 zpos=0.5 xvel=0.3 "
    "bForcedInSimFrame=1 bFixFrameOfRef=1\n"
    "Sphere radius=0.1 xpos=0.7 ypos=0.45 zpos=0.5"
)


def _run(pipelined, nsteps=5, factory=TWO_SPHERES, adapt=True,
         level_max=2, bpd=1, level_start=None):
    cfg = SimulationConfig(
        bpdx=bpd, bpdy=bpd, bpdz=bpd, levelMax=level_max,
        levelStart=level_max - 1 if level_start is None else level_start,
        extent=1.0,
        CFL=0.4, Ctol=0.1, Rtol=5.0, nu=1e-3, tend=0.0, nsteps=nsteps,
        rampup=0, dt=1e-3, poissonSolver="iterative",
        poissonTol=1e-6, poissonTolRel=1e-4, factory_content=factory,
        verbose=False, freqDiagnostics=0, pipelined=pipelined,
    )
    sim = AMRSimulation(cfg)
    sim.init()
    sim.adapt_enabled = adapt
    sim.simulate()
    return sim


def test_pipelined_two_bodies_two_levels_match_host_path():
    """The bucketed megastep with bodies traces and runs (PR 28 had left
    its body velocity dividing by a name that was gone): two spheres in
    the refined octant of a two-level forest (7 coarse blocks, 8 fine),
    three steps, so both orders of the projection compile.  Fixed dt:
    measured 1.5e-8 on a velocity of 0.21; the host path solves the
    rigid 6x6 in float64, the device chain in float32."""
    factory = (
        "Sphere radius=0.07 xpos=0.36 ypos=0.36 zpos=0.36 xvel=0.3 "
        "bForcedInSimFrame=1 bFixFrameOfRef=1\n"
        "Sphere radius=0.06 xpos=0.36 ypos=0.36 zpos=0.14"
    )
    pipe = _run(True, nsteps=3, factory=factory, adapt=False, bpd=2,
                level_start=0)
    ref = _run(False, nsteps=3, factory=factory, adapt=False, bpd=2,
               level_start=0)
    assert not pipe._pack_reader  # flushed
    assert sorted(np.bincount(np.asarray(pipe.grid.level))) == [7, 8]
    assert pipe.grid.nb == ref.grid.nb == 15
    np.testing.assert_allclose(
        np.asarray(pipe.state["vel"]), np.asarray(ref.state["vel"]),
        rtol=0, atol=1e-6,
    )
    for op, orf in zip(pipe.obstacles, ref.obstacles):
        assert np.asarray(op.chi).sum() > 10.0  # the body is on the grid
        np.testing.assert_allclose(op.position, orf.position,
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(op.transVel, orf.transVel,
                                   rtol=1e-3, atol=1e-6)
    np.testing.assert_allclose(pipe.uinf, ref.uinf, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("adapt", [False, True])
@pytest.mark.slow
def test_pipelined_matches_host_path(adapt):
    """Fixed dt: the device rigid chain never depends on host mirrors, so
    pipelined and host-path trajectories agree to f32 round-off.  The
    adapt=True case crosses one re-layout (step 0..4 adapt every step),
    exercising the flush + chain-restart boundary."""
    pipe = _run(True, adapt=adapt)
    ref = _run(False, adapt=adapt)
    assert not pipe._pack_reader  # flushed
    assert pipe.grid.nb == ref.grid.nb
    for op, orf in zip(pipe.obstacles, ref.obstacles):
        np.testing.assert_allclose(op.position, orf.position,
                                   rtol=1e-6, atol=1e-8)
        # the host path solves the 6x6 in f64 numpy, the device chain in
        # f32: symmetric (noise-level ~1e-6) components differ by round-off
        np.testing.assert_allclose(op.transVel, orf.transVel,
                                   rtol=1e-3, atol=1e-5)
        np.testing.assert_allclose(op.force, orf.force, rtol=2e-3,
                                   atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(pipe.state["vel"]), np.asarray(ref.state["vel"]),
        atol=5e-5,
    )
    np.testing.assert_allclose(pipe.uinf, ref.uinf, rtol=1e-3, atol=1e-5)


@pytest.mark.slow
def test_pipelined_two_fish_matches_host_path():
    """The resolved two-fish acceptance topology (levelMax=4): megastep
    vs host path, crossing the early-step adaptations."""
    pipe = _run(True, nsteps=3, factory=TWO_FISH, level_max=4)
    ref = _run(False, nsteps=3, factory=TWO_FISH, level_max=4)
    assert pipe.grid.nb == ref.grid.nb
    for op, orf in zip(pipe.obstacles, ref.obstacles):
        np.testing.assert_allclose(op.position, orf.position,
                                   rtol=1e-6, atol=1e-8)
        np.testing.assert_allclose(op.transVel, orf.transVel,
                                   rtol=1e-3, atol=1e-5)
    # the fish is actually resolved: it carries mass and swims
    assert np.asarray(pipe.obstacles[0].chi).sum() > 1.0
    assert np.linalg.norm(pipe.obstacles[0].transVel) > 0.0


@pytest.mark.slow
def test_pipelined_obstacle_free_matches_host():
    """Obstacle-free fused stepping (advance_pipelined_free) reproduces
    the host path on a mixed-level Taylor-Green run."""
    def run(pipe):
        cfg = SimulationConfig(
            bpdx=2, bpdy=2, bpdz=2, levelMax=2, levelStart=0,
            extent=float(2 * np.pi), CFL=0.4, Rtol=1.8, Ctol=0.05,
            nu=1e-3, tend=0.0, nsteps=6, rampup=0, dt=1e-3,
            poissonSolver="iterative", poissonTol=1e-6, poissonTolRel=1e-4,
            initCond="taylorGreen", verbose=False, freqDiagnostics=0,
            pipelined=pipe,
        )
        sim = AMRSimulation(cfg)
        sim.init()
        sim.adapt_enabled = False
        sim.simulate()
        return sim

    pipe, ref = run(True), run(False)
    np.testing.assert_allclose(
        np.asarray(pipe.state["vel"]), np.asarray(ref.state["vel"]),
        atol=2e-5,
    )


def test_pipelined_rejects_roll_corrected_fish():
    """Roll correction mutates angVel on host right after the 6x6 solve —
    incompatible with the device rigid chain."""
    with pytest.raises(ValueError):
        _run(
            True,
            factory=(
                "StefanFish L=0.4 T=1.0 xpos=0.3 ypos=0.5 zpos=0.5 "
                "heightProfile=danio widthProfile=stefan CorrectRoll=1"
            ),
        )


@pytest.mark.slow
def test_pipelined_stale_pid_fish_runs():
    """Position/depth PID fish run in pipelined mode on stale mirrors
    (bounded by the grouped-read cadence) and track the host path."""
    factory = (
        "StefanFish L=0.4 T=1.0 xpos=0.3 ypos=0.5 zpos=0.5 "
        "heightProfile=danio widthProfile=stefan CorrectPosition=1 "
        "CorrectPositionZ=1"
    )
    # nsteps must exceed 2x the grouped-read cadence (4) so the PID
    # actually consumes stale packs mid-run — the staleness under test
    pipe = _run(True, nsteps=10, factory=factory, level_max=4, adapt=False)
    ref = _run(False, nsteps=10, factory=factory, level_max=4, adapt=False)
    assert pipe._pack_reader.read_every * 2 < 10
    for ob in pipe.obstacles:
        assert np.all(np.isfinite(ob.position))
    # stale PID inputs lag by <= 2x the read cadence; the clipped, gentle
    # controllers keep the trajectory close to the fresh-mirror host path
    np.testing.assert_allclose(
        pipe.obstacles[0].position, ref.obstacles[0].position, atol=1e-5
    )


@pytest.mark.slow
def test_pipelined_collision_fallback():
    """Two spheres driven into contact: the stale overlap pre-check in the
    pack must latch _collision_hot, reroute stepping to the host path
    (which runs the fresh pre-check + impulse machinery), and keep the
    trajectory finite across the mode switch."""
    cfg = SimulationConfig(
        bpdx=1, bpdy=1, bpdz=1, levelMax=2, levelStart=1, extent=1.0,
        CFL=0.4, Ctol=0.1, Rtol=5.0, nu=1e-3, tend=0.0, nsteps=14,
        rampup=0, dt=2e-3,
        poissonSolver="iterative", poissonTol=1e-6, poissonTolRel=1e-4,
        factory_content=(
            # start interpenetrated: the overlap pre-check (chi>0.5 in both
            # bodies) must fire from the very first pack
            "Sphere radius=0.12 xpos=0.45 ypos=0.5 zpos=0.5 xvel=0.5\n"
            "Sphere radius=0.12 xpos=0.55 ypos=0.5 zpos=0.5 xvel=-0.5"
        ),
        verbose=False, freqDiagnostics=0, pipelined=True,
    )
    sim = AMRSimulation(cfg)
    sim.init()
    sim.adapt_enabled = False
    went_hot = False
    for _ in range(cfg.nsteps):
        sim.advance(sim.calc_max_timestep())
        went_hot = went_hot or sim._collision_hot
    sim.flush_packs()
    assert went_hot, "overlap pre-check never latched the host fallback"
    for ob in sim.obstacles:
        assert np.all(np.isfinite(ob.position))
        assert np.all(np.isfinite(ob.transVel))
    assert np.isfinite(np.asarray(sim.state["vel"])).all()
    # the host path's impulse machinery engaged: relative approach speed
    # must not have grown (e=1 exchange or separation)
    v_rel = sim.obstacles[1].transVel[0] - sim.obstacles[0].transVel[0]
    assert v_rel > -4.0


@pytest.mark.slow
def test_pipelined_umax_tracks_flow():
    """The stale-read dt machinery still produces a sane CFL dt chain
    (growth bounded, no runaway) when dt is adaptive."""
    cfg = SimulationConfig(
        bpdx=1, bpdy=1, bpdz=1, levelMax=2, levelStart=1, extent=1.0,
        CFL=0.4, Ctol=0.1, Rtol=5.0, nu=1e-3, tend=0.0, nsteps=6,
        rampup=0, poissonSolver="iterative", poissonTol=1e-6,
        poissonTolRel=1e-4, factory_content=TWO_SPHERES, verbose=False,
        freqDiagnostics=0, pipelined=True,
    )
    sim = AMRSimulation(cfg)
    sim.init()
    sim.adapt_enabled = False
    dts = []
    for _ in range(6):
        dts.append(sim.calc_max_timestep())
        sim.advance(sim.dt)
    sim.flush_packs()
    assert all(np.isfinite(d) and d > 0 for d in dts)
    for a, b in zip(dts, dts[1:]):
        assert b <= 1.05 * a + 1e-12


@pytest.mark.slow
def test_device_dt_chain_matches_host_policy():
    """Device-resident dt chain (dtDevice=1, obstacle-free CFL runs)
    implements the NON-pipelined fresh-umax dt policy exactly (no 1.5x
    staleness margin, no growth cap): compare against pipelined=False.
    Only f32-vs-f64 dt round-off separates the trajectories."""
    def run(pipe, dt_device):
        cfg = SimulationConfig(
            bpdx=2, bpdy=2, bpdz=2, levelMax=2, levelStart=0,
            extent=float(2 * np.pi), CFL=0.3, Rtol=1.8, Ctol=0.05,
            nu=1e-3, tend=0.0, nsteps=8, rampup=0,
            poissonSolver="iterative", poissonTol=1e-6, poissonTolRel=1e-4,
            initCond="taylorGreen", verbose=False, freqDiagnostics=0,
            pipelined=pipe, dtDevice=dt_device,
        )
        sim = AMRSimulation(cfg)
        sim.init()
        sim.adapt_enabled = False
        assert sim._use_device_dt() == (dt_device == 1)
        sim.simulate()
        sim.flush_packs()
        return sim

    dev, host = run(True, 1), run(False, 0)
    # time is a device scalar on the chain; both end after 8 CFL steps
    t_dev = float(np.asarray(dev.time))
    assert abs(t_dev - host.time) < 1e-4 * max(host.time, 1e-12)
    np.testing.assert_allclose(
        np.asarray(dev.state["vel"]), np.asarray(host.state["vel"]),
        atol=2e-4,
    )
