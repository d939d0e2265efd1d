"""Collision handling (reference preventCollidingObstacles +
ElasticCollision, main.cpp:13939-14325)."""

import jax.numpy as jnp

import pytest
import numpy as np

from cup3d_tpu.models.collisions import (
    elastic_collision,
    pair_overlap_summary,
    prevent_colliding_obstacles,
)


def test_elastic_collision_head_on_equal_masses():
    """1-D elastic head-on collision of equal masses exchanges velocities;
    momentum and kinetic energy conserved (e=1)."""
    J = np.eye(3) * 1e-4
    v1, v2 = np.array([1.0, 0, 0]), np.array([-1.0, 0, 0])
    o = np.zeros(3)
    c1, c2 = np.array([0.4, 0.5, 0.5]), np.array([0.6, 0.5, 0.5])
    n = np.array([-1.0, 0, 0])  # normal pointing j -> i
    c = np.array([0.5, 0.5, 0.5])
    nv1, nv2, no1, no2 = elastic_collision(
        1.0, 1.0, J, J, v1, v2, o, o, c1, c2, n, c, v1, v2
    )
    np.testing.assert_allclose(nv1, [-1.0, 0, 0], atol=1e-12)
    np.testing.assert_allclose(nv2, [1.0, 0, 0], atol=1e-12)
    np.testing.assert_allclose(no1, 0, atol=1e-9)
    # conservation
    np.testing.assert_allclose(nv1 + nv2, v1 + v2, atol=1e-12)
    np.testing.assert_allclose(
        nv1 @ nv1 + nv2 @ nv2, v1 @ v1 + v2 @ v2, atol=1e-12
    )


def test_elastic_collision_mass_ratio():
    """Heavy body barely deflects; light body bounces (m1 >> m2)."""
    J = np.eye(3) * 1e-4
    v1, v2 = np.array([0.0, 0, 0]), np.array([-1.0, 0, 0])
    o = np.zeros(3)
    c1, c2 = np.array([0.4, 0.5, 0.5]), np.array([0.6, 0.5, 0.5])
    n = np.array([-1.0, 0, 0])
    c = np.array([0.5, 0.5, 0.5])
    nv1, nv2, _, _ = elastic_collision(
        1e10, 1.0, J * 1e10, J, v1, v2, o, o, c1, c2, n, c, v1, v2
    )
    np.testing.assert_allclose(nv1, 0, atol=1e-9)
    np.testing.assert_allclose(nv2, [1.0, 0, 0], atol=1e-9)


class _FakeOb:
    def __init__(self, chi, mass, cm, vel):
        self.chi = chi
        self.mass = mass
        self.centerOfMass = np.asarray(cm, np.float64)
        self.transVel = np.asarray(vel, np.float64)
        self.angVel = np.zeros(3)
        self.J = np.eye(3) * 1e-4 * mass
        self.bForcedInSimFrame = np.array([False] * 3)
        self.collision_counter = 0.0


def _sphere_chi(grid, center, r):
    x = np.asarray(grid.cell_centers(np.float64))
    d = r - np.linalg.norm(x - np.asarray(center), axis=-1)
    return jnp.asarray((d > 0).astype(np.float32))


def test_prevent_colliding_spheres_head_on():
    """Two overlapping spheres approaching head-on: collision fires, the
    velocities exchange (equal masses), momentum conserved, and the latch
    is set.  Receding bodies are left alone."""
    from functools import partial

    from cup3d_tpu.grid.uniform import BC, UniformGrid
    from cup3d_tpu.ops.chi import grad_chi

    g = UniformGrid((48, 48, 48), (1.0,) * 3, (BC.periodic,) * 3)
    xc = g.cell_centers(jnp.float32)
    r = 0.12
    # overlapping: centers 0.2 apart, radii 0.12
    ob1 = _FakeOb(_sphere_chi(g, (0.4, 0.5, 0.5), r), 1.0, (0.4, 0.5, 0.5),
                  (0.5, 0.0, 0.0))
    ob2 = _FakeOb(_sphere_chi(g, (0.6, 0.5, 0.5), r), 1.0, (0.6, 0.5, 0.5),
                  (-0.5, 0.0, 0.0))
    ub = [
        jnp.broadcast_to(jnp.asarray(ob.transVel, jnp.float32), xc.shape)
        for ob in (ob1, ob2)
    ]
    p_before = ob1.mass * ob1.transVel + ob2.mass * ob2.transVel
    hit = prevent_colliding_obstacles(
        [ob1, ob2], ub, partial(grad_chi, g), xc, dt=1e-3
    )
    assert hit
    p_after = ob1.mass * ob1.transVel + ob2.mass * ob2.transVel
    np.testing.assert_allclose(p_after, p_before, atol=1e-8)
    # equal-mass head-on: velocities exchange along x
    assert ob1.transVel[0] < -0.4 and ob2.transVel[0] > 0.4
    assert ob1.collision_counter > 0 and ob2.collision_counter > 0

    # receding: no action
    ob1b = _FakeOb(ob1.chi, 1.0, (0.4, 0.5, 0.5), (-0.5, 0.0, 0.0))
    ob2b = _FakeOb(ob2.chi, 1.0, (0.6, 0.5, 0.5), (0.5, 0.0, 0.0))
    ubb = [
        jnp.broadcast_to(jnp.asarray(ob.transVel, jnp.float32), xc.shape)
        for ob in (ob1b, ob2b)
    ]
    hit2 = prevent_colliding_obstacles(
        [ob1b, ob2b], ubb, partial(grad_chi, g), xc, dt=1e-3
    )
    assert not hit2
    assert ob1b.transVel[0] == -0.5 and ob1b.collision_counter == 0.0


def test_no_overlap_no_collision():
    from functools import partial

    from cup3d_tpu.grid.uniform import BC, UniformGrid
    from cup3d_tpu.ops.chi import grad_chi

    g = UniformGrid((32, 32, 32), (1.0,) * 3, (BC.periodic,) * 3)
    xc = g.cell_centers(jnp.float32)
    ob1 = _FakeOb(_sphere_chi(g, (0.25, 0.5, 0.5), 0.1), 1.0,
                  (0.25, 0.5, 0.5), (0.5, 0, 0))
    ob2 = _FakeOb(_sphere_chi(g, (0.75, 0.5, 0.5), 0.1), 1.0,
                  (0.75, 0.5, 0.5), (-0.5, 0, 0))
    ub = [
        jnp.broadcast_to(jnp.asarray(ob.transVel, jnp.float32), xc.shape)
        for ob in (ob1, ob2)
    ]
    assert not prevent_colliding_obstacles(
        [ob1, ob2], ub, partial(grad_chi, g), xc, dt=1e-3
    )


@pytest.mark.slow
def test_two_fish_collision_in_simulation():
    """End-to-end: two fish spawned overlapping nose-to-nose on the AMR
    driver; the run stays finite and the bodies do not interpenetrate
    deeply (collision impulse + latch active)."""
    from cup3d_tpu.config import SimulationConfig
    from cup3d_tpu.sim.amr import AMRSimulation

    factory = (
        "StefanFish L=0.3 T=1.0 xpos=0.40 ypos=0.5 zpos=0.5 planarAngle=180 "
        "heightProfile=stefan widthProfile=stefan\n"
        "StefanFish L=0.3 T=1.0 xpos=0.60 ypos=0.5 zpos=0.5 "
        "heightProfile=stefan widthProfile=stefan"
    )
    cfg = SimulationConfig(
        bpdx=1, bpdy=1, bpdz=1, levelMax=2, levelStart=1, extent=1.0,
        CFL=0.4, nu=1e-4, tend=0.0, nsteps=4, factory_content=factory,
        poissonSolver="iterative", poissonTol=1e-3, poissonTolRel=1e-2,
        verbose=False, Rtol=1e9, Ctol=-1.0, freqDiagnostics=0,
    )
    sim = AMRSimulation(cfg)
    sim.init()
    while sim.step_idx < cfg.nsteps:
        sim.advance(sim.calc_max_timestep())
    for ob in sim.obstacles:
        assert np.all(np.isfinite(ob.transVel))
        assert np.all(np.isfinite(ob.position))


def test_penalization_force_conservation_and_attribution():
    """Momentum balance: per-obstacle penalization forces (body frame) sum
    to -(total fluid momentum change)/dt; overlap cells split by chi
    fraction (reference kernelFinalizePenalizationForce semantics,
    main.cpp:13913-13938)."""
    from cup3d_tpu.ops.penalization import per_obstacle_penalization_force

    rng = np.random.default_rng(3)
    shape = (16, 16, 16)
    xc = jnp.asarray(
        np.stack(np.meshgrid(*[(np.arange(16) + 0.5) / 16] * 3,
                             indexing="ij"), -1).astype(np.float32)
    )
    vol = (1.0 / 16) ** 3
    chi1 = jnp.asarray((rng.random(shape) < 0.3).astype(np.float32))
    chi2 = jnp.asarray((rng.random(shape) < 0.3).astype(np.float32))
    vo = jnp.asarray(rng.standard_normal(shape + (3,)).astype(np.float32))
    vn = jnp.asarray(rng.standard_normal(shape + (3,)).astype(np.float32))
    dt = 1e-2
    cms = jnp.asarray(np.array([[0.3, 0.5, 0.5], [0.7, 0.5, 0.5]], np.float32))
    PF = np.asarray(per_obstacle_penalization_force(
        vn, vo, (chi1, chi2), dt, vol, xc, cms
    ))
    # conservation over the union of bodies (chi-fraction weights sum to 1
    # wherever any chi > 0)
    mask = (np.asarray(chi1) + np.asarray(chi2)) > 0
    dmom = (np.asarray(vn) - np.asarray(vo)) / dt * vol
    total = dmom[mask].sum(axis=0)
    np.testing.assert_allclose(PF[:, :3].sum(axis=0), total, rtol=1e-4)
    # attribution: an obstacle with zero chi gets zero force
    PF0 = np.asarray(per_obstacle_penalization_force(
        vn, vo, (chi1, jnp.zeros_like(chi2)), dt, vol, xc, cms
    ))
    np.testing.assert_allclose(PF0[1], 0.0, atol=1e-12)


def test_contact_step_takes_the_slow_branch_then_the_fused_penalization():
    """Two forced spheres spawned overlapping and approaching on a small
    forest, per-step path: the pre-check counts cells in both, the step
    builds the bodies' velocity fields op by op for the impulse, the
    velocities exchange and latch, and the fused Penalization then runs
    with the latched velocities (held to the parent's chain, which reads
    the same mirrors)."""
    from cup3d_tpu.config import SimulationConfig
    from cup3d_tpu.obs import metrics as obs_metrics
    from tests.test_amr_fish import (
        _BodySpy,
        assert_bodies_match_parent_chain,
    )

    sim = _BodySpy(SimulationConfig(
        bpdx=1, bpdy=1, bpdz=1, levelMax=2, levelStart=1, extent=1.0,
        CFL=0.4, Ctol=0.1, Rtol=5.0, nu=1e-3, tend=0.0, nsteps=2,
        rampup=0, dt=2e-3, poissonSolver="iterative", poissonTol=1e-6,
        poissonTolRel=1e-4, verbose=False, freqDiagnostics=0,
        factory_content=(
            "Sphere radius=0.12 xpos=0.45 ypos=0.5 zpos=0.5 xvel=0.5"
            " bForcedInSimFrame=1\n"
            "Sphere radius=0.12 xpos=0.55 ypos=0.5 zpos=0.5 xvel=-0.5"
            " bForcedInSimFrame=1"),
    ))
    sim.init()
    sim.adapt_enabled = False
    sim.watch_bodies = True
    a, b = sim.obstacles
    before = obs_metrics.snapshot()
    dt = sim.calc_max_timestep()
    sim.advance(dt)
    moved = obs_metrics.delta(before)
    assert moved["operators.body_steps_contact"] == 1
    assert moved.get("operators.body_steps_fused", 0) == 0
    assert sim._overlap_now
    # the impulse fired: the approach is reversed, and latched for the
    # next step's update
    assert a.transVel[0] < 0.0 < b.transVel[0]
    for ob in (a, b):
        assert ob.collision_counter > 0
        np.testing.assert_array_equal(ob.transVel, ob.collision_vel)
    assert_bodies_match_parent_chain(sim, dt)
    # inside the first sphere the fluid was pushed the latched way
    inside = np.asarray(a.chi) > 0.99
    assert np.asarray(sim.bodies_seen["vel"])[inside][:, 0].mean() < 0.0
