"""Obstacle operators in the timestep pipeline (reference order,
main.cpp:15229-15246): CreateObstacles -> ... -> UpdateObstacles ->
Penalization -> PressureProjection -> ComputeForces.
"""

from __future__ import annotations

from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from cup3d_tpu.models.base import (
    combine_obstacle_fields,
    force_integrals,
    log_forces,
    momentum_integrals,
    pack_forces,
    pack_moments,
    rigid_update_device,
    store_force_qoi,
    unpack_forces,
    unpack_moments,
    update_penalization_forces,
    vel_unit,
    vel_unit_dev,
)
from cup3d_tpu.ops.penalization import (
    penalize,
    per_obstacle_penalization_force,
)
from cup3d_tpu.sim.data import SimulationData
from cup3d_tpu.sim.operators import Operator

_EPS = 1e-6


def _device_step(s) -> bool:
    """True when this step's rigid update ran on device (single obstacle,
    rigid_update_device): QoI join the step's single packed read."""
    return (
        len(s.obstacles) == 1
        and s.obstacles[0]._dev_rigid is not None
        and s.obstacles[0]._dev_rigid["step"] == s.step
    )


class CreateObstacles(Operator):
    """Shape kinematics -> SDF -> chi/udef, then combine obstacle fields
    (reference CreateObstacles, main.cpp:13589-13621).

    The kinematics are host NumPy and take the step as ``sim.dt``, the
    Python float the driver keeps (the ``dt`` every operator is handed is
    a device scalar: arithmetic with it would move the midline onto the
    device op by op).  Each body then costs one upload at most and one
    program (``Obstacle.create``); a body alone on the grid gets the
    combined fields out of that same program, several bodies one more
    program over their stacked fields."""

    def __init__(self, sim: SimulationData):
        super().__init__(sim)
        self._combine = jax.jit(
            lambda chis, udefs: combine_obstacle_fields(
                jnp.stack(chis), jnp.stack(udefs)
            )
        )
        self._frame_velocity = jax.jit(lambda ts: -sum(ts) / len(ts))

    def __call__(self, dt):
        self._create(self.sim.dt)

    def rebuild(self):
        """chi/udef of the bodies as they stand at ``sim.time`` with
        nothing integrated: after a restore."""
        self._create(0.0)

    def _create(self, dt: float):
        s = self.sim
        self._update_uinf()
        alone = len(s.obstacles) == 1
        for ob in s.obstacles:
            ob.update_shape(s.time, dt)
            combined = ob.create(s.time, combine=alone)
        if not alone:
            combined = self._combine(
                tuple(ob.chi for ob in s.obstacles),
                tuple(ob.udef for ob in s.obstacles),
            )
        s.state["chi"], s.state["udef"] = combined

    def _update_uinf(self):
        """Frame-fixed swimming: uinf counteracts the tracked obstacle's
        translational velocity (ObstacleVector::updateUinf,
        main.cpp:8507-8519).  In pipelined mode the value stays device-
        resident (the host mirror trails one step, feeding only logs)."""
        s = self.sim
        fixed = [ob for ob in s.obstacles if ob.bFixFrameOfRef]
        if not fixed:
            return
        s.uinf = -np.mean([ob.transVel for ob in fixed], axis=0)
        devs = [ob._dev_rigid for ob in fixed]
        if s.cfg.pipelined and all(d is not None for d in devs):
            s._uinf_dev = self._frame_velocity(
                tuple(d["trans"] for d in devs)
            )


class UpdateObstacles(Operator):
    """chi-weighted fluid momenta -> 6x6 solve -> rigid-body update
    (reference UpdateObstacles, main.cpp:13812-13837).

    Single-obstacle fast path: when the update has no host-only branch
    (no collision latch, no roll correction) the whole chain — moments,
    6x6 solve, position/quaternion update — runs on device
    (rigid_update_device) and the result joins the step's single packed
    QoI read instead of blocking here (a blocking read stalls the
    dispatch queue)."""

    def __init__(self, sim: SimulationData):
        super().__init__(sim)
        # ALL obstacles' moments in one (n_obs, 19) host read per step
        self._moments = jax.jit(
            lambda chis, vel, cms: jnp.stack(
                [
                    pack_moments(momentum_integrals(sim.grid, c, vel, cms[i]))
                    for i, c in enumerate(chis)
                ]
            )
        )
        self._rigid = jax.jit(rigid_update_device)

    def __call__(self, dt):
        s = self.sim

        def cm_of(ob):
            # pipelined chaining: the fresh CM lives on device; the host
            # mirror trails one step and would shift the moment reference
            d = ob._dev_rigid
            if d is not None:
                return d["cm"]
            return jnp.asarray(ob.centerOfMass, s.dtype)

        cms = jnp.stack([cm_of(ob) for ob in s.obstacles])
        M = self._moments(tuple(ob.chi for ob in s.obstacles),
                          s.state["vel"], cms)
        if len(s.obstacles) == 1 and s.obstacles[0].supports_device_update():
            ob = s.obstacles[0]
            out = self._rigid(
                M[0],
                ob.rigid_state_dev(s.dtype),
                # cached static mirrors (models/base.py): the flags are
                # construction-time constants — re-staging them with
                # jnp.asarray every step was pure host->device residue
                # (lint rule JX010)
                ob.forced_mask_dev(),
                ob.block_mask_dev(),
                s.uinf_device(),
                jnp.asarray(dt, s.dtype),
            )
            ob._dev_rigid = {"step": s.step, "trans": out[0:3],
                             "ang": out[3:6], "cm": out[12:15], "pack": out}
            ob._ubody_cache = None
            s.pending_parts.append(("rigid", out))
            return
        # host fallback: pipelined mode must never land here with a live
        # device chain — the host mirrors trail the chain and would feed a
        # stale state into compute_velocities (ADVICE r2)
        assert not s.cfg.pipelined or all(
            ob._dev_rigid is None for ob in s.obstacles
        ), "pipelined host fallback with live device rigid chains"
        M = np.asarray(M)
        for ob, row in zip(s.obstacles, M):
            ob.compute_velocities(unpack_moments(row))
            ob.update(s.dt)  # host kinematics: the float (CreateObstacles)


class Penalization(Operator):
    """Collision handling then Brinkman forcing toward the combined body
    velocity field (reference Penalization, main.cpp:14326-14341:
    preventCollidingObstacles runs first, main.cpp:14330)."""

    def __init__(self, sim: SimulationData):
        super().__init__(sim)
        self._penalize = jax.jit(penalize)
        from cup3d_tpu.ops.chi import grad_chi

        self._gradchi = jax.jit(partial(grad_chi, sim.grid))
        self._xc = sim.xc  # device-cached centers (sim/data.py)
        h3 = sim.grid.h ** 3
        self._penal_force = jax.jit(
            lambda vn, vo, chis, dt, cms: per_obstacle_penalization_force(
                vn, vo, chis, dt, h3, sim.xc, cms
            )
        )

    def __call__(self, dt):
        s = self.sim
        if not s.obstacles:
            return
        ubs = [ob.body_velocity_field() for ob in s.obstacles]
        if len(s.obstacles) > 1:
            from cup3d_tpu.models.collisions import prevent_colliding_obstacles

            if prevent_colliding_obstacles(
                s.obstacles, ubs, self._gradchi, self._xc, s.dt
            ):
                # collision overrode rigid velocities: rebuild the fields
                ubs = [ob.body_velocity_field() for ob in s.obstacles]
        chis = jnp.stack([ob.chi for ob in s.obstacles])
        num = sum(ob.chi[..., None] * ub for ob, ub in zip(s.obstacles, ubs))
        den = jnp.maximum(jnp.sum(chis, axis=0), _EPS)[..., None]
        ubody = num / den
        vel_old = s.state["vel"]
        dt_dev = jnp.asarray(dt, s.dtype)
        s.state["vel"] = self._penalize(
            # lambda rides the device (sim/data.lambda_device): DLM/dt
            # divides on device from the step's dt scalar instead of
            # re-staging a fresh host float every step (rule JX010)
            vel_old, s.state["chi"], ubody, s.lambda_device(dt_dev), dt_dev,
        )
        PF = update_penalization_forces(
            s.obstacles, self._penal_force, s.state["vel"], vel_old, dt,
            s.dtype,
        )
        if _device_step(s):
            s.pending_parts.append(("penal", PF.reshape(-1)))


class ComputeForces(Operator):
    """Per-obstacle force/torque/power QoI from the surface-point probe
    (ops/surface.py: one-sided tractions probed outside the body on a
    dense window, the reference KernelComputeForces measure), appended to
    forces_<i>.txt (reference ComputeForces, main.cpp:12496-12503,
    reduction 13079-13115).  The dense chi-band integral
    (models.base.force_integrals) stays available for diagnostics but the
    probe is the production measure — the band under-reads pressure by a
    flat ~28% on the sphere (VALIDATION.md)."""

    def __call__(self, dt):
        from cup3d_tpu.ops.surface import force_integrals_probe_uniform

        s = self.sim

        def probe(ob, cm, ut, om):
            return pack_forces(
                force_integrals_probe_uniform(
                    s.grid, ob, s.state["vel"], s.state["p"], ob.chi,
                    ob.sdf, ob.udef, s.nu, cm, ut, om,
                )
            )

        if _device_step(s):
            ob = s.obstacles[0]
            d = ob._dev_rigid
            F = probe(ob, d["cm"], d["trans"], d["ang"])
            s.pending_parts.append(("forces", F.reshape(-1)))
            return
        # host fallback: one batched (n_obs, 3, 3) kinematics upload per
        # step instead of three per obstacle (rule JX010); the mirrors
        # here are fresh host values by construction (no device chain)
        kin = jnp.asarray(
            np.stack(
                [
                    np.stack([ob.centerOfMass, ob.transVel, ob.angVel])
                    for ob in s.obstacles
                ]
            ),
            s.dtype,
        )
        F = np.asarray(
            jnp.stack(
                [
                    probe(ob, kin[i, 0], kin[i, 1], kin[i, 2])
                    for i, ob in enumerate(s.obstacles)
                ]
            )
        )
        for i, (ob, row) in enumerate(zip(s.obstacles, F)):
            store_force_qoi(ob, unpack_forces(row))
            log_forces(s.logger, i, s.time, ob)

