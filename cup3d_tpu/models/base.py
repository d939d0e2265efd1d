"""Obstacle base: 6-DOF rigid-body state + dense-field rasterization contract.

Reference: ``Obstacle`` (main.cpp:7482-7583, 12812-13233) keeps per-block
``ObstacleBlock`` storage (chi, udef, SDF, surface point lists).  The TPU
design replaces the ragged per-block storage with dense per-obstacle device
fields (chi_i, udef_i) produced by a jittable rasterizer, so penalization,
momentum integrals, and force reductions are fused whole-domain kernels.

6-DOF update: the reference integrates translation/rotation with a BDF-like
2nd-order update and GSL LU for the 6x6 momentum system
(computeVelocities, main.cpp:12921-13029; update, main.cpp:13116-13204).
Here the 6x6 solve is numpy (host, tiny) and the quaternion update uses the
exact exponential map.

Device fast path: every blocking host read stalls the dispatch queue, so ``rigid_update_device`` runs the same moments -> 6x6 -> position/quaternion
update entirely on device (the 6x6 is block-diagonal about the CM: u = P/m,
omega = J^-1 L).  The driver then fetches one packed QoI vector per step
(``RIGID_PACK`` below) instead of three separate round trips; host mirrors are
refreshed from that single read before any host code consumes them, so the
numerics match the host path to solver-dtype round-trip (asserted by
tests/test_sphere.py::test_device_fast_path_matches_host).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from cup3d_tpu.grid.uniform import UniformGrid
from cup3d_tpu.obs import metrics as obs_metrics
from cup3d_tpu.ops.chi import grad_chi, heaviside
from cup3d_tpu.ops.diagnostics import swim_split


def quat_to_rot(q: np.ndarray) -> np.ndarray:
    """Unit quaternion (w,x,y,z) -> 3x3 rotation matrix."""
    w, x, y, z = q
    return np.array(
        [
            [1 - 2 * (y * y + z * z), 2 * (x * y - w * z), 2 * (x * z + w * y)],
            [2 * (x * y + w * z), 1 - 2 * (x * x + z * z), 2 * (y * z - w * x)],
            [2 * (x * z - w * y), 2 * (y * z + w * x), 1 - 2 * (x * x + y * y)],
        ]
    )


def quat_multiply(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    aw, ax, ay, az = a
    bw, bx, by, bz = b
    return np.array(
        [
            aw * bw - ax * bx - ay * by - az * bz,
            aw * bx + ax * bw + ay * bz - az * by,
            aw * by - ax * bz + ay * bw + az * bx,
            aw * bz + ax * by - ay * bx + az * bw,
        ]
    )


def quat_integrate(q: np.ndarray, omega: np.ndarray, dt: float) -> np.ndarray:
    """Exact exponential-map quaternion step for constant omega over dt."""
    th = np.linalg.norm(omega) * dt
    if th < 1e-14:
        return q
    axis = omega / np.linalg.norm(omega)
    dq = np.concatenate([[np.cos(th / 2)], np.sin(th / 2) * axis])
    q = quat_multiply(dq, q)
    return q / np.linalg.norm(q)


# -- device twins of the rigid-body update (single-sync fast path) -----------

RIGID_STATE = 19  # trans(3) ang(3) pos(3) absPos(3) cm(3) quat(4)
RIGID_PACK = 29   # RIGID_STATE + mass(1) + J(9)


def quat_multiply_dev(a, b):
    aw, ax, ay, az = a[0], a[1], a[2], a[3]
    bw, bx, by, bz = b[0], b[1], b[2], b[3]
    return jnp.stack(
        [
            aw * bw - ax * bx - ay * by - az * bz,
            aw * bx + ax * bw + ay * bz - az * by,
            aw * by - ax * bz + ay * bw + az * bx,
            aw * bz + ax * by - ay * bx + az * bw,
        ]
    )


def quat_to_rot_dev(q):
    """Device twin of quat_to_rot."""
    w, x, y, z = q[0], q[1], q[2], q[3]
    return jnp.stack(
        [
            jnp.stack([1 - 2 * (y * y + z * z), 2 * (x * y - w * z),
                       2 * (x * z + w * y)]),
            jnp.stack([2 * (x * y + w * z), 1 - 2 * (x * x + z * z),
                       2 * (y * z - w * x)]),
            jnp.stack([2 * (x * z - w * y), 2 * (y * z + w * x),
                       1 - 2 * (x * x + y * y)]),
        ]
    )


def quat_integrate_dev(q, omega, dt):
    """Device twin of quat_integrate (exact exponential map)."""
    n = jnp.linalg.norm(omega)
    th = n * dt
    axis = omega / jnp.where(n > 0, n, 1.0)
    dq = jnp.concatenate([jnp.cos(th / 2)[None], jnp.sin(th / 2) * axis])
    qn = quat_multiply_dev(dq, q)
    qn = qn / jnp.linalg.norm(qn)
    return jnp.where(th < 1e-14, q, qn)


@jax.named_scope("UpdateObstacles")
def rigid_update_device(mom, state, forced_mask, block_mask, uinf, dt):
    """Moments (19,) + rigid state (RIGID_STATE,) -> updated (RIGID_PACK,).

    Device twin of compute_velocities + update: the 6x6 momentum system is
    block-diagonal about the measured CM (reference computeVelocities,
    main.cpp:12921-13029), so u = P/m and omega = J^-1 L; forced/blocked
    components keep their previous values; position/quaternion advance as in
    update (main.cpp:13116-13204)."""
    m = mom[0]
    center, P, L = mom[1:4], mom[4:7], mom[7:10]
    J = mom[10:19].reshape(3, 3)
    has = m > 0
    minv = 1.0 / jnp.where(has, m, 1.0)
    ut0, om0 = state[0:3], state[3:6]
    cm_meas = jnp.where(has, center * minv, state[12:15])
    Jsafe = jnp.where(has, J, jnp.eye(3, dtype=mom.dtype))
    ut = jnp.where(has, P * minv, ut0)
    om = jnp.where(has, jnp.linalg.solve(Jsafe, L), om0)
    ut = jnp.where(forced_mask, ut0, ut)
    om = jnp.where(block_mask, om0, om)
    pos = state[6:9] + dt * (ut + uinf)
    absp = state[9:12] + dt * ut
    cm = cm_meas + dt * (ut + uinf)
    q = quat_integrate_dev(state[15:19], om, dt)
    return jnp.concatenate(
        [ut, om, pos, absp, cm, q, m[None], J.reshape(9)]
    )


def vel_unit_dev(v):
    n = jnp.linalg.norm(v)
    return jnp.where(n > 1e-21, v / jnp.where(n > 0, n, 1.0), 0.0)


# -- SDF -> chi/udef: the tail every body shares, traced inside one program --

_EPS = 1e-6
FRAME = 12  # position(3) + rotation matrix rows(9): Obstacle.host_frame


def pos_rot_traced(frame):
    """(position, rotation) inside a program, from what
    ``Obstacle.frame_device`` hands it: the (FRAME,) upload of the host
    mirrors, or the (RIGID_PACK,) device pack of the pipelined chain."""
    # jax-lint: allow(JX003, a shape is static under trace: the two
    # layouts are two programs)
    if frame.shape[0] == RIGID_PACK:
        return frame[6:9], quat_to_rot_dev(frame[15:19])
    return frame[0:3], frame[3:FRAME].reshape(3, 3)


_pos_rot = jax.jit(pos_rot_traced)


@jax.named_scope("CreateObstacles")
def combine_obstacle_fields(chis, udefs):
    """(n_obs, ...) stacks of per-body chi and masked udef -> the combined
    fields the operators consume: chi the maximum over the bodies, udef
    their chi-weighted mean (reference CreateObstacles,
    main.cpp:13589-13621).  One expression for the uniform operator and
    the forest driver (sim/amr.py)."""
    chi = jnp.max(chis, axis=0)
    den = jnp.maximum(jnp.sum(chis, axis=0), _EPS)[..., None]
    udef = jnp.sum(chis[..., None] * udefs, axis=0) / den
    return chi, udef


@jax.named_scope("CreateObstacles")
def fields_from_sdf(grid: UniformGrid, sdf, udef, combine: bool):
    """Dense SDF -> (chi, udef, combined), the tail every body shares:
    Towers chi (reference Obstacle::create + chi kernel); the deformation
    velocity kept only where it matters, inside the mollified band (a
    rigid body, ``udef`` None, gets zeros); and, for a body alone on the
    grid, the (chi, udef) pair of ``combine_obstacle_fields``, so that
    one program goes all the way to what CreateObstacles writes."""
    from cup3d_tpu.ops.chi import towers_chi

    chi = towers_chi(grid.pad_scalar(sdf, 1), grid.h)
    if udef is None:
        udef = jnp.zeros(sdf.shape + (3,), sdf.dtype)
    else:
        udef = udef * (chi > 0)[..., None]
    combined = (
        combine_obstacle_fields(chi[None], udef[None]) if combine else None
    )
    return chi, udef, combined


_fields_from_sdf = jax.jit(fields_from_sdf,
                           static_argnames=("grid", "combine"))


class Obstacle:
    """One immersed body.  Subclasses implement ``rasterize()`` (and
    optionally ``update_shape()`` for deforming bodies)."""

    # slot budget of the force probe built for this body (ops/surface.
    # obstacle_probe_budget); 0 until a probe is built
    probe_slots = 0

    def __init__(self, sim, spec: Dict[str, str]):
        self.sim = sim
        self.spec = spec
        g = lambda k, d: float(spec.get(k, d))
        self.length = g("L", 0.1)
        self.position = np.array(
            [g("xpos", 0.5 * sim.grid.extent[0]),
             g("ypos", 0.5 * sim.grid.extent[1]),
             g("zpos", 0.5 * sim.grid.extent[2])]
        )
        self.quaternion = np.array(
            [g("quat0", 1.0), g("quat1", 0.0), g("quat2", 0.0), g("quat3", 0.0)]
        )
        # planar (yaw) spawn angle in degrees about +z (reference parses
        # planarAngle alongside the explicit quaternion, main.cpp:12820-12837)
        ang = np.deg2rad(g("planarAngle", 0.0))
        if ang != 0.0 and np.allclose(self.quaternion, [1.0, 0.0, 0.0, 0.0]):
            self.quaternion = np.array([np.cos(ang / 2), 0.0, 0.0, np.sin(ang / 2)])
        self.transVel = np.array([g("xvel", 0.0), g("yvel", 0.0), g("zvel", 0.0)])
        self.angVel = np.zeros(3)
        # forced-motion flags (main.cpp:12838-12870)
        forced = spec.get("bForcedInSimFrame", "0") == "1"
        self.bForcedInSimFrame = np.array([forced] * 3)
        self.bBlockRotation = np.array(
            [spec.get("bBlockRotation", "1" if forced else "0") == "1"] * 3
        )
        self.bFixFrameOfRef = spec.get("bFixFrameOfRef", "0") == "1"
        # absolute position: not advected by the moving frame's uinf
        # (reference absPos, main.cpp:13138-13143)
        self.absPos = self.position.copy()

        # filled by create()/integrals
        self.chi: Optional[jnp.ndarray] = None
        self.udef: Optional[jnp.ndarray] = None
        self.mass = 0.0
        self.J = np.zeros((3, 3))
        self.centerOfMass = self.position.copy()
        # force QoI (reference ComputeForces reduction, main.cpp:13079-13115)
        self.force = np.zeros(3)
        self.torque = np.zeros(3)
        self.pres_force = np.zeros(3)
        self.visc_force = np.zeros(3)
        self.pow_out = 0.0
        self.pout_bnd = 0.0
        self.thrust = 0.0
        self.drag = 0.0
        self.def_power = 0.0
        self.def_power_bnd = 0.0
        self.p_locom = 0.0
        self.Pthrust = 0.0
        self.Pdrag = 0.0
        self.EffPDef = 0.0
        self.EffPDefBnd = 0.0
        # collision latch (reference collision_counter/u_collision,
        # main.cpp:7546-7552, 13069-13077)
        self.collision_counter = 0.0
        self.collision_vel = np.zeros(3)
        self.collision_angvel = np.zeros(3)
        # device fast path (rigid_update_device): set by UpdateObstacles for
        # the current step, consumed by body_velocity_field/ComputeForces;
        # host mirrors are refreshed from the packed per-step read
        self._dev_rigid: Optional[dict] = None

    # -- checkpointing -----------------------------------------------------

    def __getstate__(self):
        """Pickle the kinematic/dynamic state only: the sim backref and all
        device arrays (chi/udef/caches) are rebuilt by create_obstacles()
        after restore (io/checkpoint.py)."""
        state = {}
        for k, v in self.__dict__.items():
            if k in ("sim", "_dev_rigid") or isinstance(v, jax.Array):
                continue
            if k.endswith("_cache"):
                continue
            state[k] = v
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self.sim = None
        self.chi = None
        self.udef = None
        self._dev_rigid = None

    # -- geometry ---------------------------------------------------------

    @property
    def _is_blocks(self) -> bool:
        """Forest (nb, bs, bs, bs) layout, not the dense uniform one."""
        return not hasattr(self.sim.grid, "shape")

    def rasterize(self, t: float):
        """Return (sdf, udef) dense fields; sdf > 0 inside, udef (.,3)."""
        raise NotImplementedError

    def _cell_centers(self):
        """Device cell centers for an analytic SDF: the uniform driver's
        cached array; the forest's own (the one its driver caches may be
        padded to a bucket's capacity)."""
        if self._is_blocks:
            return self.sim.grid.cell_centers(self.sim.dtype)
        return self.sim.xc

    def max_body_speed(self, uinf=None) -> float:
        """Fresh host-side bound on this body's maximum material speed in
        the sim frame: rigid translation (+ frame velocity) + rotation at
        the body radius (+ deformation; fish override).  The pipelined dt
        chain floors its CFL scale with this: the packed fluid max|u| can
        lag ~(1+max_inflight)*read_every steps, but the body kinematics
        that DRIVE the acceleration are known on host exactly — measured
        at 256^3, a gait spin-up outruns the stale mirror while dt sits
        at the diffusive cap and the run blows through CFL (the reference
        never faces this: findMaxU re-measures every step,
        main.cpp:8603-8623)."""
        tv = np.asarray(self.transVel, np.float64)
        if uinf is not None:
            tv = tv + np.asarray(uinf, np.float64)
        om = float(np.linalg.norm(np.asarray(self.angVel, np.float64)))
        return float(np.linalg.norm(tv)) + om * 0.5 * float(self.length)

    def update_shape(self, t: float, dt: float) -> None:
        """Advance internal deformation kinematics (fish midline etc.)."""

    def create(self, t: float, combine: bool = False):
        """SDF -> chi + udef on the dense uniform grid, as ONE program
        behind the subclass's rasterizer (``fields_from_sdf``).  The SDF
        is kept: the surface-point force probe (ops/surface.py) takes its
        outward normals from grad(phi) like the reference.  ``combine``
        (this body is alone on the grid): the same program also returns
        the combined (chi, udef) that CreateObstacles writes."""
        sdf, udef = self.rasterize(t)
        self.sdf = sdf
        self.chi, self.udef, combined = _fields_from_sdf(
            self.sim.grid, sdf, udef, combine
        )
        self.note_raster_work(1)
        return combined

    def note_raster_work(self, calls: int, scan: bool = False) -> None:
        """Count ``calls`` rasterizations of this rigid body on the
        uniform grid: per-step ones under ``operators.rigid_host_steps``,
        those of a scan dispatch under ``operators.rigid_scan_steps``
        (the fish counts its rasterizer's cells instead)."""
        name = ("operators.rigid_scan_steps" if scan
                else "operators.rigid_host_steps")
        obs_metrics.counter(name).inc(calls)

    # -- the scan megaloop's body stage (sim/megaloop.py) -------------------

    def offers_scan_stage(self) -> bool:
        """True when this body's shape can be made inside the scan: it
        gives ``scan_window``, ``scan_gait``, ``scan_state`` and
        ``window_shape_device``.  A body without the stage runs per
        step."""
        return False

    def scan_gait(self, t: float, dtype):
        """The shape's parameters frozen at ``t`` as the scan takes them
        (an argument pytree, so a fleet can stack one per lane), or None
        where they cannot be frozen; a rigid body has none to freeze."""
        return {}

    def scan_state(self, dtype):
        """The shape's own state that rides the scan's carry, or None."""
        return None

    def apply_scan_state(self, row: np.ndarray) -> None:
        """Host mirror of ``scan_state`` from a scan row's four columns."""

    def window_shape_device(self, gait, origin, h, pos, rigid, time, dt,
                            state):
        """The body on its static window ``scan_window`` placed at
        ``origin``, from the PRE-update rigid state (``pos`` is
        ``rigid[6:9]``): ``(sdf_w, udef_w or None, state')``."""
        raise NotImplementedError

    # -- device fast path --------------------------------------------------

    def supports_device_update(self) -> bool:
        """True when the rigid update has no host-only branch this step
        (collision latch active -> host path; subclasses add their own
        vetoes, e.g. StefanFish roll correction)."""
        return self.collision_counter <= 0

    def rigid_state_vec(self) -> np.ndarray:
        """Host mirrors -> (RIGID_STATE,) input for rigid_update_device."""
        return np.concatenate(
            [self.transVel, self.angVel, self.position, self.absPos,
             self.centerOfMass, self.quaternion]
        )

    def rigid_state_dev(self, dtype) -> jnp.ndarray:
        """(RIGID_STATE,) device input for rigid_update_device: chains from
        the previous step's device output when it exists (pipelined mode
        keeps the rigid trajectory device-resident), else uploads the host
        mirrors."""
        d = self._dev_rigid
        if d is not None:
            return d["pack"][:RIGID_STATE]
        return jnp.asarray(self.rigid_state_vec(), dtype)

    def forced_mask_dev(self) -> jnp.ndarray:
        """Cached device mirror of ``bForcedInSimFrame``.  The flags are
        fixed at construction (factory kwargs), so the upload happens
        once; identity-keyed like SimulationData.uinf_device so an
        exotic reassignment still invalidates (the PR 2 mirror
        pattern).  ``*_cache`` attrs are pickle-excluded and rebuild
        after restore."""
        if getattr(self, "_forced_src_cache", None) is not self.bForcedInSimFrame:
            from cup3d_tpu.analysis.runtime import sanctioned_transfer

            with sanctioned_transfer("scalar-upload"):
                self._forced_dev_cache = jnp.asarray(self.bForcedInSimFrame)
            self._forced_src_cache = self.bForcedInSimFrame
        return self._forced_dev_cache

    def block_mask_dev(self) -> jnp.ndarray:
        """Cached device mirror of ``bBlockRotation`` (see
        :meth:`forced_mask_dev`)."""
        if getattr(self, "_block_src_cache", None) is not self.bBlockRotation:
            from cup3d_tpu.analysis.runtime import sanctioned_transfer

            with sanctioned_transfer("scalar-upload"):
                self._block_dev_cache = jnp.asarray(self.bBlockRotation)
            self._block_src_cache = self.bBlockRotation
        return self._block_dev_cache

    def host_frame(self) -> np.ndarray:
        """(FRAME,) host mirrors a rasterizer needs: position, then the
        rows of the rotation matrix."""
        return np.concatenate(
            [self.position, quat_to_rot(self.quaternion).ravel()]
        )

    def frame_device(self, dtype):
        """The rasterizer's rigid frame as ONE device array, read inside
        a program by ``pos_rot_traced``: the device rigid pack when
        pipelined chaining is active (the host mirror trails one step
        there), else one upload of the host mirrors."""
        d = self._dev_rigid
        if self.sim.cfg.pipelined and d is not None:
            return d["pack"]
        # cast on the host: jnp.asarray(float64, float32) is an upload AND
        # a convert program
        return jnp.asarray(self.host_frame().astype(dtype))

    def pos_rot_device(self, dtype):
        """(position, rotation-matrix) as device arrays, for a rasterizer
        that takes them apart: one upload at most and one program."""
        return _pos_rot(self.frame_device(dtype))

    def apply_rigid_pack(self, row: np.ndarray, clear_dev: bool = True) -> None:
        """(RIGID_PACK,) output of rigid_update_device -> host mirrors."""
        row = np.asarray(row, np.float64)
        self.transVel = row[0:3]
        self.angVel = row[3:6]
        self.position = row[6:9]
        self.absPos = row[9:12]
        self.centerOfMass = row[12:15]
        self.quaternion = row[15:19]
        if row[19] > 0:
            self.mass = float(row[19])
            self.J = row[20:29].reshape(3, 3)
        if clear_dev:
            self._dev_rigid = None

    # -- rigid-body dynamics ----------------------------------------------

    def body_velocity_field(self) -> jnp.ndarray:
        """u_body = u_trans + omega x r + u_def on the whole grid.

        Uses the driver's device-cached cell centers + jitted kernel and
        memoizes per (step, rigid state): penalization and the force pass
        consume the same field each step."""
        s = self.sim
        dev = self._dev_rigid
        if dev is not None and dev["step"] == s.step:
            # device fast path: rigid state from this step's on-device update
            tag = (s.step, "dev")
            cm, ut, om = dev["cm"], dev["trans"], dev["ang"]
        else:
            tag = (s.step, tuple(self.transVel), tuple(self.angVel),
                   tuple(self.centerOfMass))
            dtype = s.dtype
            cm = jnp.asarray(self.centerOfMass, dtype)
            ut = jnp.asarray(self.transVel, dtype)
            om = jnp.asarray(self.angVel, dtype)
        cached = getattr(self, "_ubody_cache", None)
        if cached is not None and cached[0] == tag:
            return cached[1]
        fn = getattr(s, "_ubody_fn", None)
        if fn is not None:
            field = fn(self.udef, cm, ut, om)
        else:
            x = s.grid.cell_centers(s.dtype)
            r = x - cm
            field = ut + jnp.cross(jnp.broadcast_to(om, r.shape), r) + self.udef
        self._ubody_cache = (tag, field)
        return field

    def compute_velocities(self, moments: Dict[str, np.ndarray]) -> None:
        """Solve the coupled 6x6 momentum system for (u_trans, omega)
        (reference computeVelocities, main.cpp:12921-13029), then override
        forced components."""
        m = moments["mass"]
        if m <= 0:
            return
        cm = moments["center"] / m
        self.centerOfMass = cm
        P = moments["lin_mom"]
        L = moments["ang_mom"]  # about cm
        J = moments["inertia"]  # about cm
        # [[m I, 0], [0, J]] is exact when moments are taken about the CM
        A = np.zeros((6, 6))
        A[:3, :3] = m * np.eye(3)
        A[3:, 3:] = J
        b = np.concatenate([P, L])
        sol = np.linalg.solve(A, b)
        self.mass = m
        self.J = J
        new_ut, new_om = sol[:3], sol[3:]
        self.transVel = np.where(self.bForcedInSimFrame, self.transVel, new_ut)
        self.angVel = np.where(self.bBlockRotation, self.angVel, new_om)
        # a fresh collision overrides the fluid-coupled solve for one step
        # (reference main.cpp:13069-13077)
        if self.collision_counter > 0:
            self.collision_counter -= self.sim.dt
            self.transVel = self.collision_vel.copy()
            self.angVel = self.collision_angvel.copy()

    def update(self, dt: float) -> None:
        """Advance position/orientation (reference update, main.cpp:13116-13204)."""
        uinf = self.sim.uinf
        self.position = self.position + dt * (self.transVel + uinf)
        self.absPos = self.absPos + dt * self.transVel
        self.centerOfMass = self.centerOfMass + dt * (self.transVel + uinf)
        self.quaternion = quat_integrate(self.quaternion, self.angVel, dt)


# QoI packing: a blocking host read stalls the dispatch queue, so per-step
# reductions travel as ONE packed vector instead of one array per quantity
# (the reference's analogue is batching 29 QoI into one MPI_Allreduce,
# main.cpp:13783)

_MOMENT_KEYS = ("mass", "center", "lin_mom", "ang_mom", "inertia")
_FORCE_KEYS = ("pres_force", "visc_force", "torque", "power", "pout_bnd",
               "thrust", "drag", "def_power", "def_power_bnd", "p_locom",
               "n_surf")
# packed force-vector width (3+3+3 vectors + 7 scalars + n_surf): the full
# 19-QoI reduction set of the reference's ComputeForces
# (main.cpp:13089-13108 — surfForce there is presForce+viscForce, derived
# on unpack here) plus the probe's surface-cell count (held against the
# probe's slot budget where the row is stored, store_force_qoi)
FORCE_PACK = 17


def pack_moments(m: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """Momentum-integral dict -> (19,) device vector."""
    return jnp.concatenate([jnp.reshape(m[k], (-1,)) for k in _MOMENT_KEYS])


def unpack_moments(a) -> Dict[str, np.ndarray]:
    a = np.asarray(a, np.float64)
    return {
        "mass": a[0],
        "center": a[1:4],
        "lin_mom": a[4:7],
        "ang_mom": a[7:10],
        "inertia": a[10:19].reshape(3, 3),
    }


def pack_forces(f: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """Force-integral dict -> (FORCE_PACK,) device vector.  Band-integral
    producers (force_integrals) lack the probe-only clipped/locomotion
    QoI; those slots pack as 0."""
    z = jnp.zeros((), jnp.result_type(*(jnp.asarray(f[k]).dtype
                                        for k in ("power", "thrust"))))
    return jnp.concatenate(
        [jnp.reshape(jnp.asarray(f.get(k, z)), (-1,)) for k in _FORCE_KEYS]
    )


def unpack_forces(a) -> Dict[str, np.ndarray]:
    a = np.asarray(a, np.float64)
    return {
        "pres_force": a[0:3],
        "visc_force": a[3:6],
        "torque": a[6:9],
        "power": float(a[9]),
        "pout_bnd": float(a[10]),
        "thrust": float(a[11]),
        "drag": float(a[12]),
        "def_power": float(a[13]),
        "def_power_bnd": float(a[14]),
        "p_locom": float(a[15]),
        "n_surf": float(a[16]),
    }


def derived_force_qoi(f: Dict[str, np.ndarray], trans_vel: np.ndarray,
                      eps: float = 1e-21) -> Dict[str, float]:
    """Host-side derived swimming QoI (reference computeForces tail,
    main.cpp:13098-13114): thrust/drag powers and deformation
    efficiencies (EffPDefBnd uses the clipped defPowerBnd, which is
    <= 0 by construction)."""
    vnorm = float(np.linalg.norm(trans_vel))
    pthrust = f["thrust"] * vnorm
    pdrag = f["drag"] * vnorm
    def_power = f["def_power"]
    eff = pthrust / (pthrust - min(def_power, 0.0) + eps)
    eff_bnd = pthrust / (pthrust - f.get("def_power_bnd", 0.0) + eps)
    return {"Pthrust": pthrust, "Pdrag": pdrag, "EffPDef": eff,
            "EffPDefBnd": eff_bnd}


@jax.named_scope("UpdateObstacles")
def momentum_integrals_core(x: jnp.ndarray, vol, chi: jnp.ndarray,
                            vel: jnp.ndarray, cm_guess: jnp.ndarray):
    """Layout-generic chi-weighted moments (KernelIntegrateFluidMomenta,
    main.cpp:13625-13735).  x: (..., 3) cell centers; vol: scalar or array
    broadcastable to chi (per-cell volume); works for the dense uniform
    layout and the (nb, bs, bs, bs) AMR block layout alike.

    The products run at HIGHEST: the default rounds their float32
    operands to bfloat16 on the TPU, and the rigid velocity built from
    these sums then misses a float64 step by up to 2.6e-3 of a body's
    speed on the forest and 1.2e-3 on the uniform 256^3 grid (PERF.md
    section 7, fault 4)."""
    w = (chi * vol).reshape(-1)
    xf = x.reshape(-1, 3)
    vf = vel.reshape(-1, 3)
    mass = jnp.sum(w)
    r = xf - cm_guess
    r2 = jnp.sum(r * r, axis=-1)
    eye = jnp.eye(3, dtype=vel.dtype)
    with jax.default_matmul_precision("highest"):
        center = w @ xf
        lin = w @ vf
        ang = w @ jnp.cross(r, vf)
        inertia = jnp.sum(w * r2) * eye - jnp.einsum("n,na,nb->ab", w, r, r)
    return {"mass": mass, "center": center, "lin_mom": lin, "ang_mom": ang,
            "inertia": inertia}


def momentum_integrals(grid: UniformGrid, chi: jnp.ndarray, vel: jnp.ndarray,
                       cm_guess: jnp.ndarray):
    """Uniform-grid wrapper of momentum_integrals_core."""
    return momentum_integrals_core(
        grid.cell_centers(vel.dtype), grid.h ** 3, chi, vel, cm_guess
    )


def force_integrals(grid: UniformGrid, chi: jnp.ndarray, p: jnp.ndarray,
                    vel: jnp.ndarray, nu: float, cm: jnp.ndarray,
                    ubody: jnp.ndarray,
                    udef: Optional[jnp.ndarray] = None,
                    vel_unit: Optional[jnp.ndarray] = None):
    """Surface tractions via the chi-gradient surface measure.

    With n_hat the outward normal and delta the surface density,
    grad(chi) = -n_hat * delta, so

      F_pres = integral(-p n_hat) dS      = sum  p * grad_chi * h^3
      F_visc = integral(2 nu S . n_hat)dS = sum -2 nu S . grad_chi * h^3
      power  = integral(traction . u_body) dS

    The swimming split follows the reference per point
    (main.cpp:12476-12485): forcePar = traction . vel_unit, thrust sums
    its positive part, drag its negative part, and def_power is
    traction . u_def (deformation power).

    Reference: ComputeForces probes one-sided stencils at surface points
    (main.cpp:12250-12494); the dense formulation trades its 5h-outside
    probing for the mollified band, consistent with the smoothed chi.
    """
    from cup3d_tpu.ops import stencils as st

    h3 = grid.h ** 3
    gchi = grad_chi(grid, chi)
    up = grid.pad_vector(vel, 1)
    g = [[st.d1_central(up[..., c], 1, a, grid.h) for a in range(3)] for c in range(3)]
    # S_ca = (d_a u_c + d_c u_a)/2
    fpres = jnp.stack(
        [jnp.sum(p * gchi[..., a]) * h3 for a in range(3)]
    )
    fvisc = jnp.stack(
        [
            -nu * jnp.sum(sum((g[c][a] + g[a][c]) * gchi[..., c] for c in range(3)))
            * h3
            for a in range(3)
        ]
    )
    x = grid.cell_centers(vel.dtype)
    r = x - cm
    traction = p[..., None] * gchi - nu * jnp.stack(
        [sum((g[c][a] + g[a][c]) * gchi[..., c] for c in range(3)) for a in range(3)],
        axis=-1,
    )
    torque = jnp.einsum("xyzc->c", jnp.cross(r, traction)) * h3
    power = jnp.sum(traction * ubody) * h3
    return {"pres_force": fpres, "visc_force": fvisc, "torque": torque,
            "power": power,
            **swim_split(traction, h3, udef, vel_unit)}


def vel_unit(v: np.ndarray) -> np.ndarray:
    n = np.linalg.norm(v)
    return v / n if n > 1e-21 else np.zeros(3)


def store_force_qoi(ob, f: Dict[str, np.ndarray]) -> None:
    """Unpacked force vector -> obstacle attributes incl. the derived
    swimming QoI (reference computeForces tail, main.cpp:13098-13114)."""
    ob.pres_force = f["pres_force"]
    ob.visc_force = f["visc_force"]
    ob.force = ob.pres_force + ob.visc_force
    ob.torque = f["torque"]
    ob.pow_out = f["power"]
    ob.pout_bnd = f.get("pout_bnd", 0.0)
    ob.thrust = f["thrust"]
    ob.drag = f["drag"]
    ob.def_power = f["def_power"]
    ob.def_power_bnd = f.get("def_power_bnd", 0.0)
    ob.p_locom = f.get("p_locom", 0.0)
    # measured surface-band size against the probe's slot budget: a band
    # over it was cut to its largest cells (ops/surface.
    # surface_force_window), and the forces then miss the tail
    n_surf = f.get("n_surf", 0.0)
    if n_surf > 0 and ob.probe_slots:
        obs_metrics.counter(
            "operators.probe_compacted" if n_surf <= ob.probe_slots
            else "operators.probe_truncated"
        ).inc()
    d = derived_force_qoi(f, ob.transVel)
    ob.Pthrust, ob.Pdrag, ob.EffPDef = d["Pthrust"], d["Pdrag"], d["EffPDef"]
    ob.EffPDefBnd = d["EffPDefBnd"]


def log_forces(logger, i: int, time: float, ob) -> None:
    """forces_<i>.txt row: the reference's full per-obstacle QoI set
    (computeForces reduction + derived tail, main.cpp:13089-13114)."""
    logger.write(
        f"forces_{i}.txt",
        f"{time:.8e} " + " ".join(f"{v:.8e}" for v in ob.force)
        + f" {ob.pow_out:.8e} {ob.pout_bnd:.8e} {ob.thrust:.8e}"
        + f" {ob.drag:.8e} {ob.def_power:.8e} {ob.def_power_bnd:.8e}"
        + f" {ob.p_locom:.8e} {ob.EffPDef:.8e} {ob.EffPDefBnd:.8e}\n",
    )


def update_penalization_forces(obstacles, penal_force_fn, vel_new, vel_old,
                               dt, dtype) -> jnp.ndarray:
    """Attach per-obstacle momentum-balance force/torque ON THE BODY
    (reference kernelFinalizePenalizationForce, main.cpp:13913-13938) —
    the negative of the momentum the penalization injects into the fluid,
    so the sign convention matches ob.force from the surface integral.
    Computed every step like the reference.  The (n_obs, 6) result stays
    a device array — rows are attached as lazy slices so the hot loop
    never blocks on a host transfer; consumers that read ob.penal_force
    trigger the (tiny) conversion themselves.  Returns the (n_obs, 6)
    device array so the fast path can fold it into the step's single
    packed read.  CMs come from the device rigid state when this step ran
    rigid_update_device (host mirrors are one update behind there)."""
    def _cm(ob):
        d = ob._dev_rigid
        if d is not None and d["step"] == ob.sim.step:
            return d["cm"]
        return jnp.asarray(ob.centerOfMass, dtype)

    cms = jnp.stack([_cm(ob) for ob in obstacles])
    PF = -penal_force_fn(
        vel_new, vel_old, tuple(ob.chi for ob in obstacles),
        jnp.asarray(dt, dtype), cms,
    )
    for i, ob in enumerate(obstacles):
        ob.penal_force = PF[i, :3]
        ob.penal_torque = PF[i, 3:]
    return PF
