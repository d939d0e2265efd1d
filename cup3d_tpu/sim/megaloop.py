"""K-step scan megaloop: the whole obstacle pipeline inside one dispatch.

BENCH_r05 showed the uniform step loop host-bound: ~28-43 ms/step of fish
midline re-evaluation + SDF re-staging (CreateObstacles) and a regressed
pack read (SyncQoI), against ~0.5 ms of device BiCGSTAB at 128^3.  This
module wraps K full timesteps — dt policy, midline kinematics, SDF/chi
rasterization, advection-diffusion, the 6-DOF rigid update, penalization,
projection, and the surface force probe — in a single jitted ``lax.scan``,
so the host dispatches once per K steps and reads one (K, ROW) QoI block
through the existing stream/qoi.py path.

Step semantics reproduce the host pipelined chain exactly:

- dt comes from the CARRIED umax (one step stale — the same staleness as
  the host chain's freshly-consumed pack, so no 1.5x staleness margin),
  capped by the combined advection-diffusion bound and the 1.03x growth
  limiter (sim/dtpolicy.py).
- The body's shape is made on its static window, placed from the
  PRE-update rigid state (the host rasterizes before UpdateObstacles
  runs): the fish's midline by the frozen-gait device port
  (models/fish/device_midline.py) at the carried time and the same
  window as StefanFish.rasterize; a rigid body (Sphere) analytically.
- umax is measured with the PRE-update uinf, matching the host emit point
  (Simulation._emit_step_pack reads s._uinf_dev set from the previous
  rigid state).
- The QoI row layout (FISH_ROW) carries everything _consume_pack needs to
  refresh the host mirrors per step k: the rigid pack, penalization
  force/torque (already negated, models.base.update_penalization_forces
  convention), the force probe pack, solver stats, the shape's state (the
  fish's internal quaternion; zeros for a rigid body), and the (umax,
  dt, time) chain for failure detection.

The carry is donated: callers must rebind every field from the returned
carry and never touch the passed-in arrays again (JX002 discipline).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from cup3d_tpu.models.base import (
    RIGID_STATE,
    momentum_integrals_core,
    pack_forces,
    pack_moments,
    rigid_update_device,
)
from cup3d_tpu.ops.advection import GHOSTS, rk3_step
from cup3d_tpu.ops.chi import towers_chi
from cup3d_tpu.ops.diagnostics import max_velocity
from cup3d_tpu.ops.penalization import (
    penalize,
    per_obstacle_penalization_force,
)
from cup3d_tpu.ops.projection import project
from cup3d_tpu.sim import dtpolicy
from cup3d_tpu.sim.operators import forced, forcing_stage

# QoI row layouts.  Fish: rigid pack 0:29 | penal force/torque 29:35 |
# force probe pack 35:52 | [residual, iterations] 52:54 | internal
# quaternion 54:58 | umax 58 | dt 59 | time 60.
FISH_ROW = 61
# TGV (obstacle-free): [residual, iterations] 0:2 | umax 2 | dt 3 | time 4;
# a forced flow's row holds the bulk velocity its forcing measured at
# TGV_BULK, before (umax, dt, time), which stay the row's last three
# columns in every layout (tgv_row_width).
TGV_ROW = 5
TGV_BULK = 2


def tgv_row_width(cfg) -> int:
    """Width of the obstacle-free scan body's row for this flow."""
    return TGV_ROW + int(forced(cfg))

DEFAULT_SCAN_K = 8


def resolve_scan_k(cfg) -> int:
    """Effective K: the CUP3D_SCAN_K env knob overrides cfg.scan_k.
    K <= 1 disables the megaloop (per-step host loop, the seed behavior)."""
    env = os.environ.get("CUP3D_SCAN_K")
    if env is not None:
        try:
            return max(0, int(env))
        # jax-lint: allow(JX009, malformed env knob falls back to the
        # config value; the resolved K is printed by the verbose driver
        # banner, so the fallback is observable)
        except ValueError:
            pass
    return max(0, int(cfg.scan_k))


def _solver_stats(dtype):
    """Placeholder stats for non-iterative solvers: the host packs nothing
    there; the row keeps a fixed layout with iterations = -1 (ignored by
    the consumer)."""
    return jnp.asarray([0.0, -1.0], dtype)


def init_tgv_carry(s):
    """Obstacle-free carry from the current host/device state.  The umax
    seed is measured on device (no host read); dt/time seed from the host
    scalars so the first in-scan dt chains off the last host dt."""
    dtype = s.dtype
    uinf = s.uinf_device()
    vel = s.state["vel"]
    return {
        "vel": vel,
        "p": s.state["p"],
        "umax": max_velocity(vel, uinf),
        "time": jnp.asarray(s.time, dtype),
        "dt": jnp.asarray(s.dt, dtype),
    }


def init_body_carry(s, ob):
    """Single-body carry: field state + 6-DOF rigid vector + the shape's
    own state where the body has one (the fish's internal quaternion,
    ``qint``), all device-resident.  chi/udef ride the carry so dumps and
    resilience restores see a consistent set (the scan body overwrites
    them every step).  The umax seed is floored by the host's fresh
    max_body_speed bound — the cold-start case where the fields are still
    at rest but the gait is about to accelerate them (see
    Obstacle.max_body_speed)."""
    dtype = s.dtype
    vel, udef = s.state["vel"], s.state["udef"]
    rigid = jnp.asarray(ob.rigid_state_vec(), dtype)
    uinf = -rigid[0:3] if ob.bFixFrameOfRef else s.uinf_device()
    umax = jnp.maximum(max_velocity(vel, uinf), jnp.max(jnp.abs(udef)))
    umax = jnp.maximum(umax, jnp.asarray(ob.max_body_speed(s.uinf), dtype))
    carry = {
        "vel": vel,
        "p": s.state["p"],
        "chi": s.state["chi"],
        "udef": udef,
        "rigid": rigid,
        "umax": umax,
        "time": jnp.asarray(s.time, dtype),
        "dt": jnp.asarray(s.dt, dtype),
    }
    state = ob.scan_state(dtype)
    if state is not None:
        carry["qint"] = state
    return carry


def _body_stages(s, ob):
    """What both single-body scan bodies (solo and x-slab) do between
    their field operators, written once for every body that offers a
    scan stage (``Obstacle.offers_scan_stage``): everything geometric
    frozen static at build time (the body's window, the probe window and
    its slot budget, the forced/blocked masks), and the three stages
    that are lines of the body rather than calls into ``ops/`` —
    each under its operator's name in a device trace.  Only the shape
    on the window comes from the body (``window_shape_device``)."""
    from types import SimpleNamespace

    from cup3d_tpu.ops.surface import (
        _uniform_window_probe,
        obstacle_probe_budget,
        window_size_cells,
    )

    grid, nu, dtype = s.grid, s.nu, s.dtype
    cfg = s.cfg
    h = float(grid.h)

    n = np.asarray(grid.shape)
    grid_shape = tuple(int(v) for v in n)
    window_shape = tuple(ob.scan_window)
    half_win = jnp.asarray(0.5 * np.asarray(window_shape) * h, dtype)
    lim_win = jnp.asarray(n - np.asarray(window_shape), jnp.int32)
    wp = int(min(window_size_cells(ob.length, h), n.min()))
    half_probe = jnp.asarray(0.5 * wp * h, dtype)
    lim_probe = jnp.asarray(n - wp, jnp.int32)
    budget = obstacle_probe_budget(ob, h)
    xc = s.xc
    h3 = h ** 3
    hd = jnp.asarray(h, dtype)
    zero3 = jnp.zeros(3, dtype)
    dlm = float(cfg.DLM)
    lam_static = jnp.asarray(cfg.lambda_penalization, dtype)

    @jax.named_scope("CreateObstacles")
    def create(gait, time, dt, state, rigid):
        """The body's shape on its window, placed from the PRE-update
        rigid state (host order: CreateObstacles runs before
        UpdateObstacles): (sdf, chi, udef, the shape's new state)."""
        pos = rigid[6:9]
        idx0 = jnp.clip(jnp.floor((pos - half_win) / hd).astype(jnp.int32),
                        0, lim_win)
        origin = idx0.astype(dtype) * hd
        sdf_w, udef_w, state_new = ob.window_shape_device(
            gait, origin, hd, pos, rigid, time, dt, state)
        sdf = jnp.full(grid_shape, -1.0, dtype)
        sdf = jax.lax.dynamic_update_slice(
            sdf, sdf_w, (idx0[0], idx0[1], idx0[2]))
        chi = towers_chi(grid.pad_scalar(sdf, 1), grid.h)
        udef = None  # a rigid body deforms nowhere: its zeros are carried
        if udef_w is not None:
            udef = jnp.zeros(grid_shape + (3,), dtype)
            udef = jax.lax.dynamic_update_slice(
                udef, udef_w, (idx0[0], idx0[1], idx0[2], 0))
            udef = udef * (chi > 0)[..., None]
        return sdf, chi, udef, state_new

    @jax.named_scope("Penalization")
    def penalization(vel, chi, udef, ut, om, cm, dt):
        """Penalization toward the updated body velocity field:
        (velocity, minus the momentum it injected)."""
        ubody = ut + jnp.cross(jnp.broadcast_to(om, xc.shape), xc - cm) \
            + udef
        lam = dlm / dt if dlm > 0 else lam_static
        vel_new = penalize(vel, chi, ubody, lam, dt)
        PF = -per_obstacle_penalization_force(
            vel_new, vel, (chi,), dt, h3, xc, cm[None])[0]
        return vel_new, PF

    @jax.named_scope("ComputeForces")
    def forces(vel, p, chi, sdf, udef, pos_new, cm, ut, om):
        """Surface-probe force QoI around the updated position."""
        idx0f = jnp.clip(
            jnp.floor((pos_new - half_probe) / hd).astype(jnp.int32),
            0, lim_probe)
        return pack_forces(_uniform_window_probe(
            vel, p, chi, sdf, udef, idx0f, hd, zero3, nu, cm, ut, om,
            wcells=wp, max_points=budget))

    return SimpleNamespace(create=create, penalization=penalization,
                           forces=forces)


@jax.named_scope("DtPolicy")
def _umax_with_body(vel, uinf, udef):
    """The CFL scale the next step's dt is formed from: the fluid's and
    the body's own (see Simulation.calc_max_timestep)."""
    return jnp.maximum(max_velocity(vel, uinf), jnp.max(jnp.abs(udef)))


def make_tgv_step(s):
    """The obstacle-free scan body as a pure function
    ``one_step(carry, cfl_eff) -> (carry', row (tgv_row_width,))``.  All
    grid / solver / uinf statics are frozen in the closure; the function
    has no leading batch axis, so fleet/batch.py can ``vmap`` it over a
    scenario axis unchanged (the lane independence the fleet isolation
    contract relies on: no cross-lane reduction anywhere in the body; a
    fleet scenario cannot be forced, so its rows keep TGV_ROW).  A forced
    flow runs the per-step path's own forcing stage
    (``sim/operators.forcing_stage``) between advection-diffusion and
    the projection, and its row carries the measured bulk velocity."""
    grid, nu, dtype = s.grid, s.nu, s.dtype
    h = float(grid.h)
    solver = s.poisson_solver
    with_stats = bool(getattr(solver, "supports_stats", False))
    uinf = s.uinf_device()
    forcing = forcing_stage(s)

    def one_step(carry, cfl_eff):
        vel, p = carry["vel"], carry["p"]
        umax, time, dtprev = carry["umax"], carry["time"], carry["dt"]
        dt = dtpolicy.dt_scan(cfl_eff, h, nu, umax, dtprev)
        vel = rk3_step(grid, vel, dt, nu, uinf)
        bulk = []
        if forcing is not None:
            vel, u_bulk = forcing(vel, uinf, dt)
            bulk = [jnp.asarray(u_bulk, dtype)[None]]
        if with_stats:
            vel, p, stats = project(grid, vel, dt, solver, p_init=p,
                                    with_stats=True)
            stats = jnp.asarray(stats, dtype)
        else:
            vel, p = project(grid, vel, dt, solver, p_init=p)
            stats = _solver_stats(dtype)
        umax_new = max_velocity(vel, uinf)
        time_new = time + dt
        out = {"vel": vel, "p": p, "umax": umax_new, "time": time_new,
               "dt": dt}
        row = jnp.concatenate([stats, *bulk, umax_new[None], dt[None],
                               time_new[None]])
        return out, row

    return one_step


def build_tgv_megaloop(s):
    """jitted (carry, cfl_eff (K,)) -> (carry', rows (K, tgv_row_width))
    for the obstacle-free uniform pipeline.  The carry is DONATED."""
    one_step = make_tgv_step(s)

    def megaloop(carry, cfl_eff):
        return jax.lax.scan(one_step, carry, cfl_eff)

    return jax.jit(megaloop, donate_argnums=(0,))


def make_body_step(s, ob):
    """The single-body scan body (a StefanFish or a rigid body such as a
    Sphere) as a pure function
    ``one_step(gait, carry, cfl_eff) -> (carry', row (FISH_ROW,))``.

    Everything geometric is frozen static at build time
    (:func:`_body_stages`).  The
    frozen-gait parameters are an ARGUMENT pytree rather than a closure,
    so the solo megaloop can bake one gait in as trace-time constants
    while fleet/batch.py stacks per-lane gaits and vmaps over them (a
    rigid body's gait is empty).  The row's columns 54:58 hold the
    shape's state, zeros for a body without one."""
    grid, nu, dtype = s.grid, s.nu, s.dtype
    h = float(grid.h)
    solver = s.poisson_solver
    with_stats = bool(getattr(solver, "supports_stats", False))
    stage = _body_stages(s, ob)
    forced_mask = ob.forced_mask_dev()
    block_mask = ob.block_mask_dev()
    fix_frame = bool(ob.bFixFrameOfRef)
    uinf_const = None if fix_frame else s.uinf_device()
    xc = s.xc
    h3 = h ** 3

    def one_step(gait, carry, cfl_eff):
        vel, p = carry["vel"], carry["p"]
        rigid, qint = carry["rigid"], carry.get("qint")
        umax, time, dtprev = carry["umax"], carry["time"], carry["dt"]
        dt = dtpolicy.dt_scan(cfl_eff, h, nu, umax, dtprev)
        uinf = -rigid[0:3] if fix_frame else uinf_const
        sdf, chi, udef, qint_new = stage.create(gait, time, dt, qint, rigid)
        if udef is None:
            udef = carry["udef"]
        # advection-diffusion
        vel = rk3_step(grid, vel, dt, nu, uinf)
        # chi-weighted fluid momenta -> 6-DOF rigid update, on device
        mom = pack_moments(
            momentum_integrals_core(xc, h3, chi, vel, rigid[12:15]))
        out = rigid_update_device(mom, rigid, forced_mask, block_mask,
                                  uinf, dt)
        rigid_new = out[:RIGID_STATE]
        ut, om, cm = out[0:3], out[3:6], out[12:15]
        vel, PF = stage.penalization(vel, chi, udef, ut, om, cm, dt)
        # projection, warm-started from the carried pressure
        if with_stats:
            vel, p, stats = project(grid, vel, dt, solver, chi, udef,
                                    p_init=p, with_stats=True)
            stats = jnp.asarray(stats, dtype)
        else:
            vel, p = project(grid, vel, dt, solver, chi, udef, p_init=p)
            stats = _solver_stats(dtype)
        F = stage.forces(vel, p, chi, sdf, udef, out[6:9], cm, ut, om)
        # umax with the PRE-update uinf: the host emit point reads the
        # previous step's frame velocity (Simulation._emit_step_pack)
        umax_new = _umax_with_body(vel, uinf, udef)
        time_new = time + dt
        carry_new = {
            "vel": vel, "p": p, "chi": chi, "udef": udef,
            "rigid": rigid_new,
            "umax": umax_new, "time": time_new, "dt": dt,
        }
        if qint_new is not None:
            carry_new["qint"] = qint_new
        else:  # the row keeps its layout: zeros for a shape with no state
            qint_new = jnp.zeros(4, dtype)
        row = jnp.concatenate([out, PF, F, stats, qint_new,
                               umax_new[None], dt[None], time_new[None]])
        return carry_new, row

    return one_step


def build_body_megaloop(s, ob):
    """jitted (carry, cfl_eff (K,)) -> (carry', rows (K, FISH_ROW)) for the
    single-body uniform pipeline.  Returns None when the body's gait is
    not freezable (``Obstacle.scan_gait``; for the fish
    models/fish/device_midline.freeze_gait).  The carry is DONATED.  The
    frozen gait is bound here as trace-time constants (the same leaves
    the closure used to capture), so the compiled artifact is unchanged
    by the make_body_step refactor."""
    gait = ob.scan_gait(s.time, s.dtype)
    if gait is None:
        return None
    one_step = make_body_step(s, ob)

    def megaloop(carry, cfl_eff):
        return jax.lax.scan(
            lambda c, x: one_step(gait, c, x), carry, cfl_eff)

    return jax.jit(megaloop, donate_argnums=(0,))


# -- x-slab sharded megaloop (round 18) ---------------------------------
#
# The whole K-step scan body runs under shard_map on the topology
# layer's "x" axis: advection-diffusion consumes ring-halo-padded slabs
# (parallel/ring.pad_slab_vector — the two boundary messages per
# component are issued BEFORE the interior stencil, async remote copies
# on TPU), while the global phases (the spectral Poisson solve, the
# body integrals, the force probe) compute REPLICATED on
# ``lax.all_gather(..., tiled=True)`` results.  Replication instead of
# host staging keeps the collective on-device (the JX016 line) and buys
# bitwise equivalence with the solo megaloop for free: every sharded
# element sees the identical arithmetic, max-reductions cross shards
# through ``pmax`` (fp max is exactly associative), and sum-reductions
# run on full gathered arrays in the solo reduction order.


def _slab_specs(keys, axis):
    """shard_map carry specs: field leaves (vel/p/chi/udef) slab-shard
    dim 0 over ``axis``; the scalar chain replicates."""
    from jax.sharding import PartitionSpec as P

    from cup3d_tpu.parallel.topology import FIELD_KEYS

    return {k: (P(axis) if k in FIELD_KEYS else P()) for k in keys}


def _require_slabbable(s, mesh, axis) -> None:
    """``CUP3D_MESH_X`` asked for the x-slab scan body: raise where it
    cannot be built, instead of running the solo loop under a mesh's
    name.  The scan body solves Poisson replicated, which the spectral
    solver supports; the iterative front-ends advertise [residual,
    iterations] telemetry that has no replicated form yet."""
    from cup3d_tpu.parallel import topology as topo

    if getattr(s.poisson_solver, "supports_stats", False):
        raise NotImplementedError(
            "CUP3D_MESH_X: the x-slab megaloop needs the spectral Poisson "
            "solver (-poissonSolver spectral); the iterative solver has "
            "no sharded scan body yet")
    D = topo.mesh_axis_size(mesh, axis)
    nx = s.grid.shape[0]
    if nx % D or nx // D < GHOSTS:
        raise ValueError(
            f"CUP3D_MESH_X: {D} x-shards cannot slab nx={nx} (need even "
            f"slabs of >= {GHOSTS} planes for the one-hop ring halo)")


def make_tgv_step_sharded(s, axis="x"):
    """The obstacle-free scan body on one x-slab, to run INSIDE
    shard_map over mesh axis ``axis``.  Same carry keys and row layout
    as make_tgv_step; vel/p arrive as the local (nx/D, ny, nz[, 3])
    slabs.  RK3 and the divergence read ring-padded slabs; the Poisson
    solve runs replicated on the gathered rhs and each shard slices its
    own pressure slab (and its sx+2 gradient window) back out.  It has
    no forcing stage: a forced flow raises rather than run unforced."""
    from cup3d_tpu.ops import stencils as st
    from cup3d_tpu.parallel import collectives as coll
    from cup3d_tpu.parallel import ring as _ring

    if forced(s.cfg):
        raise NotImplementedError(
            "CUP3D_MESH_X: the x-slab megaloop has no forcing stage "
            "(-bFixMassFlux / -uMax_forced); run the solo scan")

    grid, nu, dtype = s.grid, s.nu, s.dtype
    h = float(grid.h)
    solver = s.poisson_solver
    uinf = s.uinf_device()

    def pad_vec(u, w):
        return _ring.pad_slab_vector(grid, u, w, axis)

    def one_step(carry, cfl_eff):
        vel, p = carry["vel"], carry["p"]
        umax, time, dtprev = carry["umax"], carry["time"], carry["dt"]
        dt = dtpolicy.dt_scan(cfl_eff, h, nu, umax, dtprev)
        vel = rk3_step(grid, vel, dt, nu, uinf, pad=pad_vec)
        # projection: slab divergence, replicated global solve
        # (ops/projection.pressure_rhs semantics on the slab)
        rhs_l = st.divergence(pad_vec(vel, 1), 1, grid.h) / dt
        rhs = coll.all_gather_tiled(rhs_l, axis)
        p_full = solver(rhs, coll.all_gather_tiled(p, axis))
        sx = vel.shape[0]
        me = jax.lax.axis_index(axis)
        p_new = jax.lax.dynamic_slice_in_dim(p_full, me * sx, sx, axis=0)
        win = jax.lax.dynamic_slice_in_dim(
            grid.pad_scalar(p_full, 1), me * sx, sx + 2, axis=0)
        vel = vel - dt * st.grad(win, 1, grid.h)
        umax_new = coll.pmax_axis(max_velocity(vel, uinf), axis)
        time_new = time + dt
        out = {"vel": vel, "p": p_new, "umax": umax_new,
               "time": time_new, "dt": dt}
        row = jnp.concatenate([_solver_stats(dtype), umax_new[None],
                               dt[None], time_new[None]])
        return out, row

    return one_step


def build_tgv_megaloop_sharded(s, mesh, axis="x"):
    """jitted (carry, cfl_eff (K,)) -> (carry', rows (K, TGV_ROW)) with
    the scan body shard_mapped over the mesh's ``axis`` slabs.  Global
    shapes in and out match the solo megaloop exactly.  A run that
    cannot slab raises (:func:`_require_slabbable`) — the mesh was
    asked for, so the solo loop is not a stand-in."""
    from jax.sharding import PartitionSpec as P

    from cup3d_tpu.parallel.compat import shard_map

    _require_slabbable(s, mesh, axis)
    one_step = make_tgv_step_sharded(s, axis)

    def megaloop(carry, cfl_eff):
        return jax.lax.scan(one_step, carry, cfl_eff)

    specs = _slab_specs(("vel", "p", "umax", "time", "dt"), axis)
    sm = shard_map(megaloop, mesh, in_specs=(specs, P()),
                   out_specs=(specs, P()))
    return jax.jit(sm, donate_argnums=(0,))


def make_fish_step_sharded(s, ob, axis="x"):
    """The single-StefanFish scan body on one x-slab (inside shard_map
    over ``axis``).  The stencil-heavy advection-diffusion runs sharded
    on ring-padded slabs; the body phases (rasterization, chi, the
    momentum integrals, penalization, projection, probe) compute
    replicated — rasterization from replicated rigid scalars is already
    identical everywhere, and the rest works on the gathered velocity,
    so every reduction keeps the solo order and the step stays bitwise
    against make_body_step.  A body that is not a fish raises: the x-slab
    body is the fish's alone."""
    from cup3d_tpu.parallel import collectives as coll
    from cup3d_tpu.parallel import ring as _ring

    if not hasattr(ob, "myFish"):
        raise NotImplementedError(
            f"CUP3D_MESH_X: the x-slab megaloop runs a StefanFish only, "
            f"not a {type(ob).__name__}; run the solo scan")

    grid, nu, dtype = s.grid, s.nu, s.dtype
    h = float(grid.h)
    solver = s.poisson_solver
    stage = _body_stages(s, ob)
    forced_mask = ob.forced_mask_dev()
    block_mask = ob.block_mask_dev()
    fix_frame = bool(ob.bFixFrameOfRef)
    uinf_const = None if fix_frame else s.uinf_device()
    xc = s.xc
    h3 = h ** 3

    def pad_vec(u, w):
        return _ring.pad_slab_vector(grid, u, w, axis)

    def one_step(gait, carry, cfl_eff):
        vel, p = carry["vel"], carry["p"]
        rigid, qint = carry["rigid"], carry["qint"]
        umax, time, dtprev = carry["umax"], carry["time"], carry["dt"]
        dt = dtpolicy.dt_scan(cfl_eff, h, nu, umax, dtprev)
        uinf = -rigid[0:3] if fix_frame else uinf_const
        # shape kinematics + rasterization: replicated (pure functions
        # of the replicated rigid/gait scalars)
        sdf, chi, udef, qint_new = stage.create(gait, time, dt, qint, rigid)
        # advection-diffusion on the slab, halos by ring permute
        vel = rk3_step(grid, vel, dt, nu, uinf, pad=pad_vec)
        vel_full = coll.all_gather_tiled(vel, axis)
        mom = pack_moments(
            momentum_integrals_core(xc, h3, chi, vel_full, rigid[12:15]))
        out = rigid_update_device(mom, rigid, forced_mask, block_mask,
                                  uinf, dt)
        rigid_new = out[:RIGID_STATE]
        ut, om, cm = out[0:3], out[3:6], out[12:15]
        vel_pen, PF = stage.penalization(vel_full, chi, udef, ut, om, cm,
                                         dt)
        p_prev = coll.all_gather_tiled(p, axis)
        vel_proj, p_full = project(grid, vel_pen, dt, solver, chi, udef,
                                   p_init=p_prev)
        stats = _solver_stats(dtype)
        F = stage.forces(vel_proj, p_full, chi, sdf, udef, out[6:9], cm,
                         ut, om)
        umax_new = _umax_with_body(vel_proj, uinf, udef)
        time_new = time + dt
        sx = vel.shape[0]
        me = jax.lax.axis_index(axis)

        def sl(a):
            return jax.lax.dynamic_slice_in_dim(a, me * sx, sx, axis=0)

        carry_new = {
            "vel": sl(vel_proj), "p": sl(p_full), "chi": sl(chi),
            "udef": sl(udef), "rigid": rigid_new, "qint": qint_new,
            "umax": umax_new, "time": time_new, "dt": dt,
        }
        row = jnp.concatenate([out, PF, F, stats, qint_new,
                               umax_new[None], dt[None], time_new[None]])
        return carry_new, row

    return one_step


def build_fish_megaloop_sharded(s, ob, mesh, axis="x"):
    """jitted (carry, cfl_eff (K,)) -> (carry', rows (K, FISH_ROW)) with
    the fish scan body shard_mapped over ``axis`` slabs.  Returns None
    when the gait is not freezable (no megaloop at all, exactly like
    :func:`build_body_megaloop`); a run that cannot slab raises
    (:func:`_require_slabbable`), and so does a body that is not a fish.
    """
    from jax.sharding import PartitionSpec as P

    from cup3d_tpu.parallel.compat import shard_map

    gait = ob.scan_gait(s.time, s.dtype)
    if gait is None:
        return None
    _require_slabbable(s, mesh, axis)
    one_step = make_fish_step_sharded(s, ob, axis)

    def megaloop(carry, cfl_eff):
        return jax.lax.scan(
            lambda c, x: one_step(gait, c, x), carry, cfl_eff)

    specs = _slab_specs(("vel", "p", "chi", "udef", "rigid", "qint",
                         "umax", "time", "dt"), axis)
    sm = shard_map(megaloop, mesh, in_specs=(specs, P()),
                   out_specs=(specs, P()))
    return jax.jit(sm, donate_argnums=(0,))
