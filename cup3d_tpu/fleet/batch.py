"""Vmapped many-simulation batching: one dispatch advances B lanes.

BENCH_r04/r05 put every config's floor at ~0.03 s/step of host overhead.
The megaloop (PR 6) amortizes that over K steps of ONE simulation; this
module amortizes it over *scenarios* by laying a leading ``lane`` axis
over the megaloop scan body (sim/megaloop.make_tgv_step /
make_body_step) with ``jax.vmap``:

- the batched carry stacks vel/p/chi/udef + the 6-DOF rigid vector and
  internal quaternion per lane, so every lane owns its own state;
- the (umax, time, dt) chain is per-lane carry state, so each lane runs
  its own dt policy (stale-umax CFL bound + 1.03x growth limiter) with
  no cross-lane coupling;
- per-lane frozen-gait parameters (models/fish/device_midline.
  freeze_gait) are stacked into a batched pytree and passed as traced
  arguments, so lanes in one executable swim different gaits;
- a per-lane integer ``left`` budget gates the scan body: a lane with
  ``left == 0`` (finished, retired, or padding) has its carry passed
  through a lane-wise ``jnp.where`` select, which reproduces the frozen
  bits exactly — the foundation of the isolation contract
  (fleet/isolate.py, VALIDATION.md "Round 14").

Every operation in the scan body is elementwise over the lane axis under
vmap (per-lane FFTs, per-lane reductions, per-lane while_loops), so lane
trajectories are mutually independent: NaNs cannot cross lanes, and a
frozen or rolled-back lane never perturbs another lane's bits.

Optionally the lane axis is sharded over devices through the
parallel/compat.py shard_map wrapper (CUP3D_FLEET_MESH=1): the body has
no cross-lane collective, so the per-device program is the unmodified
vmapped advance over the local lane shard.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from cup3d_tpu.sim.megaloop import (  # noqa: F401  (rows re-exported)
    FISH_ROW,
    TGV_ROW,
    init_body_carry,
    init_tgv_carry,
    make_body_step,
    make_tgv_step,
)

#: carry key holding the per-lane remaining-step budget (int32, (B,))
LEFT = "left"


def init_amr_carry(s):
    """Obstacle-free bucketed-AMR lane carry: the (capacity, 8, 8, 8)
    padded vel/p forest plus the (umax, time, dt) chain — same keys as
    init_tgv_carry, so stack_carries/the gated body treat adaptive and
    uniform lanes identically.  umax is measured with the mega_free
    convention (max |vel + uinf| over the padded forest; padding rows
    are zero, so they never win the max)."""
    dtype = s.dtype
    uinf = s.uinf_device()
    vel = s.state["vel"]
    return {
        "vel": vel,
        "p": s.state["p"],
        "umax": jnp.max(jnp.abs(vel + uinf)),
        "time": jnp.asarray(s.time, dtype),
        "dt": jnp.asarray(s.dt, dtype),
    }


def stack_gaits(gaits, dtype):
    """Per-lane frozen-gait dicts -> one batched pytree (leading lane
    axis).  Python-float leaves become (B,) device scalars so vmap can
    batch them (the solo megaloop bakes them in as constants instead);
    array leaves must share shape across lanes — mixed midline
    discretizations belong in different buckets (fleet/server.py keys
    assembly on the static signature)."""
    keys = sorted(gaits[0])
    for g in gaits:
        if sorted(g) != keys:
            raise ValueError("lane gaits disagree on parameter set")
    out = {}
    for k in keys:
        leaves = [jnp.asarray(g[k], dtype) for g in gaits]
        shapes = {leaf.shape for leaf in leaves}
        if len(shapes) != 1:
            raise ValueError(
                f"gait leaf {k!r} varies in shape across lanes: {shapes}"
            )
        out[k] = jnp.stack(leaves)
    return out


def stack_carries(carries, targets):
    """Stack per-lane solo carries (init_tgv_carry / init_body_carry
    outputs) into one batched carry, attaching the per-lane ``left``
    budget.  ``targets[b] <= 0`` marks lane b as padding: its state is a
    clone that the gated body freezes from step 0."""
    keys = sorted(carries[0])
    for c in carries:
        if sorted(c) != keys:
            raise ValueError("lane carries disagree on state set")
    out = {k: jnp.stack([c[k] for c in carries]) for k in keys}
    out[LEFT] = jnp.asarray(np.asarray(targets, np.int32))
    return out


def abstract_advance_args(carry, gait, B, K, dtype):
    """The ``jax.ShapeDtypeStruct`` avals of one batched advance call
    — exactly the shapes stack_carries / _cfl_block / stack_gaits
    produce for ``B`` lanes and ``K`` steps — from a SINGLE lane's
    solo (carry, gait) payload.  This is what the background compile
    service (aot/compiler.py) lowers against: no batched arrays are
    materialized, no device memory is touched, and the resulting AOT
    executable is bit-for-bit the one a live dispatch would build.
    Returns ``(carry_avals, cfl_aval, gaits_avals_or_None)``."""
    sds = jax.ShapeDtypeStruct

    def batched(v):
        leaf = jnp.asarray(v) if not hasattr(v, "shape") else v
        return sds((int(B),) + tuple(leaf.shape), leaf.dtype)

    carry_avals = {k: batched(v) for k, v in carry.items()}
    carry_avals[LEFT] = sds((int(B),), jnp.int32)
    cfl_aval = sds((int(B), int(K)), np.dtype(dtype))
    gaits_avals = None
    if gait is not None:
        # mirror stack_gaits: every leaf is cast to the sim dtype and
        # stacked along a new lane axis (floats become (B,) scalars)
        gaits_avals = {
            k: sds((int(B),) + tuple(np.shape(v)), np.dtype(dtype))
            for k, v in gait.items()
        }
    return carry_avals, cfl_aval, gaits_avals


def _gated(core, has_gait):
    """Wrap a solo scan body with the per-lane freeze gate.  Inside vmap
    each lane sees scalar ``left``; a finished/retired/padding lane
    (left == 0) recomputes the step but keeps its old carry through an
    elementwise select — bit-exact freezing, no shape change, and the
    rows it produces are replays the consumer drops by budget."""
    if has_gait:
        def body(gait, carry, cfl_eff):
            left = carry[LEFT]
            act = left > 0
            inner = {k: v for k, v in carry.items() if k != LEFT}
            new, row = core(gait, inner, cfl_eff)
            merged = jax.tree_util.tree_map(
                lambda n, o: jnp.where(act, n, o), new, inner)
            merged[LEFT] = left - act.astype(left.dtype)
            return merged, row
    else:
        def body(gait, carry, cfl_eff):
            del gait
            left = carry[LEFT]
            act = left > 0
            inner = {k: v for k, v in carry.items() if k != LEFT}
            new, row = core(inner, cfl_eff)
            merged = jax.tree_util.tree_map(
                lambda n, o: jnp.where(act, n, o), new, inner)
            merged[LEFT] = left - act.astype(left.dtype)
            return merged, row
    return body


@jax.jit
def _upload_lane_carry(carry, lane, solo, nsteps):
    """One lane's rows of the batched carry <- a solo carry, with the
    lane's ``left`` budget set to ``nsteps``.  ``lane`` is a traced
    int32 scalar, so ``.at[lane].set`` lowers to a dynamic_update_slice
    and every lane index shares ONE compiled specialization — the
    zero-recompile half of the reseed contract.  jnp's scatter-update
    writes only the addressed rows: every other lane's bits come
    through untouched (the round-14 isolation contract extended to
    reseeding, VALIDATION.md "Round 17")."""
    out = {}
    for k, v in carry.items():
        if k == LEFT:
            out[k] = v.at[lane].set(nsteps.astype(v.dtype))
        else:
            out[k] = v.at[lane].set(solo[k].astype(v.dtype))
    return out


@jax.jit
def _upload_lane_gait(gaits, lane, gait):
    return {k: gaits[k].at[lane].set(gait[k]) for k in gaits}


def reseed_lane_carry(carry, lane, solo, nsteps, mesh=None):
    """Splice a fresh job's solo carry into lane ``lane`` of a batched
    carry (per-lane upload, NOT a host restack): the continuous-batching
    reseed primitive.  ``solo`` is an init_*_carry output for the same
    bucket signature; ``nsteps`` becomes the lane's ``left`` budget.
    Like the rollback selects (fleet/isolate.py) the result is a new
    carry — the input is not donated, so in-flight consumers of the old
    buffers stay valid.  With a ``mesh`` the update runs shard-local
    (:func:`_sharded_lane_upload`) so reseeding a mesh-resident carry
    never gathers it to one device.

    Provenance (round 22): this upload is the K-boundary reseed splice
    — on the waiting job's timeline it sits inside the
    ``reseed_wait -> reseeded`` interval (``obs.trace.now()`` clock),
    which the phase decomposition attributes to ``reseed_wait``.  The
    ``fleet.reseed_uploads`` counter gives the per-scrape rate without
    waiting for job terminals."""
    from cup3d_tpu.obs import metrics as M

    M.counter("fleet.reseed_uploads").inc()
    solo = {k: jnp.asarray(solo[k]) for k in carry if k != LEFT}
    up = (_sharded_lane_upload(mesh) if mesh is not None
          else _upload_lane_carry)
    return up(carry, jnp.asarray(lane, jnp.int32), solo,
              jnp.asarray(nsteps, jnp.int32))


def reseed_lane_gaits(gaits, lane, gait, mesh=None):
    """Swap one lane's row of the stacked frozen-gait pytree (fish
    bucket reseed); None passes through for gait-free bodies.  The new
    gait must share the batch's parameter set and leaf shapes — reseeds
    are same-signature by construction (fleet/server.py matches on the
    static signature before calling this).  ``mesh`` routes the update
    through the shard-local upload like :func:`reseed_lane_carry`."""
    if gaits is None:
        return None
    if sorted(gait) != sorted(gaits):
        raise ValueError("reseed gait disagrees with the batch gait set")
    solo = {k: jnp.asarray(gait[k], gaits[k].dtype) for k in gaits}
    if mesh is not None:
        # gait rows ride the same shard-local update as carry rows (the
        # gait pytree has no LEFT key, so nsteps is inert)
        return _sharded_lane_upload(mesh)(
            gaits, jnp.asarray(lane, jnp.int32), solo,
            jnp.asarray(0, jnp.int32))
    return _upload_lane_gait(gaits, jnp.asarray(lane, jnp.int32), solo)


def lane_carry_host(carry, lane):
    """One lane's rows of a batched carry as host numpy copies — the
    serialization half of the round-23 durability contract (the upload
    half is :func:`reseed_lane_carry`).  ``np.asarray`` round-trips the
    f32 bits exactly, so journal snapshot -> ``recover()`` reseed -> the
    SAME compiled advance reproduces the never-crashed trajectory
    bitwise.  The LEFT budget row is dropped: placement decides the
    resumed lane's budget (``nsteps`` arg of the reseed upload), exactly
    as it does for a fresh splice."""
    return {k: np.asarray(v[lane]) for k, v in carry.items() if k != LEFT}


#: lane-track tid stride: lane tids are ``batch_id * LANE_TID_STRIDE +
#: lane`` so concurrent batches never share a Perfetto thread track
#: (the pid-3 job-occupancy export, obs/trace.LANE_PID)
LANE_TID_STRIDE = 4096


def lane_track_id(batch_id: int, lane: int) -> int:
    """Stable Perfetto tid of one (batch, lane) occupancy track —
    shared by fleet/server.py (emission) and tools/trace_check.py
    (validation: spans on one tid must not overlap)."""
    return int(batch_id) * LANE_TID_STRIDE + int(lane)


def fleet_mesh() -> Optional["jax.sharding.Mesh"]:
    """The optional fleet mesh behind CUP3D_FLEET_MESH: now the 2-D
    ``(lanes, x)`` factory (parallel/topology.fleet_mesh2d), whose
    ``CUP3D_MESH`` auto default of ``(ndevices, 1)`` reproduces the old
    1-D lanes mesh bit-for-bit as the L-by-1 special case.  None keeps
    the pure-vmap single-device fleet."""
    from cup3d_tpu.parallel import topology as topo

    return topo.fleet_mesh2d()


def mesh_lane_multiple(mesh) -> int:
    """Lane counts must divide evenly over the mesh; 1 when unsharded.
    On the 2-D mesh the batch axis shards over EVERY mesh device (the
    lane axis flattens across ``lanes`` and ``x``), so the multiple is
    the full device count."""
    return int(mesh.devices.size) if mesh is not None else 1


def resolve_fleet_mesh(n_lanes: int, mesh) -> Optional[
        "jax.sharding.Mesh"]:
    """The loud mesh gate: the mesh the fleet will actually use for a
    batch of ``n_lanes``.  A lane count that does not divide over the
    mesh devices cannot shard evenly — the fleet then falls back to the
    unsharded vmap advance, visibly: a warning, the
    ``fleet.mesh_fallbacks`` counter, and a None that callers store in
    place of the mesh (so /health and the CLI report the shard state
    that is really running, not the one that was asked for)."""
    if mesh is None:
        return None
    mult = mesh_lane_multiple(mesh)
    if n_lanes % mult == 0:
        return mesh
    import warnings

    from cup3d_tpu.obs import metrics as M

    warnings.warn(
        f"{n_lanes} lanes do not divide over the {mult}-device fleet "
        f"mesh {dict(mesh.shape)}: batch runs unsharded", stacklevel=2)
    M.counter("fleet.mesh_fallbacks").inc()
    return None


#: per-mesh memo of the shard_map'd lane-upload executables (one entry
#: per live mesh; jit's own cache keys the shapes under it)
_SHARDED_UPLOADS: dict = {}


def _sharded_lane_upload(mesh):
    """The round-17 reseed upload for a mesh-sharded carry: a
    shard_map'd dynamic-update-slice in LOCAL lane coordinates.  A
    plain ``.at[lane].set`` on a sharded carry would make the SPMD
    partitioner materialize cross-device gathers around the update;
    here every shard computes its flat shard id, rebases ``lane`` into
    its own block, and applies a where-masked one-row update — the
    owning shard writes, every other shard reproduces its bits
    untouched.  Memoized per mesh so steady-state reseeding never
    retraces."""
    fn = _SHARDED_UPLOADS.get(mesh)
    if fn is not None:
        return fn
    from jax.sharding import PartitionSpec as P

    from cup3d_tpu.parallel.compat import shard_map

    axes = tuple(mesh.axis_names)
    minor = int(mesh.shape[axes[1]]) if len(axes) > 1 else 1

    def upload(carry, lane, solo, nsteps):
        sid = jax.lax.axis_index(axes[0])
        if len(axes) > 1:
            sid = sid * minor + jax.lax.axis_index(axes[1])
        some = next(iter(carry.values()))
        bl = some.shape[0]  # local lanes per shard (B // nshards)
        loc = lane - sid * bl
        ok = (loc >= 0) & (loc < bl)
        locc = jnp.clip(loc, 0, bl - 1)

        def upd(v, row):
            cur = jax.lax.dynamic_slice_in_dim(v, locc, 1, axis=0)
            new = jnp.where(ok, row[None].astype(v.dtype), cur)
            return jax.lax.dynamic_update_slice_in_dim(
                v, new, locc, axis=0)

        out = {}
        for k, v in carry.items():
            if k == LEFT:
                out[k] = upd(v, nsteps)
            else:
                out[k] = upd(v, solo[k])
        return out

    def specs(tree):
        return jax.tree_util.tree_map(lambda _: P(axes), tree)

    def wrapped(carry, lane, solo, nsteps):
        sm = shard_map(
            upload, mesh,
            in_specs=(specs(carry), P(),
                      jax.tree_util.tree_map(lambda _: P(), solo), P()),
            out_specs=specs(carry),
            check_vma=False)
        return sm(carry, lane, solo, nsteps)

    fn = jax.jit(wrapped)
    _SHARDED_UPLOADS[mesh] = fn
    return fn


def build_fleet_advance(s, ob=None, mesh=None, kind=None):
    """jitted ``(carry_B, cfl (B, K), gaits_B) -> (carry_B', rows
    (B, K, ROW))``: B independent lanes, K steps each, one dispatch.

    ``s`` is the bucket's template Simulation (grid, solver, statics);
    ``ob`` its template obstacle for the fish pipeline (None selects an
    obstacle-free body, where ``gaits`` is passed as None).  ``kind``
    picks the scan body explicitly — "fish", "tgv", or "amr_tgv" (the
    bucketed block-forest body from sim/amr.make_amr_tgv_step, whose
    frozen padded-topology closure is what fleet/server.py's
    (capacity, topology-signature) bucket key guarantees is shared) —
    defaulting to fish/tgv by ``ob`` for older callers.  With a
    ``mesh`` the lane axis is sharded across devices via the
    parallel/compat.py shard_map wrapper — the body is collective-free,
    so each device runs the vmapped advance over its lane shard.

    The carry is deliberately NOT donated: the batched advance's result
    feeds lane-wise where-selects against the previous carry on the
    rollback path (fleet/isolate.py), so the pre-dispatch buffers must
    stay valid until the isolation layer releases them."""
    if kind is None:
        kind = "fish" if ob is not None else "tgv"
    has_gait = kind == "fish"
    if kind == "fish":
        core = make_body_step(s, ob)
    elif kind == "amr_tgv":
        from cup3d_tpu.sim.amr import make_amr_tgv_step

        core = make_amr_tgv_step(s)
    else:
        core = make_tgv_step(s)
    body = _gated(core, has_gait)

    def lane_scan(gait, carry, cfl_eff):
        return jax.lax.scan(
            lambda c, x: body(gait, c, x), carry, cfl_eff)

    gait_axes = 0 if has_gait else None

    def advance(carry, cfl_eff, gaits):
        return jax.vmap(lane_scan, in_axes=(gait_axes, 0, 0))(
            gaits, carry, cfl_eff)

    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        from cup3d_tpu.parallel.compat import shard_map

        # the batch axis shards over the FLATTENED mesh (2-D (lanes, x)
        # or the legacy 1-D (lanes,)): the body is collective-free, so
        # each device runs the vmapped advance over its lane block
        lanes = P(tuple(mesh.axis_names))
        advance = shard_map(
            advance, mesh,
            in_specs=(lanes, lanes, lanes),
            out_specs=(lanes, lanes),
        )
    return jax.jit(advance)
