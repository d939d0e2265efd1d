"""StefanFish: the self-propelled carangiform swimmer.

Reference: StefanFish (main.cpp:8960-8978, 15668-15981) on top of Fish
(main.cpp:7586-7617, 10597-10958).  Combines:

- CurvatureDefinedFishData gait generation + deformation-momentum removal;
- PID feedback on streamwise/lateral position (alpha/beta), depth (gamma)
  and roll (angular-velocity correction) toward the spawn point;
- the RL interface: act() commands bending/period/torsion,
  state() returns the 25-dim observation with 3 shear sensors.

The SDF/udef rasterization runs as one jitted window kernel
(cup3d_tpu.models.fish.rasterize) instead of per-block surface scatters.
"""

from __future__ import annotations

from collections import deque
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from cup3d_tpu.models.base import (
    FRAME,
    Obstacle,
    fields_from_sdf,
    pos_rot_traced,
    quat_to_rot,
    quat_to_rot_dev,
)
from cup3d_tpu.models.fish.curvature import CurvatureDefinedFishData
from cup3d_tpu.models.fish.device_midline import (
    device_midline_eligible,
    freeze_gait,
    midline_state_device,
)
from cup3d_tpu.models.fish.rasterize import (
    raster_box,
    raster_work,
    rasterize_midline,
    rasterize_points,
)
from cup3d_tpu.models.fish.shapes import compute_widths_heights
from cup3d_tpu.obs import metrics as obs_metrics
from cup3d_tpu.ops.chi import heaviside


def _split_midline(pack):
    """(Nm, 20) device midline pack -> the rasterizer's dict."""
    return {
        "r": pack[:, 0:3], "v": pack[:, 3:6],
        "nor": pack[:, 6:9], "vnor": pack[:, 9:12],
        "bin": pack[:, 12:15], "vbin": pack[:, 15:18],
        "width": pack[:, 18], "height": pack[:, 19],
    }


@jax.named_scope("CreateObstacles")
def raster_blocks(xc, real, slots, pack, frame):
    """Block-layout rasterization, traced: gather the candidate blocks'
    centers from ``xc`` (rows, bs, bs, bs, 3) -> midline distance over
    their cells -> scatter into arrays of ``xc``'s row count.  A real
    block that is no candidate holds SDF -1 and udef 0; a padding row of a
    capacity bucket (``real`` (rows, 1, 1, 1) is 0 there; None: no
    padding) holds the all-zero SDF the bucket's invariants rest on.
    Padded entries of ``slots`` lie past the end: the gather fills
    far-away centers and the scatter drops them.  ``frame`` None: the
    pack's last row carries the host mirrors' frame, as in
    ``_raster_window``."""
    if frame is None:
        pack, frame = pack[:-1], pack[-1, :FRAME]
    pos, rot = pos_rot_traced(frame)
    centers = jnp.take(xc, slots, axis=0, mode="fill", fill_value=1e6)
    sdf_c, udef_c = rasterize_points(centers, _split_midline(pack), pos, rot)
    shape = xc.shape[:4]
    sdf = jnp.full(shape, -1.0, xc.dtype)
    if real is not None:
        sdf = jnp.where(real > 0, sdf, 0.0)
    sdf = sdf.at[slots].set(sdf_c, mode="drop")
    udef = jnp.zeros(shape + (3,), xc.dtype)
    udef = udef.at[slots].set(udef_c, mode="drop")
    return sdf, udef


_raster_blocks = jax.jit(raster_blocks)


@jax.named_scope("CreateObstacles")
def _raster_window(pack, frame, grid, window_shape, box):
    """Window snap + midline rasterization + dense placement, traced.
    ``frame`` None: the pack's last row carries the host mirrors' frame
    (``StefanFish._dense_inputs``).  The window half-width, the body's
    raster box and ``h`` are trace-time constants."""
    dtype = pack.dtype
    if frame is None:
        pack, frame = pack[:-1], pack[-1, :FRAME]
    pos, rot = pos_rot_traced(frame)
    h = jnp.asarray(grid.h, dtype)
    half = jnp.asarray(0.5 * np.asarray(window_shape) * grid.h, dtype)
    idx0 = jnp.clip(
        jnp.floor((pos - half) / h).astype(jnp.int32),
        0,
        jnp.asarray(np.asarray(grid.shape) - np.asarray(window_shape),
                    jnp.int32),
    )
    origin = idx0.astype(dtype) * h
    starts = (idx0[0], idx0[1], idx0[2])
    sdf_w, udef_w = rasterize_midline(
        origin, h, window_shape, box, _split_midline(pack), pos, rot,
    )
    sdf = jnp.full(grid.shape, -1.0, dtype)
    sdf = jax.lax.dynamic_update_slice(sdf, sdf_w, starts)
    udef = jnp.zeros(tuple(grid.shape) + (3,), dtype)
    udef = jax.lax.dynamic_update_slice(udef, udef_w, starts + (0,))
    return sdf, udef


_raster_window_dense = jax.jit(
    _raster_window, static_argnames=("grid", "window_shape", "box"))


@partial(jax.jit, static_argnames=("grid", "window_shape", "box", "combine"))
def _create_dense(pack, frame, grid, window_shape, box, combine):
    """CreateObstacles for one fish on the dense grid as ONE program: from
    the step's single upload to (sdf, chi, udef, combined) — rasterizer,
    ghost padding, Towers chi, band mask and, for a fish alone on the
    grid, the combine (``models/base.fields_from_sdf``)."""
    sdf, udef = _raster_window(pack, frame, grid, window_shape, box)
    return (sdf,) + fields_from_sdf(grid, sdf, udef, combine)


def _clip_quantities(fmax, dfmax, dt, fcandidate, dfcandidate, f, df):
    """PID anti-windup clipping (main.cpp:15698-15713): limit both the
    correction and its rate.  Returns (f, df)."""
    if abs(dfcandidate) > dfmax:
        df = dfmax if dfcandidate > 0 else -dfmax
        f = f + dt * df
    elif abs(fcandidate) < fmax:
        f, df = fcandidate, dfcandidate
    else:
        f = fmax if fcandidate > 0 else -fmax
        df = 0.0
    return f, df


class StefanFish(Obstacle):
    def __init__(self, sim, spec: Dict[str, str]):
        super().__init__(sim, spec)
        g = lambda k, d: float(spec.get(k, d))
        b = lambda k: spec.get(k, "0").lower() in ("1", "true")
        self.Tperiod = g("T", 1.0)
        self.phaseShift = g("phi", 0.0)
        amp = g("amplitudeFactor", 1.0)
        self.bCorrectPosition = b("CorrectPosition")
        self.bCorrectPositionZ = b("CorrectPositionZ")
        self.bCorrectRoll = b("CorrectRoll")
        height_name = spec.get("heightProfile", "baseline")
        width_name = spec.get("widthProfile", "baseline")
        self.wyp = g("wyp", 1.0)
        self.wzp = g("wzp", 1.0)
        if (self.bCorrectPosition or self.bCorrectPositionZ or self.bCorrectRoll
                ) and abs(self.quaternion[0] - 1) > 1e-6:
            raise ValueError("PID controllers require zero initial angles")

        # midline resolution follows the finest spacing the grid can offer
        # (reference: sim.hmin, main.cpp:15402); layout-generic
        h = sim.grid.hmin
        self.myFish = CurvatureDefinedFishData(
            self.length, self.Tperiod, self.phaseShift, h, amp
        )
        self.myFish.height, self.myFish.width = compute_widths_heights(
            height_name, width_name, self.length, self.myFish.rS
        )
        self.origC = self.position.copy()  # PID target (spawn point)
        self.r_axis: deque = deque()  # roll-axis history for bCorrectRoll

        # dense uniform layout: a static rasterization window (the deformed
        # fish stays within ~0.6 L of its center; margin for the mollified
        # band) and the box each group of segments is evaluated in.  Block
        # layout: candidate blocks are found per call.
        if not self._is_blocks:
            nw = int(np.ceil(1.25 * self.length / h)) + 8
            self._window_shape = tuple(min(nw, n) for n in sim.grid.shape)
            self._raster_box = raster_box(
                self.myFish.width, self.myFish.height, self.myFish.rS, h,
                self._window_shape)

    # -- geometry pipeline (Fish::create, main.cpp:10952-10958) ------------

    def update_shape(self, t: float, dt: float) -> None:
        self._apply_position_pid(dt)
        self.myFish.compute_midline(t, dt)
        self.myFish.integrate_linear_momentum()
        self.myFish.integrate_angular_momentum(max(dt, 1e-12))
        self._update_sensor_locations()

    def max_body_speed(self, uinf=None) -> float:
        """Rigid bound + the midline's max deformation speed — the fast,
        host-exact part of the fish's material velocity (see
        Obstacle.max_body_speed for why the pipelined dt chain needs
        this fresh)."""
        base = super().max_body_speed(uinf)
        v = np.asarray(self.myFish.v, np.float64)
        return base + float(np.sqrt((v * v).sum(-1).max()))

    def _apply_position_pid(self, dt: float) -> None:
        """alpha/beta/gamma corrections (StefanFish::create,
        main.cpp:15716-15778)."""
        cf = self.myFish
        q = self.quaternion
        s = self.sim
        # pitch: x-component of the head->mid direction in the lab z-row
        Rrow = np.array(
            [2 * (q[1] * q[3] - q[2] * q[0]), 2 * (q[2] * q[3] + q[1] * q[0]),
             1 - 2 * (q[1] * q[1] + q[2] * q[2])]
        )
        nm = cf.Nm
        d = cf.r[0] - cf.r[nm // 2]
        dn = np.linalg.norm(d) + 1e-21
        pitch = np.arcsin(np.clip(Rrow @ (d / dn), -1.0, 1.0))
        roll = np.arctan2(2 * (q[3] * q[2] + q[0] * q[1]),
                          1 - 2 * (q[1] * q[1] + q[2] * q[2]))
        yaw = np.arctan2(2 * (q[3] * q[0] + q[1] * q[2]),
                         -1 + 2 * (q[0] * q[0] + q[1] * q[1]))
        roll_small = abs(roll) < np.pi / 9
        yaw_small = abs(yaw) < np.pi / 9
        dt_eff = max(dt, 1e-12)

        if self.bCorrectPosition:
            cf.alpha = 1.0 + (self.position[0] - self.origC[0]) / self.length
            cf.dalpha = (self.transVel[0] + s.uinf[0]) / self.length
            if not roll_small:
                cf.alpha, cf.dalpha = 1.0, 0.0
            elif cf.alpha < 0.9:
                cf.alpha, cf.dalpha = 0.9, 0.0
            elif cf.alpha > 1.1:
                cf.alpha, cf.dalpha = 1.1, 0.0
            dy = (self.origC[1] - self.absPos[1]) / self.length
            sign_y = 1.0 if dy > 0 else -1.0
            dphi = yaw - 0.0
            bb = self.wyp * sign_y * dy * dphi if roll_small else 0.0
            dbdt = (bb - cf.beta) / dt_eff if s.step > 1 else 0.0
            cf.beta, cf.dbeta = _clip_quantities(
                1.0, 5.0, dt_eff, bb, dbdt, cf.beta, cf.dbeta
            )
        if self.bCorrectPositionZ:
            dphi = pitch - 0.0
            dz = (self.origC[2] - self.absPos[2]) / self.length
            sign_z = 1.0 if dz > 0 else -1.0
            gg = -self.wzp * dphi * dz * sign_z if (roll_small and yaw_small) else 0.0
            dgdt = (gg - cf.gamma) / dt_eff if s.step > 1 else 0.0
            gmax = 0.10 / self.length
            dRdtmax = 0.1 * self.length / cf.Tperiod
            dgdtmax = abs(gmax * gmax * dRdtmax)
            cf.gamma, cf.dgamma = _clip_quantities(
                gmax, dgdtmax, dt_eff, gg, dgdt, cf.gamma, cf.dgamma
            )

    def _midline_pack(self) -> np.ndarray:
        """(Nm, 20) host midline: frames, their velocities, profiles."""
        cf = self.myFish
        return np.concatenate(
            [cf.r, cf.v, cf.nor, cf.vnor, cf.bin, cf.vbin,
             cf.width[:, None], cf.height[:, None]], axis=1
        )

    def _dense_inputs(self):
        """(pack, frame) for the dense-layout programs: ONE upload per
        call.  Host mirrors' frame: it rides as one more row of the
        midline pack and ``frame`` is None.  Pipelined chaining: the frame
        is the device rigid pack (host mirrors trail it one step)."""
        pack = self._midline_pack()
        d = self._dev_rigid
        frame = d["pack"] if self.sim.cfg.pipelined and d is not None else None
        if frame is None:
            row = np.zeros((1, pack.shape[1]))
            row[0, :FRAME] = self.host_frame()
            pack = np.concatenate([pack, row])
        # cast on the host: jnp.asarray(float64, float32) is an upload AND
        # a convert program
        return jnp.asarray(pack.astype(self.sim.dtype)), frame

    #: the traced half of the block-layout rasterizer: a forest driver
    #: that finds it on a body traces it inside its own program
    raster_blocks = staticmethod(raster_blocks)

    def block_inputs(self):
        """The host half of the block-layout rasterizer, NumPy only:
        ``(pack, frame, slots)`` for ``raster_blocks``.

        ``slots``: candidate blocks by AABB intersection (the TPU analogue
        of prepare_segPerBlock, main.cpp:10672-10717), the fish AABB
        around the body center padded per block by the mollification band
        at that block's spacing (the same margin the surface-probe windows
        use — ops/surface.probe_margin); the count is bucketed so XLA
        retraces only on bucket changes.  ``pack``: the float64 midline.
        ``frame``: the device rigid pack in pipelined mode (exact current
        state; the host mirror only sizes the AABB, whose 8h margin covers
        the grouped-read staleness of ~8 steps x CFL*h of drift), else
        None and the host mirrors' frame rides as the pack's last row."""
        from cup3d_tpu.ops.surface import probe_margin

        grid = self.sim.grid
        half = probe_margin(self.length, grid.h)  # (nb,)
        lo = grid.origin  # (nb, 3)
        hi = grid.origin + (grid.bs * grid.h)[:, None]
        cand = np.all(hi > self.position - half[:, None], axis=1) & np.all(
            lo < self.position + half[:, None], axis=1
        )
        idx = np.where(cand)[0]
        mpad = max(16, -(-len(idx) // 16) * 16)
        slots = np.full(mpad, np.iinfo(np.int32).max, np.int32)
        slots[: len(idx)] = idx
        pack = self._midline_pack()
        d = self._dev_rigid
        frame = d["pack"] if self.sim.cfg.pipelined and d is not None else None
        if frame is None:
            row = np.zeros((1, pack.shape[1]))
            row[0, :FRAME] = self.host_frame()
            pack = np.concatenate([pack, row])
        return pack, frame, slots

    def _rasterize_blocks(self, t: float):
        """``rasterize`` on the block layout, (nb, bs, bs, bs) arrays: for
        a driver that does its own padding (the sharded forest)."""
        grid = self.sim.grid
        dtype = self.sim.dtype
        xc = getattr(self.sim, "_xc", None)
        if xc is None or xc.shape[0] != grid.nb:
            xc = jnp.asarray(grid.cell_centers(dtype))
        pack, frame, slots = self.block_inputs()
        return _raster_blocks(xc, None, jnp.asarray(slots),
                              jnp.asarray(pack.astype(dtype)), frame)

    def rasterize(self, t: float):
        if self._is_blocks:
            return self._rasterize_blocks(t)
        return _raster_window_dense(
            *self._dense_inputs(), self.sim.grid, self._window_shape,
            self._raster_box,
        )

    def create(self, t: float, combine: bool = False):
        """``Obstacle.create`` with the rasterizer inside the program."""
        self.sdf, self.chi, self.udef, combined = _create_dense(
            *self._dense_inputs(), self.sim.grid, self._window_shape,
            self._raster_box, combine,
        )
        self.note_raster_work(1)
        return combined

    def note_raster_work(self, calls: int, scan: bool = False) -> None:
        """Raise ``operators.raster_cells`` (cells the boxed rasterizer
        evaluated) and ``operators.raster_sweep_cells`` (what the full
        sweep of the window would have) for ``calls`` dense-window
        rasterizations of this body, per step or in a scan alike."""
        cells, sweep = raster_work(self.myFish.Nm, self._window_shape,
                                   self._raster_box)
        obs_metrics.counter("operators.raster_cells").inc(calls * cells)
        obs_metrics.counter("operators.raster_sweep_cells").inc(calls * sweep)

    # -- the scan megaloop's body stage: the frozen-gait device midline -----

    def offers_scan_stage(self) -> bool:
        return device_midline_eligible(self)

    @property
    def scan_window(self):
        return self._window_shape

    def scan_gait(self, t: float, dtype):
        return freeze_gait(self, t, dtype)

    def scan_state(self, dtype):
        """The internal quaternion the midline's frame integrates."""
        return jnp.asarray(self.myFish.quaternion_internal, dtype)

    def apply_scan_state(self, row: np.ndarray) -> None:
        self.myFish.quaternion_internal = np.asarray(row, np.float64)

    def window_shape_device(self, gait, origin, h, pos, rigid, time, dt,
                            state):
        """Shape kinematics at the carried time, then the boxed window
        rasterizer: (sdf, udef, the new internal quaternion)."""
        mid, qint_new = midline_state_device(gait, time, dt, state)
        rot = quat_to_rot_dev(rigid[15:19])
        sdf_w, udef_w = rasterize_midline(origin, h, self._window_shape,
                                          self._raster_box, mid, pos, rot)
        return sdf_w, udef_w, qint_new

    # -- rigid-body override: roll correction ------------------------------

    def supports_device_update(self) -> bool:
        # roll correction mutates angVel on host right after the 6x6 solve
        return super().supports_device_update() and not self.bCorrectRoll

    def compute_velocities(self, moments) -> None:
        super().compute_velocities(moments)
        if not self.bCorrectRoll:
            return
        cf = self.myFish
        s = self.sim
        q = self.quaternion
        o = self.angVel
        dq = 0.5 * np.array(
            [
                -o[0] * q[1] - o[1] * q[2] - o[2] * q[3],
                +o[0] * q[0] + o[1] * q[3] - o[2] * q[2],
                -o[0] * q[3] + o[1] * q[0] + o[2] * q[1],
                +o[0] * q[2] - o[1] * q[1] + o[2] * q[0],
            ]
        )
        nom = 2 * (q[3] * q[2] + q[0] * q[1])
        dnom = 2 * (dq[3] * q[2] + dq[0] * q[1] + q[3] * dq[2] + q[0] * dq[1])
        denom = 1 - 2 * (q[1] * q[1] + q[2] * q[2])
        ddenom = -4 * (q[1] * dq[1] + q[2] * dq[2])
        arg = nom / denom
        darg = (dnom * denom - nom * ddenom) / denom**2
        a = np.arctan2(nom, denom)
        da = darg / (1 + arg * arg)

        # running 5-second average of the head->tail axis = roll axis
        nm = cf.Nm
        d = cf.r[0] - cf.r[nm - 1]
        dn = np.linalg.norm(d) + 1e-21
        self.r_axis.append(np.array([-d[0] / dn, -d[1] / dn, -d[2] / dn, s.dt]))
        roll_axis = np.zeros(3)
        time_roll = 0.0
        keep = 0
        for entry in reversed(self.r_axis):
            if time_roll + entry[3] > 5.0:
                break
            roll_axis += entry[:3] * entry[3]
            time_roll += entry[3]
            keep += 1
        for _ in range(len(self.r_axis) - keep):
            self.r_axis.popleft()
        time_roll += 1e-21
        roll_axis /= time_roll
        if s.time < 1.0 or time_roll < 1.0:
            return
        o -= (o @ roll_axis) * roll_axis  # kill the roll component
        corr, _ = _clip_quantities(0.025, 1e4, s.dt, a + 0.05 * da, 0.0, 0.0, 0.0)
        o -= corr * roll_axis
        self.angVel = o

    # -- sensors / RL interface (main.cpp:15860-15981) ---------------------

    def _update_sensor_locations(self) -> None:
        cf = self.myFish
        rot = quat_to_rot(self.quaternion)
        to_comp = lambda x: self.position + rot @ x
        cf.sensorLocation[0:3] = to_comp(cf.r[0])
        # station with rS[ss] <= 0.04 L < rS[ss+1] (main.cpp:11438)
        ss = int(np.searchsorted(cf.rS, 0.04 * self.length, side="right")) - 1
        ss = min(max(ss, 1), cf.Nm - 2)
        offset = np.pi / 2 if cf.height[ss] > cf.width[ss] else 0.0
        for idx, theta in ((1, offset), (2, offset + np.pi)):
            p = (
                cf.r[ss]
                + cf.width[ss] * np.cos(theta) * cf.nor[ss]
                + cf.height[ss] * np.sin(theta) * cf.bin[ss]
            )
            cf.sensorLocation[3 * idx : 3 * idx + 3] = to_comp(p)

    def act(self, t_rl_action: float, action) -> None:
        action = list(np.atleast_1d(action))
        if len(action) > 1 and self.bForcedInSimFrame[2]:
            action[1] = 0.0
        cf = self.myFish
        cf.oldrCurv = cf.lastCurv
        cf.lastCurv = float(action[0])
        cf.lastTact = float(t_rl_action)
        cf.execute(self.sim.time, t_rl_action, action)

    def get_learn_t_period(self) -> float:
        return self.myFish.next_period

    def get_phase(self, t: float) -> float:
        cf = self.myFish
        arg = (
            2 * np.pi * ((t - cf.time0) / cf.periodPIDval + cf.timeshift)
            + np.pi * cf.phaseShift
        )
        return float(np.mod(arg, 2 * np.pi))

    def state(self) -> np.ndarray:
        """25-dim RL observation (main.cpp:15889-15931)."""
        cf = self.myFish
        Tp, L = cf.Tperiod, self.length
        S = np.zeros(25)
        S[0:3] = self.position
        S[3:7] = self.quaternion
        S[7] = self.get_phase(self.sim.time)
        S[8:11] = self.transVel * Tp / L
        S[11:14] = self.angVel * Tp
        S[14] = cf.lastCurv
        S[15] = cf.oldrCurv
        # reference quirk kept for parity: upper/lower sensors are swapped
        # when sampled (main.cpp:15917-15919)
        locs = cf.sensorLocation
        for i, j in ((0, 0), (1, 2), (2, 1)):
            S[16 + 3 * i : 19 + 3 * i] = self.get_shear(locs[3 * j : 3 * j + 3]) * (
                Tp / L
            )
        return S

    def get_shear(self, pos: np.ndarray) -> np.ndarray:
        """Viscous traction nu (grad u + grad u^T) . n_hat at a point, with
        n_hat the outward body normal from -grad(chi).

        Dense-field equivalent of the reference's nearest-surface-point
        viscous force lookup (getShear, main.cpp:15933-15981).
        """
        s = self.sim
        grid = s.grid
        pos = np.asarray(pos, np.float64)
        if self._is_blocks:
            # holding leaf, finest level first (holdingBlockID,
            # main.cpp:15933-15981); sample the 4^3 patch inside the block,
            # clamped to its interior (sensors sit on the body surface whose
            # blocks are at the finest level, so the clamp is <= 1 cell)
            bs = grid.bs
            slot = -1
            for l in range(grid.tree.cfg.level_max - 1, -1, -1):
                hl = grid.h0 / (1 << l)
                bpos = np.floor(pos / (bs * hl)).astype(int)
                n = grid.tree.blocks_per_dim(l)
                if np.any(bpos < 0) or np.any(bpos >= np.asarray(n)):
                    continue
                sl = grid._slot_maps[l][tuple(bpos)]
                if sl >= 0:
                    slot, h = int(sl), hl
                    bcell0 = bpos * bs
                    break
            if slot < 0:
                return np.zeros(3)
            gidx = np.floor(pos / h - 0.5).astype(int)
            lidx = np.clip(gidx - bcell0, 1, bs - 3)
            idx = bcell0 + lidx
            patch_v = jax.lax.dynamic_slice(
                s.state["vel"][slot], tuple(lidx - 1) + (0,), (4, 4, 4, 3)
            )
            patch_c = jax.lax.dynamic_slice(
                s.state["chi"][slot], tuple(lidx - 1), (4, 4, 4)
            )
        else:
            h = grid.h
            idx = np.clip(
                np.floor(pos / h - 0.5).astype(int), 1,
                np.asarray(grid.shape) - 3,
            )
            patch_v = jax.lax.dynamic_slice(
                s.state["vel"], tuple(idx - 1) + (0,), (4, 4, 4, 3)
            )
            patch_c = jax.lax.dynamic_slice(s.state["chi"], tuple(idx - 1), (4, 4, 4))
        pv = np.asarray(patch_v, np.float64)
        pc = np.asarray(patch_c, np.float64)
        # centered gradients on the 2x2x2 interior of the patch
        gv = np.stack(np.gradient(pv, h, axis=(0, 1, 2)), axis=-1)[1:3, 1:3, 1:3]
        gc = np.stack(np.gradient(pc, h, axis=(0, 1, 2)), axis=-1)[1:3, 1:3, 1:3]
        # trilinear weights of pos within the interior cell corners
        frac = np.asarray(pos) / h - 0.5 - idx
        w = np.ones((2, 2, 2))
        for ax in range(3):
            t = np.clip(frac[ax], 0.0, 1.0)
            shape = [1, 1, 1]
            shape[ax] = 2
            w = w * np.array([1 - t, t]).reshape(shape)
        gv_p = np.einsum("xyz,xyzcd->cd", w, gv)  # d u_c / d x_d
        gc_p = np.einsum("xyz,xyzd->d", w, gc)
        n = -gc_p / (np.linalg.norm(gc_p) + 1e-21)
        return s.nu * (gv_p + gv_p.T) @ n

    def save_midline(self, step_id: int, filename: str = "fish") -> None:
        """writeMidline2File (main.cpp:8116-8146)."""
        cf = self.myFish
        rows = "\n".join(
            f"{cf.rS[i]:g} {cf.r[i,0]:g} {cf.r[i,1]:g} {cf.r[i,2]:g} "
            f"{cf.v[i,0]:g} {cf.v[i,1]:g} {cf.v[i,2]:g}"
            for i in range(cf.Nm)
        )
        self.sim.logger.write(
            f"{filename}_midline_{step_id:07d}.txt", "s x y z vX vY vZ\n" + rows + "\n"
        )
