"""Fish rasterization + StefanFish end-to-end (reference PutFishOnBlocks,
StefanFish; main.cpp:11350-11739, 15668-15981)."""

from functools import lru_cache

import jax
import jax.numpy as jnp

import pytest
import numpy as np

from cup3d_tpu.config import SimulationConfig
from cup3d_tpu.models.base import quat_to_rot
from cup3d_tpu.models.fish.curvature import CurvatureDefinedFishData
from cup3d_tpu.models.fish.rasterize import (
    RasterBox,
    _segment,
    _segment_distance,
    raster_box,
    raster_work,
    rasterize_midline,
    rasterize_points,
)
from cup3d_tpu.models.fish.shapes import compute_widths_heights
from cup3d_tpu.obs import metrics as M
from cup3d_tpu.ops.chi import towers_chi
from cup3d_tpu.sim.simulation import Simulation
from tests._cases import fish_cfg


def _tube_midline(nm=64, length=0.5, radius=0.06, dtype=np.float32):
    """Straight midline along x with constant circular cross-section."""
    s = np.linspace(0, length, nm)
    z = np.zeros((nm, 3))
    mid = {
        "r": np.stack([s, np.zeros(nm), np.zeros(nm)], 1),
        "v": z.copy(),
        "nor": np.tile([0.0, 1.0, 0.0], (nm, 1)),
        "vnor": z.copy(),
        "bin": np.tile([0.0, 0.0, 1.0], (nm, 1)),
        "vbin": z.copy(),
        "width": np.full(nm, radius),
        "height": np.full(nm, radius),
    }
    return {k: jnp.asarray(v, dtype) for k, v in mid.items()}


def test_rasterize_cylinder_sdf():
    n, h = 48, 1.0 / 48
    mid = _tube_midline()
    origin = jnp.zeros(3, jnp.float32)
    pos = jnp.array([0.25, 0.5, 0.5], jnp.float32)  # tube spans x in [.25,.75]
    rot = jnp.eye(3, dtype=jnp.float32)
    sdf, udef = rasterize_midline(origin, h, (n, n, n),
                                  RasterBox((n, n, n), 1), mid, pos, rot)
    sdf = np.asarray(sdf)
    x = (np.arange(n) + 0.5) * h
    X, Y, Z = np.meshgrid(x, x, x, indexing="ij")
    r_yz = np.hypot(Y - 0.5, Z - 0.5)
    interior = (X > 0.3) & (X < 0.7)
    inside = interior & (r_yz < 0.06 - 2 * h)
    outside = (r_yz > 0.06 + 2 * h) | (X < 0.2) | (X > 0.8)
    assert np.all(sdf[inside] > 0)
    assert np.all(sdf[outside] < 0)
    # sdf approximates radial distance in the smooth mid-tube region
    band = interior & (np.abs(r_yz - 0.06) < 1.5 * h)
    err = np.abs(sdf[band] - (0.06 - r_yz[band]))
    assert np.max(err) < 0.5 * h
    assert np.all(np.asarray(udef) == 0)


def test_rasterize_udef_rotating_section():
    """A midline translating in +y must produce udef_y = vY everywhere
    inside."""
    n, h = 32, 1.0 / 32
    mid = _tube_midline(dtype=np.float32)
    mid = dict(mid)
    mid["v"] = jnp.tile(jnp.asarray([0.0, 0.3, 0.0], jnp.float32), (64, 1))
    origin = jnp.zeros(3, jnp.float32)
    pos = jnp.array([0.25, 0.5, 0.5], jnp.float32)
    rot = jnp.eye(3, dtype=jnp.float32)
    sdf, udef = rasterize_midline(origin, h, (n, n, n),
                                  RasterBox((n, n, n), 1), mid, pos, rot)
    inside = np.asarray(sdf) > 0
    uy = np.asarray(udef)[..., 1][inside]
    assert np.allclose(uy, 0.3, atol=1e-5)


def _fish_sim(n=48, tend=0.0, nsteps=3, correct=False):
    extra = " CorrectPosition=1 CorrectPositionZ=1" if correct else ""
    cfg = SimulationConfig(
        bpdx=1, bpdy=1, bpdz=1, levelMax=1, levelStart=0,
        block_size=n, extent=1.0, CFL=0.3, nu=1e-4, tend=tend, nsteps=nsteps,
        factory_content=f"stefanfish L=0.3 T=1.0 xpos=0.5{extra}",
        verbose=False, freqDiagnostics=1, dtype="float32",
    )
    s = Simulation(cfg)
    s.init()
    return s


@pytest.mark.slow
def test_stefanfish_swims():
    sim = _fish_sim(n=48, nsteps=6)
    fish = sim.sim.obstacles[0]
    # chi is a sensible body fraction: fish volume ~ 1e-3 of the domain
    sim.advance(1e-3)
    chi_vol = float(jnp.sum(sim.sim.state["chi"])) / 48**3
    assert 1e-5 < chi_vol < 0.05
    sim.simulate()
    assert np.all(np.isfinite(np.asarray(sim.sim.state["vel"])))
    # the undulating body must have picked up motion (any direction)
    assert np.linalg.norm(fish.transVel) > 1e-6
    assert np.isfinite(fish.transVel).all()


def test_stefanfish_rl_interface():
    sim = _fish_sim(n=32, nsteps=1)
    fish = sim.sim.obstacles[0]
    S = fish.state()
    assert S.shape == (25,)
    assert np.all(np.isfinite(S))
    assert 0 <= S[7] <= 2 * np.pi  # phase
    fish.act(0.5, [0.3])
    assert fish.myFish.lastCurv == 0.3
    fish.act(0.6, [0.2, 0.1, 0.0])  # curvature + period (+z-vel) action
    assert abs(fish.get_learn_t_period() - 1.1) < 1e-12
    sim.simulate()
    assert np.all(np.isfinite(np.asarray(sim.sim.state["vel"])))


def test_rasterize_degenerate_tips_far_field():
    """Regression: sections with width=height~0 (fish nose/tail tips) must
    not paint near-surface sdf far from the body.  The f/|grad f| ellipse
    distance both overflowed float32 at w=h=1e-10 (u/w^2 -> inf) and
    underestimates far-field distance for eccentric sections; far cells
    then carried |sdf| ~ h and chi banded the whole domain."""
    from cup3d_tpu.models.fish.rasterize import rasterize_points

    nm = 32
    s = np.linspace(0, 0.3, nm)
    taper = np.sin(np.pi * s / 0.3)  # exact zeros at both tips
    z = np.zeros((nm, 3))
    mid = {
        "r": jnp.asarray(np.stack([s, np.zeros(nm), np.zeros(nm)], 1), jnp.float32),
        "v": jnp.asarray(z, jnp.float32),
        "nor": jnp.asarray(np.tile([0.0, 1.0, 0.0], (nm, 1)), jnp.float32),
        "vnor": jnp.asarray(z, jnp.float32),
        "bin": jnp.asarray(np.tile([0.0, 0.0, 1.0], (nm, 1)), jnp.float32),
        "vbin": jnp.asarray(z, jnp.float32),
        # eccentric sections: thin width, taller height, hard-zero tips
        "width": jnp.asarray(0.002 * taper, jnp.float32),
        "height": jnp.asarray(0.04 * taper, jnp.float32),
    }
    rng = np.random.default_rng(0)
    pts = rng.uniform(-0.5, 0.8, (4096, 3)).astype(np.float32)
    pos = jnp.zeros(3, jnp.float32)
    rot = jnp.eye(3, dtype=jnp.float32)
    sdf, _ = rasterize_points(jnp.asarray(pts), mid, pos, rot)
    sdf = np.asarray(sdf)
    # true distance to the midline polyline (body is thinner than this)
    r = np.stack([s, np.zeros(nm), np.zeros(nm)], 1)
    td = np.min(
        np.linalg.norm(pts[:, None, :] - r[None], axis=-1), axis=1
    )
    far = td > 0.15
    assert far.sum() > 1000
    # every far point must be clearly outside: sdf <= -(dist - max height)
    assert float(sdf[far].max()) < -0.1
    # and the signed distance tracks the true distance in the far field
    err = np.abs(-sdf[far] - td[far])
    assert float(err.max()) < 0.05


# -- the boxed window against the full sweep --------------------------------

L_FISH = 0.4
MIDLINE_KEYS = ("r", "v", "nor", "vnor", "bin", "vbin", "width", "height")
# quaternions of the body frame: level, and tilted about an oblique axis
ROTATIONS = {"level": (1.0, 0.0, 0.0, 0.0),
             "tilted": (np.cos(0.35), 0.3 * np.sin(0.35),
                        0.5 * np.sin(0.35), np.sqrt(0.66) * np.sin(0.35))}
FISH_CASES = [(n, t, rot) for n in (48, 64) for t in (0.0, 0.35, 0.8)
              for rot in sorted(ROTATIONS)]


@lru_cache(maxsize=None)
def _fish_window(n, t, rot_name):
    """A StefanFish midline (danio/stefan, L=0.4) at gait phase ``t`` on
    an n^3 grid, off the cell centres, with the window and box its
    StefanFish would take: (args of rasterize_midline, box)."""
    h = 1.0 / n
    f = CurvatureDefinedFishData(L_FISH, 1.0, 0.0, h, 1.0)
    f.height, f.width = compute_widths_heights("danio", "stefan", L_FISH,
                                               f.rS)
    f.compute_midline(t, 1e-3)
    mid = {k: jnp.asarray(getattr(f, k), jnp.float32) for k in MIDLINE_KEYS}
    q = np.asarray(ROTATIONS[rot_name])
    rot = jnp.asarray(quat_to_rot(q / np.linalg.norm(q)), jnp.float32)
    pos = jnp.asarray([0.5012, 0.4991, 0.5023], jnp.float32)
    nw = int(np.ceil(1.25 * L_FISH / h)) + 8
    window = (nw, nw, nw)
    idx0 = np.floor((np.asarray(pos) - 0.5 * nw * h) / h).astype(int)
    origin = jnp.asarray(idx0 * h, jnp.float32)
    box = raster_box(f.width, f.height, f.rS, h, window)
    assert max(box.shape) < nw, "the box engages at this size"
    return (origin, jnp.asarray(h, jnp.float32), window, mid, pos, rot), box


def _raster(args, box):
    origin, h, window, mid, pos, rot = args
    return tuple(np.asarray(a) for a in rasterize_midline(
        origin, h, window, box, mid, pos, rot))


def _chi(sdf, h):
    """Towers chi of a window placed on a grid that holds -1 around it."""
    lab = jnp.pad(jnp.asarray(sdf), 1, constant_values=-1.0)
    return np.asarray(towers_chi(lab, float(h)))


@pytest.mark.parametrize("n, t, rot", FISH_CASES)
def test_boxed_window_matches_the_sweep(n, t, rot):
    """chi equal everywhere, sdf equal where sdf >= -4h, udef equal
    where chi > 0: what every consumer reads is the sweep's."""
    args, box = _fish_window(n, t, rot)
    h = float(args[1])
    window = args[2]
    sdf_s, udef_s = _raster(args, RasterBox(window, 1))
    sdf_b, udef_b = _raster(args, box)
    band = sdf_s >= -4 * h
    assert band.sum() > 500
    np.testing.assert_array_equal(sdf_b[band], sdf_s[band])
    assert float(sdf_b[~band].max()) < -4 * h
    chi_s, chi_b = _chi(sdf_s, h), _chi(sdf_b, h)
    assert chi_s.sum() > 10  # the body is on the window
    np.testing.assert_array_equal(chi_b, chi_s)
    body = chi_s > 0
    np.testing.assert_array_equal(udef_b[body], udef_s[body])


@pytest.mark.parametrize("n, t, rot", FISH_CASES)
def test_every_band_cell_lies_in_its_winners_box(n, t, rot):
    """Coverage: the segment that wins a cell with sweep sdf >= -4h (the
    first in index order at the least distance) belongs to a group whose
    box holds the cell, with the floor of the box's start to spare: the
    cell's centre is within half - 1/2 cells of the group's centre."""
    args, box = _fish_window(n, t, rot)
    origin, h, window, mid, pos, rot_m = args
    hf = float(h)
    idx = [np.arange(w, dtype=np.float32) for w in window]
    X, Y, Z = np.meshgrid(*idx, indexing="ij")
    p_comp = np.stack([float(origin[a]) + (c + 0.5) * hf
                       for a, c in enumerate((X, Y, Z))], -1)
    p = jnp.einsum("...c,cd->...d", jnp.asarray(p_comp) - pos, rot_m,
                   precision=jax.lax.Precision.HIGHEST)
    nm = mid["r"].shape[0]
    d = np.stack([np.asarray(_segment_distance(p, _segment(mid, s))[0])
                  for s in range(nm - 1)])
    winner = np.argmin(d, axis=0)
    band = -d.min(axis=0) >= -4 * hf
    k = box.group
    r = np.asarray(mid["r"], np.float64)
    first = (winner[band] // k) * k
    last = np.minimum(first + k, nm - 1)
    centre = np.asarray(pos, np.float64) + 0.5 * (
        r[first] + r[last]) @ np.asarray(rot_m, np.float64).T
    at = (centre - np.asarray(origin, np.float64)) / hf
    cells = np.stack([X[band], Y[band], Z[band]], -1) + 0.5
    reach = np.abs(cells - at).max(axis=0)
    half = np.asarray(box.shape) // 2
    assert np.all(reach <= half - 0.5), (reach, half)


@jax.jit
def _parent_window(origin, h, midline, position, rot, like):
    """The parent's ``rasterize_midline``: the window's cell centres, then
    every one against every segment (``rasterize_points``), one program."""
    axes = [jnp.arange(w, dtype=jnp.float32) for w in like.shape]
    X = origin[0] + (axes[0][:, None, None] + 0.5) * h
    Y = origin[1] + (axes[1][None, :, None] + 0.5) * h
    Z = origin[2] + (axes[2][None, None, :] + 0.5) * h
    p_comp = jnp.stack(jnp.broadcast_arrays(X, Y, Z), axis=-1)
    return rasterize_points(p_comp, midline, position, rot)


@pytest.mark.parametrize("n, t, rot", FISH_CASES[::3])
def test_box_equal_to_the_window_is_todays_sweep(n, t, rot):
    """A box as large as the window is the parent's window rasterizer
    (every cell of the window against every segment) bit for bit."""
    args, _ = _fish_window(n, t, rot)
    origin, h, window, mid, pos, rot_m = args
    sdf, udef = _raster(args, RasterBox(window, 1))
    sdf_p, udef_p = _parent_window(origin, h, mid, pos, rot_m,
                                   jnp.zeros(window, jnp.int8))
    np.testing.assert_array_equal(sdf, np.asarray(sdf_p))
    np.testing.assert_array_equal(udef, np.asarray(udef_p))


def test_two_vmap_lanes_equal_two_single_calls():
    """fleet/batch.py vmaps the scan body: the box's start becomes a
    per-lane gather/scatter and each lane keeps its own result."""
    lanes = [_fish_window(64, 0.35, "tilted"), _fish_window(64, 0.8, "level")]
    box = lanes[0][1]
    window = lanes[0][0][2]
    h = lanes[0][0][1]

    def stack(i):
        vals = [lane[0][i] for lane in lanes]
        if isinstance(vals[0], dict):
            return {k: jnp.stack([v[k] for v in vals]) for k in vals[0]}
        return jnp.stack(vals)

    batched = jax.vmap(lambda o, m, p, r: rasterize_midline(
        o, h, window, box, m, p, r))(stack(0), stack(3), stack(4), stack(5))
    for i, (args, _) in enumerate(lanes):
        sdf, udef = _raster(args, box)
        # the batched program fuses its own way: float32 rounding apart
        np.testing.assert_allclose(np.asarray(batched[0][i]), sdf,
                                   rtol=0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(batched[1][i]), udef,
                                   rtol=0, atol=1e-6 * np.abs(udef).max())


def test_raster_counters_per_step_call(tmp_path):
    """One per-step CreateObstacles raises operators.raster_cells by the
    boxed rasterizer's cells and operators.raster_sweep_cells by the
    window's sweep: the benchmark's operators.raster_work_share."""
    sim = _fish_sim(n=32, nsteps=2)
    ob = sim.sim.obstacles[0]
    before = M.snapshot()
    ob.create(sim.sim.time)
    moved = M.delta(before)
    cells, sweep = raster_work(ob.myFish.Nm, ob._window_shape,
                               ob._raster_box)
    assert cells < sweep
    assert moved["operators.raster_cells"] == cells
    assert moved["operators.raster_sweep_cells"] == sweep


def test_raster_counters_per_scan_dispatch(tmp_path):
    """A K-step scan dispatch rasterizes the body K times."""
    K = 2
    sim = Simulation(fish_cfg(tmp_path, scan_k=K, nsteps=K))
    sim.init()
    before = M.snapshot()
    sim.simulate()
    moved = M.delta(before)
    assert sim._scan_k == K and moved["megaloop.dispatches"] == 1
    ob = sim.sim.obstacles[0]
    cells, sweep = raster_work(ob.myFish.Nm, ob._window_shape,
                               ob._raster_box)
    assert moved["operators.raster_cells"] == K * cells
    assert moved["operators.raster_sweep_cells"] == K * sweep
