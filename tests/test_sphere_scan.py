"""A rigid body inside the uniform driver's scan megaloop: the towed
Sphere of the configuration ``sphere300`` (``benchmarks/configs/
sphere300.json``) in a box with free-space faces on all three axes.

- The K=4 scan against four per-step steps taken with the scan's own dt,
  from the same start: velocity, pressure, chi, the rigid row, the
  penalisation force and the probe's forces.
- The program against the benchmark's plain reference
  (``benchmarks/lib/reference_sphere.py``, its own chi from the sphere's
  analytic distance) on one step from a seeded perturbed field, through
  the cell's adapter (``benchmarks/grids/freespace.py``) and check
  (``checks/scan_chain_body.py``), on every number of the check.
- The reference's exact all-Neumann solve against manufactured
  solutions.
- The scan's gate, the counters of both paths, the x-slab body's
  refusal of a body that is not a fish.
- The fish's scan body unchanged by the body-generic stage: the lowered
  HLO of the 32^3 fish megaloop by opcode and shape is the one recorded
  from the program before it (``tests/data/fish32_megaloop_hlo.json``).
"""

import collections
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.checks import scan_chain_body as check
from benchmarks.lib import compare, reference_sphere as rs, spec
from cup3d_tpu.__main__ import build_driver
from cup3d_tpu.models.base import unpack_forces
from cup3d_tpu.obs import metrics as obs
from cup3d_tpu.sim import megaloop as ml
from cup3d_tpu.sim.simulation import Simulation
from tests._cases import fish_cfg

BODY = ("Sphere L=0.2 xpos={x} ypos=0.5 zpos=0.5 xvel=-1.0 "
        "bForcedInSimFrame=1 bFixFrameOfRef=1")
HERE = os.path.dirname(os.path.abspath(__file__))


def driver(tmp_path, body=BODY.format(x=0.6), bpd=(6, 3, 3), **over):
    """The configuration's flags on a box of ``bpd`` blocks of 8^3
    (extent 2: 48 x 24 x 24 cells, D/h 4.8, by default)."""
    flags = {"bpdx": bpd[0], "bpdy": bpd[1], "bpdz": bpd[2], "extent": 2,
             "levelMax": 1, "levelStart": 0, "BC_x": "freespace",
             "BC_y": "freespace", "BC_z": "freespace",
             "nu": 6.666666666666667e-4, "CFL": 0.4, "rampup": 0,
             "poissonSolver": "iterative", "poissonTol": 1e-6,
             "poissonTolRel": 1e-4, "tend": 0, "verbose": 0,
             "freqDiagnostics": 0, "tdump": 0, "pipelined": 1,
             "scan_k": 4, "nsteps": 4, "path4serialization": tmp_path,
             **over}
    argv = [t for k, v in flags.items() for t in ("-" + k, str(v))]
    d = build_driver(argv + ["-factory-content", body])
    d.init()
    return d


def rel(a, b):
    a, b = (np.asarray(x, np.float64) for x in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope="module")
def scan_and_steps(tmp_path_factory):
    """One K=4 dispatch from the start of a scan driver, and four
    per-step steps of a per-step driver of the same flags, each with the
    dt the scan's row took."""
    scan = driver(tmp_path_factory.mktemp("scan"))
    assert scan._scan_k == 4
    s = scan.sim
    fn = ml.build_body_megaloop(s, s.obstacles[0])
    cfl = jnp.asarray([0.4] * 4, s.dtype)
    carry, rows = fn(ml.init_body_carry(s, s.obstacles[0]), cfl)
    rows = np.asarray(rows, np.float64)
    assert rows.shape == (4, ml.FISH_ROW)
    step = driver(tmp_path_factory.mktemp("step"), pipelined=0, scan_k=0)
    assert step._scan_k == 0
    for k in range(4):
        step.advance(float(rows[k, 59]))
    return {"carry": carry, "rows": rows, "step": step, "scan": scan}


def test_the_scan_is_the_per_step_path_over_four_steps(scan_and_steps):
    """The same four steps on both paths: the fields to float32 rounding
    of four steps (the per-step sdf is the analytic one on every cell,
    the scan's on the sphere's window only, so chi agrees to rounding of
    the cell centres); the rigid row and the penalisation force to the
    same; the probe's forces to 1e-4, the solve's tolerance being where
    the two paths' pressures part."""
    carry, rows, step = (scan_and_steps[k] for k in ("carry", "rows",
                                                     "step"))
    s, ob = step.sim, step.sim.obstacles[0]
    for k in ("vel", "p", "chi"):
        assert rel(carry[k], s.state[k]) < 1e-5, k
    assert float(jnp.abs(carry["udef"]).max()) == 0.0
    last = rows[-1]
    np.testing.assert_allclose(last[6:9], ob.position, rtol=0, atol=1e-6)
    np.testing.assert_allclose(last[0:3], ob.transVel, rtol=0, atol=1e-7)
    np.testing.assert_allclose(last[12:15], ob.centerOfMass, rtol=0,
                               atol=1e-6)
    assert np.all(last[54:58] == 0.0)  # a rigid body has no shape state
    assert rel(last[29:32], ob.penal_force) < 1e-4
    forces = unpack_forces(last[35:52])
    assert rel(forces["pres_force"] + forces["visc_force"], ob.force) < 1e-4
    # towed at U with the frame on it: the body stays where it is, and
    # the stream drags it downstream
    np.testing.assert_allclose(last[6:9], [0.6, 0.5, 0.5], atol=1e-6)
    assert ob.force[0] > 0.0 and last[29] > 0.0


def test_the_row_keeps_the_fish_s_layout_and_the_time_chain(
        scan_and_steps):
    rows = scan_and_steps["rows"]
    np.testing.assert_allclose(np.cumsum(rows[:, 59]), rows[:, 60],
                               rtol=1e-6)
    assert np.all(rows[:, 53] >= 1)  # iterations of each step's solve
    assert np.all(rows[:, 58] > 0.9)  # umax: the stream past the body


@pytest.fixture(scope="module")
def cell():
    bench = spec.load_benchmark()
    _, config, traffic = spec.load_cell(bench, "sphere300.scan")
    return {"config": {**config, **config["rehearse"]},
            "traffic": {**traffic, **traffic["rehearse"]},
            "grid": spec.load_grid(bench, "freespace")}


@pytest.fixture(scope="module")
def perturbed_link(cell, tmp_path_factory):
    """One scan step from a seeded perturbed field at the rehearse size,
    captured as the check captures a link."""
    d = driver(tmp_path_factory.mktemp("link"), bpd=(8, 4, 4))
    s, ob = d.sim, d.sim.obstacles[0]
    rng = np.random.default_rng(4300000013)
    noise = rng.standard_normal(s.state["vel"].shape)
    for axis in range(3):  # a few cells of correlation
        for _ in range(3):
            noise = (noise + np.roll(noise, 1, axis)
                     + np.roll(noise, -1, axis)) / 3.0
    vel = 0.3 * noise / noise.std()
    s.state["vel"] = jnp.asarray(vel, s.dtype)
    carry = ml.init_body_carry(s, ob)
    pre = check._host(carry)
    one = jax.jit(ml.make_body_step(s, ob))
    out, row = one({}, carry, jnp.asarray(0.4, s.dtype))
    post = check._host(out)
    grid = cell["grid"]
    geom = grid.geometry(d, cell["config"])
    (shape,) = [b["shape"] for b in cell["config"]["bodies"]]
    return check._link(pre, post, np.asarray(row), geom, shape,
                       np.asarray(s.uinf))


def numbers(grid, pre, post, phys, r):
    return {**compare.link_numbers(grid, pre, post, phys, r),
            **grid.body_numbers(pre, post, phys, r)}


def test_one_step_agrees_with_the_reference(cell, perturbed_link):
    """Every number of the check within the cell's limits: the program's
    chi against the reference's own (the rasteriser checked on its own),
    the forced rigid update, the penalisation force, the free-space
    advection and the all-Neumann solve."""
    grid, phys = cell["grid"], cell["config"]["physics"]
    pre, post = perturbed_link
    r = compare.reference_step(grid, pre, post, phys)
    got = numbers(grid, pre, post, phys, r)
    limits = cell["traffic"]["limits"]
    assert set(got) | {"scan_chain_gap"} == set(limits)
    over = {k: v for k, v in got.items() if not v <= limits[k]}
    assert not over, got
    # the stream past the body moves it nowhere: the rigid update's
    # centre is where chi puts it, within rounding
    assert got["rigid_cm_gap_h"] < 1e-4 and got["chi_gap"] < 1e-6


@pytest.mark.parametrize("fault", ["control", "periodic", "ghost_copy",
                                   "chi_off", "uinf_flipped", "altered"])
def test_a_planted_fault_fails_a_limit(cell, perturbed_link, fault):
    """The reference keeping its stages in bfloat16, or with a fault of
    the free-space box planted in it, put in the program's place; or the
    program's velocity times 1.001: each fails at least one limit."""
    grid, phys = cell["grid"], cell["config"]["physics"]
    pre, post = perturbed_link
    r = compare.reference_step(grid, pre, post, phys)
    if fault == "altered":
        bad = {**post, "vel": np.asarray(post["vel"], np.float64) * 1.001}
    else:
        store = (compare.bf16_store if fault == "control" else
                 (lambda x: np.asarray(x, np.float32).astype(np.float64)))
        on = None if fault == "control" else grid.Reference(
            post, **grid.FAULTS[fault])
        bad = grid.stand_in(post, compare.reference_step(
            grid, pre, post, phys, store=store, on=on), store)
    got = numbers(grid, pre, bad, phys, r)
    limits = cell["traffic"]["limits"]
    assert {k: v for k, v in got.items() if not v <= limits[k]}, got


@pytest.mark.parametrize("modes", [(1, 0, 0), (2, 3, 1), (5, 1, 4)])
def test_the_reference_solve_is_exact_on_neumann_modes(modes):
    """A product of cosines cos(pi m (i + 1/2) / n) is an eigenvector of
    the 7-point Laplacian with zero-gradient ghosts: the solve gives it
    back, and on a seeded right-hand side leaves the residual at
    rounding once its mean (the part no pressure balances) is out."""
    shape, h = (16, 12, 10), 0.1
    box = rs.FreeSpace(h)
    axes = [np.cos(np.pi * m * (np.arange(n) + 0.5) / n)
            for m, n in zip(modes, shape)]
    p = axes[0][:, None, None] * axes[1][None, :, None] * axes[2][None, None]
    lam = sum((2.0 * np.cos(np.pi * m / n) - 2.0) / h ** 2
              for m, n in zip(modes, shape))
    np.testing.assert_allclose(box.laplacian(p), lam * p, atol=1e-9)
    np.testing.assert_allclose(box.poisson(lam * p), p, atol=1e-12)
    rhs = np.random.default_rng(7).standard_normal(shape)
    x = box.poisson(rhs)
    assert abs(x.mean()) < 1e-12
    np.testing.assert_allclose(box.laplacian(x), rhs - rhs.mean(),
                               atol=1e-9)


def test_the_reference_s_chi_holds_the_sphere():
    """The Towers chi of the analytic distance: 1 deep inside, 0 far
    out, and the sphere's volume to the band's rounding at D/h 16."""
    h, radius = 1.0 / 32, 0.25
    box, _, chi = rs.sphere_chi((40, 40, 40), h, np.array([0.6, 0.6, 0.6]),
                                radius)
    assert chi.max() == 1.0 and chi.min() == 0.0
    exact = rs.sphere_volume(radius)
    assert abs(chi.sum() * h ** 3 - exact) / exact < 2e-3


def test_the_scan_takes_a_sphere_and_a_steady_fish(tmp_path,
                                                   scan_and_steps):
    sphere = scan_and_steps["scan"]
    assert sphere._megaloop_eligible() and sphere._scan_k == 4
    fish = Simulation(fish_cfg(tmp_path / "fish", scan_k=8))
    fish.init()
    assert fish._megaloop_eligible() and fish._scan_k == 8


@pytest.mark.parametrize("case", ["naca", "two_bodies", "forced_flow"])
def test_the_scan_refuses_what_it_cannot_run(tmp_path, case):
    """A Naca runs per step (its shape has no scan stage), two bodies and
    a forced flow with a body too; a pipelined run refuses two bodies at
    init, so that rule is asked of the gate directly."""
    if case == "naca":
        d = driver(tmp_path, body="Naca L=0.2 xpos=0.6 ypos=0.5 zpos=0.5")
    elif case == "forced_flow":
        d = driver(tmp_path, bFixMassFlux=1, uMax_forced=1.0)
    else:
        d = driver(tmp_path, body="\n".join(
            [BODY.format(x=0.5), BODY.format(x=1.2)]), pipelined=0)
        d.cfg.pipelined = True
    assert not d._megaloop_eligible()
    assert case == "two_bodies" or d._scan_k == 0


def test_both_paths_count_their_rigid_steps(tmp_path, scan_and_steps):
    """``operators.rigid_host_steps`` once per per-step CreateObstacles of
    a body with no midline, ``operators.rigid_scan_steps`` ``scan_k``
    times a dispatch; the benchmark's reader makes the share of them."""
    read = spec.load_reader(spec.load_benchmark(),
                            "operators.rigid_scan_share").read
    step = scan_and_steps["step"]
    obs0 = obs.snapshot()
    step.advance(step.calc_max_timestep())
    unit = obs.delta(obs0)
    assert unit["operators.rigid_host_steps"] == 1
    assert not unit.get("operators.rigid_scan_steps")
    assert read({"obs": unit}) == 0.0
    scan = driver(tmp_path / "scan", scan_k=2, nsteps=2)
    obs0 = obs.snapshot()
    scan.simulate()
    unit = obs.delta(obs0)
    assert unit["megaloop.dispatches"] == 1
    assert unit["operators.rigid_scan_steps"] == 2
    assert not unit.get("operators.rigid_host_steps")
    assert not unit.get("operators.raster_cells")  # the fish's counter
    assert read({"obs": unit}) == 100.0
    assert read({"obs": {}}) is None


def test_the_x_slab_scan_refuses_a_body_that_is_not_a_fish(tmp_path):
    d = driver(tmp_path)
    with pytest.raises(NotImplementedError, match="StefanFish"):
        ml.make_fish_step_sharded(d.sim, d.sim.obstacles[0])


def hlo_by_opcode_and_shape(text):
    """Count of (opcode, result shape) over a lowered HLO module's
    instructions, layouts and names left out."""
    pat = re.compile(r"^\s*(?:ROOT )?\S+ = (.+?) ([a-z][\w-]*)\(")
    out = collections.Counter()
    for line in text.splitlines():
        m = pat.match(line)
        if m:
            out[m.group(2) + " " + re.sub(r"\{[^}]*\}", "",
                                          m.group(1))] += 1
    return dict(out)


def test_the_fish_megaloop_is_the_one_before_the_body_stage(tmp_path):
    sim = Simulation(fish_cfg(tmp_path, poissonSolver="iterative",
                              poissonTol=1e-6, poissonTolRel=1e-4))
    sim.init()
    s, ob = sim.sim, sim.sim.obstacles[0]
    fn = ml.build_body_megaloop(s, ob)
    text = fn.lower(ml.init_body_carry(s, ob),
                    jnp.full((8,), 0.3, s.dtype)).as_text(dialect="hlo")
    with open(os.path.join(HERE, "data", "fish32_megaloop_hlo.json")) as f:
        want = json.load(f)
    assert hlo_by_opcode_and_shape(text) == want
