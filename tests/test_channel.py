"""The configuration ``channel180`` and its cell ``channel180.scan``, here
on the CPU at the configuration's own ``rehearse`` size (64 x 32 x 48
cells, ``-bpdx 8 -bpdy 4 -bpdz 6 -extent 4``): the walled, forced
channel of the uniform driver against the benchmark's plain reference
(``benchmarks/lib/reference_channel.py``), through the cell's own
adapter (``benchmarks/grids/channel.py``) and checks
(``checks/one_step.py`` on the per-step path, ``checks/scan_chain_free
.py`` on the scan), held to the limits of the cell's traffic file.

Two drivers, each built as ``run.py`` builds it: one on the per-step path
(``pipelined 0``: FixMassFlux as its own operator, its read of the bulk
velocity through the ``flux-read`` seam) and one on the scan megaloop
(the cell's flags: FixMassFlux inside the scan).  The reference keeping
its stages in bfloat16, and references with a wall's fault planted in
them, put in the program's place, have to fail.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import compare, drive, seeding, spec
from cup3d_tpu.__main__ import build_driver
from cup3d_tpu.config import parse_args
from cup3d_tpu.grid.uniform import BC, UniformGrid
from cup3d_tpu.obs import metrics as obs
from cup3d_tpu.sim import megaloop as ml
from cup3d_tpu.sim import operators as ops
from cup3d_tpu.utils.flows import turbulent_channel

SEED = 4100000013
SHAPE = (64, 32, 48)


@pytest.fixture(scope="module")
def cell():
    bench = spec.load_benchmark()
    _, config, traffic = spec.load_cell(bench, "channel180.scan")
    config = {**config, **config["rehearse"]}
    traffic = {**traffic, **traffic["rehearse"]}
    assert config["driver"]["cells"] == list(SHAPE)
    assert traffic["flags"] == {"pipelined": 1, "scan_k": 8}
    return {"bench": bench, "config": config, "traffic": traffic,
            "grid": spec.load_grid(bench, config["driver"]["kind"])}


def built(cell, traffic, workdir):
    driver = build_driver(seeding.build_argv(cell["config"], traffic, SEED,
                                             str(workdir)))
    spans = drive.Spans()
    drive.wrap_spans(driver, traffic["spans"], spans, cell["grid"].cells)
    driver.init()
    return driver, spans


def judged(cell, links):
    """Per link the numbers of the sound program; for the last link (the
    timed unit's own product) those of the bfloat16 control and of each
    fault of the channel's grid, each put in the program's place."""
    grid, phys = cell["grid"], cell["config"]["physics"]
    sound = []
    for pre, post in links:
        r = compare.reference_step(grid, pre, post, phys)
        sound.append(compare.link_numbers(grid, pre, post, phys, r))
    bad = {"control": compare.control_link(grid, pre, post, phys)}
    for name, fault in grid.FAULTS.items():
        bad[name] = compare.control_link(
            grid, pre, post, phys, on=grid.Reference(post, **fault))
    return {"sound": sound, "post": post,
            "bad": {k: compare.link_numbers(grid, pre, b, phys, r)
                    for k, b in bad.items()},
            "facts": compare.guarantees(grid, post)}


def step_path(cell, workdir):
    traffic = {**cell["traffic"], "flags": {"pipelined": 0, "scan_k": 0},
               "warmup_steps": 2}
    driver, spans = built(cell, traffic, workdir)
    drive.run_steps(driver, traffic["warmup_steps"])
    obs0 = obs.snapshot()
    links, extra = spec.load_check(cell["bench"], "one_step").links(
        driver, cell["grid"], traffic, cell["config"], spans, SEED)
    assert extra == {} and driver._scan_k == 0
    return {**judged(cell, links), "obs": obs.delta(obs0)}


def scan_path(cell, workdir):
    traffic = cell["traffic"]
    driver, spans = built(cell, traffic, workdir)
    drive.run_steps(driver, traffic["warmup_steps"])
    obs0 = obs.snapshot()
    links, extra = spec.load_check(cell["bench"], "scan_chain_free").links(
        driver, cell["grid"], traffic, cell["config"], spans, SEED)
    rows = [r for r in spans.rows if r[0] == "advance_megaloop"]
    return {**judged(cell, links), "extra": extra, "unit": obs.delta(obs0),
            "steps_through_span": sum(r[3] for r in rows),
            "steps": int(driver.sim.step)}


@pytest.fixture(scope="module")
def paths(cell, tmp_path_factory):
    """``paths(name)``: what the driver on that path gave, built when a
    test first asks for it."""
    @functools.cache
    def get(name):
        return {"step": step_path, "scan": scan_path}[name](
            cell, tmp_path_factory.mktemp(name))

    return get


def over(numbers, limits):
    return {k: v for k, v in numbers.items() if not v <= limits[k]}


@pytest.mark.parametrize("path", ["step", "scan"])
def test_the_channel_agrees_with_the_reference(cell, paths, path):
    got, limits = paths(path), cell["traffic"]["limits"]
    assert len(got["sound"]) == (1 if path == "step" else 2)
    for numbers in got["sound"]:
        assert set(numbers) == {"vel_step_gap", "vel_step_gap_max",
                                "poisson_resid"}
        assert not over(numbers, limits), numbers
    ok, facts = got["facts"]
    assert ok and facts["cells_compared"] == np.prod(SHAPE)
    if path == "scan":
        assert got["extra"]["scan_chain_gap"] <= limits["scan_chain_gap"]
        assert got["extra"]["bulk_velocity_gap"] \
            <= limits["bulk_velocity_gap"]


@pytest.mark.parametrize("fault", ["control", "y_periodic", "no_flux",
                                   "ghost_copy"])
@pytest.mark.parametrize("path", ["step", "scan"])
def test_a_planted_fault_fails_a_limit(cell, paths, path, fault):
    """The bfloat16 control, the walls made periodic, the flux correction
    left out, the wall's velocity ghosts copied and not negated: each put
    in the program's place fails at least one limit."""
    numbers = paths(path)["bad"][fault]
    assert over(numbers, cell["traffic"]["limits"]), numbers


def test_a_run_of_the_cell_takes_the_scan_and_holds_the_bulk(cell, paths):
    scan = paths("scan")
    k = cell["traffic"]["check_unit_steps"]
    assert scan["steps_through_span"] == scan["steps"]
    assert scan["unit"]["megaloop.dispatches"] == 1
    assert scan["unit"]["operators.flux_scan_steps"] == k
    assert scan["unit"].get("operators.flux_host_steps", 0) == 0
    u = np.asarray(scan["post"]["vel"], np.float64)
    assert abs(u[..., 0].mean() - 1.0) < 1e-6


def test_one_per_step_call_counts_one_host_flux_step_and_one_read(paths):
    unit = paths("step")["obs"]
    assert unit["operators.flux_host_steps"] == 1
    assert unit.get("operators.flux_scan_steps", 0) == 0
    assert unit["transfers.sanctioned{site=flux-read}"] == 1


@pytest.mark.parametrize("path", ["step", "scan"])
def test_each_walled_solve_is_counted_as_an_increment_solve(cell, paths,
                                                             path):
    """The walled solve takes the increment entry on both paths: once for
    the per-step call, ``check_unit_steps`` times for the dispatch."""
    got = paths(path)
    moved = got["obs" if path == "step" else "unit"]
    want = 1 if path == "step" else cell["traffic"]["check_unit_steps"]
    assert moved["poisson.increment_solves"] == want
    assert not moved.get("poisson.composed_solves")
    read = spec.load_reader(cell["bench"], "poisson.increment_share").read
    assert read({"obs": moved}) == 100.0


def _small(tmp_path, **over):
    flags = {"bpdx": 2, "bpdy": 2, "bpdz": 2, "extent": 1, "BC_y": "wall",
             "nu": 1e-2, "uMax_forced": 1.5, "bFixMassFlux": 1,
             "initCond": "turbulentChannel", "rampup": 0, "CFL": 0.4,
             "tend": 0, "verbose": 0, "poissonSolver": "iterative",
             "path4serialization": tmp_path, **over}
    return build_driver([t for k, v in flags.items()
                         for t in ("-" + k, str(v))])


def test_the_scan_step_is_the_per_step_path_s_step(tmp_path):
    """One forced step of make_tgv_step and one of the per-step operators
    from the same state with the same dt: the same step to float32
    rounding; the bulk velocity holds its target after both."""
    sim = _small(tmp_path)
    sim.init()
    s = sim.sim
    carry = ml.init_tgv_carry(s)
    start = {k: jnp.copy(s.state[k]) for k in ("vel", "p")}
    vel0 = np.asarray(start["vel"], np.float64)
    step = jax.jit(ml.make_tgv_step(s))
    out, row = step(carry, jnp.asarray(0.4, s.dtype))
    row = np.asarray(row, np.float64)
    assert row.shape == (ml.tgv_row_width(s.cfg),) == (ml.TGV_ROW + 1,)
    dt = float(row[-2])
    s.state.update(start)
    sim.advance(dt)
    vel_scan = np.asarray(out["vel"], np.float64)
    vel_step = np.asarray(s.state["vel"], np.float64)
    change = vel_step - vel0
    assert np.linalg.norm(vel_scan - vel_step) \
        <= 1e-5 * np.linalg.norm(change)
    for vel in (vel_scan, vel_step):
        assert abs(vel[..., 0].mean() - 1.0) < 1e-6
    # the row's bulk is the one measured before the correction, as the
    # per-step path writes it to flux.txt
    s.logger.flush()
    logged = float(open(tmp_path / "flux.txt").read().split()[2])
    assert row[ml.TGV_BULK] == pytest.approx(logged, rel=1e-6)


def test_a_k2_dispatch_counts_its_forced_steps(tmp_path):
    sim = _small(tmp_path, pipelined=1, scan_k=2, nsteps=2)
    sim.init()
    assert sim._scan_k == 2
    obs0 = obs.snapshot()
    sim.simulate()
    unit = obs.delta(obs0)
    assert unit["megaloop.dispatches"] == 1
    assert unit["operators.flux_scan_steps"] == 2
    assert unit.get("operators.flux_host_steps", 0) == 0
    assert unit["poisson.increment_solves"] == 2
    lines = open(tmp_path / "flux.txt").read().splitlines()
    assert [int(line.split()[0]) for line in lines] == [0, 1]


def test_the_x_slab_scan_refuses_a_forced_flow(tmp_path):
    sim = _small(tmp_path)
    sim.init()
    with pytest.raises(NotImplementedError, match="forcing"):
        ml.make_tgv_step_sharded(sim.sim)


def test_a_flow_that_is_not_forced_keeps_its_row(tmp_path):
    sim = _small(tmp_path, initCond="taylorGreen", uMax_forced=0,
                 bFixMassFlux=0, BC_y="periodic")
    sim.init()
    _, row = jax.jit(ml.make_tgv_step(sim.sim))(
        ml.init_tgv_carry(sim.sim), jnp.asarray(0.4, sim.sim.dtype))
    assert row.shape == (ml.TGV_ROW,) and ml.tgv_row_width(sim.cfg) == 5


def test_the_turbulent_start_is_seeded_walled_and_holds_the_bulk():
    grid = UniformGrid(SHAPE, (4.0, 2.0, 3.0),
                       (BC.periodic, BC.wall, BC.periodic))
    nu = 2.0 / 5600.0
    u = turbulent_channel(grid, 1.0, nu, seed=3)
    again = turbulent_channel(grid, 1.0, nu, seed=3)
    other = turbulent_channel(grid, 1.0, nu, seed=4)
    assert np.array_equal(np.asarray(u), np.asarray(again))
    assert not np.allclose(np.asarray(u), np.asarray(other))
    v = np.asarray(u, np.float64)
    assert abs(v[..., 0].mean() - 1.0) < 1e-6
    pert = v - v.mean(axis=(0, 2), keepdims=True)
    rms = np.sqrt(np.mean(np.square(pert)))
    assert 0.05 < rms < 0.15
    # zero on the wall faces: the face value is the mean of the edge
    # cell and its ghost, and the wall-normal velocity vanishes there
    padded = np.asarray(grid.pad_vector(u, 1), np.float64)
    for face in (padded[1:-1, 0:2, 1:-1], padded[1:-1, -2:, 1:-1]):
        assert np.abs(face.mean(axis=1)).max() < 1e-6
    assert np.abs(v[:, (0, -1), :, 1]).max() \
        < 0.05 * np.abs(v[..., 1]).max()


def test_the_cli_takes_the_channel_s_flags():
    cfg = parse_args(["-initCond", "turbulentChannel", "-initSeed", "7",
                      "-BC_y", "wall", "-bFixMassFlux", "1"])
    assert (cfg.initCond, cfg.initSeed, cfg.bc[1]) == (
        "turbulentChannel", 7, "wall")
    assert ops.forced(cfg) and ops.bulk_target(cfg) == 0.0
