"""The configuration ``fish256`` and its cell ``fish256.scan``, here on the
CPU at the configuration's own ``rehearse`` size (64^3, ``bpd 8``: the
smallest grid of this case on which the body is wider than a cell): the
uniform fish driver against the benchmark's plain reference, through the
same adapter (``benchmarks/grids/uniform.py``), the same checks
(``benchmarks/checks/one_step.py``, ``scan_chain.py``) and the same
numbers (``compare.link_numbers``), held to the limits the cell's traffic
file states for a rehearsal.

Per seed two drivers, each built as ``run.py`` builds it
(``seeding.build_argv`` then ``build_driver``): one on the per-step path
(``pipelined 0``: three warm-up steps, since the body is at rest until
the third and a rigid velocity of zero has no relative gap, then one
checked step as ``checks/one_step.py`` takes it) and one on the scan
megaloop (the cell's own flags: one K=8 dispatch of warm-up, then the
checked dispatch and its chain).  The reference repeats each link in
float64; the same reference keeping its stages in bfloat16, put in the
program's place, has to fail.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import compare, drive, seeding, spec
from cup3d_tpu.__main__ import build_driver
from cup3d_tpu.grid.uniform import UniformGrid
from cup3d_tpu.models.base import momentum_integrals, store_force_qoi
from cup3d_tpu.obs import metrics as obs
from cup3d_tpu.ops.surface import obstacle_probe_budget
from tests._grids import assert_dots_highest

SEEDS = (3600000011, 17)
K = 8
N = 64


@pytest.fixture(scope="module")
def cell():
    bench = spec.load_benchmark()
    _, config, traffic = spec.load_cell(bench, "fish256.scan")
    config = {**config, **config["rehearse"]}
    traffic = {**traffic, **traffic["rehearse"]}
    assert config["driver"]["cells"] == [N, N, N]
    assert traffic["flags"] == {"pipelined": 1, "scan_k": K}
    return {"bench": bench, "config": config, "traffic": traffic,
            "grid": spec.load_grid(bench, config["driver"]["kind"])}


def built(cell, traffic, seed, workdir):
    driver = build_driver(seeding.build_argv(cell["config"], traffic, seed,
                                             str(workdir)))
    spans = drive.Spans()
    drive.wrap_spans(driver, traffic["spans"], spans, cell["grid"].cells)
    driver.init()
    return driver, spans


def judged(cell, links):
    """Per link the numbers of the sound program; for the last link (the
    timed unit's own product) those of the bfloat16 control beside it."""
    grid, phys = cell["grid"], cell["config"]["physics"]
    sound = []
    for pre, post in links:
        r = compare.reference_step(grid, pre, post, phys)
        sound.append(compare.link_numbers(grid, pre, post, phys, r))
    control = compare.link_numbers(
        grid, pre, compare.control_link(grid, pre, post, phys), phys, r)
    return {"sound": sound, "control": control,
            "facts": compare.guarantees(grid, post)}


def step_path(cell, seed, workdir):
    traffic = {**cell["traffic"], "flags": {"pipelined": 0, "scan_k": 0},
               "warmup_steps": 3}
    driver, spans = built(cell, traffic, seed, workdir)
    obs0 = obs.snapshot()
    drive.run_steps(driver, traffic["warmup_steps"])
    links, extra = spec.load_check(cell["bench"], "one_step").links(
        driver, cell["grid"], traffic, cell["config"], spans, seed)
    assert extra == {} and driver._scan_k == 0
    return {**judged(cell, links), "obs": obs.delta(obs0)}


def scan_path(cell, seed, workdir):
    traffic = cell["traffic"]
    driver, spans = built(cell, traffic, seed, workdir)
    obs0 = obs.snapshot()
    drive.run_steps(driver, traffic["warmup_steps"])
    warmup = obs.delta(obs0)
    links, extra = spec.load_check(cell["bench"], "scan_chain").links(
        driver, cell["grid"], traffic, cell["config"], spans, seed)
    rows = [r for r in spans.rows if r[0] == "advance_megaloop"]
    return {**judged(cell, links), "extra": extra, "warmup": warmup,
            "unit": obs.delta(obs0),
            "steps_through_span": sum(r[3] for r in rows),
            "steps": int(driver.sim.step)}


@pytest.fixture(scope="module", params=SEEDS)
def paths(request, cell, tmp_path_factory):
    """``paths(name)``: what one seed's driver on that path gave, built
    when a test first asks for it."""
    @functools.cache
    def get(name):
        return {"step": step_path, "scan": scan_path}[name](
            cell, request.param, tmp_path_factory.mktemp(name))

    return get


def over(numbers, limits):
    return {k: v for k, v in numbers.items() if not v <= limits[k]}


def test_one_step_of_the_per_step_path_agrees_with_the_reference(
        cell, paths):
    (numbers,) = paths("step")["sound"]
    assert set(numbers) == set(cell["traffic"]["limits"]) - {
        "scan_chain_gap"}
    assert not over(numbers, cell["traffic"]["limits"]), numbers
    ok, facts = paths("step")["facts"]
    assert ok and facts["cells_compared"] == N ** 3


def test_one_scan_dispatch_agrees_with_the_reference(cell, paths):
    scan, limits = paths("scan"), cell["traffic"]["limits"]
    # the chain's first link and its last, which ends on the dispatch's
    # own product: the chain has to reproduce it
    assert cell["traffic"]["check"] == {"kind": "scan_chain",
                                        "more_links": 0}
    assert len(scan["sound"]) == 2
    assert scan["extra"]["scan_chain_gap"] <= limits["scan_chain_gap"]
    for numbers in scan["sound"]:
        assert not over(numbers, limits), numbers
    ok, facts = scan["facts"]
    assert ok and facts["cells_compared"] == N ** 3
    # every step went through advance_megaloop, as run.py demands
    assert scan["steps_through_span"] == scan["steps"] == 2 * K


@pytest.mark.parametrize("path", ["step", "scan"])
def test_the_bfloat16_control_fails_the_limits(cell, paths, path):
    control = paths(path)["control"]
    failed = over(control, cell["traffic"]["limits"])
    assert {"vel_step_gap", "poisson_resid"} <= set(failed), control


def test_the_moments_run_at_highest():
    """The body's moments feed the rigid velocity the cell holds to 1.5e-4
    of the body's speed: with float32 operands rounded to bfloat16 (the
    TPU's default, which no CPU run sees) the 256^3 fish read 1.2e-3.
    Both uniform paths (``UpdateObstacles`` and the scan body) take
    their moments from this one function."""
    grid = UniformGrid((8, 8, 8), (1.0, 1.0, 1.0))
    chi = jnp.ones(grid.shape, jnp.float32)
    vel = jnp.ones(grid.shape + (3,), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda c, v: momentum_integrals(grid, c, v, jnp.zeros(3)))(chi, vel)
    assert_dots_highest(jaxpr, at_least=4)


def test_each_dispatch_is_counted_once(cell, paths):
    """``megaloop.dispatches`` rises by one per ``advance_megaloop`` call:
    the K warm-up steps are one dispatch, the checked unit one more (the
    chain drives the jitted scan itself and is no dispatch of the
    driver's).  The reader puts the harness's step count over it."""
    scan = paths("scan")
    assert scan["warmup"].get("megaloop.dispatches") == 1
    assert scan["unit"].get("megaloop.dispatches") == 2
    read = spec.load_reader(cell["bench"], "megaloop.steps_per_dispatch").read
    assert read({"obs": scan["warmup"], "window": {"steps": K}}) == K
    # a dispatch that fell short, or steps from another path, show
    short = {"obs": scan["unit"], "window": {"steps": K + 3}}
    assert read(short) == (K + 3) / 2
    # a program without the counter (the parent), or an empty window
    assert read({"obs": {}, "window": {"steps": K}}) is None
    assert read({"obs": scan["unit"], "window": {"steps": 0}}) is None


def test_the_per_step_path_counts_no_dispatch(paths):
    assert not paths("step")["obs"].get("megaloop.dispatches")


def test_each_force_row_is_counted_against_its_probes_budget(cell, paths):
    """``store_force_qoi`` raises ``operators.probe_compacted`` once per
    stored row whose band fitted the slot budget of its probe (every row
    here: one a step on the per-step path, one a scanned step on the
    scan), ``operators.probe_truncated`` never; the reader makes 100 of
    that, and nothing of a program without the counters."""
    step, scan = paths("step")["obs"], paths("scan")["unit"]
    assert step.get("operators.probe_compacted") == 4  # 3 warm-up + 1
    assert scan.get("operators.probe_compacted") == 2 * K
    for moved in (step, scan):
        assert not moved.get("operators.probe_truncated")
    read = spec.load_reader(cell["bench"],
                            "operators.probe_compact_share").read
    assert read({"obs": step}) == 100.0 and read({"obs": scan}) == 100.0
    assert read({"obs": {}}) is None
    assert read({"obs": {"megaloop.dispatches": 2}}) is None


def test_the_rasterizer_counts_its_cells_against_the_sweep(cell, paths):
    """``operators.raster_cells`` / ``operators.raster_sweep_cells`` rise
    once per per-step CreateObstacles (3 warm-up steps + the checked one)
    and K times per scan dispatch (the warm-up's and the checked unit's):
    the reader makes the box's share of the window's sweep of that, the
    same on both paths, and nothing of a program without the counters."""
    step, scan = paths("step")["obs"], paths("scan")["unit"]
    for name in ("operators.raster_cells", "operators.raster_sweep_cells"):
        assert scan[name] == 2 * K * step[name] / 4, name
    read = spec.load_reader(cell["bench"], "operators.raster_work_share").read
    share = read({"obs": step})
    assert 0 < share < 100 and read({"obs": scan}) == share
    assert read({"obs": {}}) is None
    assert read({"obs": {"megaloop.dispatches": 2}}) is None


def test_each_solve_is_counted_by_its_entry(cell, paths):
    """``poisson.increment_solves`` rises once per per-step projection (3
    warm-up steps + the checked one) and K times per scan dispatch (the
    warm-up's and the checked unit's); no solve takes the composed
    entry.  The reader makes 100 of either path, and nothing of a
    program without the counters."""
    step, scan = paths("step")["obs"], paths("scan")["unit"]
    assert step["poisson.increment_solves"] == 4
    assert scan["poisson.increment_solves"] == 2 * K
    read = spec.load_reader(cell["bench"], "poisson.increment_share").read
    for moved in (step, scan):
        assert not moved.get("poisson.composed_solves")
        assert read({"obs": moved}) == 100.0
    assert read({"obs": {}}) is None
    assert read({"obs": {"megaloop.dispatches": 2}}) is None
    assert read({"obs": {"poisson.increment_solves": 1,
                         "poisson.composed_solves": 3}}) == 25.0


def test_an_overflowing_row_is_counted_as_truncated(cell):
    """The sink alone: a row whose n_surf is over the slot budget of the
    body's probe counts as truncated, one at most the budget as
    compacted, a row of no probe (n_surf 0: the chi-band integrals) as
    neither."""
    class Body:
        length = 0.4
        transVel = np.array([0.1, 0.0, 0.0])

    row = {k: 0.0 for k in ("power", "thrust", "drag", "def_power")}
    row.update(pres_force=jnp.zeros(3), visc_force=jnp.zeros(3),
               torque=jnp.zeros(3))
    ob = Body()
    assert obstacle_probe_budget(ob, 1 / 64) == 13312 == ob.probe_slots
    obs0 = obs.snapshot()
    for n_surf in (13312.0, 700.0, 13313.0, 0.0):
        store_force_qoi(ob, {**row, "n_surf": n_surf})
    moved = obs.delta(obs0)
    assert moved["operators.probe_compacted"] == 2
    assert moved["operators.probe_truncated"] == 1
    read = spec.load_reader(cell["bench"],
                            "operators.probe_compact_share").read
    assert read({"obs": moved}) == pytest.approx(200 / 3)


def _differing(a, b, at=""):
    """Paths of the leaves in which two JSON values differ."""
    if isinstance(a, dict) and isinstance(b, dict):
        return [p for k in sorted(set(a) | set(b))
                for p in _differing(a.get(k), b.get(k), f"{at}/{k}")]
    return [] if a == b else [at]


def test_the_configuration_is_fish128_with_bpd_32():
    """``fish256`` is the source's own size: ``fish128``'s file with
    ``bpd 16 -> 32`` and what follows from it, no width of the source
    changed (body, profiles, period, CFL, nu, tolerances, ramp)."""
    bench = spec.load_benchmark()
    small = spec.load_cell(bench, "fish128.scan")[1]
    big = spec.load_cell(bench, "fish256.scan")[1]
    assert set(_differing(small, big)) == {
        "/name", "/source", "/argv", "/seed/finest_h", "/driver/cells",
        "/guarantees/div_fluid_gate/limit", "/guarantees/div_fluid_gate/why",
        "/rehearse/argv", "/rehearse/seed/finest_h",
        "/rehearse/driver/cells"}
    for a, b, bpd in ((small["argv"], big["argv"], ("16", "32")),
                      (small["rehearse"]["argv"], big["rehearse"]["argv"],
                       ("4", "8"))):
        changed = [(i, x, y) for i, (x, y) in enumerate(zip(a, b)) if x != y]
        assert len(a) == len(b) and [c[1:] for c in changed] == [bpd] * 3
        assert [a[i - 1] for i, _, _ in changed] == ["-bpdx", "-bpdy", "-bpdz"]
    assert big["reduced"] == [] and big["driver"]["cells"] == [256] * 3
    assert big["seed"]["finest_h"] == 1 / 256
    entry = next(c for c in bench["configs"] if c["name"] == "fish256")
    assert entry["reduced"] == [] and entry["source"] == big["source"]


def test_the_cell_s_traffic_is_the_issue_s():
    bench = spec.load_benchmark()
    cell_entry, _, traffic = spec.load_cell(bench, "fish256.scan")
    small = spec.load_cell(bench, "fish128.scan")[2]
    assert cell_entry["chips"] == 1
    assert traffic["flags"] == {"pipelined": 1, "scan_k": 8}
    assert (traffic["warmup_steps"], traffic["chunk_steps"],
            traffic["trace_chunks"], traffic["check_unit_steps"]) == (
                104, 32, 1, 8)
    assert traffic["spans"] == small["spans"]
    assert traffic["window_span"] == small["window_span"]
    # no limit is wider than the accepted cell's
    assert all(traffic["limits"][k] <= v for k, v in small["limits"].items())
