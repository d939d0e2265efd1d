"""The lower-precision control at a size a test run can hold: the plain
reference stands in for the program on a synthetic body in a periodic
box, once in float32 (a sound program) and once keeping every stage in
bfloat16 (the control), on each kind of grid.  Held to each cell's own
limits, the first has to pass all of them and the second has to fail at
least one; so has each fault that only that kind of grid can have (a
forest's coarse-fine faces), planted in the reference that stands in.
(The two numbers of the body's shape are left out: the synthetic body is
a ball, not the configuration's fish.)"""

import numpy as np
import pytest

from benchmarks.lib import compare, reference as ref, spec
from benchmarks.tests import faults, forest_cell

# the benchmark's cells and the forest cell that waits (forest_cell.py)
BENCH = forest_cell.entries(spec.load_benchmark())
PHYS = {"nu": 1e-3, "DLM": 1.0, "extent": 1.0}


def two_level_leaves(bpd=2):
    """Half the box at level 0, the other half refined once."""
    coarse = [(0, i, j, k) for i in range(bpd // 2, bpd)
              for j in range(bpd) for k in range(bpd)]
    fine = [(1, i, j, k) for i in range(bpd) for j in range(2 * bpd)
            for k in range(2 * bpd)]
    return np.array(coarse + fine, np.int64)


def geometry(kind, n=32):
    if kind == "uniform":
        h = 1.0 / n
        ax = (np.arange(n) + 0.5) * h
        x = np.stack(np.meshgrid(ax, ax, ax, indexing="ij"), axis=-1)
        return {"x": x, "h": h}, x, h
    geom = {"leaves": two_level_leaves(n // 16), "bs": 8,
            "blocks0": (n // 16,) * 3, "h0": 1.0 / (n // 2)}
    forest = spec.load_grid(BENCH, kind).reference(geom).forest
    return geom, forest.x, forest.h_of(forest.lmax)


def synthetic(kind="uniform", n=32, seed=0, with_body=True):
    """A (pre, post) link on a grid of this kind.  The ball sits on the
    coarse-fine face of the forest, so that the body's fields cross it;
    without it the flow has no body at all."""
    grid = spec.load_grid(BENCH, kind)
    rng = np.random.default_rng(seed)
    geom, x, h = geometry(kind, n)
    vel = np.zeros(x.shape)
    for _ in range(6):
        k = rng.integers(1, 4, 3)
        amp = rng.uniform(-0.2, 0.2, 3)
        wave = np.sin(2 * np.pi * (x @ k) + rng.uniform(0, 2 * np.pi))
        vel += amp * wave[..., None]
    r = np.linalg.norm(x - 0.5, axis=-1)
    chi = 0.5 * (1.0 - np.tanh((r - 0.15) / (1.5 * h)))
    chi[chi < 1e-4] = 0.0
    chi *= with_body
    udef = 0.05 * np.sin(2 * np.pi * x) * chi[..., None]
    body = {"chi": chi, "udef": udef, "cm": np.full(3, 0.5),
            "trans": np.array([0.1, 0.0, 0.02]),
            "ang": np.array([0.0, 0.0, 0.3]), "length": 0.4,
            "width": "stefan", "height": "danio"}
    base = {**geom, "chi": chi, "udef": udef,
            "uinf": np.array([-0.1, 0.0, 0.0])}
    dt = 0.4 * h / 0.5
    bodies = [body] if with_body else []
    pre = {**base, "vel": vel, "p": np.zeros(chi.shape), "time": 0.0,
           "dt": dt, "bodies": bodies}
    post = {**base, "time": dt, "dt": dt, "bodies": bodies}
    # a sound program: the reference's own step, kept in float32, that
    # reports the rigid state it penalised towards
    for _ in range(2):
        out = compare.reference_step(grid, pre, post, PHYS)
        post["bodies"] = [{**b, **{k: np.float32(mine[k]).astype(np.float64)
                                   for k in ("trans", "ang", "cm")}}
                          for b, mine in zip(bodies, out["rigid"])]
    post.update(vel=out["u1"].astype(np.float32),
                p=out["p"].astype(np.float32))
    return grid, pre, post


def cells():
    out = []
    for w in BENCH["workloads"]:
        _, config, traffic = spec.load_cell(BENCH, w["name"])
        out.append(pytest.param(config["driver"]["kind"], traffic["limits"],
                                id=w["name"]))
    return out


def held(numbers, limits):
    return {k: v for k, v in numbers.items()
            if k in limits and not k.startswith("chi_")}


@pytest.mark.parametrize("kind,limits", cells())
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_float32_passes_and_bfloat16_fails(kind, limits, seed):
    grid, pre, post = synthetic(kind, seed=seed)
    sound = held(compare.link_numbers(grid, pre, post, PHYS), limits)
    low = held(compare.link_numbers(
        grid, pre, compare.control_link(grid, pre, post, PHYS), PHYS),
        limits)
    for name, value in sound.items():
        assert value <= limits[name], (name, value)
    assert any(low[k] > limits[k] for k in low), low


def test_a_flow_with_no_body_is_held_to_the_fluid_s_numbers():
    (kind, limits), = [c.values for c in cells() if c.values[0] == "forest"]
    grid, pre, post = synthetic(kind, seed=4, with_body=False)
    sound = compare.link_numbers(grid, pre, post, PHYS)
    low = compare.link_numbers(
        grid, pre, compare.control_link(grid, pre, post, PHYS), PHYS)
    assert set(sound) == {"vel_step_gap", "vel_step_gap_max",
                          "poisson_resid"}
    assert all(sound[k] <= limits[k] for k in sound), sound
    assert any(low[k] > limits[k] for k in low), low
    assert compare.guarantees(grid, post)[0]


def faults_of_the_grid():
    return [pytest.param(*c.values, fault, id=f"{c.id}-{fault}")
            for c in cells()
            for fault in faults.GRID_FAULTS.get(c.values[0], {})]


@pytest.mark.parametrize("kind,limits,fault", faults_of_the_grid())
def test_a_fault_of_the_kind_of_grid_fails(kind, limits, fault):
    grid, pre, post = synthetic(kind, seed=3)
    bad = compare.control_link(grid, pre, post, PHYS,
                               on=faults.faulty(grid, kind, post, fault))
    numbers = held(compare.link_numbers(grid, pre, bad, PHYS), limits)
    assert any(numbers[k] > limits[k] for k in numbers), numbers


def test_the_published_profiles_give_the_body_its_volume():
    # pi int w h ds of the stefan width and danio height at L = 0.4
    assert ref.fish_volume(0.4, "stefan", "danio") == \
        pytest.approx(3.969e-4, rel=1e-3)
