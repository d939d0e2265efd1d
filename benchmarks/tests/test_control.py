"""The lower-precision control at a size a test run can hold: the plain
reference stands in for the program on a synthetic body in a periodic
box, once in float32 (a sound program) and once keeping every stage in
bfloat16 (the control).  Held to each cell's own limits, the first has to
pass all of them and the second has to fail at least one.  (The two
numbers of the body's shape are left out: the synthetic body is a ball,
not the configuration's fish.)"""

import numpy as np
import pytest

from benchmarks.lib import compare, reference as ref, spec

BENCH = spec.load_benchmark()
PHYS = {"nu": 1e-3, "DLM": 1.0, "extent": 1.0}


def synthetic(n=32, seed=0):
    rng = np.random.default_rng(seed)
    h = 1.0 / n
    ax = (np.arange(n) + 0.5) * h
    x = np.stack(np.meshgrid(ax, ax, ax, indexing="ij"), axis=-1)
    vel = np.zeros((n, n, n, 3))
    for _ in range(6):
        k = rng.integers(1, 4, 3)
        amp = rng.uniform(-0.2, 0.2, 3)
        wave = np.sin(2 * np.pi * (x @ k) + rng.uniform(0, 2 * np.pi))
        vel += amp * wave[..., None]
    r = np.linalg.norm(x - 0.5, axis=-1)
    chi = 0.5 * (1.0 - np.tanh((r - 0.15) / (1.5 * h)))
    chi[chi < 1e-4] = 0.0
    udef = 0.05 * np.sin(2 * np.pi * x) * chi[..., None]
    body = {"chi": chi, "udef": udef, "cm": np.full(3, 0.5),
            "trans": np.array([0.1, 0.0, 0.02]),
            "ang": np.array([0.0, 0.0, 0.3]), "length": 0.4,
            "width": "stefan", "height": "danio"}
    base = {"x": x, "h": h, "chi": chi, "udef": udef,
            "uinf": np.array([-0.1, 0.0, 0.0])}
    dt = 0.4 * h / 0.5
    pre = {**base, "vel": vel, "p": np.zeros((n, n, n)), "time": 0.0,
           "dt": dt, "bodies": [body]}
    post = {**base, "time": dt, "dt": dt, "bodies": [body]}
    # a sound program: the reference's own step, kept in float32, that
    # reports the rigid state it penalised towards
    for _ in range(2):
        out = compare.reference_step(pre, post, PHYS)
        post["bodies"] = [{**body, **{k: np.float32(out["rigid"][0][k])
                                      .astype(np.float64)
                                      for k in ("trans", "ang", "cm")}}]
    post.update(vel=out["u1"].astype(np.float32),
                p=out["p"].astype(np.float32))
    return pre, post


def cells():
    out = []
    for w in BENCH["workloads"]:
        _, _, traffic = spec.load_cell(BENCH, w["name"])
        out.append(pytest.param(traffic["limits"], id=w["name"]))
    return out


def held(numbers, limits):
    return {k: v for k, v in numbers.items()
            if k in limits and not k.startswith("chi_")}


@pytest.mark.parametrize("limits", cells())
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_float32_passes_and_bfloat16_fails(limits, seed):
    pre, post = synthetic(seed=seed)
    sound = held(compare.link_numbers(pre, post, PHYS), limits)
    low = held(compare.link_numbers(
        pre, compare.control_link(pre, post, PHYS), PHYS), limits)
    for name, value in sound.items():
        assert value <= limits[name], (name, value)
    assert any(low[k] > limits[k] for k in low), low


def test_the_published_profiles_give_the_body_its_volume():
    # pi int w h ds of the stefan width and danio height at L = 0.4
    assert ref.fish_volume(0.4, "stefan", "danio") == \
        pytest.approx(3.969e-4, rel=1e-3)
