"""CreateObstacles on the uniform per-step path: host NumPy kinematics,
one upload per body, one program per body (models/pipeline.py).

The cases are the benchmark's 32^3 rehearsal of ``fish128`` built through
``build_driver`` with ``-pipelined 0``, a sphere and two fish on the same
grid.  Programs and uploads are counted as ``tests/_dispatch.py`` says,
inside a span around each operator.

The equivalence cases hold the fused programs to the chain the parent
dispatched op by op, written out below as plain ``jnp`` calls.  The step
path against the scan body: ``tests/test_megaloop.py::
test_device_midline_chi_udef_matches_host`` (unchanged, same limits).
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cup3d_tpu.__main__ import build_driver
from cup3d_tpu.analysis.runtime import device_scalar
from cup3d_tpu.models.base import quat_to_rot
from cup3d_tpu.models.fish.rasterize import rasterize_midline
from cup3d_tpu.ops.chi import towers_chi
from tests._dispatch import dispatches, span

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FISH = ("StefanFish L=0.4 T=1.0 xpos={x} ypos=0.4991 zpos=0.5023 phi=0.0 "
        "bFixFrameOfRef={fix} heightProfile=danio widthProfile=stefan")
CASES = {
    "fish": [FISH.format(x=0.5012, fix=1)],
    "sphere": ["Sphere L=0.2 xpos=0.5012 ypos=0.4991 zpos=0.5023"],
    "twofish": [FISH.format(x=0.3, fix=1), FISH.format(x=0.7, fix=0)],
}
MIDLINE = ("r", "v", "nor", "vnor", "bin", "vbin", "quaternion_internal",
           "angvel_internal")
RIGID = ("position", "quaternion", "transVel", "angVel", "centerOfMass")


@pytest.fixture(scope="module", params=sorted(CASES))
def stepped(request, tmp_path_factory):
    """The case's driver after ``init()`` and three ``advance()`` calls."""
    with open(os.path.join(ROOT, "benchmarks/configs/fish128.json")) as f:
        argv = list(json.load(f)["rehearse"]["argv"])
    argv += ["-pipelined", "0", "-scan_k", "0", "-nsteps", "1000",
             "-factory-content", "\n".join(CASES[request.param]),
             "-path4serialization",
             str(tmp_path_factory.mktemp(request.param))]
    driver = build_driver(argv)
    driver.init()
    for _ in range(3):
        driver.advance(driver.calc_max_timestep())
    driver.case = request.param
    return driver


def test_host_kinematics_stay_float64_numpy(stepped):
    for ob in stepped.sim.obstacles:
        held = [(k, getattr(ob, k)) for k in RIGID]
        if hasattr(ob, "myFish"):
            held += [(k, getattr(ob.myFish, k)) for k in MIDLINE]
        for name, a in held:
            assert type(a) is np.ndarray and a.dtype == np.float64, name


def test_update_shape_touches_no_device(stepped):
    s = stepped.sim
    with jax.transfer_guard("disallow_explicit"):
        for ob in s.obstacles:
            ob.update_shape(s.time, s.dt)


def test_create_obstacles_reads_nothing_back(stepped):
    s = stepped.sim
    dt_dev = device_scalar(s.dt, s.dtype)
    with jax.transfer_guard_device_to_host("disallow_explicit"):
        stepped.pipeline[0](dt_dev)


def one_more_advance(driver):
    """One more ``advance()``; every operator of the pipeline is a span
    of the program's own (its profiler section)."""
    dt = driver.calc_max_timestep()
    with span("advance"):
        driver.advance(dt)
    jax.block_until_ready(driver.sim.state["vel"])


def test_create_obstacles_dispatch_counts(stepped, tmp_path):
    counts = dispatches(lambda: one_more_advance(stepped), str(tmp_path))
    programs, uploads = counts["CreateObstacles"]
    # one fish: 1 and 1 (157 and 98 before); a sphere: its SDF and the
    # shared tail; two fish: one program each and the combine
    assert programs <= 3 and uploads <= 2, counts
    if stepped.case == "fish":
        assert (programs, uploads) == (1, 1), counts
        assert counts["advance"][0] <= 60, counts  # 205 before
        assert counts["step"] == counts["advance"], counts
        assert counts["read:qoi-read"] == (0, 0), counts
    print("uniform advance dispatches", stepped.case, counts)


def parent_chain(s, ob):
    """(sdf, chi, udef) of one body from its host mirrors, as the parent
    dispatched it: upload, window snap, rasterizer, placement, ghost
    padding, Towers chi, band mask."""
    grid, dtype = s.grid, s.dtype
    pos = jnp.asarray(ob.position, dtype)
    if not hasattr(ob, "myFish"):
        x = grid.cell_centers(dtype)
        sdf = ob.radius - jnp.linalg.norm(x - pos, axis=-1)
        chi = towers_chi(grid.pad_scalar(sdf, 1), grid.h)
        return sdf, chi, jnp.zeros(grid.shape + (3,), dtype)
    cf = ob.myFish
    dev = jnp.asarray(np.concatenate(
        [cf.r, cf.v, cf.nor, cf.vnor, cf.bin, cf.vbin,
         cf.width[:, None], cf.height[:, None]], axis=1), dtype)
    mid = {"r": dev[:, 0:3], "v": dev[:, 3:6], "nor": dev[:, 6:9],
           "vnor": dev[:, 9:12], "bin": dev[:, 12:15], "vbin": dev[:, 15:18],
           "width": dev[:, 18], "height": dev[:, 19]}
    rot = jnp.asarray(quat_to_rot(ob.quaternion), dtype)
    window = tuple(ob._window_shape)
    h = jnp.asarray(grid.h, dtype)
    half = jnp.asarray(0.5 * np.asarray(window) * grid.h, dtype)
    idx0 = jnp.clip(
        jnp.floor((pos - half) / h).astype(jnp.int32), 0,
        jnp.asarray(np.asarray(grid.shape) - np.asarray(window), jnp.int32))
    starts = (idx0[0], idx0[1], idx0[2])
    sdf_w, udef_w = rasterize_midline(idx0.astype(dtype) * h, h, window,
                                      ob._raster_box, mid, pos, rot)
    sdf = jax.lax.dynamic_update_slice(
        jnp.full(grid.shape, -1.0, dtype), sdf_w, starts)
    udef = jax.lax.dynamic_update_slice(
        jnp.zeros(grid.shape + (3,), dtype), udef_w, starts + (0,))
    chi = towers_chi(grid.pad_scalar(sdf, 1), grid.h)
    return sdf, chi, udef * (chi > 0)[..., None]


def test_fused_programs_match_the_parents_chain(stepped):
    s = stepped.sim
    stepped.pipeline[0](device_scalar(s.dt, s.dtype))
    # the host mirrors are now what the programs were given
    want = [parent_chain(s, ob) for ob in s.obstacles]
    chis = jnp.stack([chi for _, chi, _ in want])
    num = sum(chi[..., None] * udef for _, chi, udef in want)
    den = jnp.maximum(jnp.sum(chis, axis=0), 1e-6)[..., None]
    want_state = {"chi": jnp.max(chis, axis=0), "udef": num / den}

    def close(got, ref, scale, what):
        gap = float(jnp.max(jnp.abs(got - ref)))
        assert gap <= 1e-6 * scale, (what, gap)

    for i, (ob, (sdf, chi, udef)) in enumerate(zip(s.obstacles, want)):
        close(ob.sdf, sdf, 1.0, f"sdf {i}")
        close(ob.chi, chi, 1.0, f"chi {i}")
        close(ob.udef, udef, float(jnp.max(jnp.abs(udef))), f"udef {i}")
        assert float(jnp.max(chi)) > 0.05, "the body is on the grid"
    close(s.state["chi"], want_state["chi"], 1.0, "state chi")
    close(s.state["udef"], want_state["udef"],
          float(jnp.max(jnp.abs(want_state["udef"]))), "state udef")
