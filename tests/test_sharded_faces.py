"""Sharded face-slab halo assembly (parallel/faces.py) must reproduce the
single-device FaceTables (grid/faces.py) exactly on the virtual 8-device
CPU mesh — the round-4 port of the fast path to the forest (VERDICT r3
item 2)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cup3d_tpu.config import SimulationConfig
from cup3d_tpu.grid.uniform import BC
from cup3d_tpu.parallel.faces import build_sharded_face_tables
from cup3d_tpu.parallel.forest import make_block_mesh
from cup3d_tpu.sim.amr import AMRSimulation
from tests._grids import (
    BS, THREE_LEVEL, assemble, assert_dots_highest, forest, laplacian,
    mixed_grid, rand,
)


@pytest.mark.parametrize("width", [1, 3])
@pytest.mark.parametrize(
    "refine",
    [
        ((0, 0, 0, 0), (0, 1, 1, 1)),  # two-level mixed
        THREE_LEVEL,  # pyramid exchange across a deeper subtree
    ],
)
@pytest.mark.slow
def test_sharded_faces_match_single_device(width, refine):
    g = mixed_grid(refine=refine)
    fo = forest(g)
    tab = g.face_tables(width)
    stab = build_sharded_face_tables(fo, width)

    x = rand(g)
    ref = tab.assemble_scalar(x, BS)
    got = fo.unpad(assemble(stab, "scalar", fo.pad(x)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=0, atol=2e-6)

    v = rand(g, 3, seed=1)
    refv = tab.assemble_vector(v, BS)
    gotv = fo.unpad(assemble(stab, "vector", fo.pad(v)))
    np.testing.assert_allclose(np.asarray(gotv), np.asarray(refv),
                               rtol=0, atol=2e-6)


def test_sharded_coarse_halo_dots_carry_highest_precision():
    """The sharded twin of grid/faces.py::_coarse_halo interpolates in
    float32 on every backend (tests/test_faces.py has the single-device
    guard and the value test)."""
    fo = forest(mixed_grid())
    stab = build_sharded_face_tables(fo, 1)
    closed = jax.make_jaxpr(lambda a: stab.assemble_scalar(a, BS))(
        fo.pad(rand(fo.grid)))
    assert_dots_highest(closed.jaxpr, at_least=3)


@pytest.mark.parametrize("bc", [
    (BC.wall, BC.periodic, BC.periodic),
    (BC.freespace,) * 3,
])
def test_sharded_faces_closed_bcs(bc):
    g = mixed_grid(bc=bc)
    fo = forest(g)
    tab = g.face_tables(1)
    if tab.fb_rows is not None:
        pytest.skip("degenerate topology: sharded path falls back")
    stab = build_sharded_face_tables(fo, 1)
    v = rand(g, 3, seed=2)
    refv = tab.assemble_vector(v, BS)
    gotv = fo.unpad(assemble(stab, "vector", fo.pad(v)))
    np.testing.assert_allclose(np.asarray(gotv), np.asarray(refv),
                               rtol=0, atol=2e-6)
    # component path (chi/p style scalars with a sign component)
    refc = tab.assemble_component(v[..., 0], BS, 0)
    gotc = fo.unpad(assemble(stab, "component", fo.pad(v[..., 0]), 0))
    np.testing.assert_allclose(np.asarray(gotc), np.asarray(refc),
                               rtol=0, atol=2e-6)


def test_sharded_faces_uneven_shards():
    """nb not divisible by D: padding blocks stay exactly zero."""
    g = mixed_grid(refine=((0, 0, 0, 0),))  # 8 - 1 + 8 = 15 blocks
    assert g.nb % 8 != 0
    fo = forest(g)
    stab = build_sharded_face_tables(fo, 1)
    tab = g.face_tables(1)
    x = rand(g, seed=3)
    ref = tab.assemble_scalar(x, BS)
    padded = assemble(stab, "scalar", fo.pad(x))
    got = fo.unpad(padded)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=0, atol=2e-6)
    assert float(jnp.max(jnp.abs(padded[g.nb:]))) == 0.0


def test_sharded_laplacian_with_face_tables():
    """The refluxed Laplacian on sharded face tables == single device."""
    from cup3d_tpu.grid.flux import build_flux_tables

    g = mixed_grid()
    fo = forest(g)
    stab = build_sharded_face_tables(fo, 1)
    tab = g.face_tables(1)
    ftab = build_flux_tables(g)
    x = rand(g, seed=4)
    ref = laplacian(g, x, tab, ftab)
    got = fo.unpad(laplacian(fo.geom, fo.pad(x), stab, fo.flux_tables))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=0, atol=5e-5)


def _single_and_sharded(**cfg):
    """The pipelined forest driver, adaptation off, for 4 steps of 1e-3:
    on one device and on the 8-device block mesh."""
    def run(mesh):
        sim = AMRSimulation(SimulationConfig(
            CFL=0.4, nu=1e-3, tend=0.0, nsteps=4, rampup=0, dt=1e-3,
            poissonSolver="iterative", poissonTol=1e-5, poissonTolRel=1e-3,
            verbose=False, freqDiagnostics=0, pipelined=True, **cfg,
        ), mesh=mesh)
        sim.init()
        sim.adapt_enabled = False
        sim.simulate()
        return sim

    return run(None), run(make_block_mesh(jax.devices()[:8]))


@pytest.mark.slow
def test_pipelined_megastep_on_mesh_matches_single_device():
    """Round 4: the fused pipelined megastep runs ON the sharded forest
    (VERDICT r3 item 2) — trajectories match the single-device pipelined
    driver."""
    single, sharded = _single_and_sharded(
        bpdx=1, bpdy=1, bpdz=1, levelMax=2, levelStart=1, extent=1.0,
        Ctol=0.1, Rtol=5.0, factory_content=(
            "Sphere radius=0.12 xpos=0.35 ypos=0.5 zpos=0.5 xvel=0.3 "
            "bForcedInSimFrame=1 bFixFrameOfRef=1\n"
            "Sphere radius=0.1 xpos=0.7 ypos=0.45 zpos=0.5"
        ),
    )
    assert sharded.forest is not None
    assert not sharded._pack_reader  # flushed
    for a, b in zip(single.obstacles, sharded.obstacles):
        np.testing.assert_allclose(a.position, b.position,
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(a.transVel, b.transVel,
                                   rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(sharded.forest.unpad(sharded.state["vel"])),
        np.asarray(single.state["vel"]),
        atol=5e-4,
    )


def test_pipelined_free_megastep_on_mesh():
    """Obstacle-free fused stepping on the mesh (TGV regime)."""
    single, sharded = _single_and_sharded(
        bpdx=2, bpdy=2, bpdz=2, levelMax=2, levelStart=0,
        extent=float(2 * np.pi), Rtol=1.8, Ctol=0.05,
        initCond="taylorGreen",
    )
    np.testing.assert_allclose(
        np.asarray(sharded.forest.unpad(sharded.state["vel"])),
        np.asarray(single._unpad(single.state["vel"])),
        atol=5e-4,
    )
