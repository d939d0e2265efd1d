"""Surface-point force probing (ops/surface.py): analytic checks on a
sphere — the surface measure must integrate to the sphere area, a linear
pressure field must produce the exact buoyancy force (divergence theorem),
and a constant-gradient velocity field must produce zero net viscous force
on a closed surface."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cup3d_tpu.ops import surface as sf
from cup3d_tpu.ops.chi import heaviside


def _sphere_window(n=48, r=0.3):
    h = 1.0 / n
    loc = (np.arange(n) + 0.5) * h
    x, y, z = np.meshgrid(loc, loc, loc, indexing="ij")
    xc = np.stack([x, y, z], axis=-1).astype(np.float32)
    c = np.array([0.5, 0.5, 0.5])
    dist = np.sqrt(((xc - c) ** 2).sum(-1))
    sdf = (r - dist).astype(np.float32)  # >0 inside
    chi = np.asarray(heaviside(jnp.asarray(sdf), h))
    return h, xc, jnp.asarray(sdf), jnp.asarray(chi), c


# one compile per (shapes, h, nu, per_point, max_points): eager, the
# probe's loop over its chunks compiles its body anew at every call
_probe_jit = jax.jit(sf.surface_force_window,
                     static_argnames=("h", "nu", "per_point", "max_points"))


def _probe(vel, p, h, xc, sdf, chi, nu=1e-2, cm=(0.5, 0.5, 0.5)):
    shape = sdf.shape
    valid = jnp.ones(shape, bool)
    udef = jnp.zeros(shape + (3,), jnp.float32)
    return _probe_jit(
        vel, p, chi, sdf, udef, valid, jnp.asarray(xc), h=h, nu=nu,
        cm=jnp.asarray(cm, jnp.float32), u_trans=jnp.zeros(3, jnp.float32),
        omega=jnp.zeros(3, jnp.float32),
    )


def test_surface_measure_integrates_to_area():
    h, xc, sdf, chi, c = _sphere_window()
    p = jnp.ones(sdf.shape, jnp.float32)  # constant pressure
    vel = jnp.zeros(sdf.shape + (3,), jnp.float32)
    out = _probe(vel, p, h, xc, sdf, chi)
    # constant P: F_pres = -P * closed-surface integral of n dS = 0
    area = 4.0 * np.pi * 0.3**2
    assert np.linalg.norm(np.asarray(out["pres_force"])) < 0.02 * area
    # and the measure itself: integrate P=1 against |n dS| via a linear
    # pressure probe below instead (n dS signed cancels here)


def test_linear_pressure_gives_buoyancy():
    """P = x: F = -closed-integral(P n dS) = -V grad(P) = -V e_x."""
    h, xc, sdf, chi, c = _sphere_window()
    p = jnp.asarray(xc[..., 0])
    vel = jnp.zeros(sdf.shape + (3,), jnp.float32)
    out = _probe(vel, p, h, xc, sdf, chi)
    V = 4.0 / 3.0 * np.pi * 0.3**3
    F = np.asarray(out["pres_force"])
    assert abs(F[0] + V) / V < 0.05, (F, V)
    assert abs(F[1]) / V < 0.02 and abs(F[2]) / V < 0.02


def test_constant_shear_zero_net_viscous_force():
    """u = (gamma*z, 0, 0): grad u constant -> closed-surface viscous
    force = nu * laplacian(u) * V = 0."""
    h, xc, sdf, chi, c = _sphere_window()
    gamma = 2.0
    vel = jnp.zeros(sdf.shape + (3,), jnp.float32)
    vel = vel.at[..., 0].set(gamma * xc[..., 2])
    p = jnp.zeros(sdf.shape, jnp.float32)
    out = _probe(vel, p, h, xc, sdf, chi, nu=1e-2)
    # scale: the one-sided traction magnitude ~ nu*gamma*area
    scale = 1e-2 * gamma * 4.0 * np.pi * 0.3**2
    F = np.asarray(out["visc_force"])
    assert np.linalg.norm(F) < 0.08 * scale, (F, scale)


def test_torque_about_center_vanishes_for_radial_pressure():
    """P = |x-c|^2 is radially symmetric: torque about the center = 0."""
    h, xc, sdf, chi, c = _sphere_window()
    p = jnp.asarray(((xc - c) ** 2).sum(-1))
    vel = jnp.zeros(sdf.shape + (3,), jnp.float32)
    out = _probe(vel, p, h, xc, sdf, chi)
    T = np.asarray(out["torque"])
    assert np.linalg.norm(T) < 1e-4


@pytest.mark.slow
def test_block_window_matches_dense():
    """The AMR block-window extraction reproduces the same integrals as a
    direct dense window on a uniform single-level forest."""
    from cup3d_tpu.grid.blocks import BlockGrid
    from cup3d_tpu.grid.octree import Octree, TreeConfig
    from cup3d_tpu.grid.uniform import BC

    nbd = 6
    t = Octree(TreeConfig((nbd,) * 3, 1, (False,) * 3), 0)
    g = BlockGrid(t, (1.0,) * 3, (BC.freespace,) * 3, bs=8)
    n = nbd * 8
    h = 1.0 / n
    xc_b = g.cell_centers(np.float32)  # (nb, 8,8,8,3)
    c = np.array([0.5, 0.5, 0.5])
    r = 0.22
    dist = np.sqrt(((xc_b - c) ** 2).sum(-1))
    sdf_b = jnp.asarray((r - dist).astype(np.float32))
    chi_b = heaviside(sdf_b, h)
    p_b = jnp.asarray(xc_b[..., 0])
    vel_b = jnp.zeros(sdf_b.shape + (3,), jnp.float32)
    udef_b = jnp.zeros_like(vel_b)

    out = sf.force_integrals_probe_blocks(
        g, {"vel": vel_b, "p": p_b}, chi_b, sdf_b, udef_b, 1e-2,
        position=c, length=2 * r, cm=c,
        u_trans=np.zeros(3), omega=np.zeros(3),
    )
    V = 4.0 / 3.0 * np.pi * r**3
    F = np.asarray(out["pres_force"])
    assert abs(F[0] + V) / V < 0.06, (F, V)


def test_bnd_qoi_and_p_locom():
    """PoutBnd/defPowerBnd are the negative-part sums (reference
    main.cpp:12483-12485): <= 0 and <= the unclipped totals; with a pure
    solid-body translation field and no deformation, pLocom equals Pout
    exactly and defPower vanishes."""
    h, xc, sdf, chi, c = _sphere_window()
    ut = jnp.asarray([0.3, -0.1, 0.2], jnp.float32)
    vel = jnp.broadcast_to(ut, sdf.shape + (3,))
    p = jnp.asarray(xc[..., 0] ** 2 - xc[..., 1])
    out = _probe_jit(
        vel, p, chi, sdf, jnp.zeros(sdf.shape + (3,), jnp.float32),
        jnp.ones(sdf.shape, bool), jnp.asarray(xc), h, 1e-2,
        jnp.asarray(c, jnp.float32), ut, jnp.zeros(3, jnp.float32),
    )
    pout = float(out["power"])
    pout_bnd = float(out["pout_bnd"])
    assert pout_bnd <= 1e-12
    assert pout_bnd <= pout + 1e-12
    assert float(out["def_power"]) == 0.0
    assert float(out["def_power_bnd"]) == 0.0
    # v = u_solid everywhere (omega = 0, udef = 0) -> pLocom == Pout
    assert abs(float(out["p_locom"]) - pout) < 1e-5 * max(1.0, abs(pout))


def test_force_pack_roundtrip_19_qoi():
    """pack_forces/unpack_forces carry the full reference QoI set
    (main.cpp:13089-13108) incl. the Bnd variants and pLocom."""
    from cup3d_tpu.models.base import (
        FORCE_PACK, derived_force_qoi, pack_forces, unpack_forces,
    )

    h, xc, sdf, chi, c = _sphere_window()
    vel = jnp.asarray(np.random.default_rng(0).standard_normal(
        sdf.shape + (3,)).astype(np.float32) * 0.1)
    p = jnp.asarray(xc[..., 2])
    out = _probe_jit(
        vel, p, chi, sdf, 0.05 * vel, jnp.ones(sdf.shape, bool),
        jnp.asarray(xc), h, 1e-2, jnp.asarray(c, jnp.float32),
        jnp.asarray([0.1, 0.0, 0.0], jnp.float32),
        jnp.zeros(3, jnp.float32),
    )
    v = pack_forces(out)
    assert v.shape == (FORCE_PACK,)
    f = unpack_forces(v)
    for k in ("power", "pout_bnd", "thrust", "drag", "def_power",
              "def_power_bnd", "p_locom"):
        assert abs(f[k] - float(out[k])) < 1e-5 * max(1.0, abs(f[k])), k
    assert f["n_surf"] == float(out["n_surf"]) > 0
    d = derived_force_qoi(f, np.array([0.1, 0.0, 0.0]))
    assert "EffPDefBnd" in d and np.isfinite(d["EffPDefBnd"])


def test_per_point_export_consistent_with_reductions():
    """The per-point record (reference ObstacleBlock arrays,
    main.cpp:12300-12330) compacts to n_surf rows whose column sums
    reproduce the reduced forces."""
    h, xc, sdf, chi, c = _sphere_window()
    vel = jnp.asarray(np.random.default_rng(1).standard_normal(
        sdf.shape + (3,)).astype(np.float32) * 0.1)
    p = jnp.asarray(xc[..., 0])
    out = _probe_jit(
        vel, p, chi, sdf, jnp.zeros(sdf.shape + (3,), jnp.float32),
        jnp.ones(sdf.shape, bool), jnp.asarray(xc), h, 1e-2,
        jnp.asarray(c, jnp.float32), jnp.zeros(3, jnp.float32),
        jnp.zeros(3, jnp.float32), per_point=True,
    )
    rows = sf.compact_surface_points(out["points"])
    assert rows.shape == (int(out["n_surf"]), len(sf.SURFACE_POINT_COLUMNS))
    cols = {k: i for i, k in enumerate(sf.SURFACE_POINT_COLUMNS)}
    fP_sum = rows[:, [cols["fxP"], cols["fyP"], cols["fzP"]]].sum(0)
    fV_sum = rows[:, [cols["fxV"], cols["fyV"], cols["fzV"]]].sum(0)
    np.testing.assert_allclose(fP_sum, np.asarray(out["pres_force"]),
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(fV_sum, np.asarray(out["visc_force"]),
                               rtol=1e-4, atol=1e-7)
    # dS column integrates to the sphere area like the reduction does
    area = 4.0 * np.pi * 0.3**2
    assert abs(rows[:, cols["dS"]].sum() - area) / area < 0.06


def test_probe_budget_is_static_and_noted_on_the_body():
    """obstacle_probe_budget: the generous 20 (L/h)^2 of probe_max_points
    for the body's length on this grid, noted on the body for the sink's
    overflow count, and the same whatever band was measured since."""
    from cup3d_tpu.models.base import Obstacle, store_force_qoi

    class Ob(Obstacle):
        def __init__(self):
            self.length = 0.4
            self.transVel = np.zeros(3)

    ob = Ob()
    assert ob.probe_slots == 0
    k0 = sf.obstacle_probe_budget(ob, 1.0 / 128)
    assert k0 == sf.probe_max_points(0.4, 1.0 / 128) == 53248
    assert ob.probe_slots == k0
    row = dict(pres_force=np.zeros(3), visc_force=np.zeros(3),
               torque=np.zeros(3), power=0.0, thrust=0.0, drag=0.0,
               def_power=0.0, n_surf=2674.0)
    store_force_qoi(ob, row)
    assert sf.obstacle_probe_budget(ob, 1.0 / 128) == k0
    assert sf.obstacle_probe_budget(ob, 1.0 / 256) == 209920


def test_truncation_keeps_largest_measure():
    """With max_points below the band size the top-K compaction keeps the
    largest-dS cells: the buoyancy integral degrades gracefully (a few %),
    and n_surf still reports the TRUE band size."""
    h, xc, sdf, chi, c = _sphere_window()
    p = jnp.asarray(xc[..., 0])
    vel = jnp.zeros(sdf.shape + (3,), jnp.float32)
    full = sf.surface_force_window(
        vel, p, chi, sdf, jnp.zeros_like(vel), jnp.ones(sdf.shape, bool),
        jnp.asarray(xc), h, 1e-2, jnp.asarray(c, jnp.float32),
        jnp.zeros(3, jnp.float32), jnp.zeros(3, jnp.float32),
    )
    n_true = int(full["n_surf"])
    K = max(1024, int(0.6 * n_true))
    cut = sf.surface_force_window(
        vel, p, chi, sdf, jnp.zeros_like(vel), jnp.ones(sdf.shape, bool),
        jnp.asarray(xc), h, 1e-2, jnp.asarray(c, jnp.float32),
        jnp.zeros(3, jnp.float32), jnp.zeros(3, jnp.float32),
        max_points=K,
    )
    assert int(cut["n_surf"]) == n_true
    F_full = np.asarray(full["pres_force"])
    F_cut = np.asarray(cut["pres_force"])
    rel = np.linalg.norm(F_cut - F_full) / max(np.linalg.norm(F_full), 1e-12)
    assert rel < 0.15


# -- the loop over the band's occupied slots (PR 37) -----------------------


def _window(case):
    """(h, xc, sdf, chi, valid, centre) of a probe window: a sphere, a
    slender fish-shaped body, and the sphere with holes in ``valid`` as
    a forest window has where a block slot is -1."""
    if case == "fish":
        n = 48
        h = 1.0 / n
        loc = (np.arange(n) + 0.5) * h
        x, y, z = np.meshgrid(loc, loc, loc, indexing="ij")
        xc = np.stack([x, y, z], axis=-1).astype(np.float32)
        c = np.array([0.5, 0.5, 0.5])
        q = np.sqrt(((x - .5) / .4) ** 2 + ((y - .5) / .06) ** 2
                    + ((z - .5) / .1) ** 2)
        sdf = jnp.asarray(((1.0 - q) * .06).astype(np.float32))
        chi = heaviside(sdf, h)
    else:
        h, xc, sdf, chi, c = _sphere_window()
    valid = np.ones(sdf.shape, bool)
    if case == "holes":
        valid[:8, 16:24, :] = False
        valid[32:40, 40:, 8:16] = False
    return h, xc, sdf, chi, jnp.asarray(valid), c


def _band_of(h, sdf, chi, valid):
    """The window's surface mask and measure, flat, on the host."""
    dS, _, surf = sf._surface_band(sdf, chi, valid, h)
    surf = np.asarray(surf).reshape(-1)
    return surf, np.where(surf, np.asarray(dS).reshape(-1), 0.0)


def _probe_args(case, seed=0):
    h, xc, sdf, chi, valid, c = _window(case)
    rng = np.random.default_rng(seed)
    vel = jnp.asarray(rng.normal(size=sdf.shape + (3,)), jnp.float32)
    p = jnp.asarray(rng.normal(size=sdf.shape), jnp.float32)
    udef = 0.1 * jnp.asarray(rng.normal(size=sdf.shape + (3,)), jnp.float32)
    cm, ut, om = (jnp.asarray(v, jnp.float32)
                  for v in (c, [.1, .2, .3], [.3, .1, .2]))
    return (vel, p, chi, sdf, udef, valid, jnp.asarray(xc), h, 1e-2,
            cm, ut, om)


def _probe_window(case, **kw):
    a = _probe_args(case)
    return _probe_jit(*a[:7], h=a[7], nu=a[8], cm=a[9], u_trans=a[10],
                      omega=a[11], **kw)


def _cells(out, xc):
    """Flat window indices of the evaluated points of a per-point run,
    in the order of their slots."""
    surf = np.asarray(out["points"]["surf"])
    x = np.asarray(out["points"]["x"])[surf]
    n = xc.shape[0]
    ijk = np.floor(x * n).astype(np.int64)  # the windows span the unit box
    return (ijk[:, 0] * n + ijk[:, 1]) * n + ijk[:, 2]


def _pack_gap(a, b):
    """Largest gap of any entry of two force packs, relative to the
    entry's own size."""
    worst = 0.0
    for k in a:
        if k == "points":
            continue
        x, y = np.asarray(a[k], np.float64), np.asarray(b[k], np.float64)
        worst = max(worst, np.max(np.abs(x - y)) / max(np.max(np.abs(x)),
                                                       1e-30))
    return worst


@pytest.mark.parametrize("case", ["sphere", "fish", "holes"])
def test_loop_over_the_occupied_slots_sums_the_whole_band(case):
    """A band that fits its budget by one slot: the slots hold exactly
    the band's cells (NumPy's, of the mask), largest measure first, and
    every entry of the force pack, summed chunk by chunk over the
    occupied slots alone, agrees to 1e-5 of itself with the sums over
    all slots at once; a budget of 20x the band, as the drivers give it,
    changes no bit of them."""
    h, xc, sdf, chi, valid, c = _window(case)
    band = np.flatnonzero(_band_of(h, sdf, chi, valid)[0])
    assert band.size > 1000
    K = band.size + 1
    looped = _probe_window(case, max_points=K)
    at_once = _probe_window(case, max_points=K, per_point=True)
    for out in (looped, at_once):
        assert int(out["n_surf"]) == band.size
    assert (np.sort(_cells(at_once, xc)) == band).all()
    pts = at_once["points"]
    assert not np.asarray(pts["surf"])[band.size:].any()
    assert (np.diff(np.asarray(pts["dS"])) <= 0).all()  # not by index
    assert _pack_gap(looped, at_once) < 1e-5
    if case == "sphere":
        roomy = _probe_window(case, max_points=20 * band.size)
        assert _pack_gap(looped, roomy) == 0.0


def test_overflowing_band_keeps_its_largest_cells():
    """One slot too few: the one cell of the smallest measure is dropped,
    the true count is reported, and the loop sums the K slots it has."""
    h, xc, sdf, chi, valid, c = _window("sphere")
    surf, dS = _band_of(h, sdf, chi, valid)
    band = np.flatnonzero(surf)
    K = band.size - 1
    cut = _probe_window("sphere", max_points=K, per_point=True)
    assert int(cut["n_surf"]) == band.size
    kept = _cells(cut, xc)
    assert kept.size == K
    dropped = np.setdiff1d(band, kept)
    assert dropped.size == 1 and dS[dropped[0]] == dS[band].min()
    assert _pack_gap(_probe_window("sphere", max_points=K), cut) < 1e-5


def _primitives(jaxpr, inside=()):
    """(primitive name, names of the enclosing eqns) of every eqn."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub, inside + (eqn.primitive.name,))


def _traced(**kw):
    a = _probe_args("sphere")

    def fn(*arrays):
        return sf.surface_force_window(*arrays[:7], a[7], a[8],
                                       *arrays[7:], **kw)

    return jax.make_jaxpr(fn)(*a[:7], *a[9:]).jaxpr


def test_the_samples_are_gathered_inside_the_loop():
    """One top_k fills the slots, outside the loop and with no second
    arm beside it; the ~60 samples a slot are gathered inside the loop
    over the occupied chunks, unless the per-point record asks for every
    slot."""
    eqns = list(_primitives(_traced(max_points=20000)))
    assert [at for p, at in eqns if p in ("sort", "top_k")] == [()]
    assert "cond" not in {p for p, _ in eqns}
    looped = [at for p, at in eqns if p == "gather"]
    assert sum("while" in at for at in looped) > 50
    assert sum("while" not in at for at in looped) == 0
    record = {p for p, _ in _primitives(_traced(max_points=20000,
                                                per_point=True))}
    assert "while" not in record and "gather" in record


@pytest.mark.slow
def test_dump_surface_points_driver(tmp_path):
    """End-to-end: a sphere on the AMR driver dumps a compact per-point
    surface record whose traction sums match the obstacle's stored
    force QoI."""
    from cup3d_tpu.config import SimulationConfig
    from cup3d_tpu.sim.amr import AMRSimulation

    cfg = SimulationConfig(
        bpdx=2, bpdy=2, bpdz=2, levelMax=2, levelStart=1, extent=1.0,
        CFL=0.3, nu=1e-3, tend=0.0, nsteps=3, rampup=0, dt=1e-3,
        poissonSolver="iterative", poissonTol=1e-5, poissonTolRel=1e-3,
        factory_content="Sphere radius=0.14 xpos=0.5 ypos=0.5 zpos=0.5 "
                        "xvel=0.3 bForcedInSimFrame=1",
        verbose=False, freqDiagnostics=0,
    )
    sim = AMRSimulation(cfg)
    sim.init()
    sim.simulate()
    ob = sim.obstacles[0]
    path = str(tmp_path / "surf.npy")
    n = sf.dump_surface_points(
        path, sim.grid, {"vel": sim.state["vel"], "p": sim.state["p"]},
        ob, sim.nu,
    )
    rows = np.load(path)
    assert rows.shape == (n, len(sf.SURFACE_POINT_COLUMNS)) and n > 0
    cols = {k: i for i, k in enumerate(sf.SURFACE_POINT_COLUMNS)}
    F = (rows[:, [cols["fxP"], cols["fyP"], cols["fzP"]]].sum(0)
         + rows[:, [cols["fxV"], cols["fyV"], cols["fzV"]]].sum(0))
    np.testing.assert_allclose(F, np.asarray(ob.force), rtol=1e-3,
                               atol=1e-8)
