"""Fused bucketed-forest BiCGSTAB + on-device regrid decision + AMR
fleet tenancy (ISSUE 11; VALIDATION.md "Round 15").

The contract under test:

- every Pallas stage of ops/fused_amr_bicgstab.py matches its jnp twin
  in interpreter mode on a PADDED mixed-level forest, with the traced
  per-block h^2/volume columns in play;
- the fused driver matches the legacy krylov.bicgstab composition
  (build_amr_poisson_solver_dynamic with CUP3D_FUSED off) to <= 1e-4
  relative on a two-level system at matched residual targets;
- padding blocks contribute nothing: garbage in padding rows of the
  rhs never perturbs the real solution, and the returned x is exactly
  zero there;
- the on-device regrid decision (grid/adapt.py device_tags) agrees
  BITWISE with the host tag_states composition on a mixed R/C/L field,
  before and after applying the regrid it decided;
- an amr_tgv job is a first-class fleet tenant: in a mixed drain its
  lane reproduces the solo lax.scan of sim/amr.make_amr_tgv_step, and
  a NaN injected into one AMR lane leaves sibling lanes bitwise
  identical while the faulted lane rolls back and completes;
- regrids steer through the device tags without breaking the bucketed
  compiled-step cache: re-entering a visited bucket via the
  refine -> coarsen -> refine ping-pong adds ZERO compiles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cup3d_tpu.analysis.runtime import RecompileCounter
from cup3d_tpu.config import SimulationConfig
from cup3d_tpu.grid import adapt as ad
from cup3d_tpu.grid import bucket as bk
from cup3d_tpu.grid.blocks import BlockGrid
from cup3d_tpu.grid.faces import pad_face_tables
from cup3d_tpu.grid.flux import build_flux_tables, pad_flux_tables
from cup3d_tpu.grid.octree import Octree, TreeConfig
from cup3d_tpu.grid.uniform import BC
from cup3d_tpu.ops import amr_ops, krylov
from cup3d_tpu.ops import fused_amr_bicgstab as fa
from cup3d_tpu.sim.amr import AMRSimulation
from tests._grids import BS, randn


class _Geom:
    """Duck-typed padded geometry (the sim/amr._ArgGeom shape)."""

    def __init__(self, g, cap, h):
        self.bs, self.nb, self.extent = g.bs, cap, g.extent
        self.h = jnp.asarray(h, jnp.float32)


def _forest(nref=1):
    """Two-level periodic forest with ``nref`` refined octants, bucket-
    padded: (geom, grid, tab, ftab, graph, vol, mask)."""
    tree = Octree(TreeConfig((2, 2, 2), 2, (True,) * 3), 0)
    for leaf in sorted(tree.leaves)[:nref]:
        tree.refine(leaf)
    g = BlockGrid(tree, (1.0,) * 3, (BC.periodic,) * 3, BS)
    cap = bk.capacity(g.nb)
    tab = pad_face_tables(g.face_tables(1), g, cap)
    ftab = pad_flux_tables(build_flux_tables(g), g.bs, cap)
    graph = krylov.block_graph_tables(g, cap=cap)
    h = np.ones(cap)
    h[: g.nb] = g.h
    vol = np.zeros((cap, 1, 1, 1), np.float32)
    vol[: g.nb, 0, 0, 0] = g.h ** 3
    mask = (vol > 0).astype(np.float32)
    return (_Geom(g, cap, h), g, tab, ftab, graph,
            jnp.asarray(vol), jnp.asarray(mask))


def _masked_rhs(g, vol, mask, seed=0):
    rng = np.random.default_rng(seed)
    cap = int(mask.shape[0])
    rhs = np.zeros((cap, BS, BS, BS), np.float32)
    rhs[: g.nb] = rng.standard_normal((g.nb, BS, BS, BS))
    rhs = jnp.asarray(rhs)
    b = rhs - jnp.sum(rhs * vol) / (jnp.sum(vol) * BS ** 3)
    return b * mask


# -- per-stage interpret-mode kernel parity on the padded forest -------------


def _stage_pair(npad):
    C = min(fa.BLOCK_CHUNK, npad)
    mk = lambda k: fa._Stages(bs=BS, npad=npad, C=C, store=jnp.float32,
                              kernels=k, interpret=k)
    return mk(False), mk(True)


def _close(a, b, tol=2e-6):
    a, b = jnp.asarray(a), jnp.asarray(b)
    sc = max(float(jnp.max(jnp.abs(a))), 1.0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=0, atol=tol * sc)


def test_stage_parity_on_padded_forest():
    """update/getz/lap/axpy/finish: interpret kernels vs jnp twins with
    traced per-block geometry columns, padding rows zero."""
    from cup3d_tpu.ops import tilesolve
    from cup3d_tpu.ops.fused_bicgstab import _scalars

    geom, g, tab, ftab, graph, vol, mask = _forest()
    npad = geom.nb
    tw, kn = _stage_pair(npad)
    rng = np.random.default_rng(3)
    mask4 = np.asarray(mask).reshape(npad, 1, 1, 1)
    r, p, v, rhat = (randn(rng, npad, BS, BS, BS) * mask4
                     for _ in range(4))
    h_col = jnp.reshape(geom.h, (npad, 1, 1, 1))
    h2, inv_h2 = h_col * h_col, 1.0 / (h_col * h_col)
    S3, lam3, _ = tilesolve._basis(BS, "float32")
    lam = lam3.reshape(1, BS ** 3)

    sc = _scalars(0.7, 1.3, 0.0)
    for a, b in zip(tw.update(r, p, v, rhat, vol, sc),
                    kn.update(r, p, v, rhat, vol, sc)):
        _close(a, b)
    zc = randn(rng, npad, 1, 1, 1)
    azf = randn(rng, npad, BS, BS, BS) * mask4
    _close(tw.getz(p, azf, zc, h2, S3, lam),
           kn.getz(p, azf, zc, h2, S3, lam), tol=1e-5)
    _close(tw.getz(p, None, None, h2, S3, lam),
           kn.getz(p, None, None, h2, S3, lam), tol=1e-5)
    lab = jnp.asarray(tab.assemble_scalar(p, BS))
    corr = randn(rng, npad, BS, BS, BS) * mask4
    for a, b in zip(tw.lap(lab, corr, rhat, inv_h2),
                    kn.lap(lab, corr, rhat, inv_h2)):
        _close(a, b)
    for a, b in zip(tw.axpy(r, v, vol, _scalars(0.3)),
                    kn.axpy(r, v, vol, _scalars(0.3))):
        _close(a, b)
    x = randn(rng, npad, BS, BS, BS) * mask4
    for a, b in zip(tw.finish(x, p, v, r, rhat, rhat, _scalars(0.3, 0.8)),
                    kn.finish(x, p, v, r, rhat, rhat, _scalars(0.3, 0.8))):
        _close(a, b)


def test_fused_driver_interpret_matches_twin():
    """Whole-solve parity: identical iteration counts, matching x, and
    padding rows exactly zero on both paths."""
    geom, g, tab, ftab, graph, vol, mask = _forest()
    b = _masked_rhs(g, vol, mask)
    kw = dict(tab=tab, ftab=ftab, vol=vol, graph=graph, tol_abs=1e-8,
              tol_rel=1e-5, maxiter=40, store_dtype=jnp.float32,
              rnorm_ref=jnp.sqrt(jnp.sum(b * b)))
    x_tw, rn_tw, k_tw = fa.fused_amr_bicgstab(geom, b, kernels=False, **kw)
    x_kn, rn_kn, k_kn = fa.fused_amr_bicgstab(geom, b, interpret=True, **kw)
    assert int(k_tw) == int(k_kn)
    _close(x_tw, x_kn, tol=1e-5)
    assert float(jnp.max(jnp.abs(x_tw[g.nb:]))) == 0.0
    assert float(jnp.max(jnp.abs(x_kn[g.nb:]))) == 0.0


# -- fused vs legacy solve equivalence ---------------------------------------


def _dynamic_solver_args(geom, tab, ftab, graph, vol, mask):
    return dict(tab_arg=tab, flux_arg=ftab, geom=geom, vol=vol,
                pmask=mask, graph=graph)


@pytest.mark.parametrize("two_level", [True, False])
def test_fused_matches_legacy_dynamic_solver(monkeypatch, two_level):
    """build_amr_poisson_solver_dynamic with CUP3D_FUSED=1 vs the legacy
    composition: <= 1e-4 relative agreement at matched residual targets
    on the mixed two-level forest (the ISSUE 11 pinned bound)."""
    geom, g, tab, ftab, graph, vol, mask = _forest(nref=2)
    if not two_level:
        graph = None
    rhs = _masked_rhs(g, vol, mask, seed=7)
    kw = _dynamic_solver_args(geom, tab, ftab, graph, vol, mask)

    monkeypatch.delenv("CUP3D_FUSED", raising=False)
    monkeypatch.delenv("CUP3D_KRYLOV_DTYPE", raising=False)
    legacy = amr_ops.build_amr_poisson_solver_dynamic(
        BS, tol_abs=1e-8, tol_rel=1e-6, maxiter=200)
    x_leg = legacy(rhs, **kw)

    monkeypatch.setenv("CUP3D_FUSED", "1")
    fused = amr_ops.build_amr_poisson_solver_dynamic(
        BS, tol_abs=1e-8, tol_rel=1e-6, maxiter=200)
    x_fus, stats = fused(rhs, with_stats=True, **kw)
    assert int(stats[1]) > 0
    scale = float(jnp.max(jnp.abs(x_leg))) or 1.0
    rel = float(jnp.max(jnp.abs(x_fus - x_leg))) / scale
    assert rel <= 1e-4, rel


@pytest.mark.parametrize("knob", [("CUP3D_FUSED", "1"),
                                  ("CUP3D_KRYLOV_DTYPE", "bf16")])
def test_fused_forest_refused_where_kernels_would_compile(monkeypatch,
                                                          knob):
    """The fused forest stages abort the TPU's compiler (module
    docstring), so where they would be compiled natively — a backend on
    which ``use_pallas()`` is true — selecting the fused forest solver
    raises at BUILD time; the stock solver builds as ever, and nothing
    stands in for the refused one."""
    from cup3d_tpu.ops import getz_pallas

    monkeypatch.delenv("CUP3D_FUSED", raising=False)
    monkeypatch.delenv("CUP3D_KRYLOV_DTYPE", raising=False)
    monkeypatch.setattr(getz_pallas, "use_pallas", lambda: True)
    amr_ops.build_amr_poisson_solver_dynamic(BS)  # stock f32: fine
    monkeypatch.setenv(*knob)
    with pytest.raises(NotImplementedError, match="do not compile"):
        amr_ops.build_amr_poisson_solver_dynamic(BS)
    # pinned-row modes keep the legacy loop and are not refused
    if knob[0] == "CUP3D_FUSED":
        amr_ops.build_amr_poisson_solver_dynamic(BS, mean_constraint=1)


def test_padding_rows_contribute_nothing(monkeypatch):
    """Garbage in the padding rows of the INPUT rhs is masked out by the
    dynamic solver's pmask and never reaches the real solution; the
    returned x carries exactly-zero padding rows."""
    geom, g, tab, ftab, graph, vol, mask = _forest()
    rhs = _masked_rhs(g, vol, mask, seed=5)
    rng = np.random.default_rng(11)
    garbage = np.zeros(rhs.shape, np.float32)
    garbage[g.nb:] = 1e3 * rng.standard_normal(
        (rhs.shape[0] - g.nb, BS, BS, BS))
    monkeypatch.setenv("CUP3D_FUSED", "1")
    solve = amr_ops.build_amr_poisson_solver_dynamic(
        BS, tol_abs=1e-8, tol_rel=1e-6, maxiter=80)
    kw = _dynamic_solver_args(geom, tab, ftab, graph, vol, mask)
    x_clean = solve(rhs, **kw)
    x_dirty = solve(rhs + jnp.asarray(garbage), **kw)
    np.testing.assert_array_equal(np.asarray(x_clean[: g.nb]),
                                  np.asarray(x_dirty[: g.nb]))
    assert float(jnp.max(jnp.abs(x_dirty[g.nb:]))) == 0.0


# -- on-device regrid decision ----------------------------------------------


def _amr_cfg(tmp_path, **kw):
    base = dict(
        bpdx=4, bpdy=4, bpdz=4, levelMax=2, levelStart=0,
        extent=float(2 * np.pi), nu=1e-3, nsteps=2, rampup=0, tend=-1.0,
        dt=1e-3, Rtol=1e9, Ctol=-1.0, initCond="taylorGreen",
        step_2nd_start=0, pipelined=True, verbose=False,
        path4serialization=str(tmp_path),
    )
    base.update(kw)
    return SimulationConfig(**base)


def _host_states(sim):
    """The exact adapt_mesh host composition, replicated."""
    g, cfg = sim.grid, sim.cfg
    vort, near_body = sim._scores(sim.state["vel"], sim.state["chi"])
    score = np.asarray(vort, np.float64)[: g.nb]
    near = np.asarray(near_body)[: g.nb] > 0.5
    if cfg.bAdaptChiGradient and near.any():
        score = np.where(near, np.inf, score)
    cap = np.where(near, cfg.levelMax - 1, cfg.levelMaxVorticity - 1)
    return ad.tag_states(g, score, cfg.Rtol, cfg.Ctol, cap)


def test_device_tags_bitwise_match_host(tmp_path):
    """The on-device regrid decision reproduces the host tag_states
    BITWISE on a genuinely mixed R/C/L field, the regrid it steers
    applies cleanly, and post-regrid tags agree across levels too."""
    sim = AMRSimulation(_amr_cfg(tmp_path))
    sim.init()
    assert sim._device_tags is not None  # bucketed path binds it
    g = sim.grid
    vort, _ = sim._scores(sim.state["vel"], sim.state["chi"])
    score = np.asarray(vort, np.float64)[: g.nb]
    # thresholds at the f32-rounded 70th/30th percentiles of the live
    # field guarantee a mixed tag set; f32-representable values keep
    # the host's float64 comparison bitwise-equal to the device's f32
    sim.cfg.Rtol = float(np.float32(np.percentile(score, 70)))
    sim.cfg.Ctol = float(np.float32(np.percentile(score, 30)))
    sim._exec_cache.clear()  # ex["tags"] bakes Rtol/Ctol in: rebuild
    sim._rebuild()

    tags = np.asarray(sim._device_tags(sim.state["vel"],
                                       sim.state["chi"]))[: g.nb]
    dev_states = ad.states_from_tags(g, tags)
    assert set(dev_states.values()) >= {"R", "L"}  # genuinely mixed
    assert dev_states == _host_states(sim)

    nb_before = g.nb
    sim.adapt_mesh()  # steered by the device tags
    assert sim.grid.nb != nb_before
    g2 = sim.grid
    tags2 = np.asarray(sim._device_tags(sim.state["vel"],
                                        sim.state["chi"]))[: g2.nb]
    assert ad.states_from_tags(g2, tags2) == _host_states(sim)


def test_device_tag_padding_slots_stay_leave(tmp_path):
    """Padding slots carry level 0 and zero fields: their tag decodes
    to 'L'/'C'-free no-ops — nothing outside the real blocks can steer
    a regrid."""
    sim = AMRSimulation(_amr_cfg(tmp_path))
    sim.init()
    tags = np.asarray(sim._device_tags(sim.state["vel"],
                                       sim.state["chi"]))
    assert tags.shape[0] == sim._cap
    # level 0 blocks cannot coarsen; zero score under Rtol=1e9 cannot
    # refine -> padding tags are exactly 0 ('L')
    assert np.all(tags[sim.grid.nb:] == 0)


def test_regrid_ping_pong_zero_new_compiles(tmp_path):
    """refine -> coarsen -> refine through _apply_states: compiles are
    bounded by DISTINCT buckets (2), and re-entering a visited bucket —
    with the tags executable in the bundle — adds zero."""
    sim = AMRSimulation(_amr_cfg(tmp_path))
    key = (0, 0, 0, 0)

    def states(refine=None, coarsen_parent=None):
        st = {k: "L" for k in sim.grid.keys}
        if refine is not None:
            st[refine] = "R"
        if coarsen_parent is not None:
            l, i, j, k = coarsen_parent
            for di in (0, 1):
                for dj in (0, 1):
                    for dk in (0, 1):
                        st[(l + 1, 2 * i + di, 2 * j + dj,
                            2 * k + dk)] = "C"
        return st

    with RecompileCounter() as rc:
        sim.init()
        sim.advance(sim.calc_max_timestep())
        sim._apply_states(states(refine=key))          # bucket B
        sim.advance(sim.calc_max_timestep())
        sim._apply_states(states(coarsen_parent=key))  # back to bucket A
        sim.advance(sim.calc_max_timestep())
        seen = rc.total_compiles
        sim._apply_states(states(refine=key))          # bucket B again
        sim.advance(sim.calc_max_timestep())
        sim._apply_states(states(coarsen_parent=key))  # bucket A again
        sim.advance(sim.calc_max_timestep())
        assert rc.total_compiles == seen, (
            "bucket re-entry must reuse the compiled bundle "
            f"(+{rc.total_compiles - seen} compiles)")
    # both buckets live in the cache (keys also carry the table treedef
    # and non-capacity entries like the megaloop bundle, so we only pin
    # the number of distinct capacities)
    caps = {k[0] for k in sim._exec_cache if isinstance(k[0], int)}
    assert len(caps) == 2, caps


# -- AMR lanes as fleet tenants ---------------------------------------------


def _amr_spec(**kw):
    spec = dict(kind="amr_tgv", bpd=2, levelMax=2, nsteps=8, cfl=0.3,
                nu=0.02)
    spec.update(kw)
    return spec


def _solo_amr(tmp, spec):
    """The solo twin of an amr_tgv lane: same config factory, topology
    frozen after init, direct lax.scan of make_amr_tgv_step."""
    from cup3d_tpu.fleet import batch as FB
    from cup3d_tpu.fleet.server import _job_config
    from cup3d_tpu.sim.amr import make_amr_tgv_step
    from cup3d_tpu.sim.dtpolicy import ramped_cfl

    _, cfg = _job_config(spec, str(tmp))
    sim = AMRSimulation(cfg)
    sim.init()
    sim.adapt_enabled = False
    core = make_amr_tgv_step(sim)
    carry = FB.init_amr_carry(sim)
    cfl = jnp.asarray(
        [ramped_cfl(cfg.CFL, k, cfg.rampup)
         for k in range(int(spec["nsteps"]))], sim.dtype)
    carry, rows = jax.lax.scan(core, carry, cfl)
    return sim, jax.device_get(carry), np.asarray(rows)


def test_amr_lane_in_mixed_drain_matches_solo(tmp_path):
    """Mixed drain (2 amr_tgv tenants + 1 uniform tgv tenant): the AMR
    lanes run as first-class tenants and each reproduces its solo scan
    to the vmap-lowering tolerance; distinct CFLs stay distinct."""
    from cup3d_tpu.fleet.server import DONE, FleetServer

    specs = [_amr_spec(cfl=0.3), _amr_spec(cfl=0.25),
             dict(kind="tgv", n=16, nsteps=8, cfl=0.3)]
    srv = FleetServer(workdir=str(tmp_path / "fleet"))
    ids = [srv.submit(f"tenant-{i}", sp) for i, sp in enumerate(specs)]
    srv.drain()
    for i, (job_id, spec) in enumerate(zip(ids[:2], specs[:2])):
        assert srv.poll(job_id)["status"] == DONE
        lane = srv.lane_state(job_id)
        _, carry, _ = _solo_amr(tmp_path / f"solo{i}", spec)
        np.testing.assert_allclose(lane["vel"], np.asarray(carry["vel"]),
                                   rtol=0, atol=1e-4)
        assert np.isclose(float(lane["time"]), float(carry["time"]),
                          rtol=1e-4)
        assert np.isclose(float(lane["dt"]), float(carry["dt"]),
                          rtol=1e-4)
    assert srv.poll(ids[2])["status"] == DONE
    assert srv.poll(ids[0])["time"] != srv.poll(ids[1])["time"]


def test_amr_lane_nan_isolated_bitwise(tmp_path):
    """A NaN injected into one AMR lane leaves its sibling AMR lanes
    BITWISE identical to the unfaulted drain while the faulted lane
    rolls back and completes (per-lane isolation extends to adaptive
    tenants)."""
    from cup3d_tpu.fleet.server import DONE, FleetServer
    from cup3d_tpu.obs import metrics as M
    from cup3d_tpu.resilience import faults

    specs = [_amr_spec(cfl=0.3, nsteps=12), _amr_spec(cfl=0.28, nsteps=12),
             _amr_spec(cfl=0.25, nsteps=12)]

    def drain(tmp):
        srv = FleetServer(workdir=str(tmp), snap_every=4)
        ids = [srv.submit(f"t{i}", sp) for i, sp in enumerate(specs)]
        srv.drain()
        return srv, ids

    faults.clear()
    ref, ref_ids = drain(tmp_path / "ref")
    ref_lanes = [ref.lane_state(j) for j in ref_ids]

    faults.arm("fleet.lane_nan", 1, 1)
    try:
        s0 = M.snapshot()
        flt, flt_ids = drain(tmp_path / "flt")
        d = M.delta(s0)
    finally:
        faults.clear()

    for lane in (0, 2):
        a, b = ref_lanes[lane], flt.lane_state(flt_ids[lane])
        assert sorted(a) == sorted(b)
        for key in a:
            np.testing.assert_array_equal(a[key], b[key], err_msg=key)
    assert flt.poll(flt_ids[1])["status"] == DONE
    assert np.isfinite(flt.lane_state(flt_ids[1])["vel"]).all()
    assert d["fleet.lane_rollbacks{reason=nan-velocity}"] == 1
