"""The IR audit driver: canonical entry points, traced and checked.

``analysis/ir.py`` knows how to walk a jaxpr and read alias maps; this
module knows WHAT to walk — the registry below builds every canonical
executable of the tree on tiny shapes (16^3 slabs, B=2 fleets, a
two-level 8^3-block forest) and runs the JP rules over each:

- ``uniform_tgv_megaloop`` / ``uniform_fish_megaloop`` — the solo
  K-step scan megaloops (sim/megaloop.py), carry donated.
- ``amr_tgv_megastep`` — the bucketed-AMR one_step under its own
  scan+jit with the carry donated (the fleet wraps the same body).
- ``fleet_advance`` / ``fleet_reseed_upload`` — the batched vmap
  advance and the one-lane reseed upload (fleet/batch.py); both
  DOCUMENT a no-donation contract (rollback/in-flight consumers need
  the old buffers), so JP001 checks the absence of aliasing.
- ``sharded_tgv_megaloop`` — the mesh-sharded megaloop on a (1, 4)
  (lanes, x) device mesh (parallel/topology.py), carry donated; its
  replicated coarse solve is an ANNOTATED JP003 gather.
- ``fused_bicgstab`` / ``fused_amr_bicgstab`` — the fused Krylov
  stages (ops/), jnp-twin form on CPU.

Contract mirror of the AST linter: stable IDs (JP001–JP005), an
EMPTY shipped baseline (``analysis/audit_baseline.json``),
``--write-baseline`` to start a burn-down, per-entry ``allow``
annotations with reasons (the IR analogue of inline suppression — IR
findings have no source line to annotate), ``--format json`` for CI.

Run it: ``python -m cup3d_tpu.analysis audit`` (tools/lint.sh stage).
Entries trace in-process; the CLI bootstraps JAX_PLATFORMS=cpu and an
8-device host platform BEFORE jax initializes, same as
tests/conftest.py.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from cup3d_tpu.analysis import ir as IR
from cup3d_tpu.analysis import lint as lint_mod
from cup3d_tpu.analysis.rules import Violation
from cup3d_tpu.obs import trace as OT

#: devices the sharded entry needs (a 1x4 (lanes, x) mesh)
MESH_DEVICES = 4


def bootstrap_platform() -> None:
    """Pin jax to CPU with >= MESH_DEVICES virtual devices.  Must run
    before the first jax device access; a jax that already initialized
    (pytest under conftest.py) keeps whatever it has — entries that
    need more devices than exist skip themselves."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


# -- built entries -----------------------------------------------------------


@dataclass
class Built:
    """One traced entry: the jitted callable, its example args, and the
    donation expectation the rules check against.  ``jaxpr`` overrides
    tracing (fixture tests audit hand-mutated jaxprs for the invariant
    classes jax refuses to trace); with ``fn=None`` the lowered/
    compiled donation checks are skipped."""

    fn: Any
    args: Tuple[Any, ...]
    donate_argnums: Tuple[int, ...] = ()
    jaxpr: Any = None


@dataclass
class EntryPoint:
    name: str
    build: Callable[[], Optional[Built]]   # None -> entry skips itself
    compile: bool = True     # cross-check the compiled HLO alias map
    expect_no_donation: bool = False
    #: rule id -> reason: the registry-level suppression (IR findings
    #: have no source line, so the annotation lives with the entry)
    allow: Dict[str, str] = field(default_factory=dict)


def _tmpdir() -> str:
    import tempfile

    d = os.path.join(tempfile.gettempdir(), "cup3d_audit")
    os.makedirs(d, exist_ok=True)
    return d


def _tgv_cfg(**kw):
    import numpy as np

    from cup3d_tpu.config import SimulationConfig

    base = dict(
        bpdx=1, bpdy=1, bpdz=1, block_size=16, levelMax=1, levelStart=0,
        extent=2 * np.pi, CFL=0.3, nu=0.02, nsteps=2, tend=0.0, rampup=0,
        initCond="taylorGreen", dtype="float32", pipelined=True,
        verbose=False, freqDiagnostics=0, path4serialization=_tmpdir(),
    )
    base.update(kw)
    return SimulationConfig(**base)


def _build_uniform_tgv() -> Built:
    import jax.numpy as jnp

    from cup3d_tpu.sim.megaloop import build_tgv_megaloop, init_tgv_carry
    from cup3d_tpu.sim.simulation import Simulation

    sim = Simulation(_tgv_cfg())
    sim.init()
    fn = build_tgv_megaloop(sim.sim)
    carry = init_tgv_carry(sim.sim)
    cfl = jnp.full((2,), 0.3, sim.sim.dtype)
    return Built(fn, (carry, cfl), donate_argnums=(0,))


def _build_uniform_fish() -> Built:
    import jax.numpy as jnp

    from cup3d_tpu.config import SimulationConfig
    from cup3d_tpu.sim.megaloop import build_body_megaloop, init_body_carry
    from cup3d_tpu.sim.simulation import Simulation

    cfg = SimulationConfig(
        bpdx=1, bpdy=1, bpdz=1, block_size=16, levelMax=1, levelStart=0,
        extent=1.0, CFL=0.3, nu=1e-4, nsteps=2, tend=0.0, rampup=0,
        factory_content="stefanfish L=0.3 T=1.0 xpos=0.5",
        dtype="float32", pipelined=True, verbose=False,
        freqDiagnostics=0, path4serialization=_tmpdir(),
    )
    sim = Simulation(cfg)
    sim.init()
    ob = sim.sim.obstacles[0]
    fn = build_body_megaloop(sim.sim, ob)
    carry = init_body_carry(sim.sim, ob)
    cfl = jnp.full((2,), 0.3, sim.sim.dtype)
    return Built(fn, (carry, cfl), donate_argnums=(0,))


def _build_amr_megastep() -> Built:
    import jax
    import jax.numpy as jnp

    from cup3d_tpu.fleet.batch import init_amr_carry
    from cup3d_tpu.sim.amr import AMRSimulation, make_amr_tgv_step

    cfg = _tgv_cfg(bpdx=2, bpdy=2, bpdz=2, block_size=8, levelMax=2,
                   levelStart=1, Rtol=1e9, Ctol=-1.0)
    sim = AMRSimulation(cfg)
    sim.init()
    sim.adapt_enabled = False          # frozen topology, one bucket
    step = make_amr_tgv_step(sim)

    def megaloop(carry, cfl_eff):
        return jax.lax.scan(step, carry, cfl_eff)

    fn = jax.jit(megaloop, donate_argnums=(0,))
    carry = init_amr_carry(sim)
    cfl = jnp.full((2,), 0.3, jnp.float32)
    return Built(fn, (carry, cfl), donate_argnums=(0,))


def _fleet_batch():
    import jax.numpy as jnp

    from cup3d_tpu.fleet.batch import stack_carries
    from cup3d_tpu.sim.megaloop import init_tgv_carry
    from cup3d_tpu.sim.simulation import Simulation

    sim = Simulation(_tgv_cfg())
    sim.init()
    solo = init_tgv_carry(sim.sim)
    batch = stack_carries([solo, solo], [8, 8])
    cfl = jnp.full((2, 2), 0.3, sim.sim.dtype)
    return sim, solo, batch, cfl


def _build_fleet_advance() -> Built:
    from cup3d_tpu.fleet.batch import build_fleet_advance

    sim, _solo, batch, cfl = _fleet_batch()
    fn = build_fleet_advance(sim.sim)
    return Built(fn, (batch, cfl, None))


def _build_fleet_reseed() -> Built:
    import jax.numpy as jnp

    from cup3d_tpu.fleet import batch as FB

    _sim, solo, batch, _cfl = _fleet_batch()
    solo = dict(solo)
    return Built(FB._upload_lane_carry,
                 (batch, jnp.asarray(0, jnp.int32), solo,
                  jnp.asarray(8, jnp.int32)))


def _build_sharded_tgv() -> Optional[Built]:
    import jax
    import jax.numpy as jnp

    if len(jax.devices()) < MESH_DEVICES:
        return None
    from cup3d_tpu.parallel.topology import make_mesh2d, shard_carry
    from cup3d_tpu.sim.megaloop import (
        build_tgv_megaloop_sharded,
        init_tgv_carry,
    )
    from cup3d_tpu.sim.simulation import Simulation

    mesh = make_mesh2d(lanes=1, x=MESH_DEVICES,
                       devices=jax.devices()[:MESH_DEVICES])
    sim = Simulation(_tgv_cfg())
    sim.init()
    fn = build_tgv_megaloop_sharded(sim.sim, mesh)
    if fn is None:
        return None
    carry = shard_carry(init_tgv_carry(sim.sim), mesh)
    cfl = jnp.full((2,), 0.3, sim.sim.dtype)
    return Built(fn, (carry, cfl), donate_argnums=(0,))


def _build_fused_bicgstab() -> Built:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cup3d_tpu.grid.uniform import BC, UniformGrid
    from cup3d_tpu.ops import krylov
    from cup3d_tpu.ops.fused_bicgstab import fused_bicgstab

    n = 16
    g = UniformGrid((n, n, n), (1.0,) * 3, (BC.periodic,) * 3)
    rng = np.random.default_rng(0)
    rhs = jnp.asarray(rng.standard_normal((n, n, n)), jnp.float32)
    bt = krylov.to_lanes(rhs - jnp.mean(rhs))

    def solve(b):
        return fused_bicgstab(g, b, tol_abs=1e-6, tol_rel=1e-5,
                              maxiter=8, two_level=True,
                              store_dtype=jnp.float32, kernels=False)

    return Built(jax.jit(solve), (bt,))


def _build_fused_amr_bicgstab() -> Built:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cup3d_tpu.grid import bucket as bk
    from cup3d_tpu.grid.blocks import BlockGrid
    from cup3d_tpu.grid.faces import pad_face_tables
    from cup3d_tpu.grid.flux import build_flux_tables, pad_flux_tables
    from cup3d_tpu.grid.octree import Octree, TreeConfig
    from cup3d_tpu.grid.uniform import BC
    from cup3d_tpu.ops import krylov
    from cup3d_tpu.ops.fused_amr_bicgstab import fused_amr_bicgstab

    tree = Octree(TreeConfig((2, 2, 2), 2, (True,) * 3), 0)
    tree.refine(sorted(tree.leaves)[0])
    g = BlockGrid(tree, (1.0,) * 3, (BC.periodic,) * 3, 8)
    cap = bk.capacity(g.nb)
    tab = pad_face_tables(g.face_tables(1), g, cap)
    ftab = pad_flux_tables(build_flux_tables(g), g.bs, cap)
    graph = krylov.block_graph_tables(g, cap=cap)
    h = np.ones(cap)
    h[: g.nb] = g.h
    vol = np.zeros((cap, 1, 1, 1), np.float32)
    vol[: g.nb, 0, 0, 0] = g.h ** 3

    class _Geom:
        pass

    geom = _Geom()
    geom.bs, geom.nb, geom.extent = g.bs, cap, g.extent
    geom.h = jnp.asarray(h, jnp.float32)
    jvol = jnp.asarray(vol)

    rng = np.random.default_rng(0)
    rhs = np.zeros((cap, 8, 8, 8), np.float32)
    rhs[: g.nb] = rng.standard_normal((g.nb, 8, 8, 8))
    b = jnp.asarray(rhs)
    mask = jnp.asarray((vol > 0).astype(np.float32))

    def solve(bb):
        bb = (bb - jnp.sum(bb * jvol) / (jnp.sum(jvol) * g.bs ** 3))
        bb = bb * mask
        return fused_amr_bicgstab(
            geom, bb, tab=tab, ftab=ftab, vol=jvol, graph=graph,
            tol_abs=1e-8, tol_rel=1e-5, maxiter=8,
            store_dtype=jnp.float32,
            rnorm_ref=jnp.sqrt(jnp.sum(bb * bb)), kernels=False)

    return Built(jax.jit(solve), (b,))


#: documented no-donation contract on the fleet paths (fleet/batch.py
#: docstrings): advance keeps the pre-dispatch buffers alive for the
#: isolate.py rollback, the reseed upload for in-flight consumers
_FLEET_CONTRACT = (
    "fleet/batch.py documents the no-donation contract: the rollback/"
    "in-flight-consumer paths need the pre-dispatch buffers"
)

REGISTRY: Tuple[EntryPoint, ...] = (
    EntryPoint("uniform_tgv_megaloop", _build_uniform_tgv),
    EntryPoint("uniform_fish_megaloop", _build_uniform_fish,
               # the fish step compiles ~17 s on the CPU container —
               # JP001 reads the lowered tf.aliasing_output marks
               # instead (where jax records the donation decision)
               compile=False),
    EntryPoint("amr_tgv_megastep", _build_amr_megastep),
    EntryPoint("fleet_advance", _build_fleet_advance,
               expect_no_donation=True),
    EntryPoint("fleet_reseed_upload", _build_fleet_reseed,
               expect_no_donation=True),
    EntryPoint("sharded_tgv_megaloop", _build_sharded_tgv,
               allow={
                   "JP003": (
                       "designed replicated stage: the slab megaloop "
                       "gathers rhs/p for the replicated coarse "
                       "Poisson solve so every shard runs the bitwise-"
                       "identical solver (sim/megaloop.py 'replicated "
                       "global solve'); the distributed-solver rung "
                       "(ROADMAP item 2) retires it"
                   ),
               }),
    EntryPoint("fused_bicgstab", _build_fused_bicgstab),
    EntryPoint("fused_amr_bicgstab", _build_fused_amr_bicgstab),
)


# -- driver ------------------------------------------------------------------


def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(__file__), "audit_baseline.json")


def audit_entry(ep: EntryPoint) -> Tuple[List[Violation], Dict[str, Any]]:
    """Trace (and optionally compile) one entry and run every JP rule.
    Returns (violations, meta); a builder returning None skips the
    entry (meta notes why)."""
    import jax

    # jax-lint: allow(JX008, audit wall budget, not a perf measurement:
    # the 60 s lint.sh stage budget is enforced on trace+lower time)
    t0 = OT.now()
    built = ep.build()
    if built is None:
        return [], {"entry": ep.name, "skipped": True,
                    # jax-lint: allow(JX006, times host-side trace and
                    # lower work only; the audit dispatches no device
                    # execution by design)
                    "wall_s": round(OT.now() - t0, 3)}

    if built.jaxpr is not None:
        closed = built.jaxpr
    else:
        closed = jax.make_jaxpr(built.fn)(*built.args)
    violations = IR.audit_jaxpr(closed, ep.name)

    lowered_text = None
    compiled_text = None
    lower = getattr(built.fn, "lower", None) if built.fn is not None else None
    if lower is not None:
        lowered = lower(*built.args)
        lowered_text = lowered.as_text()
        if ep.compile:
            compiled_text = lowered.compile().as_text()
    donated = IR.donated_leaf_indices(built.args, built.donate_argnums)
    violations += IR.audit_donation(
        ep.name, donated, lowered_text, compiled_text,
        expect_no_donation=ep.expect_no_donation)

    for v in violations:
        reason = ep.allow.get(v.rule)
        if reason is not None:
            v.suppressed = True
            v.suppression_reason = reason
    meta = {
        "entry": ep.name, "skipped": False,
        "compiled": bool(compiled_text is not None),
        "donated_params": donated,
        "wall_s": round(OT.now() - t0, 3),
    }
    return violations, meta


def run_audit(
    entries: Optional[Sequence[EntryPoint]] = None,
    baseline_path: Optional[str] = None,
    rules: Optional[set] = None,
) -> Tuple[List[Violation], List[Dict[str, Any]]]:
    """Audit every registry entry; apply the baseline; return all
    violations (suppressed/baselined flags set) plus per-entry meta."""
    violations: List[Violation] = []
    metas: List[Dict[str, Any]] = []
    for ep in (REGISTRY if entries is None else entries):
        vs, meta = audit_entry(ep)
        violations.extend(vs)
        metas.append(meta)
    if rules:
        violations = [v for v in violations if v.rule in rules]
    baseline = lint_mod.load_baseline(baseline_path)
    lint_mod.apply_baseline(violations, baseline)
    return violations, metas


def summary_line(violations: List[Violation],
                 metas: List[Dict[str, Any]],
                 baseline_path: Optional[str]) -> str:
    """The one-line JSON the CI driver tail greps."""
    failing = lint_mod.failing(violations)
    baseline = lint_mod.load_baseline(baseline_path)
    rules = sorted({v.rule for v in violations})
    return json.dumps({
        "audit": "ir",
        "entries": len(metas),
        "skipped": sum(1 for m in metas if m.get("skipped")),
        "rules_fired": rules,
        "findings": len(violations),
        "failing": len(failing),
        "annotated": sum(1 for v in violations if v.suppressed),
        "baseline_size": len(baseline),
        "wall_s": round(sum(m.get("wall_s", 0.0) for m in metas), 3),
    }, sort_keys=True)
