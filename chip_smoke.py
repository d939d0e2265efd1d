"""Chip smoke: the stock solver path, once, on the attached TPU.

    python chip_smoke.py              # one chip: phase A, then phase B
    python chip_smoke.py --phase A    # one phase only (cache re-run check)
    python chip_smoke.py --chips 4    # four chips: the x-slab megaloop only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # CPU rehearsal

One process, no CPU branch: the first thing it does is ask JAX for its
devices and exit non-zero unless the first one is a TPU.  ``--rehearse``
only shrinks sizes and skips that assertion (and the kernel-in-the-
executable checks that follow from it); its last line never says
``"ok": true``.

- Phase A, uniform: the ``bench.py`` headline case through the public
  driver — ``Simulation`` at 128^3, one StefanFish, the iterative
  Poisson solver at 1e-6/1e-4 — a few ``advance()`` steps, then
  ``simulate()`` on the K=8 scan megaloop.
- Phase B, forest: the README acceptance case (two StefanFish,
  ``-levelMax 4 -levelStart 3``, iterative solver) through
  ``cup3d_tpu.__main__.main``, first fish placed as ``bench.py`` does.
- ``--chips 4``: the 128^3 fish megaloop under ``CUP3D_MESH_X=4``
  against the same case unsharded, and no other phase.

Every phase prints one JSON line of smoke readings (compile and steady
seconds, iterations, blocks, peak device memory).  They are proof that
the path runs, not benchmark numbers.  A failed check raises; nothing
is caught.  The LAST line is the device line and nothing else.
"""

from __future__ import annotations

import argparse
import json
import os
import shlex
import sys
import tempfile
import time

FISH = ("StefanFish L=0.4 T=1.0 xpos=0.5 ypos=0.5 zpos=0.5 "
        "bFixFrameOfRef=1 heightProfile=danio widthProfile=stefan")

#: README.md "Running": the reference acceptance case (run.sh flags),
#: with the first fish at xpos=0.3 as in bench.py's two_fish_amr cell and
#: tests/test_cli.py.  The divergence gate was set on that placement; at
#: run.sh's own xpos=0.2 the body reaches the periodic x boundary and
#: the fluid-divergence probe reads 0.012 by step 7 and 0.042 by step 31
#: (CPU, either stepping path) against 0.0006 and 0.0012 here — a
#: property of the case (the reference binary reads 0.04-0.11 on it,
#: bench.py bench_fish_uniform), not of the chip.
README_CASE = (
    "-bpdx 1 -bpdy 1 -bpdz 1 -CFL 0.4 -Ctol 0.1 -extentx 1 "
    "-factory-content 'StefanFish L=0.4 T=1.0 xpos=0.3 ypos=0.5 zpos=0.5 "
    "planarAngle=180 heightProfile=danio widthProfile=stefan "
    "bFixFrameOfRef=1\n"
    "StefanFish L=0.4 T=1.0 xpos=0.7 ypos=0.5 zpos=0.5 "
    "heightProfile=danio widthProfile=stefan' "
    "-levelMax 4 -levelStart 3 -nu 0.001 -poissonSolver iterative -Rtol 5"
)

SCAN_K = 8


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: CHECK FAILED: {what}")


def timed(fn, live_state):
    """``(seconds, fn())`` with the device work done: ``live_state(result)``
    is fetched after the call (donated buffers rebind every step)."""
    import jax

    t0 = time.perf_counter()
    result = fn()
    jax.block_until_ready(live_state(result))
    return time.perf_counter() - t0, result


def peak_bytes(device):
    stats = device.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def all_finite(state, keys) -> bool:
    import jax.numpy as jnp

    return all(bool(jnp.all(jnp.isfinite(state[k]))) for k in keys)


def iters_per_solve(delta: dict, driver: str):
    """Mean BiCGSTAB iterations per solve over a window of the obs
    registry (the drivers feed ``poisson.iters_hist`` from the packed
    per-step solver telemetry)."""
    key = f"poisson.iters_hist{{driver={driver}}}"
    n = delta.get(f"{key}.count", 0)
    return round(delta.get(f"{key}.sum", 0.0) / n, 2) if n else None


def fish_cfg(n: int, workdir: str, nsteps: int, solver: str = "iterative"):
    from cup3d_tpu.config import SimulationConfig

    bpd = n // 8
    return SimulationConfig(
        bpdx=bpd, bpdy=bpd, bpdz=bpd, levelMax=1, levelStart=0, extent=1.0,
        CFL=0.4, nu=1e-3, tend=0.0, nsteps=nsteps, rampup=100,
        poissonSolver=solver, poissonTol=1e-6, poissonTolRel=1e-4,
        factory_content=FISH, verbose=False, freqDiagnostics=0,
        pipelined=True, scan_k=SCAN_K, path4serialization=workdir,
    )


# -- the kernel cannot be missing ------------------------------------------


def getz_kernel_check(n_tiles: int, on_tpu: bool) -> dict:
    """``krylov.block_cg_tiles`` at the run's tile count: on a TPU the
    lowered program must hold the Pallas kernel (a silent drop to
    ``block_cg_tiles_reference`` cannot pass), and its result must agree
    with that reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cup3d_tpu.ops import getz_pallas, krylov

    iters = 24
    b = jax.random.normal(jax.random.PRNGKey(0), (n_tiles, 8, 8, 8),
                          jnp.float32)
    fast = jax.jit(lambda x: krylov.block_cg_tiles(x, iters))
    has_kernel = "tpu_custom_call" in fast.lower(b).as_text()
    if on_tpu:
        check(getz_pallas.use_pallas(), "use_pallas() is false on a TPU")
        check(has_kernel, "no tpu_custom_call in block_cg_tiles: the "
                          "getZ kernel fell back to the jnp reference")
        got = fast(b)
    else:  # rehearsal: the same kernel body, interpreted
        got = getz_pallas.block_cg_tiles_fast(b, iters, interpret=True)
    want = jax.jit(
        lambda x: krylov.block_cg_tiles_reference(x, iters))(b)
    err = float(jnp.max(jnp.abs(got - want)))
    check(np.isfinite(err) and err < 1e-3,
          f"getZ kernel vs reference: max|diff| = {err}")
    return {"getz_tiles": n_tiles, "getz_has_tpu_custom_call": has_kernel,
            "getz_max_abs_diff_vs_reference": err}


def aot_round_trip(workdir: str) -> dict:
    """serialize -> deserialize of one jitted getZ application through
    the repo's executable store (aot/store.py)."""
    import jax
    import jax.numpy as jnp

    from cup3d_tpu.aot.store import ExecutableStore
    from cup3d_tpu.ops import krylov

    t0 = time.perf_counter()
    b = jax.random.normal(jax.random.PRNGKey(1), (512, 8, 8, 8),
                          jnp.float32)
    compiled = jax.jit(
        lambda x: krylov.block_cg_tiles(x, 24)).lower(b).compile()
    store = ExecutableStore(os.path.join(workdir, "aot-store"))
    sig = ("chip_smoke", "getz", b.shape)
    check(store.put(sig, compiled, name="getz") is not None,
          "aot store could not serialize the executable")
    loaded = store.get(sig, name="getz")
    check(loaded is not None, "aot store could not load what it wrote")
    same = bool(jnp.all(loaded(b) == compiled(b)))
    check(same, "deserialized executable disagrees with the original")
    return {"aot_round_trip_ok": same,
            "aot_round_trip_s": round(time.perf_counter() - t0, 2)}


# -- phase A: uniform ------------------------------------------------------


def phase_a(n: int, workdir: str, on_tpu: bool, device) -> None:
    import jax

    from bench import _div_gate
    from cup3d_tpu import native
    from cup3d_tpu.obs import metrics as obs_metrics
    from cup3d_tpu.ops import diagnostics as diag
    from cup3d_tpu.sim.simulation import Simulation

    per_step, scan_dispatches = 4, 2
    cfg = fish_cfg(n, os.path.join(workdir, "A"),
                   nsteps=1 + per_step + SCAN_K * scan_dispatches)
    sim = Simulation(cfg)
    sim.init()

    def vel(_):
        return sim.sim.state["vel"]

    def advance(steps):
        for _ in range(steps):
            sim.advance(sim.calc_max_timestep())

    m0 = obs_metrics.snapshot()
    first_step_s, _ = timed(lambda: advance(1), vel)
    step_s = timed(lambda: advance(per_step), vel)[0] / per_step

    # simulate(): the remaining budget is whole K-step scan dispatches;
    # the first call compiles the scan, the second (budget extended by
    # the same amount) is steady
    scan_first_s, _ = timed(sim.simulate, vel)
    check(sim._scan_k == SCAN_K and sim._scan_carry is not None,
          "simulate() did not take the scan megaloop")
    cfg.nsteps += SCAN_K * scan_dispatches
    scan_step_s = timed(sim.simulate, vel)[0] / (SCAN_K * scan_dispatches)
    check(sim.sim.step == cfg.nsteps, f"stopped at step {sim.sim.step}")
    delta = obs_metrics.delta(m0)

    state = sim.sim.state
    check(all_finite(state, ("vel", "p", "chi")), "phase A fields not finite")
    check(float(state["chi"].max()) > 0.0, "phase A: no fish in chi")
    div_fluid = float(diag.fluid_divergence_max(
        sim.sim.grid, state["vel"], state["chi"]))
    gate = _div_gate("fish", 128)
    check(div_fluid < gate, f"phase A fluid divergence {div_fluid} >= {gate}")
    iters = iters_per_solve(delta, "uniform")
    check(iters is not None and iters >= 1, "no BiCGSTAB iterations seen")

    # the driver's own Poisson solve, lowered for this backend: the stock
    # f32 solve is the XLA composition (exact tile solve on the MXU), so
    # the kernel guard is the getZ entry it would otherwise dispatch to
    rhs = jax.ShapeDtypeStruct(state["p"].shape, state["p"].dtype)
    solve_text = jax.jit(
        lambda b: sim.sim.poisson_solver(b)).lower(rhs).as_text()
    emit(phase="A", n=n, steps=sim.sim.step,
         first_step_compile_s=round(first_step_s, 2),
         step_s=step_s, scan_first_compile_s=round(scan_first_s, 2),
         scan_step_s=scan_step_s, scan_k=SCAN_K,
         bicgstab_iters_per_solve=iters, div_max_fluid=div_fluid,
         div_fluid_gate=gate,
         driver_solve_has_tpu_custom_call="tpu_custom_call" in solve_text,
         native_tables_loaded=native.available(),
         peak_bytes_in_use=peak_bytes(device),
         **getz_kernel_check((n // 8) ** 3, on_tpu),
         **aot_round_trip(workdir))


# -- phase B: forest, through the CLI --------------------------------------


def phase_b(nsteps: int, workdir: str, device) -> None:

    from bench import _div_gate
    from cup3d_tpu import native
    from cup3d_tpu.__main__ import main as cli_main
    from cup3d_tpu.analysis.runtime import RecompileCounter
    from cup3d_tpu.obs import metrics as obs_metrics
    from cup3d_tpu.ops.diagnostics import fluid_divergence_max_blocks

    argv = shlex.split(README_CASE) + [
        "-tend", "0", "-nsteps", str(nsteps), "-tdump", "0",
        "-path4serialization", os.path.join(workdir, "B"),
    ]
    m0 = obs_metrics.snapshot()
    main_s, sim = timed(lambda: cli_main(argv), lambda s: s.state["vel"])
    regrids = obs_metrics.delta(m0).get("amr.regrids", 0)
    check(sim.step_idx == nsteps, f"CLI stopped at step {sim.step_idx}")
    check(regrids >= 1, "the run crossed no adaptation")

    # steady window: the same driver, a few more steps (past step 10 the
    # forest adapts 1 step in 20, so these reuse the compiled step)
    more = 4
    sim.cfg.nsteps += more
    sim.cfg.verbose = False
    m1 = obs_metrics.snapshot()
    with RecompileCounter() as rc:
        step_s = timed(sim.simulate, lambda _: sim.state["vel"])[0] / more
    iters = iters_per_solve(obs_metrics.delta(m1), "amr")

    blocks = int(sim.grid.nb)
    check(blocks > 1, f"forest collapsed to {blocks} block(s)")
    check(len(sim.obstacles) == 2, "expected two fish")
    chi_sums = [float(ob.chi.sum()) for ob in sim.obstacles]
    check(all(c > 0.0 for c in chi_sums), f"a fish is missing: {chi_sums}")
    check(all_finite(sim.state, ("vel", "p", "chi")),
          "phase B fields not finite")
    div_fluid = float(fluid_divergence_max_blocks(
        getattr(sim, "_geom", None) or sim.grid,
        sim.state["vel"], sim.state["chi"], sim._tab1))
    gate = _div_gate("two_fish_amr")
    check(div_fluid < gate, f"phase B fluid divergence {div_fluid} >= {gate}")
    emit(phase="B", entry="cup3d_tpu.__main__.main", level_max=4,
         steps=sim.step_idx, blocks=blocks,
         bucket_capacity=int(getattr(sim, "_cap", blocks)),
         regrids_in_main=int(regrids), chi_sum_per_fish=chi_sums,
         main_wall_s_init_compile_and_steps=round(main_s, 2),
         step_s=step_s, steady_window_compiles=int(rc.total_compiles),
         bicgstab_iters_per_solve=iters, div_max_fluid=div_fluid,
         div_fluid_gate=gate, native_tables_loaded=native.available(),
         peak_bytes_in_use=peak_bytes(device))


# -- four chips: the x-slab megaloop ---------------------------------------


def phase_mesh(n: int, workdir: str, on_tpu: bool) -> None:
    """CUP3D_MESH_X=4 against the same case unsharded on device 0.  The
    sharded scan body solves Poisson replicated with the spectral solver
    (the iterative front-ends have no slab form; asking for them under a
    mesh raises), so both legs use it."""
    import jax.numpy as jnp
    import numpy as np

    from cup3d_tpu.parallel import ring
    from cup3d_tpu.sim.simulation import Simulation

    dispatches = 3

    def run(mesh_x: int, tag: str):
        os.environ.pop("CUP3D_MESH_X", None)
        if mesh_x:
            os.environ["CUP3D_MESH_X"] = str(mesh_x)
        sim = Simulation(fish_cfg(n, os.path.join(workdir, tag),
                                  nsteps=SCAN_K * dispatches,
                                  solver="spectral"))
        sim.init()
        wall, _ = timed(sim.simulate, lambda _: sim.sim.state["vel"])
        os.environ.pop("CUP3D_MESH_X", None)
        check(sim.sim.step == SCAN_K * dispatches and sim._scan_carry
              is not None, f"{tag}: the scan megaloop did not run")
        return sim, wall

    solo, solo_s = run(0, "solo")
    check(solo._scan_mesh is None, "solo leg is sharded")
    shd, shd_s = run(4, "sharded")
    check(shd._scan_mesh is not None, "sim._scan_mesh is not set")
    vel = shd.sim.state["vel"]
    holders = sorted({s.device.id for s in vel.addressable_shards})
    check(len(holders) == 4, f"vel lives on devices {holders}, not on 4")
    check(all(s.data.shape[0] == n // 4 for s in vel.addressable_shards),
          "vel shards are not x-slabs")

    fn, _ = shd._megaloop
    text = fn.lower(shd._scan_carry,
                    jnp.zeros((SCAN_K,), vel.dtype)).as_text()
    has_dma = "tpu_custom_call" in text
    has_ppermute = "collective_permute" in text
    if on_tpu:
        check(ring.use_ring_dma(), "use_ring_dma() is false on a TPU")
        check(has_dma and not has_ppermute,
              "ring transport is not the Pallas remote copy "
              f"(tpu_custom_call={has_dma}, ppermute={has_ppermute})")
    a, b = np.asarray(solo.sim.state["vel"]), np.asarray(vel)
    check(bool(np.isfinite(b).all()), "sharded velocity not finite")
    diff = float(np.abs(a - b).max())
    # the bound tests/test_sharding.py holds a sharded step to
    np.testing.assert_allclose(b, a, atol=2e-5, rtol=1e-4)
    emit(phase="mesh", n=n, mesh_x=4, steps=shd.sim.step,
         vel_shard_devices=holders, ring_tpu_custom_call=has_dma,
         ring_collective_permute=has_ppermute,
         max_abs_vel_diff_vs_solo=diff, vel_abs_max=float(np.abs(a).max()),
         solo_wall_s_with_compile=round(solo_s, 2),
         sharded_wall_s_with_compile=round(shd_s, 2))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: small sizes, no platform check")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--phase", choices=("A", "B"),
                    help="one-chip run of a single phase")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    device = devices[0]
    on_tpu = device.platform == "tpu"
    if not args.rehearse and not on_tpu:
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {device.platform!r}")
    if len(devices) < args.chips:
        raise SystemExit(
            f"chip_smoke: --chips {args.chips} with {len(devices)} device(s)")

    from cup3d_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    cached_before = compile_cache.entries(cache_dir)
    emit(cache_dir=cache_dir,
         cache_dir_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         cache_entries_before=len(cached_before))
    n = 32 if args.rehearse else 128
    with tempfile.TemporaryDirectory(prefix="cup3d-chip-smoke-") as workdir:
        if args.chips == 4:
            phase_mesh(n, workdir, on_tpu)
        else:
            if args.phase in (None, "A"):
                phase_a(n, workdir, on_tpu, device)
            if args.phase in (None, "B"):
                phase_b(3 if args.rehearse else 12, workdir, device)
    cached_after = compile_cache.entries(cache_dir)
    emit(cache_dir=cache_dir, cache_entries_after=len(cached_after),
         cache_entries_new=sorted(cached_after - cached_before))
    emit(ok=on_tpu and not args.rehearse,
         device={"platform": device.platform, "kind": device.device_kind,
                 "count": len(devices)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
