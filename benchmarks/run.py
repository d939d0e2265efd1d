"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one chip, no CPU branch: without a TPU (or with fewer chips
than the cell asks for) it exits non-zero and prints no result.
``--rehearse`` is for the CPU sandbox only: it shrinks the sizes as the
configuration and traffic files say, skips the platform check, prints no
device metric and always says ``"correct": false``.

The last line of standard output is the result; what was compared, each
number beside its limit, is on the last lines of standard error and under
the result's last key.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks.lib import spec  # noqa: E402


def log(**fields) -> None:
    print(json.dumps(fields), file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def enable_cache() -> str:
    """JAX's persistent cache at a fixed place: where the environment
    says, else ``<checkout>/.jax_cache``.  Every program is kept, also
    the many that compile in under a second (JAX's default skips those,
    and a warm start then compiles them again)."""
    import jax

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return directory


def cache_entries(directory: str) -> set:
    try:
        return {n for n in os.listdir(directory) if n.endswith("-cache")}
    except FileNotFoundError:
        return set()


class CompileLog:
    """Counts what JAX compiles or fetches from its cache, by listening
    to JAX's own monitoring events: nothing in the program is wrapped."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring as mon

        self.count = 0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.count += 1


def device_or_exit(chips: int, rehearse: bool):
    """JAX's devices; without a TPU, or with fewer chips than the cell
    asks for, no result is printed and the exit code is not 0."""
    import jax

    devices = jax.devices()
    if not rehearse:
        if devices[0].platform != "tpu":
            raise SystemExit("benchmark: needs a TPU, JAX found "
                             f"{devices[0].platform!r}")
        if len(devices) < chips:
            raise SystemExit(f"benchmark: cell needs {chips} chips, "
                             f"JAX found {len(devices)}")
    return devices


def traced_window(driver, grid, traffic, solver_spec, spans, workdir,
                  rehearse):
    """``--trace 1``: some further chunks and the solve probe under the
    profiler, reduced to busy time, top operations and named gaps."""
    import jax

    from benchmarks.lib import drive, probe, trace_reduce

    tdir = os.path.join(workdir, "trace")
    spans.annotate = True
    trace_reduce.start(tdir)
    with jax.profiler.TraceAnnotation("bench:traced_window"):
        drive.run_steps(driver,
                        traffic["chunk_steps"] * traffic["trace_chunks"])
        p_before = jax.numpy.copy(driver.sim.state["p"])
        drive.run_steps(driver, int(traffic["check_unit_steps"]))
        drive.sync(driver)
    # a configuration that names no solver has no solve probe, and the
    # metrics that read it say nothing in its cells
    probe_out = None
    if "solver" in solver_spec:
        with jax.profiler.TraceAnnotation("bench:solve_probe"):
            probe_out = probe.run(driver, grid, solver_spec, p_before)
    jax.profiler.stop_trace()
    spans.annotate = False
    trace = trace_reduce.reduce_dir(tdir, window="bench:traced_window",
                                    span_prefix="bench:",
                                    modules=[probe.MODULE])
    if trace is None:
        if not rehearse:  # a CPU rehearsal has no device plane
            raise SystemExit("benchmark: the trace holds no device plane "
                             "or no traced window")
        return None
    trace["probe"] = probe_out
    return trace


def main(argv=None) -> int:
    args = parse_args(argv)
    bench = spec.load_benchmark()
    cell, config, traffic = spec.load_cell(bench, args.workload)
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])
    if args.rehearse:
        config = {**config, **config.get("rehearse", {})}
        traffic = {**traffic, **traffic.get("rehearse", {})}
    devices = device_or_exit(int(cell["chips"]), args.rehearse)
    device = devices[0]

    from benchmarks.lib import compare, drive, peaks, seeding
    from cup3d_tpu import native
    from cup3d_tpu.__main__ import build_driver
    from cup3d_tpu.obs import metrics as obs

    chip = None if args.rehearse else peaks.peaks_for_kind(device.device_kind)
    grid = spec.load_grid(bench, config["driver"]["kind"])
    cache_dir = enable_cache()
    cached_before = cache_entries(cache_dir)
    compiles = CompileLog()

    with tempfile.TemporaryDirectory(prefix="cup3d-bench-") as workdir:
        # -- set-up: build, init, cross the CFL ramp -----------------------
        argv_run = seeding.build_argv(config, traffic, args.seed, workdir)
        driver = build_driver(argv_run)
        spans = drive.Spans()
        drive.wrap_spans(driver, traffic["spans"], spans, grid.cells)
        driver.init()
        drive.run_steps(driver, traffic["warmup_steps"])
        drive.sync(driver)
        log(native_tables_loaded=bool(native.available()),
            cache_dir=cache_dir, cache_entries_before=len(cached_before),
            warmup_steps=int(driver.sim.step),
            argv=argv_run[:argv_run.index("-nsteps")])
        at_open = drive.fluid_state(driver, grid, config)
        setup_s = time.perf_counter() - T_START

        # -- the measured window ------------------------------------------
        obs0 = obs.snapshot()
        prof0 = dict(drive.need(driver.sim, "profiler").totals)
        compiles0 = compiles.count
        win = drive.window(driver, seconds, traffic["chunk_steps"], spans,
                           traffic["window_span"])
        if win["steps_through_span"] != win["steps"]:
            raise SystemExit(
                f"benchmark: {win['steps']} steps in the window, "
                f"{win['steps_through_span']} of them through "
                f"{traffic['window_span']!r}: the driver did not take the "
                f"path this cell measures")
        ctx = {
            "window": win, "setup_s": setup_s, "obs": obs.delta(obs0),
            "profiler": {k: v - prof0.get(k, 0.0) for k, v in
                         driver.sim.profiler.totals.items()},
            "compiles_in_window": compiles.count - compiles0,
            "peak_bytes": (device.memory_stats() or {}).get(
                "peak_bytes_in_use"),
            "chip": chip,
        }
        ctx["trace"] = (traced_window(driver, grid, traffic,
                                      config["driver"], spans, workdir,
                                      args.rehearse)
                        if args.trace else None)

        # -- correctness: one more unit of the timed entry, the reference -
        links, extra = spec.load_check(bench, traffic["check"]["kind"]).links(
            driver, grid, traffic, config, spans, args.seed)
        ctx["cells"] = grid.cells(driver.sim.grid)
        ctx["iteration_work"] = grid.iteration_work(driver.sim.grid)
        del driver  # the program's state goes before the reference runs
        gc.collect()
        passed, compared, guar = compare.judge(
            grid, links, extra, at_open, config, traffic["limits"])
    ctx["cache_new_entries"] = len(cache_entries(cache_dir) - cached_before)
    failed_steps = int(ctx["obs"].get("resilience.rollbacks", 0)) \
        + (1 if win["error"] else 0)
    passed = passed and not win["error"] and win["steps"] > 0

    # -- the result -------------------------------------------------------
    metrics = {}
    if not args.rehearse:  # off the chip no metric is printed
        kind = "per_layer" if args.trace else "end_to_end"
        for m in spec.metrics_of(bench, cell["name"], kind):
            value = spec.load_reader(bench, m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(devices), "memory_peak_bytes": ctx["peak_bytes"]}
    # a rehearsal says what the comparison found ("passed"), never that a
    # run on the chip was correct
    result = {"correct": bool(passed and not args.rehearse),
              "attempted": int(win["steps"]) + failed_steps,
              "failed": failed_steps, "metrics": metrics, "device": dev}
    trace = ctx["trace"]
    if trace is not None:
        if trace["probe"]:
            result["probe"] = trace["probe"]
        if not args.rehearse:
            dev["busy_s"], dev["window_s"] = trace["busy_s"], trace["window_s"]
            result["breakdown"] = {"device_ops": trace["top_ops"],
                                   "idle_gaps": trace["top_gaps"]}
    result["seed"] = args.seed
    result["window"] = {k: win[k] for k in ("steps", "wall_s", "cells",
                                            "error")}
    result["grid"] = grid.counters(ctx["obs"])
    result["check"] = {"passed": passed, "compared": compared,
                       "guarantees": guar, "links": len(links),
                       "unit_steps": int(traffic["check_unit_steps"])}
    for name, c in compared.items():
        print(f"compared {name} = {c['value']:.6g}  limit {c['limit']:.6g}",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
