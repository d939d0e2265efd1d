"""Readings that the limits are set from, taken on the chip at a cell's
own size: for each seed one fresh driver in this one process (built,
warmed up and driven through a short window exactly as ``run.py`` does),
then the comparison's numbers for the sound program and, on the first
three seeds, for the lower-precision control (the reference keeping its
stages in bfloat16, put in the program's place), for each fault planted
in the last link (the timed unit's own product) and for each fault of the
cell's kind of grid planted in the reference put in the program's place.
One JSON line per seed.

    chiprun -- python benchmarks/tests/seed_sweep.py <cell> <seconds> <seed> [<seed> ...]
"""

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

FAULTS = ("unchanged", "half", "altered", "chi_shifted", "chi_fat")


def planted(fault, pre, post):
    """``post`` as a program with this fault would have left it."""
    import numpy as np

    vel = np.array(post["vel"], np.float64)
    x0 = np.ndim(post["chi"]) - 3  # the first cell axis (after a forest's rows)
    if fault == "unchanged":  # the step hands its state back
        return {**post, "vel": pre["vel"], "p": pre["p"]}
    if fault == "half":  # half of the domain is left out
        half = vel.shape[0] // 2
        vel[:half] = pre["vel"][:half]
        return {**post, "vel": vel}
    if fault == "altered":  # an answer altered where it is made
        return {**post, "vel": vel * (1.0 + 1e-3)}
    if fault == "chi_shifted":  # the body rasterised one cell off
        move = lambda a: np.roll(a, 1, axis=x0)
    elif fault == "chi_fat":  # the body rasterised one cell too fat
        def move(a):
            if a.ndim == x0 + 4:
                return a
            return np.maximum.reduce(
                [a] + [np.roll(a, s, axis=x0 + ax) for ax in range(3)
                       for s in (1, -1)])
    else:
        raise ValueError(fault)
    bodies = [{**b, "chi": move(b["chi"]), "udef": move(b["udef"])}
              for b in post["bodies"]]
    return {**post, "chi": move(post["chi"]), "udef": move(post["udef"]),
            "bodies": bodies}


def main(cell_name, seconds, seeds, rehearse=False, bench=None):
    """``bench``: the benchmark's entries where they are not those of
    ``BENCHMARK.json`` (a test that drives a cell of its own)."""
    import jax

    from benchmarks import run as bench_run
    from benchmarks.lib import compare, drive, seeding, spec
    from benchmarks.tests import faults
    from cup3d_tpu.__main__ import build_driver

    if not rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("seed_sweep: needs a TPU")
    bench = bench or spec.load_benchmark()
    cell, config, traffic = spec.load_cell(bench, cell_name)
    if rehearse:
        config = {**config, **config.get("rehearse", {})}
        traffic = {**traffic, **traffic.get("rehearse", {})}
    cache_dir = bench_run.enable_cache()
    phys, limits = config["physics"], traffic["limits"]
    kind = config["driver"]["kind"]
    grid = spec.load_grid(bench, kind)
    check = spec.load_check(bench, traffic["check"]["kind"])
    for n_seed, seed in enumerate(seeds):
        t0 = time.perf_counter()
        workdir = tempfile.mkdtemp(prefix="cup3d-sweep-")
        try:
            driver = build_driver(
                seeding.build_argv(config, traffic, seed, workdir))
            spans = drive.Spans()
            drive.wrap_spans(driver, traffic["spans"], spans, grid.cells)
            driver.init()
            drive.run_steps(driver, traffic["warmup_steps"])
            drive.sync(driver)
            setup = time.perf_counter() - t0
            at_open = drive.fluid_state(driver, grid, config)
            win = drive.window(driver, seconds, traffic["chunk_steps"], spans,
                               traffic["window_span"])
            links, extra = check.links(driver, grid, traffic, config, spans,
                                       seed)
            del driver
            t1 = time.perf_counter()
            row = {"cell": cell_name, "seed": seed, "setup_s": setup,
                   "cache_entries": len(bench_run.cache_entries(cache_dir)),
                   "steps": win["steps"], "links": len(links),
                   "step_ms": 1e3 * win["wall_s"] / max(win["steps"], 1)}
            ok, compared, facts = compare.judge(grid, links, extra, at_open,
                                                config, limits)
            row["sound"] = {k: c["value"] for k, c in compared.items()}
            row["sound"]["passed"] = ok
            row["facts"] = facts
            if n_seed < 3:  # control and faults: the first three seeds
                pre, post = links[-1]
                r = compare.reference_step(grid, pre, post, phys)
                row["control_bf16"] = compare.link_numbers(
                    grid, pre, compare.control_link(grid, pre, post, phys),
                    phys, r)
                for fault in FAULTS:
                    if fault.startswith("chi") and not post["bodies"]:
                        continue  # a flow with no body has no chi to move
                    bad = planted(fault, pre, post)
                    row["fault_" + fault] = compare.link_numbers(
                        grid, pre, bad, phys,
                        None if fault.startswith("chi") else r)
                for fault in faults.GRID_FAULTS.get(kind, {}):
                    bad = compare.control_link(
                        grid, pre, post, phys,
                        on=faults.faulty(grid, kind, post, fault))
                    row["fault_" + fault] = compare.link_numbers(
                        grid, pre, bad, phys, r)
            row["check_s"] = time.perf_counter() - t1
            print(json.dumps(row), flush=True)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--rehearse"]
    main(args[0], float(args[1]), [int(s) for s in args[2:]],
         rehearse="--rehearse" in sys.argv)
