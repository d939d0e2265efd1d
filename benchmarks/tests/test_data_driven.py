"""A configuration, a cell, a per-layer metric, a check and a kind of grid
are added as new files and new entries; no file that is there is edited,
and the harness lists and loads all five."""

import hashlib
import json
import os
import shutil

from benchmarks.lib import spec
from benchmarks.tests import forest_cell


def digest(root):
    out = {}
    for dirpath, _, files in os.walk(os.path.join(root, "benchmarks")):
        if "__pycache__" in dirpath:
            continue
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def copy_of_the_benchmark(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(spec.ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root, digest(root)


def test_add_config_cell_and_metric_as_files(tmp_path):
    root, before = copy_of_the_benchmark(tmp_path)
    bench = spec.load_benchmark(root)
    old_cell = bench["workloads"][0]["name"]

    # new files only
    cfg = spec.load_json(os.path.join(root, bench["configs"][0]["file"]))
    cfg["name"] = "fish_new"
    with open(os.path.join(root, "benchmarks/configs/fish_new.json"), "w") as f:
        json.dump(cfg, f)
    traffic = spec.load_json(os.path.join(
        root, "benchmarks/workloads", old_cell + ".json"))
    traffic["config"] = "fish_new"
    traffic["check"] = {"kind": "new_check"}
    with open(os.path.join(root, "benchmarks/checks/new_check.py"), "w") as f:
        f.write('def links(driver, grid, traffic, config, spans, seed):\n'
                '    return [], {"new_number": 0.0}\n')
    with open(os.path.join(root, "benchmarks/workloads/fish_new.burst.json"),
              "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "benchmarks/metrics/new.steps.py"), "w") as f:
        f.write('META = {"name": "new.steps", "layer": "drivers", '
                '"unit": "count", "moves": "step_ms", '
                '"source": "program_counter", "better": "higher"}\n\n\n'
                'def read(ctx):\n    return float(ctx["window"]["steps"])\n')
    # new entries only
    bench["configs"].append({"name": "fish_new", "source": "test",
                             "file": "benchmarks/configs/fish_new.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "fish_new.burst", "config": "fish_new",
                               "traffic": "burst", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "new.steps", "unit": "count",
                               "better": "higher", "source": "program_counter",
                               "layer": "drivers", "moves": "step_ms",
                               "workloads": ["fish_new.burst"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    bench = spec.load_benchmark(root)
    cell, config, traffic = spec.load_cell(bench, "fish_new.burst", root)
    assert config["name"] == "fish_new" and traffic["config"] == "fish_new"
    names = [m["name"] for m in spec.metrics_of(bench, "fish_new.burst",
                                                "per_layer")]
    assert "new.steps" in names and "device.idle_pct" in names
    assert "new.steps" not in [m["name"] for m in spec.metrics_of(
        bench, old_cell, "per_layer")]
    check = spec.load_check(bench, traffic["check"]["kind"], root)
    assert check.links(None, None, traffic, config, None, 0) == \
        ([], {"new_number": 0.0})
    reader = spec.load_reader(bench, "new.steps", root)
    assert reader.read({"window": {"steps": 7}}) == 7.0
    after = digest(root)
    assert {k: after[k] for k in before} == before


def test_a_new_kind_of_grid_is_an_adapter_file_and_entries(tmp_path):
    """A cell on a kind of grid the harness has never seen: its adapter,
    configuration and traffic are new files, the rest new entries."""
    root, before = copy_of_the_benchmark(tmp_path)
    bench = spec.load_benchmark(root)
    old = bench["workloads"][0]["name"]
    cfg = spec.load_json(os.path.join(root, bench["configs"][0]["file"]))
    cfg["name"] = "fish_curved"
    cfg["driver"] = {**cfg["driver"], "kind": "curvilinear"}
    with open(os.path.join(root, "benchmarks/configs/fish_curved.json"),
              "w") as f:
        json.dump(cfg, f)
    traffic = spec.load_json(os.path.join(
        root, "benchmarks/workloads", old + ".json"))
    traffic["config"] = "fish_curved"
    with open(os.path.join(
            root, "benchmarks/workloads/fish_curved.step.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "benchmarks/grids/curvilinear.py"),
              "w") as f:
        f.write('def cells(grid):\n    return 7 * grid\n\n\n'
                'def iteration_work(n):\n'
                '    return {"bytes": 100 * n, "flops": n}\n')
    bench["configs"].append({"name": "fish_curved", "source": "test",
                             "file": "benchmarks/configs/fish_curved.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "fish_curved.step",
                               "config": "fish_curved", "traffic": "step",
                               "chips": 1, "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    bench = spec.load_benchmark(root)
    _, config, _ = spec.load_cell(bench, "fish_curved.step", root)
    grid = spec.load_grid(bench, config["driver"]["kind"], root)
    assert grid.cells(3) == 21
    assert grid.iteration_work(10) == {"bytes": 1000, "flops": 10}
    # the kinds that are there are found as before
    for w in bench["workloads"][:-1]:
        _, c, _ = spec.load_cell(bench, w["name"], root)
        assert spec.load_grid(bench, c["driver"]["kind"], root).cells
    after = digest(root)
    assert {k: after[k] for k in before} == before


def test_every_adapter_gives_what_the_harness_calls():
    bench = spec.load_benchmark()
    kinds = {f[:-3] for f in os.listdir(os.path.join(spec.BENCH_DIR, "grids"))
             if f.endswith(".py") and f != "__init__.py"}
    assert {spec.load_cell(bench, w["name"])[1]["driver"]["kind"]
            for w in bench["workloads"]} <= kinds
    for kind in kinds:
        grid = spec.load_grid(bench, kind)
        for name in ("cells", "host", "geometry", "reference", "Reference",
                     "live_system", "iteration_work", "counters"):
            assert hasattr(grid, name), (kind, name)
        for name in ("check", "one_step", "gradient", "laplacian", "norm",
                     "mean", "volume", "fluid_divergence_max"):
            assert hasattr(grid.Reference, name), (kind, name)


def test_the_forest_cell_is_added_as_files_and_entries(tmp_path):
    """The forest cell that waits (``forest_cell.py``): its configuration,
    its traffic and its two readers dropped into a copy of the benchmark
    as new files, its entries appended, and it loads like any cell."""
    root, before = copy_of_the_benchmark(tmp_path)
    for folder in ("configs", "workloads", "metrics"):
        src = os.path.join(spec.ROOT, forest_cell.DIR, folder)
        for f in os.listdir(src):
            if not f.startswith("__"):
                shutil.copy(os.path.join(src, f),
                            os.path.join(root, "benchmarks", folder))
    base = spec.load_benchmark(root)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(forest_cell.entries(base, "benchmarks"), f)

    bench = spec.load_benchmark(root)
    assert bench["paths"] == base["paths"]
    cell, config, traffic = spec.load_cell(bench, forest_cell.CELL, root)
    assert traffic["config"] == config["name"] == cell["config"]
    assert len(cell["why"]) <= 200 and config["reduced"] == []
    spec.load_check(bench, traffic["check"]["kind"], root)
    assert spec.load_grid(bench, config["driver"]["kind"], root).cells
    reported = [m["name"] for m in spec.metrics_of(bench, forest_cell.CELL,
                                                   "per_layer")]
    assert set(forest_cell.JOINS) | set(forest_cell.READERS) <= set(reported)
    for name in forest_cell.READERS:
        entry = spec.by_name(bench["per_layer"], name, "metric")
        meta = spec.load_reader(bench, name, root).META
        assert {k: meta[k] for k in entry if k != "workloads"} == \
            {k: v for k, v in entry.items() if k != "workloads"}
        assert name not in [m["name"] for m in spec.metrics_of(
            bench, base["workloads"][0]["name"], "per_layer")]
    after = digest(root)
    assert {k: after[k] for k in before} == before


def test_every_metric_file_agrees_with_its_entry():
    bench = spec.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        meta = spec.load_reader(bench, m["name"]).META
        for key in ("name", "unit", "better", "source"):
            assert meta[key] == m[key], (m["name"], key)
        if "moves" in m:
            assert meta["moves"] == m["moves"] and meta["layer"] == m["layer"]


def test_a_reader_that_finds_nothing_returns_nothing():
    bench = spec.load_benchmark()
    empty = {"window": {"steps": 0, "wall_s": 1.0, "cells": 0, "rows": []},
             "obs": {}, "profiler": {}, "trace": None, "peak_bytes": None,
             "chip": None, "cells": None, "setup_s": 1.0,
             "compiles_in_window": 0, "cache_new_entries": 0}
    for m in bench["per_layer"]:
        if m["source"] == "device_trace" or m["name"].startswith(
                ("poisson", "operators", "device", "driver.host",
                 "stream")):
            assert spec.load_reader(bench, m["name"]).read(empty) is None, \
                m["name"]


def test_no_cell_name_in_harness_code():
    bench = spec.load_benchmark()
    words = [w["name"] for w in bench["workloads"]] + \
        [c["name"] for c in bench["configs"]]
    for rel in ["run.py"] + [d + "/" + f for d in ("lib", "checks", "grids")
                             for f in os.listdir(
            os.path.join(spec.BENCH_DIR, d)) if f.endswith(".py")]:
        with open(os.path.join(spec.BENCH_DIR, rel)) as f:
            text = f.read()
        assert "import bench" not in text and "from bench " not in text
        for w in words:
            assert w not in text, (rel, w)


def test_no_kind_of_grid_in_harness_code_outside_the_adapters():
    import re

    kinds = [f[:-3] for f in os.listdir(os.path.join(spec.BENCH_DIR, "grids"))
             if f.endswith(".py") and f != "__init__.py"]
    metrics = [m["name"] for m in spec.load_benchmark()["per_layer"]]
    for rel in ["run.py", "lib/compare.py", "lib/probe.py", "lib/spec.py",
                "lib/seeding.py", "lib/trace_reduce.py"] + [
            "checks/" + f for f in os.listdir(
                os.path.join(spec.BENCH_DIR, "checks")) if f.endswith(".py")]:
        with open(os.path.join(spec.BENCH_DIR, rel)) as f:
            text = f.read()
        for m in metrics:
            assert m not in text, (rel, m)
        for kind in kinds:
            # as a name of a kind: quoted, or the module's
            assert not re.search(rf"[\"']{kind}[\"']|grids[./]{kind}|"
                                 rf"import {kind}\b", text), (rel, kind)
