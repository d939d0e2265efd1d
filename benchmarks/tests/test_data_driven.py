"""A configuration, a cell, a per-layer metric and a check are added as
new files and new entries; no file that is there is edited, and the
harness lists and loads all four."""

import hashlib
import json
import os
import shutil

from benchmarks.lib import spec


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


def test_add_config_cell_and_metric_as_files(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(spec.ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(root)
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
        f.write('def links(driver, traffic, config, spans, seed):\n'
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
    assert check.links(None, traffic, config, None, 0) == \
        ([], {"new_number": 0.0})
    reader = spec.load_reader(bench, "new.steps", root)
    assert reader.read({"window": {"steps": 7}}) == 7.0
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
    for rel in ["run.py"] + [d + "/" + f for d in ("lib", "checks")
                             for f in os.listdir(
            os.path.join(spec.BENCH_DIR, d)) if f.endswith(".py")]:
        with open(os.path.join(spec.BENCH_DIR, rel)) as f:
            text = f.read()
        assert "import bench" not in text and "from bench " not in text
        for w in words:
            assert w not in text, (rel, w)
