"""Finds the benchmark's data by name: cells, configurations, traffic
files, per-layer readers, checks and grid adapters all come from
``BENCHMARK.json`` and from files named after its entries.  No cell,
configuration, metric or kind of grid is named in harness code."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def load_cell(bench: dict, name: str, root: str = ROOT):
    """(cell entry, configuration file, traffic file) of one cell."""
    cell = by_name(bench["workloads"], name, "workload")
    cfg_entry = by_name(bench["configs"], cell["config"], "config")
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(_find(bench, "workloads", name + ".json", root))
    return cell, config, traffic


def _find(bench: dict, folder: str, filename: str, root: str) -> str:
    """``<path>/<folder>/<filename>`` under the first of the benchmark's
    ``paths`` that holds it."""
    tried = [os.path.join(root, p, folder, filename) for p in bench["paths"]]
    for path in tried:
        if os.path.exists(path):
            return path
    raise SystemExit(f"benchmark: no file {tried[0]!r} (looked under "
                     f"every entry of paths)")


def metrics_of(bench: dict, cell_name: str, kind: str):
    """Entries of ``end_to_end`` or ``per_layer`` that this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if kind == "end_to_end":
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and m["moves"] in reported]


def _load(bench: dict, folder: str, name: str, root: str):
    path = _find(bench, folder, name + ".py", root)
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(bench: dict, name: str, root: str = ROOT):
    """The module ``<path>/metrics/<name>.py``: ``META`` and ``read``."""
    return _load(bench, "metrics", name, root)


def load_check(bench: dict, kind: str, root: str = ROOT):
    """The module ``<path>/checks/<kind>.py``: ``links``, which takes
    one more unit of the timed entry and hands back what is compared."""
    return _load(bench, "checks", kind, root)


def load_grid(bench: dict, kind: str, root: str = ROOT):
    """The module ``<path>/grids/<kind>.py``: what depends on the
    kind of grid a configuration's driver runs on (``driver.kind``)."""
    return _load(bench, "grids", kind, root)
