"""The forest cell that the tests drive.  It is not in ``BENCHMARK.json``:
it waits for a repair of the program (PERF.md section 7).  Its
configuration, its traffic and the two readers of its layer are the files
a later PR adds, kept under ``tests/data/forest``; the entries that PR
appends are made here, in memory, and nothing outside the tests reads
either."""

import copy

from benchmarks.lib import spec

DIR = "benchmarks/tests/data/forest"
CONFIG, CELL = "twofish_l4", "twofish_l4.step"
#: metrics that are there and find something to read in this cell
JOINS = ("operators.create_obstacles_host_ms", "poisson.iters_per_solve",
         "poisson.iter_device_us", "poisson_iter_roofline")
READERS = {"amr.adapt_host_ms_per_step": ("ms", "program_span"),
           "amr.regrids_in_window": ("count", "program_counter")}


def entries(bench: dict, file_dir: str = DIR) -> dict:
    """``bench`` with the cell's entries appended; its files are looked
    for under ``DIR`` first unless they were copied elsewhere."""
    bench = copy.deepcopy(bench)
    if file_dir == DIR:
        bench["paths"] = [DIR] + bench["paths"]
    source = spec.load_json(f"{spec.ROOT}/{DIR}/configs/{CONFIG}.json")
    bench["configs"].append({
        "name": CONFIG, "source": source["source"],
        "file": f"{file_dir}/configs/{CONFIG}.json", "reduced": [],
        "why": "upstream's own acceptance run: two self-propelled fish on "
               "an octree of 8^3 blocks that adapts every 20 steps"})
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "step", "chips": 1,
        "why": "two fish, 176 blocks of 8^3 on 2 levels, per step "
               "(pipelined 0), 20-step chunks: forest operators, blocking "
               "reads, adaptation pass, forest BiCGSTAB"})
    for m in bench["per_layer"]:
        if m["name"] in JOINS:
            m["workloads"] = m["workloads"] + [CELL]
    for name, (unit, source) in READERS.items():
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "forest", "moves": "step_ms", "workloads": [CELL]})
    return bench
