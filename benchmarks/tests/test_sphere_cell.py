"""The cell ``sphere300.scan`` at its configuration's rehearse size (64 x
32 x 32 cells, D/h 6.4): a rehearsal of ``run.py`` passes its comparison,
and on the links its check takes, the reference keeping its stages in
bfloat16, the reference with each fault of the free-space box planted in
it (``grids/freespace.py::FAULTS``: the faces made periodic, the
face-normal ghosts copied and not negated, chi one cell off, the frame
velocity's sign flipped), the program's velocity times 1.001 and the
state handed back, each put in the program's place, fail at least one
of the cell's limits.

    python -m pytest benchmarks/tests/test_sphere_cell.py -q
"""

import io
import json
from contextlib import redirect_stdout

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.lib import compare, drive, seeding, spec

CELL = "sphere300.scan"
SEED = 4300000003


@pytest.fixture(scope="module")
def cell():
    bench = spec.load_benchmark()
    _, config, traffic = spec.load_cell(bench, CELL)
    return {"bench": bench, "config": {**config, **config["rehearse"]},
            "traffic": {**traffic, **traffic["rehearse"]},
            "grid": spec.load_grid(bench, config["driver"]["kind"])}


def test_a_rehearsal_of_the_cell_passes_its_comparison():
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(["--workload", CELL, "--seed", str(SEED),
                             "--seconds", "1", "--trace", "0",
                             "--rehearse"])
    assert rc == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["correct"] is False  # a rehearsal never says true
    check = result["check"]
    assert check["passed"] and check["links"] == 2, check["compared"]
    assert check["compared"]["scan_chain_gap"]["value"] == 0.0


@pytest.fixture(scope="module")
def last_link(cell, tmp_path_factory):
    """The last link of the check on a driver built, warmed up and run
    as ``run.py`` runs it, and the sound reference step of it."""
    from cup3d_tpu.__main__ import build_driver

    traffic, grid = cell["traffic"], cell["grid"]
    d = build_driver(seeding.build_argv(cell["config"], traffic, SEED,
                                        str(tmp_path_factory.mktemp("run"))))
    spans = drive.Spans()
    drive.wrap_spans(d, traffic["spans"], spans, grid.cells)
    d.init()
    drive.run_steps(d, traffic["warmup_steps"])
    links, _ = spec.load_check(cell["bench"], "scan_chain_body").links(
        d, grid, traffic, cell["config"], spans, SEED)
    pre, post = links[-1]
    phys = cell["config"]["physics"]
    return pre, post, compare.reference_step(grid, pre, post, phys)


def f32(x):
    return np.asarray(x, np.float32).astype(np.float64)


def planted(cell, pre, post, fault):
    grid, phys = cell["grid"], cell["config"]["physics"]
    if fault == "altered":
        return {**post, "vel": np.asarray(post["vel"], np.float64) * 1.001}
    if fault == "unchanged":
        return {**post, "vel": pre["vel"], "p": pre["p"]}
    if fault == "control":
        store, on = compare.bf16_store, None
    else:
        store, on = f32, grid.Reference(post, **grid.FAULTS[fault])
    return grid.stand_in(post, compare.reference_step(
        grid, pre, post, phys, store=store, on=on), store)


@pytest.mark.parametrize("fault", ["control", "periodic", "ghost_copy",
                                   "chi_off", "uinf_flipped", "altered",
                                   "unchanged"])
def test_a_planted_fault_fails_a_limit(cell, last_link, fault):
    grid, phys = cell["grid"], cell["config"]["physics"]
    pre, post, r = last_link
    bad = planted(cell, pre, post, fault)
    got = {**compare.link_numbers(grid, pre, bad, phys, r),
           **grid.body_numbers(pre, bad, phys, r)}
    limits = cell["traffic"]["limits"]
    assert {k: v for k, v in got.items() if not v <= limits[k]}, got
