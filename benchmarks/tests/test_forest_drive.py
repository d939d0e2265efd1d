"""The forest adapter, the ``forest_step`` check and the composite-grid
reference against the program itself, at the forest cell's own size on
the CPU (``forest_cell.py``: the cell is not in the benchmark yet),
through the sweep the limits are set from: every leaf cell is compared,
the program's step agrees with the reference, the configuration's gate
holds where the window opens, and the control and every planted fault,
the two at the coarse-fine faces among them, fail at the cell's limits.

Slow on a CPU: one driver of 176 blocks, about a minute.
"""

import io
import json
from contextlib import redirect_stdout

import pytest

from benchmarks.lib import spec
from benchmarks.tests import forest_cell, seed_sweep


@pytest.fixture(scope="module")
def row():
    bench = forest_cell.entries(spec.load_benchmark())
    out = io.StringIO()
    with redirect_stdout(out):
        seed_sweep.main(forest_cell.CELL, 1.0, [29], rehearse=True,
                        bench=bench)
    limits = spec.load_cell(bench, forest_cell.CELL)[2]["limits"]
    return json.loads(out.getvalue().strip().splitlines()[-1]), limits


def over(numbers, limits):
    return {k: v for k, v in numbers.items()
            if k in limits and not v <= limits[k]}


def test_every_leaf_cell_is_compared(row):
    r, _ = row
    assert r["facts"]["cells_compared"] == 176 * 512
    assert r["steps"] > 0 and r["links"] == 1


def test_the_program_s_step_agrees_with_the_reference(row):
    # ten steps into a run; what the chip reads later: PERF.md section 7
    r, limits = row
    assert not over(r["sound"], limits), r["sound"]
    assert r["sound"]["div_fluid_max_at_open"] <= 0.01
    assert r["sound"]["passed"]


def test_the_control_and_every_fault_fail(row):
    r, limits = row
    faults = [k for k in r if k.startswith("fault_")]
    assert {"fault_ghost_inject", "fault_no_reflux", "fault_unchanged",
            "fault_half", "fault_altered", "fault_chi_shifted",
            "fault_chi_fat"} <= set(faults)
    for name in ["control_bf16"] + faults:
        assert over(r[name], limits), (name, r[name])
