"""The rest of a run with the timed path broken underneath: the harness's
look for a chip is skipped (``--rehearse``, the sizes of the rehearsal),
everything else runs, and the comparison has to come out false, once for
each fault a cell can have.  A sound run of the same cell passes.

Slow on a CPU (a forest cell rehearses at its own size): run one cell
with ``-k``.
"""

import io
import json
from contextlib import redirect_stdout

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import spec
from benchmarks.tests import faults

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def rehearse(cell):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(["--workload", cell, "--seed", "12345",
                             "--seconds", "1", "--trace", "0", "--rehearse"])
    assert rc == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["correct"] is False  # a rehearsal never says true
    assert result["metrics"] == {}  # and prints no device metric
    return result["check"]


def warmup_of(cell):
    _, _, traffic = spec.load_cell(BENCH, cell)
    return {**traffic, **traffic.get("rehearse", {})}["warmup_steps"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_passes(cell):
    check = rehearse(cell)
    assert check["passed"], check["compared"]


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_step_fails(cell, fault):
    with faults.planted(fault, warmup_of(cell)):
        check = rehearse(cell)
    over = {k: c for k, c in check["compared"].items()
            if not c["value"] <= c["limit"]}
    assert not check["passed"] and over, check["compared"]
