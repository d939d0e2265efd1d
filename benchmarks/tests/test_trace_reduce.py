"""The trace reduction on a small trace recorded on the chip in PR 26
(``record_trace.py``: three launches of one jitted stencil loop, 4.19 ms
each on the device, with a 20 ms host sleep after each)."""

import os

import pytest

from benchmarks.lib import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "chip_trace_small.xplane.pb")


def test_union_clip_gaps():
    busy = tr.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.7)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.total(tr.clip(busy, 1.0, 3.5)) == pytest.approx(1.5)
    assert tr.gaps(busy, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]


def test_self_times_take_children_out_of_their_parent():
    # a while of 10 s holding two bodies of 3 s and 4 s
    rows = [(0.0, 10.0, "while"), (1.0, 4.0, "body"), (5.0, 9.0, "body")]
    assert tr.self_times(rows) == {"while": pytest.approx(3.0),
                                   "body": pytest.approx(7.0)}


def test_op_name_is_the_left_hand_side():
    long = "%fusion.14 = (f32[255,256,128]{2,1,0}) fusion(f32[2] %x), kind=kLoop"
    assert tr.op_name(long) == "fusion.14"


@pytest.fixture(scope="module")
def planes():
    return tr.read_planes(TRACE)


def test_the_chip_trace_has_one_device_plane_and_the_harness_spans(planes):
    devices, host = planes
    assert sorted(devices) == ["/device:TPU:0"]
    assert set(devices["/device:TPU:0"]) == {tr.OPS_LINE, tr.MODULES_LINE}
    names = [s[2] for s in host if s[2].startswith("bench:")]
    assert names.count("bench:traced_window") == 1
    assert names.count("bench:advance") == 3
    assert names.count("bench:host_sleep") == 3


def test_reduction_of_the_chip_trace(planes):
    devices, host = planes
    r = tr.reduce(devices, host, "bench:traced_window", "bench:",
                  ["bench_small_stencil"])
    # three programs of 4.19 ms: the XLA Modules line, unclipped
    assert r["module_s"]["bench_small_stencil"] == pytest.approx(
        3 * 4.19e-3, rel=0.01)
    assert r["module_runs"]["bench_small_stencil"] == 3
    # the window is three (launch + 20 ms sleep) rounds
    assert r["window_s"] == pytest.approx(0.0783, rel=0.01)
    # busy is the union of the operations inside the window: the device
    # clock leads the host spans by about 1 ms in this trace, so the
    # first program's head falls before the window opens
    assert 0.0110 < r["busy_s"] <= r["module_s"]["bench_small_stencil"]
    idle = 1.0 - r["busy_s"] / r["window_s"]
    assert idle == pytest.approx(0.85, abs=0.02)
    # nearly all idle time lies under the sleeps, none is unnamed
    gaps = dict(r["top_gaps"])
    assert gaps["bench:host_sleep"] == pytest.approx(0.0666, rel=0.02)
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    # the loop body's fusions lead; the while itself keeps only its own
    ops = dict(r["top_ops"])
    assert r["top_ops"][0][0].startswith("fusion")
    assert ops["while"] < 1e-5
    assert sum(ops.values()) <= r["busy_s"] * 1.001


def test_no_window_no_numbers(planes):
    devices, host = planes
    assert tr.reduce(devices, host, "bench:absent", "bench:") is None
    assert tr.reduce({}, host, "bench:traced_window", "bench:") is None
