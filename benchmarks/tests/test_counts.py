"""One count of an iteration's work, from shapes alone; one row of peaks."""

import importlib

import pytest

from benchmarks.lib import counts, peaks

SWITCHES = {"CUP3D_FUSED": ("0", "1"), "CUP3D_KRYLOV_DTYPE": ("f32", "bf16"),
            "CUP3D_GETZ": ("exact", "cg"), "CUP3D_COARSE": ("0", "1")}


@pytest.mark.parametrize("switch", sorted(SWITCHES))
def test_the_count_does_not_follow_the_implementation(monkeypatch, switch):
    seen = set()
    for value in SWITCHES[switch]:
        monkeypatch.setenv(switch, value)
        mod = importlib.reload(counts)
        w = mod.bicgstab_iteration(128 ** 3)
        seen.add((w["bytes"], w["flops"]))
    assert len(seen) == 1


def test_the_strict_count():
    w = counts.bicgstab_iteration(128 ** 3)
    assert w["vectors"] == 20
    assert w["bytes"] == 80 * 128 ** 3
    assert w["flops"] == 48 * 128 ** 3


def test_a_forest_adds_the_face_planes_of_the_operator_s_input():
    cells = 176 * 512
    w = counts.forest_bicgstab_iteration(cells, bs=8)
    # y and z, each read once more for its six face planes of 8^2 per
    # 8^3 block: 2 * 6 / 8 of a vector
    assert w["vectors"] == 20 + 1.5
    assert w["bytes"] == 86 * cells and w["flops"] == 48 * cells
    # never the whole lab the program assembles: that holds edges and
    # corners no 7-point stencil reads
    assert w["bytes"] < (20 + 2 * (10 ** 3 - 8 ** 3) / 8 ** 3) * 4 * cells
    # and never under the uniform count of as many cells
    assert w["bytes"] > counts.bicgstab_iteration(cells)["bytes"]
    least = counts.roofline_seconds(w, peaks.peaks_for_kind("TPU v5 lite"))
    assert least["bound"] == "hbm"


def test_the_count_names_the_vectors_of_the_solver_it_counts():
    """Every vector the program's iteration reads or writes is in the
    count's table, and the operator it applies is the 7-point one with a
    halo of one cell."""
    import inspect
    import re

    from cup3d_tpu.ops import amr_ops, krylov

    body = inspect.getsource(krylov.bicgstab)
    loop = body[body.index("def body("):body.index("jax.lax.while_loop")]
    vectors = {"p", "y", "v", "svec", "z", "t", "x", "r", "rhat"}
    for name in vectors:
        assert re.search(rf"^\s+{name} = ", loop, re.M), name
    # nothing of a field's size is made in the loop beside them and their
    # re-seeded copies: what M and apply_A return, and what is built from
    # the state's vectors
    made = set(re.findall(r"^\s+(\w+) = (?:M|apply_A)\(", loop, re.M))
    assert made == {"y", "v", "z", "t"}
    doc = counts.forest_bicgstab_iteration.__doc__.replace("``", " ")
    for name in vectors:
        assert re.search(rf"\b(s\.)?{name}\b", doc), name
    lap = inspect.getsource(amr_ops.laplacian_blocks)
    assert "-6.0 * c" in lap and "_off(ax, 1)" in lap and "_off(ax, -1)" in lap


def test_an_iteration_is_bound_by_hbm_on_v5e():
    chip = peaks.peaks_for_kind("TPU v5 lite")
    least = counts.roofline_seconds(counts.bicgstab_iteration(128 ** 3), chip)
    assert least["bound"] == "hbm"
    assert least["seconds"] == pytest.approx(80 * 128 ** 3 / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for_kind("cpu")
    assert peaks.peaks_for_kind("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
