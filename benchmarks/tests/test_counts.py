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
    # a forest counts its leaf cells, whatever the block layout
    assert counts.bicgstab_iteration(176 * 512)["bytes"] == 80 * 176 * 512


def test_an_iteration_is_bound_by_hbm_on_v5e():
    chip = peaks.peaks_for_kind("TPU v5 lite")
    least = counts.roofline_seconds(counts.bicgstab_iteration(128 ** 3), chip)
    assert least["bound"] == "hbm"
    assert least["seconds"] == pytest.approx(80 * 128 ** 3 / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for_kind("cpu")
    assert peaks.peaks_for_kind("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
