"""The composite-grid reference against what it has to be: on a forest of
one level the uniform reference, to round-off; across a coarse-fine face
second-order for the gradient and the divergence, and for the Laplacian
in the volume-weighted mean; conservative with the flux correction and
not without it.  (In the cells AT the face the scheme's Laplacian is not
second-order: the coarse flux is replaced by fine ones, so the two sides'
flux errors no longer cancel, and the interpolation reads leaves' point
values beside 8-to-1 averages.  Its largest error there still falls
between the two sizes tried, and that is all that is asked of it.)"""

import numpy as np
import pytest

from benchmarks.lib import reference as ref, reference_forest as rf
from benchmarks.tests.test_control import two_level_leaves


def one_level(bpd, level=0):
    n = bpd << level
    leaves = [(level, i, j, k) for i in range(n) for j in range(n)
              for k in range(n)]
    return rf.Forest(leaves, (bpd,) * 3, 8, 1.0 / (bpd * 8))


def two_level(bpd, **kw):
    return rf.Forest(two_level_leaves(bpd), (bpd,) * 3, 8, 1.0 / (bpd * 8),
                     **kw)


def smooth(x):
    """A field with no symmetry about the faces, its gradient and its
    Laplacian."""
    a, b, c = (2 * np.pi * x[..., i] for i in range(3))
    f = np.sin(a + 0.7) * np.cos(b + 0.2) * np.sin(2 * c + 0.3)
    grad = 2 * np.pi * np.stack([
        np.cos(a + 0.7) * np.cos(b + 0.2) * np.sin(2 * c + 0.3),
        -np.sin(a + 0.7) * np.sin(b + 0.2) * np.sin(2 * c + 0.3),
        2 * np.sin(a + 0.7) * np.cos(b + 0.2) * np.cos(2 * c + 0.3)], -1)
    return f, grad, -(2 * np.pi) ** 2 * 6 * f


def test_the_interpolation_is_the_parabola_a_quarter_cell_off():
    assert rf.LOW_CHILD == pytest.approx((0.15625, 0.9375, -0.09375))
    assert rf.HIGH_CHILD == rf.LOW_CHILD[::-1]
    # exact on a quadratic, away from the periodic seam
    n = 16
    xc = (np.arange(n) + 0.5) / n
    xf = (np.arange(2 * n) + 0.5) / (2 * n)
    q = lambda x, y, z: 1 + x + 2 * y * y - z * x + 0.5 * z * z
    coarse = q(*np.meshgrid(xc, xc, xc, indexing="ij"))
    fine = q(*np.meshgrid(xf, xf, xf, indexing="ij"))
    got = rf.prolong_quadratic(coarse)
    inner = (slice(2, -2),) * 3
    assert np.abs(got[inner] - fine[inner]).max() < 1e-13
    assert np.abs(rf.restrict(fine) - coarse).max() < 2e-3  # h^2 / 24 terms
    assert rf.restrict(rf.prolong_inject(coarse)) == pytest.approx(coarse)


def test_leaves_go_to_the_level_arrays_and_back():
    f = two_level(2)
    rng = np.random.default_rng(0)
    a = rng.normal(size=(f.nb, 8, 8, 8, 3))
    dense = f.fill(a)
    assert np.array_equal(f.read(dense), a)
    # under finer leaves: the 8-to-1 average of what they hold
    assert np.allclose(dense[0][f.finer[0]],
                       rf.restrict(dense[1])[f.finer[0]])
    # half the box is level 0's (16^3 / 2 cells), half level 1's
    assert f.own[0].sum() == 16 ** 3 // 2 and f.own[1].sum() == 32 ** 3 // 2
    assert f.own[0].sum() + f.own[1].sum() == f.nb * 8 ** 3
    assert f.volume == pytest.approx(1.0)


@pytest.mark.parametrize("leaves", [
    [(0, 0, 0, 0)],                                  # does not cover
    [(0, i, j, k) for i in range(2) for j in range(2) for k in range(2)]
    + [(1, 0, 0, 0)],                                # covers twice
])
def test_leaves_that_are_no_partition_are_refused(leaves):
    with pytest.raises(ValueError):
        rf.Forest(leaves, (2, 2, 2), 8, 1.0 / 16)


def test_on_one_level_the_step_is_the_uniform_reference():
    from benchmarks.tests.test_control import PHYS, synthetic

    _, pre, post = synthetic("uniform", n=32, seed=5)
    body = {**post["bodies"][0], "cm_guess": pre["bodies"][0]["cm"]}
    args = (post["dt"], PHYS["nu"], post["uinf"])
    want = ref.one_step(pre["vel"], *args, post["h"], post["x"], [body],
                        PHYS["DLM"])
    forest = one_level(4)
    to_leaves = lambda a: forest.read({0: np.asarray(a, np.float64)})
    leaf_body = {**body, "chi": to_leaves(body["chi"]),
                 "udef": to_leaves(body["udef"])}
    got = rf.one_step(to_leaves(pre["vel"]), *args, forest, [leaf_body],
                      PHYS["DLM"])
    assert np.abs(forest.x - to_leaves(post["x"])).max() < 1e-15
    for key in ("u_pen", "rhs"):
        scale = np.abs(want[key]).max()
        assert np.abs(got[key] - to_leaves(want[key])).max() \
            <= 1e-12 * scale, key
    for key in ("trans", "ang", "cm", "mass", "gyration"):
        assert got["rigid"][0][key] == pytest.approx(
            want["rigid"][0][key], rel=1e-12, abs=1e-15), key
    # the pressure is each side's own solve: the FFT's exact one there,
    # a Krylov solve to 1e-10 here
    for key, tol in (("p", 1e-8), ("u1", 1e-9)):
        scale = np.abs(want[key]).max()
        assert np.abs(got[key] - to_leaves(want[key])).max() \
            <= tol * scale, key


def errors(bpd):
    f = two_level(bpd)
    s, grad, lap = smooth(f.x)
    e_lap = np.abs(f.laplacian(s) - lap)
    e_div = np.abs(f.divergence(grad) - lap)
    return {"grad_max": np.abs(f.gradient(s) - grad).max(),
            "div_max": e_div.max(), "div_mean": f.wsum(e_div),
            "lap_max": e_lap.max(), "lap_mean": f.wsum(e_lap)}


def test_second_order_across_the_coarse_fine_face():
    coarse, fine = errors(4), errors(8)
    rate = {k: np.log2(coarse[k] / fine[k]) for k in coarse}
    for key in ("grad_max", "div_max", "div_mean", "lap_mean"):
        assert rate[key] > 1.8, rate
    assert rate["lap_max"] > 0.5, rate


def test_conservative_with_the_flux_correction_and_not_without():
    sound, broken = two_level(2), two_level(2, reflux=False)
    s, grad, _ = smooth(sound.x)
    s = s + np.exp(np.sin(2 * np.pi * sound.x[..., 0] + 1.0))
    u = grad + np.stack([s, 0.3 * s, s * s], -1)
    scale = sound.wsum(np.abs(sound.laplacian(s)))
    assert abs(sound.wsum(sound.laplacian(s))) < 1e-12 * scale
    assert abs(sound.wsum(sound.divergence(u))) < 1e-12 * scale
    assert abs(sound.wsum(sound.advection_diffusion_rhs(
        u * 0, 1.0, np.zeros(3))[..., 0])) < 1e-12
    assert abs(broken.wsum(broken.laplacian(s))) > 1e-4 * scale
    assert abs(broken.wsum(broken.divergence(u))) > 1e-6 * scale
    # diffusion alone (no advection of a field at rest) conserves momentum
    diff = sound.advection_diffusion_rhs(u, 1e-3, np.zeros(3)) \
        - sound.advection_diffusion_rhs(u, 0.0, np.zeros(3))
    assert abs(sound.wsum(diff[..., 1])) < 1e-12 * scale


def test_the_solve_is_the_composite_operator_s_inverse():
    f = two_level(2)
    s, _, _ = smooth(f.x)
    rhs = f.laplacian(s)
    p = f.poisson(rhs + 3.0)  # a constant is not in the range: removed
    assert np.abs(p - (s - f.wmean(s))).max() < 1e-8
    assert f.norm(f.laplacian(p) - rhs) <= 2e-10 * f.norm(rhs)
    assert abs(f.wmean(p)) < 1e-14


def test_the_gate_counts_blocks_that_touch_no_chi():
    f = two_level(2)
    chi = np.zeros((f.nb, 8, 8, 8))
    u = np.zeros((f.nb, 8, 8, 8, 3))
    assert f.fluid_blocks(chi).all()
    chi[5, 0, 3, 3] = 0.5  # on the low x face of block 5
    fluid = f.fluid_blocks(chi)
    # block 5 is out, and so is whoever holds the cell across that face
    assert not fluid[5] and (~fluid).sum() == 2
    u[5, 3:5, :, :, 0] = 1.0  # a divergence that stays inside block 5
    assert f.fluid_divergence_max(u, chi * 0) > 0
    assert f.fluid_divergence_max(u, chi) == 0
