"""Canonical analytic flow fields shared by ICs, tests, and benchmarks."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from cup3d_tpu.grid.uniform import UniformGrid


def taylor_green_2d(grid: UniformGrid, t: float = 0.0, nu: float = 0.0,
                    dtype=jnp.float32) -> jnp.ndarray:
    """z-invariant Taylor-Green vortex — an *exact* unsteady NS solution
    (velocity decays as exp(-2 nu k^2 t)); the correctness anchor."""
    x = grid.cell_centers(dtype)
    k = 2.0 * np.pi / grid.extent[0]
    decay = float(np.exp(-2.0 * nu * k * k * t))
    u = jnp.sin(k * x[..., 0]) * jnp.cos(k * x[..., 1]) * decay
    v = -jnp.cos(k * x[..., 0]) * jnp.sin(k * x[..., 1]) * decay
    return jnp.stack([u, v, jnp.zeros_like(u)], axis=-1)


def taylor_green_3d(grid: UniformGrid, dtype=jnp.float32) -> jnp.ndarray:
    """Classic 3-D Taylor-Green initial condition (transitions to
    turbulence) — the reference's `-initCond taylorGreen`
    (main.cpp:12722)."""
    x = grid.cell_centers(dtype)
    k = 2.0 * np.pi / grid.extent[0]
    u = jnp.sin(k * x[..., 0]) * jnp.cos(k * x[..., 1]) * jnp.cos(k * x[..., 2])
    v = -jnp.cos(k * x[..., 0]) * jnp.sin(k * x[..., 1]) * jnp.cos(k * x[..., 2])
    return jnp.stack([u, v, jnp.zeros_like(u)], axis=-1)


def coil_vorticity(xc: jnp.ndarray) -> jnp.ndarray:
    """The reference's coiled-vorticity field (IC_vorticity,
    main.cpp:12537-12614): a 90-point coil at radius R(phi) =
    0.05 sin(2 phi) centered on (1,1,1); each cell takes the unit tangent
    of the NEAREST coil point scaled by 1/(r^2+1)^2.  xc: (..., 3) cell
    centers; returns omega (..., 3).  The absolute constants are the
    reference's (meant for a domain enclosing (1,1,1))."""
    ncoil, m = 90, 2
    phi = np.arange(ncoil) * (2.0 * np.pi / ncoil)
    R = 0.05 * np.sin(m * phi)
    pts = np.stack(
        [R * np.cos(phi) + 1.0, R * np.sin(phi) + 1.0,
         R * np.cos(m * phi) + 1.0], axis=-1
    )
    dR = 0.05 * m * np.cos(m * phi)
    tang = np.stack(
        [dR * np.cos(phi) - R * np.sin(phi),
         dR * np.sin(phi) + R * np.cos(phi),
         dR * np.cos(m * phi) - m * R * np.sin(m * phi)], axis=-1
    )
    tang /= np.sqrt((tang**2).sum(-1) + 1e-21)[:, None]
    p = jnp.asarray(pts, xc.dtype)
    t = jnp.asarray(tang, xc.dtype)
    d2 = jnp.sum((xc[..., None, :] - p) ** 2, axis=-1)  # (..., ncoil)
    idx = jnp.argmin(d2, axis=-1)
    r2 = jnp.take_along_axis(d2, idx[..., None], axis=-1)[..., 0]
    mag = 1.0 / (r2 + 1.0) ** 2
    return mag[..., None] * t[idx]


def coil_velocity_uniform(grid: UniformGrid, dtype=jnp.float32):
    """Velocity recovered from the coiled vorticity: u_d = lap^-1 of
    -(curl omega)_d component-wise (the reference solves the same three
    Poisson problems with its pressure solver, main.cpp:12614-12668).
    Uses the exact spectral inverse on the uniform grid."""
    from cup3d_tpu.ops import stencils as st
    from cup3d_tpu.ops.poisson import build_spectral_solver

    om = coil_vorticity(grid.cell_centers(dtype))
    curl = st.curl(grid.pad_vector(om, 1), 1, grid.h)
    solver = build_spectral_solver(grid, dtype)
    comps = [solver(-curl[..., d]) for d in range(3)]
    return jnp.stack(comps, axis=-1)


def turbulent_channel(grid: UniformGrid, u_bulk: float, nu: float,
                      seed: int = 0, dtype=jnp.float32,
                      sigma_cells: float = 2.0,
                      rms: float = 0.1) -> jnp.ndarray:
    """A seeded, turbulence-like start for the channel between walls at
    y = 0 and y = extent_y (half-height delta), periodic in x and z.

    Mean: Reichardt's law of the wall, u+ = ln(1 + k y+) / k + 7.8 [1 -
    exp(-y+/11) - (y+/11) exp(-y+/3)], k = 0.41, in the distance to the
    nearer wall, with Re_tau = 0.09 Re_b^0.88 (Pope, Turbulent Flows,
    2000, section 7.1; Re_b = 2 delta u_bulk / nu: 5,600 gives 179).  On
    it a divergence-free perturbation u' = curl psi: psi a random vector
    potential from ``seed``, low-passed by a Gaussian of ``sigma_cells``
    cells and damped as (1 - eta^2)^2 toward the walls (eta = y/delta -
    1), u' scaled to an rms of ``rms`` u_bulk per component.  The mean is
    scaled last so that the bulk velocity (the average of u_x over the
    cells) is ``u_bulk``."""
    import jax

    shape, h = grid.shape, grid.h
    ny, ly = shape[1], grid.extent[1]
    delta = 0.5 * ly
    y = (np.arange(ny) + 0.5) * h
    re_tau = 0.09 * (2.0 * delta * u_bulk / nu) ** 0.88
    yp = np.minimum(y, ly - y) * re_tau / delta
    kappa = 0.41
    up = (np.log1p(kappa * yp) / kappa
          + 7.8 * (1.0 - np.exp(-yp / 11.0)
                   - yp / 11.0 * np.exp(-yp / 3.0)))
    damp = (1.0 - (y / delta - 1.0) ** 2) ** 2

    noise = jax.random.normal(jax.random.PRNGKey(int(seed)),
                              (3,) + tuple(shape), jnp.float32)
    k2 = sum(np.fft.fftfreq(n).reshape([-1 if a == i else 1
                                        for a in range(3)]) ** 2
             for i, n in enumerate(shape))
    gauss = jnp.asarray(np.exp(-2.0 * (np.pi * sigma_cells) ** 2 * k2),
                        jnp.float32)
    psi = jnp.real(jnp.fft.ifftn(jnp.fft.fftn(noise, axes=(1, 2, 3))
                                 * gauss, axes=(1, 2, 3)))
    psi = psi * jnp.asarray(damp, jnp.float32)[None, None, :, None]

    def d(f, axis):
        """Central difference; psi is 0 beyond the walls."""
        if axis == 1:
            fp = jnp.pad(f, [(0, 0), (1, 1), (0, 0)])
            return (fp[:, 2:] - fp[:, :-2]) / (2.0 * h)
        return (jnp.roll(f, -1, axis) - jnp.roll(f, 1, axis)) / (2.0 * h)

    pert = jnp.stack([d(psi[2], 1) - d(psi[1], 2),
                      d(psi[0], 2) - d(psi[2], 0),
                      d(psi[1], 0) - d(psi[0], 1)], axis=-1)
    pert = pert * (rms * u_bulk / jnp.sqrt(jnp.mean(pert * pert)))
    scale = (u_bulk - jnp.mean(pert[..., 0])) / float(up.mean())
    mean = scale * jnp.asarray(up, jnp.float32)[None, :, None]
    return pert.at[..., 0].add(mean).astype(dtype)
