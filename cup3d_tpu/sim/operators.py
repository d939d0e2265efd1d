"""The operator pipeline (reference Operator ABC main.cpp:6678-6684; pipeline
order fixed in setupOperators, main.cpp:15229-15246).

Each operator wraps a jitted pure function over the state dict.  Device-side
math lives in ``cup3d_tpu.ops``; operators only orchestrate.  ``dt`` is
passed as a traced scalar so per-step dt changes never retrigger compilation.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from cup3d_tpu.analysis.runtime import blocking_read
from cup3d_tpu.obs import metrics as obs_metrics
from cup3d_tpu.ops import diagnostics as diag
from cup3d_tpu.ops.advection import rk3_step
from cup3d_tpu.ops.projection import project
from cup3d_tpu.sim.data import SimulationData


class Operator:
    """Base: stateful wrapper invoked once per step as op(dt)."""

    def __init__(self, sim: SimulationData):
        self.sim = sim

    @property
    def name(self) -> str:
        return type(self).__name__

    def __call__(self, dt: float) -> None:
        raise NotImplementedError


class AdvectionDiffusion(Operator):
    """Explicit RK3 advection-diffusion (main.cpp:9640-9728)."""

    def __init__(self, sim: SimulationData):
        super().__init__(sim)
        # donate the velocity buffer (JX002): the step maps vel -> vel, so
        # XLA aliases the update in place instead of holding two fields
        self._step = jax.jit(partial(rk3_step, sim.grid, nu=sim.nu),
                             donate_argnums=(0,))

    def __call__(self, dt):
        s = self.sim
        s.state["vel"] = self._step(s.state["vel"], dt=dt, uinf=s.uinf_device())


class AdvectionDiffusionImplicit(Operator):
    """Explicit-advection Euler + implicit diffusion solve
    (main.cpp:9849-10118).  On the uniform grid the Helmholtz system
    (I - nu dt lap) u = u* is diagonalized exactly per component
    (ops/diffusion.py), so the step is unconditionally stable with no
    Krylov iteration at all."""

    def __init__(self, sim: SimulationData):
        super().__init__(sim)
        from cup3d_tpu.ops import diffusion as dif

        helm = dif.build_spectral_helmholtz(sim.grid, sim.dtype)
        self._step = jax.jit(
            partial(dif.implicit_step, sim.grid, nu=sim.nu, helmholtz=helm),
            donate_argnums=(0,),  # vel -> vel: alias in place (JX002)
        )

    def __call__(self, dt):
        s = self.sim
        s.state["vel"] = self._step(s.state["vel"], dt=dt, uinf=s.uinf_device())


def forced(cfg) -> bool:
    """Whether the configuration drives the flow streamwise
    (``-bFixMassFlux``, or ``-uMax_forced`` alone)."""
    return bool(cfg.bFixMassFlux) or cfg.uMax_forced > 0


def bulk_target(cfg) -> float:
    """The bulk velocity FixMassFlux holds: that of the parabola of peak
    ``uMax_forced``."""
    return 2.0 / 3.0 * cfg.uMax_forced


def forcing_stage(sim: SimulationData) -> Optional[Callable]:
    """The flow's streamwise forcing as one pure function ``apply(vel,
    uinf, dt) -> (vel, u_bulk)``, or None on a flow that is not forced.
    Written once: the per-step operators (:class:`FixMassFlux`,
    :class:`ExternalForcing`) and the scan body
    (``sim/megaloop.make_tgv_step``) call the same function, under the
    operator's name as its device scope.  ``u_bulk`` is the bulk
    velocity, the average of u_x + uinf_x over the cells, measured before
    the forcing.

    ``FixMassFlux`` (reference main.cpp:12199-12249) measures the bulk
    velocity and adds ``delta * P(eta)`` with ``P = 6 eta (1 - eta)``
    normalised to a mean of exactly 1 over the cell centres, so the
    deficit ``delta`` against the target is restored exactly.  Documented
    divergence from the reference: its aux = 6*(6*delta)*eta(1-eta)
    restores SIX times the measured deficit per step, which amplifies the
    flux error 5x per application — a latent bug its condensed fork never
    exercises (the factory builds only StefanFish, run.sh never sets
    -bFixMassFlux).

    ``ExternalForcing`` (main.cpp:10581-10596) adds the constant
    streamwise acceleration ``8 nu uMax / H^2`` times dt."""
    cfg, grid = sim.cfg, sim.grid
    if not forced(cfg):
        return None

    def bulk(vel, uinf):
        # one axis at a time: float32 sums of a few hundred terms each,
        # 20x closer to the exact mean than one sum over every cell
        u = jnp.mean(jnp.mean(jnp.mean(vel[..., 0], axis=2), axis=0))
        return u + uinf[0]

    if cfg.bFixMassFlux:
        ny = grid.shape[1]
        eta = (np.arange(ny) + 0.5) / ny  # y / y_max at cell centers
        profile = 6.0 * eta * (1.0 - eta)
        profile = jnp.asarray(profile / profile.mean(), sim.dtype)

        @jax.named_scope("FixMassFlux")
        def apply(vel, uinf, dt):
            u_msr = bulk(vel, uinf)
            aux = (bulk_target(cfg) - u_msr) * profile[None, :, None]
            return vel.at[..., 0].add(aux), u_msr

        return apply

    H = grid.extent[1]
    accel = 8.0 * sim.nu * cfg.uMax_forced / (H * H)

    @jax.named_scope("ExternalForcing")
    def apply(vel, uinf, dt):
        return vel.at[..., 0].add(accel * dt), bulk(vel, uinf)

    return apply


class _ForcingOperator(Operator):
    """A forced step on the per-step path: the flow's
    :func:`forcing_stage`, one program a step.  Raises
    ``operators.flux_host_steps`` once a call (the scan raises
    ``operators.flux_scan_steps`` instead)."""

    def __init__(self, sim: SimulationData):
        super().__init__(sim)
        self._apply = jax.jit(forcing_stage(sim), donate_argnums=(0,))

    def __call__(self, dt):
        s = self.sim
        obs_metrics.counter("operators.flux_host_steps").inc()
        s.state["vel"], u_msr = self._apply(s.state["vel"],
                                            s.uinf_device(), dt)
        return u_msr


class ExternalForcing(_ForcingOperator):
    """Constant streamwise acceleration for forced channel-type flows
    (:func:`forcing_stage`)."""


class FixMassFlux(_ForcingOperator):
    """Hold a target bulk flux (:func:`forcing_stage`) and log the
    measured bulk velocity to flux.txt: the designed per-step read
    ``flux-read`` (the scan path writes the same lines from its rows)."""

    def __call__(self, dt):
        s = self.sim
        u_msr = blocking_read("flux-read", super().__call__(dt), np.float64)
        s.logger.write("flux.txt", flux_line(s.step, s.time, float(u_msr),
                                             bulk_target(s.cfg)))


def flux_line(step: int, time: float, u_msr: float, target: float) -> str:
    """One line of flux.txt: step, time at the step's start, the bulk
    velocity measured before the correction, the target."""
    return f"{step} {time:.8e} {u_msr:.8e} {target:.8e}\n"


def note_poisson_solves(solver, n: int) -> None:
    """Raise ``poisson.<entry>_solves`` by ``n`` dispatched solves of a
    solver that names its entry (krylov.build_iterative_solver:
    ``increment`` or ``composed``); nothing for one that names none (the
    spectral solve).  The per-step projection raises it once a call, the
    scan ``scan_k`` times a dispatch."""
    entry = getattr(solver, "entry", None)
    if entry:
        obs_metrics.counter(f"poisson.{entry}_solves").inc(n)


class PressureProjection(Operator):
    """RHS -> Poisson solve -> velocity correction (main.cpp:15061-15160).

    Note on the reference's 2nd-order-in-time pressure option
    (``step_2nd_start``, main.cpp:15087-15100): it solves for the pressure
    *increment* about p_old as a warm start for the Krylov solver.  With the
    exact spectral solver used here the increment and full formulations are
    algebraically identical, so the option is meaningful only for the
    iterative AMR solver (cup3d_tpu.ops.krylov), which honors it.
    """

    def __init__(self, sim: SimulationData):
        super().__init__(sim)
        grid, solver = sim.grid, sim.poisson_solver
        # iterative solvers surface (residual, iterations) as a device
        # vector that rides the end-of-step QoI pack — per-step solver
        # telemetry with zero extra syncs (obs/trace.py).  The exact
        # spectral solver has no iteration count; its path is unchanged.
        self._with_stats = bool(getattr(solver, "supports_stats", False))
        self.solver_maxiter = getattr(solver, "maxiter", None)

        # vel and p_old are the step state: donated (JX002 burn-down).
        # chi/udef persist across steps and must NOT be donated.
        @partial(jax.jit, donate_argnums=(0, 4))
        def _project(vel, chi, udef, dt, p_old):
            # previous pressure warm-starts the iterative solver
            # (main.cpp:15087-15100); the spectral solver ignores it
            return project(grid, vel, dt, solver, chi, udef, p_init=p_old,
                           with_stats=self._with_stats)

        self._project = _project

    def __call__(self, dt):
        s = self.sim
        out = self._project(
            s.state["vel"], s.state["chi"], s.state["udef"], dt, s.state["p"]
        )
        note_poisson_solves(s.poisson_solver, 1)
        if self._with_stats:
            vel, p, stats = out
            s.pending_parts.append(("psolve", stats))
        else:
            vel, p = out
        s.state["vel"] = vel
        s.state["p"] = p


class ComputeDissipation(Operator):
    """Energy-budget diagnostics every freqDiagnostics steps
    (main.cpp:10436-10447); appends to energy.txt."""

    def __init__(self, sim: SimulationData):
        super().__init__(sim)
        self._diss = jax.jit(partial(diag.dissipation, sim.grid, nu=sim.nu))

    def __call__(self, dt):
        s = self.sim
        freq = s.cfg.freqDiagnostics
        if freq <= 0 or s.step % freq:
            return
        d = self._diss(s.state["vel"])
        s.logger.write(
            "energy.txt",
            # jax-lint: allow(JX001, freq-gated diagnostic: production
            # configs run freqDiagnostics=0 so this never rides the loop)
            f"{s.time:.8e} {float(d['kinetic_energy']):.8e} "
            # jax-lint: allow(JX001, freq-gated diagnostic (see above))
            f"{float(d['enstrophy']):.8e} {float(d['dissipation_rate']):.8e}\n",
        )


class ComputeDivergence(Operator):
    """Appends (step, time, sum|div u| h^3, max|div u|) to div.txt
    (main.cpp:8789-8919)."""

    def __init__(self, sim: SimulationData):
        super().__init__(sim)
        self._norms = jax.jit(partial(diag.divergence_norms, sim.grid))

    def __call__(self, dt):
        s = self.sim
        freq = s.cfg.freqDiagnostics
        if freq <= 0 or s.step % freq:
            return
        total, peak = self._norms(s.state["vel"])
        s.logger.write(
            "div.txt",
            # jax-lint: allow(JX001, freq-gated diagnostic: production
            # configs run freqDiagnostics=0 so this never rides the loop)
            f"{s.step} {s.time:.8e} {float(total):.8e} {float(peak):.8e}\n",
        )


def initial_conditions(sim: SimulationData) -> None:
    """InitialConditions operator (main.cpp:12506-12748): zero, Taylor-Green,
    the coiled vorticity, the laminar channel parabola, or a seeded
    turbulence-like channel (``turbulentChannel``, utils/flows.py)."""
    cfg, grid = sim.cfg, sim.grid
    kind = cfg.initCond
    if kind == "zero":
        return
    if kind == "taylorGreen":
        from cup3d_tpu.utils.flows import taylor_green_3d

        sim.state["vel"] = taylor_green_3d(grid, sim.dtype)
        return
    if kind == "vorticity":
        from cup3d_tpu.utils.flows import coil_velocity_uniform

        sim.state["vel"] = coil_velocity_uniform(grid, sim.dtype)
        return
    if kind == "turbulentChannel":
        from cup3d_tpu.utils.flows import turbulent_channel

        sim.state["vel"] = turbulent_channel(
            grid, bulk_target(cfg), sim.nu, cfg.initSeed,
            sim.dtype)
        return
    x = grid.cell_centers(sim.dtype)
    if kind == "channel":
        H = grid.extent[1]
        y = x[..., 1] / H
        u = 4.0 * cfg.uMax_forced * y * (1.0 - y)
        sim.state["vel"] = jnp.stack([u, jnp.zeros_like(u), jnp.zeros_like(u)], -1)
    else:
        raise ValueError(f"unknown initCond {kind!r}")
