"""The forest driver's step kernels and megasteps, each written ONCE.

Every body is a plain function of its state arguments and, last, of one
*geometry view* (``GeomView``): the things that differ between the two
ways sim/amr.py binds them and nothing else.

- The capacity-bucketed binder (single device) jits ``body(*state,
  *geo)`` and rebuilds the view INSIDE the trace from the traced
  ``_geo_args`` bundle, so no topology constant reaches the HLO.
- The sharded-forest binder (``mesh=``) builds the view once per octree
  signature from the forest's non-pytree tables and closes over it
  (parallel/forest.py ``bind_step_executable``).

Where the two computed different arithmetic before they shared a body,
the view carries the difference: ``vol_total`` (traced sum against host
float), ``mask`` (masked against unmasked constant acceleration),
``sol`` (the stats-less forest solve) and ``helm_geom`` (the Helmholtz
solve's per-block scale, traced against baked in).

What a body needs of the configuration arrives through
``make_step_bodies``; this module never sees ``AMRSimulation``.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from cup3d_tpu.grid import adapt as ad
from cup3d_tpu.models.base import (
    combine_obstacle_fields,
    momentum_integrals_core,
    pack_forces,
    pack_moments,
    rigid_update_device,
)
from cup3d_tpu.models.collisions import overlap_count
from cup3d_tpu.ops import amr_ops
from cup3d_tpu.ops import diffusion as dif
from cup3d_tpu.ops.penalization import (
    penalize,
    per_obstacle_penalization_force,
)
from cup3d_tpu.ops.surface import probe_blocks_core

_EPS = 1e-6


class GeomView(NamedTuple):
    """One layout's geometry as the bodies read it.  ``geom`` is any
    duck-typed BlockGrid over the padded block axis (bs, nb, h, extent);
    ``sol`` the Poisson solve already bound to it."""

    geom: Any
    sol: Callable
    tab1: Any
    tab3: Any
    ftab: Any
    vol: Any
    xc: Any
    #: real-row mask on the constant acceleration; None leaves it unmasked
    mask: Any = None
    #: FixMassFlux parabola 6 eta (1 - eta), 0 on padding rows
    profile: Any = None
    #: total fluid volume in cells * h^3 (FixMassFlux only)
    vol_total: Any = None
    #: geometry handed to the Helmholtz solve; None keeps its built-in one
    helm_geom: Any = None


def make_step_bodies(
    *,
    nu: float,
    bs: int,
    dtype,
    helm: Optional[Callable] = None,
    fix_mass_flux: bool = False,
    umax_forced: float = 0.0,
    tag_rule: Optional[tuple] = None,
    h_fine: Optional[float] = None,
    budgets: Sequence[int] = (),
    windows: Sequence[tuple] = (),
    dlm: bool = False,
) -> SimpleNamespace:
    """The bodies for one configuration.  ``helm`` is the built
    Helmholtz solve of an implicitDiffusion run (None: explicit RK3);
    ``tag_rule`` = (Rtol, Ctol, levelMax, levelMaxVorticity,
    bAdaptChiGradient) for ``tags``; ``h_fine``, the per-obstacle static
    point ``budgets`` and the shapes of the probe ``windows`` (in blocks
    of the finest level, ``ops/surface.block_window_slots``) feed the
    surface probe of ``forces_bodies`` and the megastep; with ``dlm`` the
    penalization coefficient a body is handed is DLM, and lambda = DLM /
    dt is formed in the trace."""

    def advdiff(vel, dt, uinf, view):
        """Advection-diffusion honoring implicitDiffusion — the step
        kernel, and the first stage of both megasteps (their former
        ``advdiff_stage``)."""
        if helm is not None:
            # the tables travel as traced args too (ADVICE r2): the
            # closure-built helm's captured tables stay unused
            return dif.implicit_step_blocks(
                view.geom, vel, dt, nu, uinf, view.tab3,
                lambda u, nudt: helm(u, nudt, tab_arg=view.tab1,
                                     flux_arg=view.ftab,
                                     geom=view.helm_geom),
            )
        return amr_ops.rk3_step_blocks(view.geom, vel, dt, nu, uinf,
                                       view.tab3, view.ftab)

    def project(vel, dt, chi, udef, p_old, view, second_order=False):
        # with_stats: (vel, p, [resid, iters]) — the stats vector joins
        # the end-of-step packed QoI read (zeros on the stats-less
        # forest solver), so solver telemetry never adds a host sync
        return amr_ops.project_blocks(
            view.geom, vel, dt, view.sol, view.tab1, view.ftab, chi, udef,
            p_init=p_old, second_order=second_order, with_stats=True,
        )

    def project_2nd(vel, dt, chi, udef, p_old, view):
        return project(vel, dt, chi, udef, p_old, view, second_order=True)

    def penal_force(vn, vo, chis, dt, cms, view):
        return per_obstacle_penalization_force(
            vn, vo, chis, dt, view.vol, view.xc, cms
        )

    @jax.named_scope("Penalization")
    def ubody(udef, cm, ut, om, view):
        # per-obstacle rigid+deformation velocity field from the cached
        # device cell centers (avoids Obstacle.body_velocity_field's host
        # rebuild of cell_centers every step)
        xc = view.xc
        return (ut + jnp.cross(jnp.broadcast_to(om, xc.shape), xc - cm)
                + udef)

    def divnorms(vel, view):
        return amr_ops.divergence_norms_blocks(view.geom, vel, view.tab1)

    def dissipation(vel, view):
        return amr_ops.dissipation_blocks(view.geom, vel, nu, view.tab1)

    def gradchi(chi, view):
        tab1 = view.tab1
        return amr_ops.grad_blocks(
            view.geom, tab1.assemble_scalar(chi, bs), tab1.width
        )

    def omega_mag(vel, view):
        tab1 = view.tab1
        return jnp.sqrt(jnp.sum(
            amr_ops.curl_blocks(
                view.geom, tab1.assemble_vector(vel, bs), tab1.width
            ) ** 2,
            axis=-1,
        ))

    @jax.named_scope("AdaptMesh")
    def scores(vel, chi, view):
        return (amr_ops.vorticity_score(view.geom, vel, view.tab1),
                amr_ops.gradchi_mask(view.geom, chi, view.tab1))

    @jax.named_scope("AdaptMesh")
    def tags(vel, chi, level, view):
        # on-device regrid DECISION: scores -> per-slot int8 tag in
        # one dispatch, so adapt_mesh downloads (cap,) bytes instead
        # of two full score fields (grid/adapt.py device_tags)
        vort, near = scores(vel, chi, view)
        rtol, ctol, level_max, level_max_vort, adapt_chi = tag_rule
        return ad.device_tags(vort, near, level, rtol, ctol, level_max,
                              level_max_vort, adapt_chi)

    @jax.named_scope("UpdateObstacles")
    def moments(chis, vel, cms, view):
        # ``chis``: a tuple of fields (host path) or their stack (megastep).
        return jnp.stack([
            pack_moments(
                momentum_integrals_core(view.xc, view.vol, chis[i], vel,
                                        cms[i])
            )
            for i in range(len(chis))
        ])

    def overlaps(chis):
        """The collision pre-check: cells inside both bodies, per pair
        (i < j), as the packed reads carry it."""
        n = len(chis)
        pairs = [(i, j) for i in range(n) for j in range(i + 1, n)]
        if not pairs:
            return jnp.zeros(0, dtype)
        return jnp.stack([overlap_count(chis[i], chis[j]).astype(dtype)
                          for i, j in pairs])

    @jax.named_scope("UpdateObstacles")
    def moments_read(chis, vel, cms, view):
        """UpdateObstacles' device half on the per-step path: the vector
        its one blocking read fetches, every body's moments and then the
        pairs' overlap counts."""
        return jnp.concatenate(
            [moments(chis, vel, cms, view).reshape(-1).astype(dtype),
             overlaps(chis)])

    @jax.named_scope("Penalization")
    def penalize_bodies(vel, chis, udefs, rigid, dt, lam, view):
        """Penalization with every body's velocity field built in the
        trace: ``rigid`` holds a row (transVel, angVel, centerOfMass) per
        body, ``chis`` and ``udefs`` the bodies' fields (tuples on the
        per-step path, stacks in the megastep), ``lam`` lambda or, under
        ``dlm``, DLM.  Returns the penalized velocity and minus the
        momentum it injected, (force, torque) per body, flat."""
        chis, udefs = jnp.stack(chis), jnp.stack(udefs)
        xc = view.xc
        col = rigid[:, None, None, None, None]
        ub = (
            col[..., 0:3]
            + jnp.cross(jnp.broadcast_to(col[..., 3:6], udefs.shape),
                        xc[None] - col[..., 6:9])
            + udefs
        )  # (n_obs, nb, bs,bs,bs, 3)
        den = jnp.maximum(jnp.sum(chis, axis=0), _EPS)[..., None]
        ubody = jnp.sum(chis[..., None] * ub, axis=0) / den
        if dlm:
            lam = lam / dt
        vel_new = penalize(vel, jnp.max(chis, axis=0), ubody, lam, dt)
        PF = -penal_force(vel_new, vel, tuple(chis), dt, rigid[:, 6:9], view)
        return vel_new, PF.reshape(-1).astype(dtype)

    @jax.named_scope("ComputeForces")
    def forces_bodies(vel, p, chis, sdfs, udefs, win, rigid, view):
        """ComputeForces: the surface-point probe of every body
        (ops/surface.py: the production force measure, on the body's dense
        window, compacted to its static point budget) and the packed rows.
        ``win`` is one int32 vector: every body's window origin (3) and
        then every body's block slots, flat, in the shapes ``windows``."""
        rows, at = [], 3 * len(windows)
        for i, shape in enumerate(windows):
            size = shape[0] * shape[1] * shape[2]
            rows.append(pack_forces(probe_blocks_core(
                vel, p, chis[i], sdfs[i], udefs[i],
                win[at:at + size].reshape(shape), win[3 * i:3 * i + 3],
                jnp.asarray(h_fine, vel.dtype), nu,
                rigid[i, 6:9], rigid[i, 0:3], rigid[i, 3:6],
                max_points=budgets[i],
            )))
            at += size
        return jnp.concatenate(rows)

    def fix_flux(vel, uinf_x, u_target, view):
        # FixMassFlux on the forest (reference avgUx_nonUniform +
        # parabolic add, main.cpp:12199-12249): volume-weighted mean of
        # u+uinf, then u += delta * 6 eta(1-eta) (exact restoration;
        # see sim/operators.py FixMassFlux for the documented
        # divergence from the reference's 6x-amplifying constant)
        u_msr = (
            jnp.sum((vel[..., 0] + uinf_x) * view.vol) / view.vol_total
        )
        delta = u_target - u_msr
        return vel.at[..., 0].add(delta * view.profile), u_msr

    def forcing_stage(vel, uinf, dt, view):
        """FixMassFlux / uMax_forced forcing — shared by both
        megasteps.  Returns (vel, flux_msr (1,)).  Padding rows stay 0
        where the view says so (the profile is 0 there; the constant
        acceleration is masked by ``view.mask``)."""
        flux_msr = jnp.zeros(1, dtype)
        if fix_mass_flux:
            u_target = 2.0 / 3.0 * umax_forced
            vel, u_msr = fix_flux(vel, uinf[0], u_target, view)
            flux_msr = u_msr.reshape(1)
        elif umax_forced > 0:
            H = view.geom.extent[1]
            add = 8.0 * nu * umax_forced / (H * H) * dt
            if view.mask is not None:
                add = add * view.mask
            vel = vel.at[..., 0].add(add)
        return vel, flux_msr

    rigid_vmapped = jax.vmap(
        rigid_update_device, in_axes=(0, 0, 0, 0, None, None)
    )

    def mega(vel, p, chis, udefs, sdfs, rigid, forced, blocked, fixmask,
             win, uinf, dt, lam, view, second_order=False):
        """The whole obstacle step: advection -> vmapped device rigid
        update -> penalization -> forcing -> projection -> force QoI ->
        packed read vector."""
        chi, udef = combine_obstacle_fields(chis, udefs)

        vel = advdiff(vel, dt, uinf, view)

        # rigid update on device, all obstacles at once
        cms = rigid[:, 12:15]
        M = moments(chis, vel, cms, view)
        out = rigid_vmapped(M, rigid, forced, blocked, uinf, dt)
        # the rows the body kernels read: transVel, angVel, centerOfMass
        moved = jnp.concatenate([out[:, 0:6], out[:, 12:15]], axis=1)

        vel, PF = penalize_bodies(vel, chis, udefs, moved, dt, lam, view)

        vel, flux_msr = forcing_stage(vel, uinf, dt, view)

        vel, p = amr_ops.project_blocks(
            view.geom, vel, dt, view.sol, view.tab1, view.ftab, chi, udef,
            p_init=p, second_order=second_order,
        )

        F = forces_bodies(vel, p, chis, sdfs, udefs, win, moved, view)

        # next step's frame velocity from the NEW rigid state, so the
        # device chain matches non-pipelined uinf semantics exactly
        with jax.named_scope("DtPolicy"):
            nfix = jnp.sum(fixmask)
            mean_tv = jnp.sum(
                out[:, 0:3] * fixmask[:, None], axis=0
            ) / jnp.maximum(nfix, 1.0)
            uinf_next = jnp.where(nfix > 0, -mean_tv, uinf)
            umax = jnp.maximum(
                jnp.max(jnp.abs(vel + uinf_next)),
                jnp.max(jnp.abs(udef)),
            ).reshape(1)
        pack = jnp.concatenate(
            [out.reshape(-1), PF, F, overlaps(chis), flux_msr, umax]
        )
        return vel, p, chi, udef, uinf_next, pack

    def mega_free(vel, p, uinf, dt, view, second_order=False):
        """Obstacle-free fused step (amr_tgv-style runs): advection +
        forcing + projection + max|u| in one dispatch, same pack
        scheme."""
        vel = advdiff(vel, dt, uinf, view)
        vel, flux_msr = forcing_stage(vel, uinf, dt, view)
        vel, p = amr_ops.project_blocks(
            view.geom, vel, dt, view.sol, view.tab1, view.ftab,
            p_init=p, second_order=second_order,
        )
        with jax.named_scope("DtPolicy"):
            umax = jnp.max(jnp.abs(vel + uinf)).reshape(1)
        pack = jnp.concatenate([flux_msr, umax])
        return vel, p, pack

    return SimpleNamespace(
        advdiff=advdiff, project=project, project_2nd=project_2nd,
        ubody=ubody, divnorms=divnorms, dissipation=dissipation,
        gradchi=gradchi, omega_mag=omega_mag, scores=scores, tags=tags,
        moments_read=moments_read, penalize_bodies=penalize_bodies,
        forces_bodies=forces_bodies, fix_flux=fix_flux, mega=mega,
        mega_free=mega_free,
    )
