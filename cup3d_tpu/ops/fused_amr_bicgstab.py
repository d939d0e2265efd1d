"""Fused Pallas BiCGSTAB iteration on the bucket-padded AMR block forest.

The AMR Poisson iteration was the slowest path in the repo (BENCH_r05
amr_tgv roofline: 0.2% MFU, 2.7% of HBM peak, ~17x worse per iteration
than the uniform path ops/fused_bicgstab.py fused): the legacy
composition (krylov.bicgstab over amr_ops.laplacian_blocks +
getz_blocks) issues each iteration as ~a dozen XLA ops and every
intermediate (p, y, v, s, z, t) round-trips HBM between them.  The
bucket-padded layout (PR 3) made the forest fixed-shape by
construction, so the fused-iteration design applies directly; this
module is its block-forest twin, with stages over ``(capacity, bs, bs,
bs)`` padded blocks:

- ``update``  p/rhat recurrence + breakdown select + the volume-
              weighted coarse restriction (per-block partials)
- ``getz``    exact DST tile solve at the block's own h^2 (+ the
              two-level coarse injection)
- ``lap``     7-point lab stencil x per-block 1/h^2 + the dense
              coarse-fine reflux increment + the iteration's dot
              partials
- ``axpy``    s = r - alpha v + coarse restriction partials
- ``finish``  x/r updates + the residual/rho dot partials

Global dots never materialize a full-size temporary: every stage emits
**per-block f32 partials** ``(capacity, 1)`` reduced over the bs^3
cells of its own block, and a cheap follow-up ``jnp.sum`` combines
them into the iteration scalars.

What stays OUTSIDE the kernels, by design: the halo gather (the
face-table lab assembly is data-dependent indexing — grid/faces.py
keeps it as jnp gathers) and the coarse-fine flux scatter, which is
precomputed per application as a DENSE per-cell increment
(``flux_tab.apply`` on a zero field) so the kernel's Laplacian stage
consumes only fixed-shape inputs.  The two-level coarse solve also
runs between stages, here still as the (capacity,)-sized graph CG
(krylov._cg_graph over the graph's idx/w/deg; this driver ignores the
dense ``BlockGraph.pinv`` that krylov.coarse_correct_blocks multiplies
by on the stock path since PR 33); its restriction input comes from the
update/axpy stage partials, so no extra full-field reduction pass
exists.

Padding-block invariants (the ``inv_hc = 0`` contract from PR 3): the
padded face tables gather zeros into padding labs, padded flux rows
carry ``inv_hc = 0`` and scatter exactly 0.0 into the dump cell,
``vol = 0`` keeps padding rows out of every restriction/dot partial,
and the graph's padding rows have ``deg = 0`` so the coarse deflation
masks them.  Zero fields on padding blocks therefore stay exactly zero
through every stage — the selftest and tests/test_fused_amr.py assert
this.

Mixed precision follows ops/precision.py verbatim: Krylov vectors may
be stored bf16, every kernel loads to f32, dots/tile-solve matmuls
(``Precision.HIGHEST``) accumulate in f32, x stays f32.  Every stage
has a pure-jnp twin (the ``*_math`` helpers are shared verbatim by the
kernel bodies), which is the CPU execution path and the reference the
``interpret=True`` parity tests check against.

Dispatch: ``amr_ops.build_amr_poisson_solver_dynamic`` routes through
this driver under ``CUP3D_FUSED`` (precision.use_fused) for the
mean-removal constraint (mode 2) with the exact getZ — the production
pressure configuration; pinned-row modes and the CUP3D_GETZ=cg ladder
keep the legacy composition.

NOT RUNNABLE NATIVELY YET.  The stages keep the block-major
``(capacity, 8, 8, 8)`` layout, which puts an 8-wide axis on the
128-wide lane dimension: the v5e compiler aborts the process (SIGABRT
in ``VectorLayoutInferer::inferReshape``, not a Python exception) on
every stage — the per-block reductions of ``_blocksum``, the
``(n, 512)`` views of ``_getz_math`` and the lab stencil of
``_lap_math`` alike; handing the element-wise stages 2-D ``(C, 512)``
views does not get ``_k_lap`` through.  Until the stages are re-laid
out, asking for the native kernels raises
(:func:`refuse_native_kernels`); the jnp twins and the interpreter run
the same math for the parity tests.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from cup3d_tpu.ops import precision
from cup3d_tpu.ops.fused_bicgstab import _combine, _scalars
from cup3d_tpu.ops.getz_pallas import use_pallas

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32

#: leading-axis chunk: padded blocks per kernel invocation.  64 blocks
#: of 8^3 f32 keep every stage's working set well under the ~16 MB VMEM
#: budget (the heaviest stage, getz, holds ~5 chunk-sized f32 arrays
#: plus the 512x512 basis: ~7.5 MB).
BLOCK_CHUNK = 64


def refuse_native_kernels() -> None:
    """The fused forest stages do not compile for the TPU (module
    docstring).  Raising beats the alternative on both sides: compiling
    them aborts the interpreter, and dropping to the jnp twins or the
    unfused solver would hide that the requested path did not run."""
    raise NotImplementedError(
        "the fused forest BiCGSTAB kernels (ops/fused_amr_bicgstab.py) "
        "do not compile for the TPU: their block-major (capacity, 8, 8, "
        "8) stages abort the chip's compiler.  Run the forest with the "
        "stock solver (unset CUP3D_FUSED and CUP3D_KRYLOV_DTYPE); bf16 "
        "Krylov storage on a forest needs the stage re-layout first"
    )


# ---------------------------------------------------------------------------
# shared stage math: the kernel bodies and the jnp twins run THIS code
# ---------------------------------------------------------------------------


def _blocksum(a: jnp.ndarray) -> jnp.ndarray:
    """Per-block partial: reduce the bs^3 cells of each block, keep the
    block axis as (n, 1) f32.  Identical per block whether computed
    chunked (kernel grid) or whole (twin), which is what makes the
    interpret parity tests tight."""
    return jnp.sum(a.astype(_F32), axis=(1, 2, 3)).reshape(a.shape[0], 1)


def _update_math(r, p, v, rhat, vol, beta, omega, broke, store):
    """p/rhat recurrence with the breakdown re-seed folded in, plus the
    volume-weighted coarse restriction of the new search direction
    (coarse_correct_blocks' ``sum(r * vol)`` computed in-stage)."""
    r32, p32, v32 = (a.astype(_F32) for a in (r, p, v))
    p_eff = jnp.where(broke > 0.5, 0.0, p32)
    v_eff = jnp.where(broke > 0.5, 0.0, v32)
    p_new = r32 + beta * (p_eff - omega * v_eff)
    rhat_new = jnp.where(broke > 0.5, r32, rhat.astype(_F32))
    p_st = p_new.astype(store)
    rh_st = rhat_new.astype(store)
    return p_st, rh_st, _blocksum(p_st.astype(_F32) * vol)


def _getz_math(w, azf, zc, S3, lam, h2, bs, two_level, store):
    """Exact-getZ application on a block chunk at the block's own h^2:
    y = zc + tilesolve(-h2 (w - A zf)) (two-level; ``azf`` is the full
    refluxed Laplacian of the injected coarse correction, computed
    between stages — the analytic face-delta shortcut of the uniform
    kernel is not correct on a general forest, see
    amr_ops.build_amr_poisson_solver) or y = tilesolve(-h2 w)
    (tile-only).  Matmuls are f32 HIGHEST like ops/tilesolve.py — the
    quality floor for the outer iteration."""
    w32 = w.astype(_F32)
    if two_level:
        b = -h2 * (w32 - azf)
    else:
        b = -h2 * w32
    n = b.shape[0]
    b2 = b.reshape(n, bs ** 3)
    t = jnp.dot(b2, S3, precision=_HI, preferred_element_type=_F32)
    t = t / lam  # (1, 512) eigenvalue row broadcast over blocks
    z2 = jnp.dot(t, S3, precision=_HI, preferred_element_type=_F32)
    y = z2.reshape(b.shape)
    if two_level:
        y = y + zc  # constant coarse injection, (n, 1, 1, 1)
    return y.astype(store)


def _lap_math(lab, corr, a, inv_h2, bs, store):
    """Refluxed 7-point Laplacian on assembled labs + dot partials.

    ``lab`` (n, bs+2, bs+2, bs+2): the width-1 halo lab from the padded
    face tables (assembled between stages); ``corr`` the dense
    coarse-fine flux increment (0.0 everywhere the flux tables are
    inert, incl. every padding row by ``inv_hc = 0``).  Emits Aw plus
    per-block partials of a . Aw and Aw . Aw (the second is free — Aw
    is already in registers)."""
    lab32 = lab.astype(_F32)
    c = lab32[:, 1:bs + 1, 1:bs + 1, 1:bs + 1]
    s = -6.0 * c
    s = s + lab32[:, 2:bs + 2, 1:bs + 1, 1:bs + 1]
    s = s + lab32[:, 0:bs, 1:bs + 1, 1:bs + 1]
    s = s + lab32[:, 1:bs + 1, 2:bs + 2, 1:bs + 1]
    s = s + lab32[:, 1:bs + 1, 0:bs, 1:bs + 1]
    s = s + lab32[:, 1:bs + 1, 1:bs + 1, 2:bs + 2]
    s = s + lab32[:, 1:bs + 1, 1:bs + 1, 0:bs]
    aw = (s * inv_h2 + corr).astype(store)
    aw32 = aw.astype(_F32)
    d_a = _blocksum(a.astype(_F32) * aw32)
    d_self = _blocksum(aw32 * aw32)
    return aw, d_a, d_self


def _axpy_math(r, v, vol, alpha, store):
    s = (r.astype(_F32) - alpha * v.astype(_F32)).astype(store)
    return s, _blocksum(s.astype(_F32) * vol)


def _finish_math(x, y, z, s, t, rhat, alpha, omega, store):
    """x/r updates + the residual / next-rho partials.  x stays f32
    (the policy's wide accumulator over the narrow stored directions)."""
    y32, z32, s32, t32 = (a.astype(_F32) for a in (y, z, s, t))
    x_new = x + alpha * y32 + omega * z32
    r_st = (s32 - omega * t32).astype(store)
    r32 = r_st.astype(_F32)
    p_rr = _blocksum(r32 * r32)
    p_rhr = _blocksum(rhat.astype(_F32) * r32)
    return x_new, r_st, p_rr, p_rhr


# ---------------------------------------------------------------------------
# Pallas kernel bodies: load refs, run the shared math, store
# ---------------------------------------------------------------------------


def _k_update(r_ref, p_ref, v_ref, rhat_ref, vol_ref, sc_ref,
              pn_ref, rh_ref, ts_ref):
    beta, omega, broke = sc_ref[0, 0], sc_ref[0, 1], sc_ref[0, 2]
    p_new, rhat_new, ts = _update_math(
        r_ref[...], p_ref[...], v_ref[...], rhat_ref[...], vol_ref[...],
        beta, omega, broke, pn_ref.dtype,
    )
    pn_ref[...] = p_new
    rh_ref[...] = rhat_new
    ts_ref[...] = ts


def _k_getz_two(w_ref, azf_ref, zc_ref, h2_ref, S3_ref, lam_ref,
                y_ref, *, bs):
    y_ref[...] = _getz_math(w_ref[...], azf_ref[...], zc_ref[...],
                            S3_ref[...], lam_ref[...], h2_ref[...],
                            bs, True, y_ref.dtype)


def _k_getz_tile(w_ref, h2_ref, S3_ref, lam_ref, y_ref, *, bs):
    y_ref[...] = _getz_math(w_ref[...], None, None, S3_ref[...],
                            lam_ref[...], h2_ref[...], bs, False,
                            y_ref.dtype)


def _k_lap(lab_ref, corr_ref, a_ref, ih2_ref, aw_ref, da_ref, ds_ref,
           *, bs):
    aw, d_a, d_self = _lap_math(lab_ref[...], corr_ref[...], a_ref[...],
                                ih2_ref[...], bs, aw_ref.dtype)
    aw_ref[...] = aw
    da_ref[...] = d_a
    ds_ref[...] = d_self


def _k_axpy(r_ref, v_ref, vol_ref, sc_ref, s_ref, ts_ref):
    s, ts = _axpy_math(r_ref[...], v_ref[...], vol_ref[...],
                       sc_ref[0, 0], s_ref.dtype)
    s_ref[...] = s
    ts_ref[...] = ts


def _k_finish(x_ref, y_ref, z_ref, s_ref, t_ref, rhat_ref, sc_ref,
              xo_ref, ro_ref, prr_ref, prh_ref):
    x_new, r_new, p_rr, p_rhr = _finish_math(
        x_ref[...], y_ref[...], z_ref[...], s_ref[...], t_ref[...],
        rhat_ref[...], sc_ref[0, 0], sc_ref[0, 1], ro_ref.dtype,
    )
    xo_ref[...] = x_new
    ro_ref[...] = r_new
    prr_ref[...] = p_rr
    prh_ref[...] = p_rhr


# ---------------------------------------------------------------------------
# stage dispatch: pallas_call (native or interpret) or the jnp twin
# ---------------------------------------------------------------------------


class _Stages(NamedTuple):
    """Static per-solve stage configuration (shapes, dtypes, dispatch).

    Per-block geometry (h^2, 1/h^2, cell volume) rides as TRACED
    (npad, 1, 1, 1) column inputs — unlike the uniform _Stages' static
    floats — so one lowered stage serves every regrid of a capacity
    bucket (the sim/amr.py compiled-step cache contract)."""

    bs: int
    npad: int
    C: int
    store: object        # storage dtype for Krylov vectors
    kernels: bool        # run pallas_call (native TPU or interpret)
    interpret: bool

    def _specs(self):
        from jax.experimental import pallas as pl

        bs, C = self.bs, self.C
        L = bs + 2
        vec = pl.BlockSpec((C, bs, bs, bs), lambda i: (i, 0, 0, 0))
        col = pl.BlockSpec((C, 1, 1, 1), lambda i: (i, 0, 0, 0))
        labs = pl.BlockSpec((C, L, L, L), lambda i: (i, 0, 0, 0))
        part = pl.BlockSpec((C, 1), lambda i: (i, 0))
        mat = pl.BlockSpec((bs ** 3, bs ** 3), lambda i: (0, 0))
        lam = pl.BlockSpec((1, bs ** 3), lambda i: (0, 0))
        scal = pl.BlockSpec((1, 8), lambda i: (0, 0))
        return vec, col, labs, part, mat, lam, scal

    @property
    def grid(self):
        return (self.npad // self.C,)

    def _shape(self, kind):
        bs, n = self.bs, self.npad
        if kind == "vec":
            return jax.ShapeDtypeStruct((n, bs, bs, bs), self.store)
        if kind == "vec32":
            return jax.ShapeDtypeStruct((n, bs, bs, bs), _F32)
        return jax.ShapeDtypeStruct((n, 1), _F32)

    # -- stages -----------------------------------------------------------

    def update(self, r, p, v, rhat, vol, scal):
        if not self.kernels:
            beta, omega, broke = scal[0, 0], scal[0, 1], scal[0, 2]
            return _update_math(r, p, v, rhat, vol, beta, omega, broke,
                                self.store)
        from jax.experimental import pallas as pl

        vec, col, _, part, _, _, scs = self._specs()
        return pl.pallas_call(
            _k_update,
            grid=self.grid,
            in_specs=[vec, vec, vec, vec, col, scs],
            out_specs=[vec, vec, part],
            out_shape=[self._shape("vec"), self._shape("vec"),
                       self._shape("part")],
            # donate the carried p/rhat buffers into their updates
            input_output_aliases={1: 0, 3: 1},
            interpret=self.interpret,
        )(r, p, v, rhat, vol, scal)

    def getz(self, w, azf, zc, h2, S3, lam):
        two = azf is not None
        if not self.kernels:
            return _getz_math(w, azf, zc, S3, lam, h2, self.bs, two,
                              self.store)
        from jax.experimental import pallas as pl

        vec, col, _, _, mat, lams, _ = self._specs()
        if two:
            return pl.pallas_call(
                partial(_k_getz_two, bs=self.bs),
                grid=self.grid,
                in_specs=[vec, vec, col, col, mat, lams],
                out_specs=vec,
                out_shape=self._shape("vec"),
                interpret=self.interpret,
            )(w, azf, zc, h2, S3, lam)
        return pl.pallas_call(
            partial(_k_getz_tile, bs=self.bs),
            grid=self.grid,
            in_specs=[vec, col, mat, lams],
            out_specs=vec,
            out_shape=self._shape("vec"),
            interpret=self.interpret,
        )(w, h2, S3, lam)

    def lap(self, lab, corr, a, inv_h2):
        if not self.kernels:
            return _lap_math(lab, corr, a, inv_h2, self.bs, self.store)
        from jax.experimental import pallas as pl

        vec, col, labs, part, _, _, _ = self._specs()
        return pl.pallas_call(
            partial(_k_lap, bs=self.bs),
            grid=self.grid,
            in_specs=[labs, vec, vec, col],
            out_specs=[vec, part, part],
            out_shape=[self._shape("vec"), self._shape("part"),
                       self._shape("part")],
            interpret=self.interpret,
        )(lab, corr, a, inv_h2)

    def axpy(self, r, v, vol, scal):
        if not self.kernels:
            return _axpy_math(r, v, vol, scal[0, 0], self.store)
        from jax.experimental import pallas as pl

        vec, col, _, part, _, _, scs = self._specs()
        return pl.pallas_call(
            _k_axpy,
            grid=self.grid,
            in_specs=[vec, vec, col, scs],
            out_specs=[vec, part],
            out_shape=[self._shape("vec"), self._shape("part")],
            interpret=self.interpret,
        )(r, v, vol, scal)

    def finish(self, x, y, z, s, t, rhat, scal):
        if not self.kernels:
            return _finish_math(x, y, z, s, t, rhat, scal[0, 0],
                                scal[0, 1], self.store)
        from jax.experimental import pallas as pl

        vec, _, _, part, _, _, scs = self._specs()
        return pl.pallas_call(
            _k_finish,
            grid=self.grid,
            in_specs=[vec, vec, vec, vec, vec, vec, scs],
            out_specs=[vec, vec, part, part],
            out_shape=[self._shape("vec32"), self._shape("vec"),
                       self._shape("part"), self._shape("part")],
            # donate x into x_new and the s buffer into r_new
            input_output_aliases={0: 0, 3: 1},
            interpret=self.interpret,
        )(x, y, z, s, t, rhat, scal)


# ---------------------------------------------------------------------------
# the fused solver driver
# ---------------------------------------------------------------------------


class _FusedState(NamedTuple):
    k: jnp.ndarray
    x: jnp.ndarray        # f32 accumulator
    r: jnp.ndarray        # storage dtype from here down
    rhat: jnp.ndarray
    p: jnp.ndarray
    v: jnp.ndarray
    rho: jnp.ndarray      # f32 scalars
    alpha: jnp.ndarray
    omega: jnp.ndarray
    rnorm: jnp.ndarray
    rho_dot: jnp.ndarray  # rhat . r, carried from the finish partials
    x_best: jnp.ndarray
    rnorm_best: jnp.ndarray


def fused_amr_bicgstab(
    geom,
    b: jnp.ndarray,
    *,
    tab,
    ftab=None,
    vol: jnp.ndarray,
    graph=None,
    tol_abs: float = 1e-6,
    tol_rel: float = 1e-4,
    maxiter: int = 1000,
    rnorm_ref=None,
    x0: Optional[jnp.ndarray] = None,
    store_dtype=None,
    kernels: Optional[bool] = None,
    interpret: bool = False,
):
    """Fused-iteration preconditioned BiCGSTAB on the padded forest.

    Same contract as the ``krylov.bicgstab`` call inside
    ``amr_ops.build_amr_poisson_solver_dynamic`` specialized to the
    production pressure system: A = the refluxed 7-point forest
    Laplacian (``tab``/``ftab``, PR 3 padded tables), M = the exact
    getZ tile solve at each block's own h (+ the block-graph coarse
    level when ``graph`` is given).  ``b`` is the mean-removed, masked
    rhs in blocks layout ``(geom.nb, bs, bs, bs)`` f32; ``vol`` the
    per-cell volume column (0 on padding blocks).  Returns
    ``(x (f32 blocks), rnorm_best, iterations)``.

    ``kernels=None`` auto-selects pallas on TPU (getz_pallas.use_pallas)
    and the jnp twins elsewhere; ``interpret=True`` forces the kernels
    through the Pallas interpreter for the CPU parity tests.
    """
    from cup3d_tpu.ops import amr_ops, krylov, tilesolve

    bs = int(geom.bs)
    nb = int(geom.nb)
    store = precision.krylov_dtype() if store_dtype is None else store_dtype
    if kernels is None:
        kernels = use_pallas()
    if interpret:
        kernels = True
    elif kernels:
        refuse_native_kernels()
    two_level = graph is not None
    if tab.width != 1:
        raise ValueError("fused AMR Laplacian needs width-1 lab tables")

    C = min(BLOCK_CHUNK, nb)
    npad = -(-nb // C) * C
    st = _Stages(bs=bs, npad=npad, C=C, store=store, kernels=kernels,
                 interpret=interpret)

    def padN(a):
        if a.shape[0] == npad:
            return a
        pad = [(0, npad - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, pad)

    # per-block geometry columns (traced; padding blocks carry h = 1 by
    # the bucket invariant, and the chunk-alignment rows added here are
    # zero — their fields stay identically zero through every stage)
    h_col = jnp.reshape(jnp.asarray(geom.h, _F32), (nb, 1, 1, 1))
    inv_h = 1.0 / h_col
    h2_col = padN(h_col * h_col)
    inv_h2_col = padN(inv_h * inv_h)
    vol_col = padN(jnp.asarray(vol, _F32))

    S3, lam3, _ = tilesolve._basis(bs, "float32")
    lam = lam3.reshape(1, bs ** 3)

    if two_level:
        # the looped coarse solve of coarse_correct_blocks (its arm
        # without graph.pinv) with the restriction already computed by
        # the update/axpy stage partials
        m = (graph.deg > 0).astype(graph.w.dtype)
        nreal = jnp.maximum(jnp.sum(m), 1.0)

        def _deflate(vv):
            return (vv - jnp.sum(vv * m) / nreal) * m

        def _C(z):
            return graph.deg * z - jnp.sum(z[graph.idx] * graph.w,
                                           axis=-1)

        def coarse_aux(tsum):
            rc = tsum[:nb, 0].astype(graph.w.dtype)
            zc = (-_deflate(krylov._cg_graph(_C, _deflate(rc), 32))
                  ).astype(_F32)
            zf = jnp.broadcast_to(zc[:, None, None, None],
                                  (nb, bs, bs, bs))
            # full refluxed A zf between stages: correct on any forest
            # topology (amr_ops.build_amr_poisson_solver docstring)
            azf = amr_ops.laplacian_blocks(geom, zf, tab, ftab)
            return padN(azf.astype(_F32)), padN(zc.reshape(nb, 1, 1, 1))
    else:
        def coarse_aux(tsum):
            return None, None

    def lab_corr(w_st):
        """Assemble the width-1 halo lab of a Krylov direction and the
        dense coarse-fine reflux increment — the two data-dependent-
        indexing pieces of A the kernels consume as fixed-shape inputs."""
        w32 = w_st[:nb].astype(_F32)
        lab = tab.assemble_scalar(w32, bs)
        if ftab is not None and ftab.ncorr:
            fl = amr_ops.face_fluxes(lab, tab.width, bs, inv_h)
            corr = ftab.apply(jnp.zeros((nb, bs, bs, bs), _F32), fl)
        else:
            corr = jnp.zeros((nb, bs, bs, bs), _F32)
        return padN(lab.astype(_F32)), padN(corr)

    b32 = padN(jnp.asarray(b, _F32))
    if x0 is None:
        x0_ = jnp.zeros_like(b32)
        r0 = b32  # A(0) == 0 exactly; skip the apply
    else:
        x0_ = padN(jnp.asarray(x0, _F32))
        r0 = b32 - padN(amr_ops.laplacian_blocks(
            geom, jnp.asarray(x0, _F32), tab, ftab))
    rr0 = krylov._dot(r0, r0)
    rnorm0 = jnp.sqrt(rr0)
    ref = rnorm0 if rnorm_ref is None else rnorm_ref
    target = jnp.maximum(tol_abs, tol_rel * ref)
    # eps in the ACCUMULATION dtype (see ops/fused_bicgstab.py)
    eps = jnp.asarray(1e-30, _F32)
    one = jnp.asarray(1.0, _F32)

    r_st = r0.astype(store)
    init = _FusedState(
        k=jnp.asarray(0, jnp.int32),
        x=x0_,
        r=r_st,
        rhat=r_st,
        p=jnp.zeros_like(r_st),
        v=jnp.zeros_like(r_st),
        rho=one,
        alpha=one,
        omega=one,
        rnorm=rnorm0,
        rho_dot=rr0,
        x_best=x0_,
        rnorm_best=rnorm0,
    )

    def cond(s: _FusedState):
        return jnp.logical_and(s.k < maxiter, s.rnorm > target)

    def body(s: _FusedState):
        safe = krylov._safe
        rn2 = s.rnorm * s.rnorm
        broke = jnp.abs(s.rho_dot) < eps * jnp.maximum(rn2, 1.0)
        rho_new = jnp.where(broke, rn2, s.rho_dot)
        beta = (rho_new / safe(s.rho)) * (s.alpha / safe(s.omega))
        beta = jnp.where(broke, 0.0, beta)

        p, rhat, ts_p = st.update(
            s.r, s.p, s.v, s.rhat, vol_col,
            _scalars(beta, s.omega, broke.astype(_F32)),
        )
        azf_p, zc_p = coarse_aux(ts_p)
        y = st.getz(p, azf_p, zc_p, h2_col, S3, lam)
        lab_y, corr_y = lab_corr(y)
        v, d_rhv, _ = st.lap(lab_y, corr_y, rhat, inv_h2_col)
        alpha = rho_new / safe(_combine(d_rhv))

        svec, ts_s = st.axpy(s.r, v, vol_col, _scalars(alpha))
        azf_s, zc_s = coarse_aux(ts_s)
        z = st.getz(svec, azf_s, zc_s, h2_col, S3, lam)
        lab_z, corr_z = lab_corr(z)
        t, d_ts, d_tt = st.lap(lab_z, corr_z, svec, inv_h2_col)
        omega = _combine(d_ts) / safe(_combine(d_tt))

        x, r, p_rr, p_rhr = st.finish(s.x, y, z, svec, t, rhat,
                                      _scalars(alpha, omega))
        rnorm = jnp.sqrt(_combine(p_rr))
        better = rnorm < s.rnorm_best
        return _FusedState(
            k=s.k + 1, x=x, r=r, rhat=rhat, p=p, v=v,
            rho=rho_new, alpha=alpha, omega=omega, rnorm=rnorm,
            rho_dot=_combine(p_rhr),
            x_best=jnp.where(better, x, s.x_best),
            rnorm_best=jnp.minimum(rnorm, s.rnorm_best),
        )

    out = jax.lax.while_loop(cond, body, init)
    return out.x_best[:nb], out.rnorm_best, out.k


# ---------------------------------------------------------------------------
# analytic traffic model + smoke test
# ---------------------------------------------------------------------------


def bytes_model(store_dtype=None, two_level: bool = True) -> dict:
    """Analytic HBM bytes per cell per fused AMR iteration (reads +
    writes), by stage — the model bench.py reports next to the measured
    rate.  e = storage bytes/cell; labs cost (bs+2)^3/bs^3 ~ 1.95 f32
    reads per cell per apply and the dense reflux increment one more;
    per-block columns/partials are O(nb) and ignored."""
    store = precision.krylov_dtype() if store_dtype is None else store_dtype
    e = jnp.dtype(store).itemsize
    lab = float((8 + 2) ** 3) / 8 ** 3  # width-1 halo amplification
    per = {
        # r, p, v, rhat in; p, rhat out
        "update": 6 * e,
        # 2x (w + azf in, y out)
        "getz": 2 * (e + 4 + e),
        # 2x (lab assemble: read w, write lab; corr: read lab, write)
        "assemble": 2 * ((e + lab * 4) + (lab * 4 + 4)),
        # 2x (lab + corr + partner in, Aw out)
        "lap": 2 * ((lab * 4 + 4 + e) + e),
        # coarse zf Laplacian between stages: lab round trip again
        "coarse_azf": 2 * (4 + lab * 4 + 4) if two_level else 0.0,
        # r, v in; s out
        "axpy": 3 * e,
        # y, z, s, t, rhat in + x f32 in; x f32 + r out
        "finish": 5 * e + 4 + 4 + e,
        # best-x select: x_new, x_best in, x_best out (f32)
        "best_x": 12,
    }
    per["total"] = round(sum(per.values()), 2)
    return per


def legacy_bytes_model(two_level: bool = True) -> float:
    """The unfused AMR composition under the same counting rules: every
    intermediate round-trips HBM between ops — 2 refluxed Laplacians
    (lab assemble + stencil + corr), 2 getZ tile solves, the two-level
    r2 Laplacians, ~10 vector ops, 4 dots, all f32."""
    lab = float((8 + 2) ** 3) / 8 ** 3
    lap = (4 + lab * 4 + 4) + (lab * 4 + 4 + 4)  # assemble + apply
    n_lap = 4 if two_level else 2
    return n_lap * lap + 2 * 8.0 + 10 * 8.0 + 4 * 4.0


def selftest() -> None:
    """Interpret-mode kernel smoke on a PADDED two-level forest: the
    fused driver with interpret kernels must match the jnp-twin driver
    iteration-for-iteration, and padding blocks must stay exactly zero.
    Wired into tools/lint.sh so CI exercises the kernels without a TPU."""
    import numpy as np

    from cup3d_tpu.grid import bucket as bk
    from cup3d_tpu.grid.blocks import BlockGrid
    from cup3d_tpu.grid.faces import pad_face_tables
    from cup3d_tpu.grid.flux import build_flux_tables, pad_flux_tables
    from cup3d_tpu.grid.octree import Octree, TreeConfig
    from cup3d_tpu.grid.uniform import BC
    from cup3d_tpu.ops import krylov

    tree = Octree(TreeConfig((2, 2, 2), 2, (True,) * 3), 0)
    tree.refine(sorted(tree.leaves)[0])
    g = BlockGrid(tree, (1.0,) * 3, (BC.periodic,) * 3, 8)
    cap = bk.capacity(g.nb)
    tab = pad_face_tables(g.face_tables(1), g, cap)
    ftab = pad_flux_tables(build_flux_tables(g), g.bs, cap)
    graph = krylov.block_graph_tables(g, cap=cap)
    h = np.ones(cap)
    h[: g.nb] = g.h
    vol = np.zeros((cap, 1, 1, 1), np.float32)
    vol[: g.nb, 0, 0, 0] = g.h ** 3

    class _Geom:
        pass

    geom = _Geom()
    geom.bs, geom.nb, geom.extent = g.bs, cap, g.extent
    geom.h = jnp.asarray(h, jnp.float32)
    jvol = jnp.asarray(vol)

    rng = np.random.default_rng(0)
    rhs = np.zeros((cap, 8, 8, 8), np.float32)
    rhs[: g.nb] = rng.standard_normal((g.nb, 8, 8, 8))
    rhs = jnp.asarray(rhs)
    b = rhs - jnp.sum(rhs * jvol) / (jnp.sum(jvol) * g.bs ** 3)
    mask = jnp.asarray((vol > 0).astype(np.float32))
    b = b * mask
    kw = dict(tab=tab, ftab=ftab, vol=jvol, graph=graph, tol_abs=1e-8,
              tol_rel=1e-5, maxiter=60, store_dtype=_F32,
              rnorm_ref=jnp.sqrt(jnp.sum(b * b)))
    x_twin, rn_twin, k_twin = fused_amr_bicgstab(geom, b, kernels=False,
                                                 **kw)
    x_kern, rn_kern, k_kern = fused_amr_bicgstab(geom, b,
                                                 interpret=True, **kw)
    assert int(k_twin) == int(k_kern), (int(k_twin), int(k_kern))
    scale = float(jnp.max(jnp.abs(x_twin))) or 1.0
    err = float(jnp.max(jnp.abs(x_twin - x_kern))) / scale
    assert err < 1e-5, err
    pad_max = float(jnp.max(jnp.abs(x_twin[g.nb:])))
    assert pad_max == 0.0, pad_max
    # bf16 storage smoke through the same twin: the narrow-storage
    # iteration has a quality floor well above the f32 target (the
    # uniform driver gates it the same way) — require 3 digits relative
    bnorm = float(jnp.sqrt(jnp.sum(b * b)))
    xb, rnb, kb = fused_amr_bicgstab(geom, b, kernels=False,
                                     **{**kw, "store_dtype": jnp.bfloat16})
    assert float(rnb) <= 1e-3 * bnorm, (float(rnb), bnorm)
    print(f"fused_amr_bicgstab selftest: OK (iters={int(k_twin)}, "
          f"interpret-vs-twin rel err {err:.2e}, padding max 0.0, "
          f"bf16 iters={int(kb)})")


if __name__ == "__main__":
    selftest()
