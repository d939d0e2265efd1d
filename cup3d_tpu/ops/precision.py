"""Mixed-precision policy for the Krylov hot loop (round 12).

The pressure-Poisson BiCGSTAB iteration is bandwidth-bound (BENCH_r05:
37% of HBM peak at 128^3, 19% at 256^3), so halving the bytes of the
Krylov *storage* is worth more than any further flop work.  The policy
split is storage-vs-accumulation, not a blanket dtype:

- **Krylov vectors** (r, rhat, p, v and the per-iteration y, z, s, t)
  may be stored bf16: they only feed short-recurrence updates whose
  error the outer iteration contracts away.
- **All accumulations stay f32**: global dot products / residual norms
  (a bf16 sum over 2M cells loses ~3 digits and corrupts alpha/omega),
  the getZ tile-solve matmuls (a default-precision bf16 preconditioner
  measurably stalls the outer solve: 133+ vs 50 iterations,
  ops/tilesolve.py), and the coarse-level einsums.
- **rhs and solution stay f32**: x accumulates alpha*y + omega*z over
  O(10) iterations; keeping the accumulator wide is what lets the
  stored directions be narrow.

``CUP3D_KRYLOV_DTYPE`` selects the storage dtype (``f32`` default —
bitwise-identical to the pre-round-12 solver — or ``bf16``).  bf16
storage runs through the fused iteration driver
(ops/fused_bicgstab.py), which is where the cast discipline lives;
``CUP3D_FUSED`` controls that driver independently (``auto`` = fused
iff bf16, ``1`` = fused even at f32, ``0`` = legacy-only, which makes
a bf16 request a loud build-time error instead of a silent downgrade).

Lint rule JX011 (analysis/rules.py) machine-checks the accumulation
half of this contract across ``cup3d_tpu/ops``: a reduction over bf16
operands without an explicit f32 accumulator is a finding.
"""

from __future__ import annotations

import os

import jax.numpy as jnp

#: env knob -> storage dtype for Krylov vectors
_DTYPES = {
    "": jnp.float32,
    "f32": jnp.float32,
    "float32": jnp.float32,
    "bf16": jnp.bfloat16,
    "bfloat16": jnp.bfloat16,
}


def krylov_dtype():
    """Storage dtype for Krylov vectors (CUP3D_KRYLOV_DTYPE; f32 default).

    Read per call like the other env knobs (use_exact_getz,
    use_coarse_correction) so tests and the resilience ladder can flip
    it without touching process-global state.
    """
    key = os.environ.get("CUP3D_KRYLOV_DTYPE", "").strip().lower()
    try:
        return _DTYPES[key]
    except KeyError:
        raise ValueError(
            f"CUP3D_KRYLOV_DTYPE={key!r}: expected one of "
            f"{sorted(k for k in _DTYPES if k)}"
        ) from None


def accum_dtype(dtype):
    """Accumulation dtype for reductions over ``dtype`` values: at least
    f32 (bf16 -> f32; f32/f64 pass through, keeping f64 solves exact)."""
    return jnp.promote_types(dtype, jnp.float32)


def use_fused() -> bool:
    """Whether build_iterative_solver routes through the fused
    per-iteration driver (ops/fused_bicgstab.py).

    CUP3D_FUSED: ``auto`` (default) = fused iff the storage dtype is
    bf16, so the stock f32 config stays bitwise-identical to the
    pre-round-12 solver; ``1`` forces the fused driver at f32 (for the
    bench side-by-side); ``0`` forces the legacy composition.
    """
    v = os.environ.get("CUP3D_FUSED", "auto").strip().lower()
    if v in ("1", "true", "yes"):
        return True
    if v in ("0", "false", "no"):
        return False
    return krylov_dtype() == jnp.bfloat16


def check_policy(mean_constraint: int = 2,
                 forest_fused: bool = False) -> None:
    """Build-time validation of the knob combination: a bf16 request the
    configuration cannot honor raises instead of silently downgrading,
    and so does a forest solve routed to the fused driver
    (``forest_fused``) where its kernels would be compiled natively —
    they do not compile for the TPU yet (ops/fused_amr_bicgstab.py), and
    neither the jnp twins nor the unfused solver may stand in unasked."""
    if krylov_dtype() == jnp.bfloat16 and not use_fused():
        raise ValueError(
            "CUP3D_KRYLOV_DTYPE=bf16 requires the fused iteration driver "
            "(its cast discipline keeps accumulations f32); unset "
            "CUP3D_FUSED=0 or use f32 storage"
        )
    if forest_fused:
        from cup3d_tpu.ops import fused_amr_bicgstab, getz_pallas

        if getz_pallas.use_pallas():
            fused_amr_bicgstab.refuse_native_kernels()
