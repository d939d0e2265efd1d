"""Capacity bucketing of the AMR forest: the compile-stability layer.

Every mesh adaptation changes the leaf count ``nb``, and every
``(nb, bs, bs, bs[, C])`` array shape change retraces every jitted step
function — a full re-lower/re-compile costs seconds
against a ~0.1 s step (BENCH_r05: amr_tgv ``wall_per_step_max_s`` 5.50 s
vs a 0.118 s median).  Bucketing rounds the padded block count up to a
geometric capacity ladder so any regrid that stays within a bucket keeps
every array shape — and therefore every compiled executable — unchanged.

The padding contract (shared with parallel/forest.py's sharded padding):

- padding rows of all state/geometry arrays stay 0;
- padding-block cell volume is 0, so volume-weighted reductions ignore
  them; per-block spacing ``h`` is 1 on padding (never divides by 0);
- gather tables route padding-block halos to the zero sentinel, so labs
  of padding blocks assemble to 0 and operators output 0 there;
- ``capacity`` is STRICTLY greater than ``nb``, so at least one padding
  block always exists — the inert dump target for padded scatter rows
  (coarse-face writes, flux corrections, fallback rows).

The ladder is per-quantity: block count, per-level shadow counts, coarse
face counts and flux-correction counts each round up independently, so a
bucket is really a *level-signature* class — two topologies share every
compiled executable iff all their padded table shapes (and static aux)
coincide.  sim/amr.py keys its compiled-step cache on exactly that.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

#: default geometric growth of the capacity ladder (~25% max padding)
RATIO = 1.25


def capacity(n: int, ratio: float = RATIO, base: int = 8) -> int:
    """Smallest ladder rung STRICTLY greater than ``n``.

    Strict so a bucketed forest always owns >= 1 padding block (see the
    module doc's dump-target invariant)."""
    c = base
    while c <= n:
        c = max(c + 1, int(math.ceil(c * ratio)))
    return c


def count_capacity(n: int, ratio: float = RATIO, base: int = 8) -> int:
    """Ladder rung >= ``n`` for auxiliary row counts (shadow entries,
    coarse-face rows, flux corrections).  0 stays 0: a topology class
    with none of a feature is its own bucket dimension."""
    if n <= 0:
        return 0
    c = base
    while c < n:
        c = max(c + 1, int(math.ceil(c * ratio)))
    return c


def pad_rows(arr, cap: int, fill=0):
    """Pad a host array's leading axis to ``cap`` rows with ``fill``."""
    a = np.asarray(arr)
    if a.shape[0] >= cap:
        return a
    pad = np.full((cap - a.shape[0],) + a.shape[1:], fill, a.dtype)
    return np.concatenate([a, pad])


def pad_field(field: jnp.ndarray, cap: int) -> jnp.ndarray:
    """Zero-pad a device field's block axis to ``cap`` (identity when
    already there)."""
    extra = cap - field.shape[0]
    if extra <= 0:
        return field
    return jnp.concatenate(
        [field, jnp.zeros((extra,) + field.shape[1:], field.dtype)]
    )
