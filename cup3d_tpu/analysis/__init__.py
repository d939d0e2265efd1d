"""JAX-aware lint + runtime sanitizers (ISSUE 2).

Static: ``python -m cup3d_tpu.analysis [paths]`` walks the package AST
and flags JAX hazards (hidden host syncs, undonated step jits, traced
control flow, per-step uploads, float64 literals, unsynced timing) with
stable rule IDs — see ``analysis/rules.py`` for the catalog and
``analysis/lint.py`` for the heuristics and suppression machinery.

Runtime: ``analysis/runtime.py`` provides the recompile counter, the
transfer-guard context with its sanctioned-site allowlist, and scoped
NaN/tracer-leak debug modes.  VALIDATION.md ("Analysis subsystem:
sanitizer contract") specifies which loops must run clean and what the
budgets are; tests/test_analysis.py enforces it.
"""

from cup3d_tpu.analysis.rules import RULES, Rule, Violation  # noqa: F401
from cup3d_tpu.analysis.runtime import (  # noqa: F401
    RecompileCounter,
    blocking_read,
    debug_nans,
    device_scalar,
    no_implicit_transfers,
    sanctioned_transfer,
    tracer_leak_checks,
)
