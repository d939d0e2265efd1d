"""IR-level static analysis: jaxpr and lowered/compiled-HLO walkers.

The AST linter (``analysis/lint.py``, JX001–JX018) reads source text and
therefore cannot see anything that only exists after tracing: a
donation XLA silently turned into a copy, a ``ppermute`` whose pair
list is not a permutation, or a bf16 accumulator introduced by dtype
promotion two helper calls away.  This module is the second tier — it
walks the *traced* artifacts of the canonical entry points
(``analysis/audit.py`` owns the registry and the CLI) with the same
contract as the linter: stable rule IDs, ``Violation`` records,
baselines, suppression with reasons.

Rules (catalog text in ``analysis/rules.py``):

- JP001  donated buffer not aliased in the compiled executable.
         Ground truth is read twice: the lowered StableHLO marks each
         aliased ``@main`` argument with ``tf.aliasing_output`` (where
         jax records the donation decision), and — when the entry is
         compiled — the scheduled HLO header's ``input_output_alias``
         map (what XLA actually does).  Entries that DOCUMENT a
         no-donation contract are checked for the absence of aliasing
         instead (``expect_no_donation``).
- JP002  unsafe collective in a shard_map body: a ppermute whose
         (src, dst) pairs have duplicate sources, duplicate
         destinations, or ids outside the mesh axis; any collective
         naming an axis absent from the enclosing mesh.  jax validates
         NEITHER at trace time — both deadlock or corrupt at pod
         scale.
- JP003  cross-shard materialization: ``all_gather`` inside a
         shard_map body of a steady-state jaxpr (the compiler-truth
         complement of JX016).  Designed gathers (the sharded
         megaloop's replicated coarse solve) are annotated at the
         registry entry.
- JP004  precision hazards: float64 avals anywhere, and reductions
         (reduce_sum / cumsum / dot_general / reduce_window_sum)
         whose OUTPUT dtype is bfloat16 — i.e. a storage-precision
         accumulator (IR-grounded JX005/JX011).
- JP005  host callbacks (pure_callback / io_callback /
         debug_callback) in a hot jaxpr.

Everything here is pure inspection — no tracing, no compilation; the
caller (audit.py) brings the jaxpr / Lowered / Compiled objects.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from cup3d_tpu.analysis.rules import Violation

# -- primitive sets ----------------------------------------------------------

#: communicating collectives whose params name mesh axes (JP002 axis
#: check).  ``psum`` lowers to ``psum2`` inside shard_map bodies on the
#: jax in this tree; both spellings are kept so the walker survives
#: version drift.
COLLECTIVE_PRIMS = frozenset({
    "ppermute", "pshuffle", "psum", "psum2", "pmax", "pmin", "pmean",
    "all_gather", "all_to_all", "reduce_scatter", "psum_scatter",
    "pbroadcast", "axis_index",
})

#: host-callback primitives (JP005)
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "host_callback",
    "outside_call",
})

#: reduction-position primitives whose output dtype names the
#: accumulator (JP004 bf16 check).  Elementwise bf16 ops are storage
#: traffic, not accumulation, and never fire.
REDUCTION_PRIMS = frozenset({
    "reduce_sum", "reduce_prod", "cumsum", "cumprod", "dot_general",
    "reduce_window_sum",
})


def _entry_path(entry: str) -> str:
    """Baseline-stable pseudo-path for one registry entry.  The lint
    baseline keys on (rule, path, func); IR findings have no source
    file, so the entry name doubles as both."""
    return f"ir://{entry}"


def _emit(out: List[Violation], rule: str, entry: str, msg: str) -> None:
    out.append(Violation(
        rule=rule, path=_entry_path(entry), line=0, col=0, func=entry,
        message=msg,
    ))


# -- jaxpr walking -----------------------------------------------------------


def _sub_jaxprs(params: Dict[str, Any]) -> Iterable[Any]:
    """Every jaxpr-valued entry of an eqn's params: ``jaxpr`` /
    ``call_jaxpr`` / ``cond_jaxpr`` / ``body_jaxpr`` / ``branches`` /
    ... — discovered structurally (isinstance on Jaxpr/ClosedJaxpr)
    so new higher-order primitives keep walking without a catalog."""
    import jax.extend.core as jcore

    kinds = (jcore.Jaxpr, jcore.ClosedJaxpr)
    for v in params.values():
        if isinstance(v, kinds):
            yield v
        elif isinstance(v, (tuple, list)):
            for item in v:
                if isinstance(item, kinds):
                    yield item


def _as_jaxpr(j: Any):
    """Unwrap ClosedJaxpr -> Jaxpr (eqns live on the inner object)."""
    return j.jaxpr if hasattr(j, "jaxpr") else j


def _mesh_axes(mesh: Any) -> Dict[str, int]:
    """axis name -> size for a (concrete or abstract) Mesh."""
    try:
        return {str(k): int(v) for k, v in dict(mesh.shape).items()}
    except Exception:
        return {}


def iter_eqns(jaxpr: Any, axis_env: Optional[Dict[str, int]] = None,
              in_shard_map: bool = False):
    """Yield ``(eqn, axis_env, in_shard_map)`` for every eqn reachable
    from ``jaxpr``, descending into all sub-jaxprs.  ``axis_env`` maps
    live mesh axis names to sizes; entering a ``shard_map`` eqn swaps
    in that mesh's axes and flips ``in_shard_map`` for its body."""
    axis_env = axis_env or {}
    for eqn in _as_jaxpr(jaxpr).eqns:
        yield eqn, axis_env, in_shard_map
        prim = eqn.primitive.name
        if prim == "shard_map":
            sub_env = _mesh_axes(eqn.params.get("mesh"))
            for sub in _sub_jaxprs(eqn.params):
                yield from iter_eqns(sub, sub_env, True)
        else:
            for sub in _sub_jaxprs(eqn.params):
                yield from iter_eqns(sub, axis_env, in_shard_map)


def _axis_names(params: Dict[str, Any]) -> List[str]:
    """The mesh-axis names a collective eqn binds: ``axis_name`` (str
    or tuple) plus any string entries of ``axes`` (psum2-style; the
    integer entries there are positional array axes, not mesh axes)."""
    names: List[str] = []
    an = params.get("axis_name")
    if isinstance(an, str):
        names.append(an)
    elif isinstance(an, (tuple, list)):
        names.extend(a for a in an if isinstance(a, str))
    axes = params.get("axes")
    if isinstance(axes, (tuple, list, frozenset, set)):
        names.extend(a for a in axes if isinstance(a, str))
    return names


def _check_ppermute(out: List[Violation], entry: str, params: Dict[str, Any],
                    axis_env: Dict[str, int], names: List[str]) -> None:
    """JP002 permutation invariants for one ppermute eqn: unique
    sources, unique destinations, every id inside the axis extent."""
    perm = [(int(a), int(b)) for a, b in params.get("perm", ())]
    size = 1
    for n in names:
        size *= axis_env.get(n, 1)
    srcs = [a for a, _ in perm]
    dsts = [b for _, b in perm]
    if len(set(srcs)) != len(srcs):
        dup = sorted({s for s in srcs if srcs.count(s) > 1})
        _emit(out, "JP002", entry,
              f"ppermute perm has duplicate source id(s) {dup} — two "
              f"pairs send from the same shard (perm={perm})")
    if len(set(dsts)) != len(dsts):
        dup = sorted({d for d in dsts if dsts.count(d) > 1})
        _emit(out, "JP002", entry,
              f"ppermute perm has duplicate destination id(s) {dup} — "
              f"two pairs write the same shard (perm={perm})")
    if all(n in axis_env for n in names) and names:
        bad = sorted({i for i in srcs + dsts if not 0 <= i < size})
        if bad:
            _emit(out, "JP002", entry,
                  f"ppermute perm id(s) {bad} outside axis "
                  f"{'x'.join(names)} of size {size} (perm={perm})")


def audit_jaxpr(closed_jaxpr: Any, entry: str) -> List[Violation]:
    """Walk one entry's jaxpr and emit JP002–JP005 violations.  f64 and
    callback findings are deduplicated per (primitive, dtype) so a
    promoted dtype flowing through a 400-eqn scan body reads as one
    finding, not 400."""
    out: List[Violation] = []
    seen_f64: set = set()
    seen_cb: set = set()
    for eqn, axis_env, in_sm in iter_eqns(closed_jaxpr):
        prim = eqn.primitive.name
        params = eqn.params

        if prim in COLLECTIVE_PRIMS:
            names = _axis_names(params)
            missing = [n for n in names if n not in axis_env]
            if missing:
                have = sorted(axis_env) or ["<none>"]
                _emit(out, "JP002", entry,
                      f"collective `{prim}` names axis "
                      f"{'/'.join(missing)} but the enclosing mesh "
                      f"declares {have} — a trace-time typo that "
                      "deadlocks a pod at runtime")
            if prim == "ppermute":
                _check_ppermute(out, entry, params, axis_env, names)
            if prim == "all_gather" and in_sm:
                shp = "x".join(str(d) for d in eqn.outvars[0].aval.shape)
                _emit(out, "JP003", entry,
                      f"all_gather over axis "
                      f"{'/'.join(names) or '?'} materializes a full "
                      f"({shp}) array on every shard, every step — a "
                      "scale-out ceiling unless it is a designed "
                      "replicated stage (annotate the registry entry "
                      "with the reason)")

        if prim in CALLBACK_PRIMS and prim not in seen_cb:
            seen_cb.add(prim)
            _emit(out, "JP005", entry,
                  f"host callback `{prim}` inside the hot jaxpr — "
                  "every step blocks the dispatch stream on the Python "
                  "interpreter; route diagnostics through the "
                  "scan-stacked row outputs instead")

        for var in tuple(eqn.invars) + tuple(eqn.outvars):
            aval = getattr(var, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and str(dt) == "float64":
                key = (prim, "f64")
                if key not in seen_f64:
                    seen_f64.add(key)
                    _emit(out, "JP004", entry,
                          f"float64 aval on `{prim}` — doubles "
                          "bandwidth/VMEM on TPU; device dtypes come "
                          "from the config (sim.dtype), f64 stays "
                          "host-side (JX005, proven at IR level)")

        if prim in REDUCTION_PRIMS:
            for var in eqn.outvars:
                dt = getattr(getattr(var, "aval", None), "dtype", None)
                if dt is not None and str(dt) == "bfloat16":
                    _emit(out, "JP004", entry,
                          f"`{prim}` accumulates in bfloat16 — the "
                          "round-12 policy stores bf16 but ACCUMULATES "
                          "in f32 (name the accumulator: dtype=/"
                          "preferred_element_type=); a bf16 Krylov "
                          "dot loses ~8 of the ~11 significand bits "
                          "the stopping test needs (JX011, proven at "
                          "IR level)")
                    break
    return out


# -- donation (JP001) --------------------------------------------------------


def donated_leaf_indices(args: Sequence[Any],
                         donate_argnums: Sequence[int]) -> List[int]:
    """Flat ``@main`` parameter indices of every leaf of every donated
    argument, under jit's left-to-right flattening of the positional
    args.  This is the audit's own offset bookkeeping — it must match
    how jax flattens, which tests pin with a known executable."""
    import jax

    donate = set(int(d) for d in donate_argnums)
    flat: List[int] = []
    offset = 0
    for i, a in enumerate(args):
        leaves = jax.tree_util.tree_leaves(a)
        if i in donate:
            flat.extend(range(offset, offset + len(leaves)))
        offset += len(leaves)
    return flat


def aliased_params_from_lowered(mlir_text: str) -> List[int]:
    """``@main`` argument indices whose donation survived lowering:
    ``tf.aliasing_output`` when jax resolved the alias itself, or
    ``jax.buffer_donor`` when the module is partitioned
    (``mhlo.num_partitions`` > 1) and the aliasing decision is deferred
    to the XLA SPMD partitioner (the compiled header is then the ground
    truth — sharded entries keep ``compile=True``).  In an unpartitioned
    module ``jax.buffer_donor`` is no alias: jax (0.9) hands XLA a
    donated arg that matched no output in shape and dtype, only in
    element count, and XLA cannot alias buffers of different bytes.  A
    donated arg that matches nothing gets NEITHER mark, plus a
    UserWarning at lowering time."""
    start = mlir_text.find("@main(")
    if start < 0:
        return []
    parts = re.search(r"mhlo\.num_partitions = (\d+)", mlir_text)
    deferred = parts is not None and int(parts.group(1)) > 1
    i = start + len("@main(")
    depth = 1
    j = i
    while j < len(mlir_text) and depth:
        c = mlir_text[j]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        j += 1
    arglist = mlir_text[i:j - 1]
    out: List[int] = []
    # each chunk starts "%argN: tensor<...> {attrs...}"
    for chunk in arglist.split("%arg")[1:]:
        head = chunk.split(":", 1)[0].strip()
        try:
            idx = int(head)
        # jax-lint: allow(JX009, non-arg %arg-prefixed token in an MLIR
        # attr string is expected; a real parse failure surfaces as a
        # JP001 missing-alias finding, never silently)
        except ValueError:
            continue
        if "tf.aliasing_output" in chunk or (
                deferred and "jax.buffer_donor" in chunk):
            out.append(idx)
    return sorted(out)


def aliased_params_from_compiled(hlo_text: str) -> List[int]:
    """Input parameter numbers in the scheduled HLO header's
    ``input_output_alias={ {out}: (param, {}, may-alias), ... }`` map —
    what the compiled executable actually aliases."""
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return []
    i = start + len("input_output_alias={")
    depth = 1
    j = i
    while j < len(hlo_text) and depth:
        c = hlo_text[j]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
        j += 1
    blob = hlo_text[i:j - 1]
    return sorted(int(m) for m in re.findall(r"\(\s*(\d+)\s*,", blob))


def audit_donation(entry: str, donated: Sequence[int],
                   lowered_text: Optional[str],
                   compiled_text: Optional[str],
                   expect_no_donation: bool = False) -> List[Violation]:
    """JP001 for one entry.  ``donated`` are the flat parameter indices
    that SHOULD alias (from :func:`donated_leaf_indices`); the lowered
    marks are always checked when available, the compiled header only
    when the entry was compiled (the expensive cross-check is
    per-entry opt-in, audit.py's ``compile=`` flag)."""
    out: List[Violation] = []
    donated = sorted(int(d) for d in donated)

    if expect_no_donation:
        for src_name, text, parse in (
            ("lowered", lowered_text, aliased_params_from_lowered),
            ("compiled", compiled_text, aliased_params_from_compiled),
        ):
            if text is None:
                continue
            aliased = parse(text)
            if aliased:
                _emit(out, "JP001", entry,
                      f"entry documents a no-donation contract (the "
                      "rollback/reseed path needs the pre-dispatch "
                      f"buffers) but the {src_name} executable aliases "
                      f"parameter(s) {aliased} — the contract and the "
                      "IR disagree")
        return out

    if not donated:
        return out

    if lowered_text is not None:
        aliased = set(aliased_params_from_lowered(lowered_text))
        missing = [d for d in donated if d not in aliased]
        if missing:
            _emit(out, "JP001", entry,
                  f"donated parameter(s) {missing} carry no "
                  "tf.aliasing_output mark in the lowered module — jax "
                  "could not alias them (shape/dtype/layout mismatch "
                  "against every output) and the donation is a silent "
                  "copy")
    if compiled_text is not None:
        aliased = set(aliased_params_from_compiled(compiled_text))
        missing = [d for d in donated if d not in aliased]
        if missing:
            _emit(out, "JP001", entry,
                  f"donated parameter(s) {missing} absent from the "
                  "compiled input_output_alias map — XLA copies "
                  "instead of aliasing; the steady-state carry pays "
                  "2x its working set")
    return out
