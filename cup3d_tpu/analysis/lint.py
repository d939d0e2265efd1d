"""JAX-aware AST lint: machine-checked hot-path invariants.

Static half of the analysis subsystem (see ``analysis/runtime`` for the
sound runtime checks).  The linter is deliberately PRECISION-first: a
Python AST cannot prove an expression holds a device array, so every
rule fires only on patterns that are device-typed by construction or by
this repo's conventions (``jnp.*`` calls, ``self._name(...)`` jitted
wrappers, values assigned from them).  What the heuristics miss, the
runtime transfer guard catches; what they flag wrongly, an inline
annotation documents:

    x = float(self._maxu(v))  # jax-lint: allow(JX001, designed dt sync)

or a checked-in baseline entry (``analysis/baseline.json``) matched by
(rule, path, enclosing function) so entries survive line drift.  The
CLI (``python -m cup3d_tpu.analysis``) exits nonzero on any violation
that is neither annotated nor baselined.

Rule summary (full rationale in ``analysis/rules.py``):

- JX001  host-sync call (``float``/``int``/``bool``/``.item()``/
         ``np.asarray``/``jax.device_get``) on a device value inside a
         hot-path function (step/solve/advance loops in ``sim/``,
         ``ops/``, ``stream/``).
- JX002  step-shaped ``jax.jit`` without ``donate_argnums``.
- JX003  Python ``if``/``while``/ternary on a traced argument inside a
         jitted body (covers the implicit ``__bool__`` host sync).
- JX004  device-array construction inside a per-step Python loop in a
         hot-path function.
- JX005  float64 dtype literal in device code.
- JX006  ``time.perf_counter()`` timing window with no device sync.
- JX007  ``jax.jit`` construction inside a loop body or an
         adaptation-path function (rebuild/adapt): a fresh jit object
         per pass/regrid defeats the per-object trace cache — the bug
         class the capacity-bucketed compiled-step cache removes.
- JX008  ``time.perf_counter()`` / manual section timing inside the
         package but outside ``cup3d_tpu/obs/``: use obs spans, so the
         measured wall reaches the registry/trace/flight recorder
         instead of a private counter.
- JX009  swallowed exception inside the package (handler body is only
         ``pass``/``continue``/``break``/a bare log call): the failure
         leaves no counter, no state, no re-raise.  ``cup3d_tpu/
         resilience/`` is exempt by path — containing already-counted
         failures is its job.
- JX010  per-step host<->device staging of obstacle state:
         ``np.asarray``/``jnp.asarray`` on a loop-carried attribute
         (``self.X``/``ob.X``/``s.X``) inside a step-loop function in
         ``sim/``, ``ops/``, ``stream/`` or ``models/`` — the residue
         the megaloop work removed (cache the mirror identity-keyed,
         derive it on device, or carry it in the scan state).
- JX011  reduction (``jnp.sum``/``dot``/``vdot``/``matmul``/
         ``tensordot``/``lax.dot``) over bfloat16-tainted operands in
         ``cup3d_tpu/ops/`` without an explicit ``dtype=`` /
         ``preferred_element_type=`` accumulator: the round-12 mixed-
         precision policy (ops/precision.py) stores Krylov vectors in
         bf16 but must ACCUMULATE in f32 — a storage-precision
         reduction silently destroys the stopping test.
- JX012  direct ``jax.profiler`` use (imports or dotted access) inside
         the package but outside ``cup3d_tpu/obs/``: the profiler
         session is process-global, so an ad-hoc capture collides with
         obs profile windows and its trace bypasses the device-time
         attribution parser — use obs.profile capture windows instead.
- JX013  per-lane Python loop over the scenario axis in ``cup3d_tpu/
         fleet/`` that dispatches device work per iteration: the lane
         axis must stay vectorized (one vmapped dispatch advances all
         B lanes — fleet/batch.py); host-only loops over lanes are
         fine in assembly/fan-out code because they touch no device
         value.
- JX014  wall-clock subtraction used as a duration: differencing two
         ``time.time()``/``datetime.now()`` reads inside the package —
         NTP slews/steps the wall clock, so the "duration" can be
         negative or jump by seconds and silently corrupts latency
         histograms and SLO burn rates.  Durations come from the
         monotonic clock (``obs.trace.now()`` / obs spans); bare
         ``time.time()`` TIMESTAMPS (history rows, postmortem
         wall_time) stay legal — only the subtraction fires.
- JX015  per-tick host reassembly of full-batch arrays in
         ``cup3d_tpu/fleet/``: a K-boundary fast-path function
         (tick/reseed/dispatch) that restacks the whole lane axis
         (``jnp.stack``/``np.stack``/``concatenate`` or the assembly
         helpers ``stack_carries``/``stack_gaits``) pays O(B) host
         work and a fresh device upload every boundary — a reseed
         must touch ONE lane through the jitted ``.at[lane].set``
         upload path (``fleet/batch.py reseed_lane_carry``).  Batch
         CONSTRUCTION (assemble/__init__) still stacks legitimately:
         the rule keys on the per-tick function names.
- JX016  full-array materialization in a sharded step path:
         ``jax.device_get``/``np.asarray``/``np.array`` (or a single-
         argument ``jax.device_put``) inside a step/advance/dispatch/
         megaloop function in ``cup3d_tpu/{sim,fleet,parallel}/``
         gathers a (possibly mesh-sharded) array whole onto one host
         or device — the scale-out ceiling the round-18 2-D mesh
         removes.  Slice shard-locally under shard_map, place with an
         explicit ``device_put(x, sharding)``, and stage host reads
         through the designed sync points (sanctioned_transfer).
- JX017  hand-typed hardware peak/bandwidth literal in a roofline or
         bench reporting path: a numeric constant >= 1e9 that is not an
         exact power of ten (``197e12``, ``819e9``) hard-codes one
         device's spec sheet into MFU/HBM math that runs on EVERY
         backend — the round-19 bug class where rooflines silently lie
         on non-v5e hardware.  Peaks live in the ``obs/costs.py``
         device-kind table (the one exempt module); consumers call
         ``device_peaks()``.  Scope: ``bench*.py`` files plus any
         function named like roofline/peak-model in the package.
- JX018  raw collective call site outside ``cup3d_tpu/parallel/``:
         ``lax.ppermute``/``psum``/``pmax``/``all_gather``/... called
         directly anywhere else in the package scatters the SPMD
         communication surface across the tree.  Collectives go
         through the parallel/ layer (``ring.py`` ring_shift/pad_slab,
         ``collectives.py`` all_gather_tiled/pmax_axis) so the IR
         audit (JP002) has ONE seam to prove permutation/axis
         invariants on and a mesh-topology change edits one module.
- JX019  direct AOT compile / jit-warmup call site outside the
         executable-store seam: a chained ``fn.lower(...).compile()``
         or an immediately-invoked ``jit(f)(...)`` warmup produces an
         XLA executable the persistent store (``cup3d_tpu/aot/``)
         never sees — recompiled on every boot, invisible to the
         aot.* telemetry.  Route compiles through ``aot.store_backed``
         / ``StoreBackedExecutable.warm`` so seen signatures
         deserialize instead.  ``cup3d_tpu/aot/`` is the seam itself
         and ``obs/costs.py`` harvests from compiled objects — both
         path-exempt.
- JX020  raw clock read inside ``cup3d_tpu/`` outside the trace
         layer: ``time.monotonic()``/``time.time()``/
         ``time.perf_counter()`` (and ``*_ns`` variants) called
         anywhere but ``obs/trace.py`` splits the package across
         clock domains — the round-22 phase decomposition only
         partitions end-to-end latency because every lifecycle
         timestamp comes off ONE monotonic clock.  Route monotonic
         reads through ``obs.trace.now()`` and wall-time stamps
         through ``obs.trace.wall()``; ``obs/trace.py`` itself is the
         sanctioned seam and is path-exempt.
"""

from __future__ import annotations

import ast
import json
import math
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from cup3d_tpu.analysis.rules import RULES, Violation

# -- scoping ----------------------------------------------------------------

#: modules whose functions can be on the per-step critical path
HOT_MODULE_RE = re.compile(r"cup3d_tpu/(sim|ops|stream)/")

#: function names that run inside (or are) the step loop
HOT_FUNC_RE = re.compile(
    r"^(advance\w*|simulate|solve\w*|calc_max_timestep|_calc_dt\w*|"
    r"_emit\w*|_consume\w*|emit|kick|poll|join|flush\w*|stage|"
    r"_fix_mass_flux|_compute_forces|__call__|\w*step\w*|\w*megastep\w*)$"
)

#: names that mark a jitted function / its target as a steady-state step
STEP_SHAPE_RE = re.compile(r"step|mega", re.IGNORECASE)

#: functions that run once per mesh adaptation (JX007): a jax.jit built
#: here is rebuilt per regrid, defeating jax's per-object trace cache
ADAPT_FUNC_RE = re.compile(r"rebuild|adapt", re.IGNORECASE)

#: loop constructs whose body re-executes (JX004/JX007)
LOOP_NODES = (ast.For, ast.While, ast.ListComp, ast.SetComp,
              ast.DictComp, ast.GeneratorExp)

#: host->device constructors relevant to JX004
JNP_CONSTRUCTORS = frozenset(
    {"asarray", "array", "zeros", "ones", "full", "arange", "linspace",
     "eye"}
)

#: calls that force (or are) a device sync, for JX001/JX006
SYNC_BUILTINS = frozenset({"float", "int", "bool"})

#: JX010 scope: the obstacle pipeline's step-loop modules.  Wider than
#: HOT_MODULE_RE because models/ operator ``__call__``s ARE the per-step
#: obstacle path even though they hold no device kernels of their own.
JX010_MODULE_RE = re.compile(r"cup3d_tpu/(sim|ops|stream|models)/")

#: receiver names whose attributes are loop-carried obstacle/driver
#: state by this repo's conventions (JX010): obstacle mirrors live on
#: ``ob``/``obstacle``/``self``, driver scalars on ``s``/``sim``/``self``
JX010_STATE_ROOTS = frozenset({"self", "s", "sim", "ob", "obstacle"})

#: the staging constructors JX010 watches (both directions: np.asarray
#: is a device->host read when the mirror went device-resident,
#: jnp.asarray a host->device upload of the same bytes every step)
ASARRAY_NAMES = frozenset(
    {"np.asarray", "numpy.asarray", "jnp.asarray", "jax.numpy.asarray"}
)

#: array attributes that live on the HOST side of a jax Array (reading
#: them never syncs), so int(x.size) etc. is not a JX001 hit
HOST_METADATA_ATTRS = frozenset(
    {"size", "ndim", "shape", "dtype", "itemsize", "nbytes", "sharding"}
)

#: JX011 scope: the Krylov/kernel modules where the round-12 mixed-
#: precision policy stores vectors in bf16 — the only place a
#: storage-precision reduction can reach the stopping test
JX011_MODULE_RE = re.compile(r"cup3d_tpu/ops/")

#: JX013 scope: the fleet serving layer, where the lane axis exists
JX013_MODULE_RE = re.compile(r"cup3d_tpu/fleet/")

#: names that mark a loop as walking the lane/scenario axis (matched
#: against the loop target and every Name in the iterable expression)
JX013_AXIS_RE = re.compile(r"(^|_)(lanes?|scenarios?)(_|$|\d)",
                           re.IGNORECASE)

#: reduction-position callables JX011 watches (the accumulator-dtype
#: hazard lives where many elements fold into few)
JX011_REDUCTIONS = frozenset(
    {"sum", "dot", "vdot", "matmul", "tensordot", "einsum", "dot_general"}
)

#: keyword args that name an explicit (>= f32) accumulator
JX011_ACCUM_KWARGS = frozenset({"dtype", "preferred_element_type"})

#: datetime constructors whose reads are wall-clock (JX014); the time
#: module's own names are resolved per file from its imports, since
#: ``from time import time`` leaves a bare ``time()`` call behind
JX014_DATETIME_ATTRS = frozenset({"now", "utcnow", "today"})

#: JX015 scope: the fleet K-boundary fast path — functions named like
#: the per-tick seam (tick/reseed/dispatch), where full-batch
#: reassembly turns an O(1)-lane reseed into O(B) host work per tick
JX015_FUNC_RE = re.compile(r"(^|_)(ticks?|reseeds?|dispatch(es)?)",
                           re.IGNORECASE)

#: callables that rebuild the full lane-stacked batch from per-lane
#: pieces: array stackers (resolved against jnp/np roots) plus this
#: repo's own assembly helpers, which stack by construction
JX015_STACKERS = frozenset({"stack", "concatenate", "vstack", "hstack"})
JX015_ASSEMBLY_HELPERS = frozenset({"stack_carries", "stack_gaits"})

#: JX016 scope: the modules hosting mesh-sharded steady-state paths
#: (solo megaloop slabs in sim/, the lane-sharded fleet advance in
#: fleet/, the forest/topology layer in parallel/)
JX016_MODULE_RE = re.compile(r"cup3d_tpu/(sim|fleet|parallel)/")

#: functions on the sharded fast path: the step bodies and their
#: drivers' per-boundary seams
JX016_FUNC_RE = re.compile(r"step|advance|dispatch|megaloop",
                           re.IGNORECASE)

#: builder factories (make_*/build_*/bind_*) run ONCE per topology to
#: stage trace-time constants — not the steady-state path.  Their inner
#: step closures are visited under their own names and stay covered.
JX016_BUILDER_RE = re.compile(r"^(make_|build_|bind_|_build_)")

#: host-materializing callables JX016 watches: full device->host pulls
#: (device_get / np.asarray / np.array on a device value) plus the
#: single-argument device_put, which re-places the WHOLE array onto
#: jax's default device (a cross-shard gather when the input was
#: sharded); device_put WITH an explicit sharding argument stays legal
JX016_HOST_PULLS = frozenset({"device_get", "asarray", "array"})

#: JX018: the communicating collectives (device<->device exchange under
#: a named axis).  ``axis_index`` is deliberately absent — it is a
#: shard-LOCAL coordinate read with no communication (the fleet's
#: shard-local lane upload uses it legitimately outside parallel/).
JX018_COLLECTIVES = frozenset(
    {"ppermute", "pshuffle", "psum", "psum_scatter", "pmax", "pmin",
     "pmean", "all_gather", "all_to_all", "pbroadcast"}
)

#: JX018 exemption: the parallel/ layer IS the sanctioned collective
#: seam (ring.py, compat.py, collectives.py, topology.py)
JX018_EXEMPT_RE = re.compile(r"cup3d_tpu/parallel/")

#: JX017 scope: the bench entrypoints (any bench*.py) and, anywhere in
#: the tree, functions whose names say they place work on a roofline
#: or model a hardware ceiling
JX017_PATH_RE = re.compile(r"(^|/)bench[^/]*\.py$")
JX017_FUNC_RE = re.compile(r"roofline|peak", re.IGNORECASE)

#: the one sanctioned home for hardware peak literals: the device-kind
#: table in obs/costs.py (provenance-annotated)
JX017_EXEMPT_RE = re.compile(r"cup3d_tpu/obs/costs\.py$")

#: spec-sheet magnitudes start at ~1e9 (GB/s bandwidths); exact powers
#: of ten below/at any magnitude are unit conversions (1e9, 1e12), not
#: hardware claims
JX017_MIN_MAGNITUDE = 1e9

#: JX019 exemption: cup3d_tpu/aot/ IS the store seam (its wrapper owns
#: the one sanctioned lower().compile()), and obs/costs.py harvests
#: cost analytics from an already-compiled object
JX019_EXEMPT_RE = re.compile(r"cup3d_tpu/(aot/|obs/costs\.py$)")

#: JX020 exemption: obs/trace.py IS the clock seam — its ``now()`` /
#: ``wall()`` own the package's two sanctioned clock reads
JX020_EXEMPT_RE = re.compile(r"cup3d_tpu/obs/trace\.py$")

#: the ``time``-module attributes JX020 treats as raw clock reads
JX020_CLOCK_ATTRS = ("time", "monotonic", "perf_counter",
                     "time_ns", "monotonic_ns", "perf_counter_ns")

#: JX021 (round 23): the sanctioned fleet job-state seams — the ONLY
#: functions in cup3d_tpu/fleet/ allowed to assign ``<job>.status``.
#: Each either journals the transition itself or sits on a path that
#: funnels into ``_job_terminal``/``mark`` (first assembly, retire,
#: reseed splice, queued-cancel, prepare-failure, journal replay); a
#: status flip anywhere else is a lifecycle transition the write-ahead
#: journal never sees, i.e. a job a crash can silently lose.
JX021_SANCTIONED_RE = re.compile(
    r"^(__init__|retire|reseed_lane|cancel|_prepare|"
    r"_install_replayed_job)$")


def _is_power_of_ten(v: float) -> bool:
    if v <= 0:
        return False
    e = round(math.log10(v))
    return abs(v - 10.0 ** e) <= 1e-6 * (10.0 ** e)


def _is_host_metadata(expr: ast.AST) -> bool:
    """True when ``expr`` only reads host-side array metadata."""
    node = expr
    while isinstance(node, ast.Attribute):
        if node.attr in HOST_METADATA_ATTRS:
            return True
        node = node.value
    return False

# reason may contain one level of nested parens: allow(JX001, freq (gated))
ALLOW_RE = re.compile(
    r"jax-lint:\s*allow\(\s*(JX\d{3})\s*"
    r"(?:,\s*((?:[^()]|\([^()]*\))*?)\s*)?\)"
)


# -- suppressions -----------------------------------------------------------


def parse_suppressions(source: str) -> Dict[int, Dict[str, str]]:
    """line -> {rule: reason}.  An annotation on a pure-comment line (or a
    block of them: a wrapped annotation continues across consecutive
    comment lines) applies to the next CODE line; on a code line, to that
    line."""
    out: Dict[int, Dict[str, str]] = {}
    lines = source.splitlines()
    i = 0
    while i < len(lines):
        text = lines[i]
        if text.lstrip().startswith("#"):
            # join the whole comment block so wrapped annotations parse
            start = i
            while i < len(lines) and lines[i].lstrip().startswith("#"):
                i += 1
            joined = " ".join(
                lines[j].lstrip().lstrip("#").strip()
                for j in range(start, i)
            )
            matches = ALLOW_RE.findall(joined)
            if matches:
                target = i + 1  # 1-based number of the next code line
                slot = out.setdefault(target, {})
                for rule, reason in matches:
                    slot[rule] = (reason or "").strip()
            continue
        # trailing annotation on a code line applies to that line
        if "#" in text:
            matches = ALLOW_RE.findall(text)
            if matches:
                slot = out.setdefault(i + 1, {})
                for rule, reason in matches:
                    slot[rule] = (reason or "").strip()
        i += 1
    return out


# -- AST helpers ------------------------------------------------------------


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of a Name/Attribute chain ('' otherwise)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _call_name(call: ast.Call) -> str:
    return _dotted(call.func)


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _is_jnp_call(call: ast.Call) -> bool:
    name = _call_name(call)
    root = name.split(".", 1)[0].lstrip("_")
    return "." in name and root in ("jnp", "jax")


def _is_jitwrapper_call(call: ast.Call) -> bool:
    """``self._name(...)`` / ``s._name(...)``: the repo convention for
    jitted step pieces held as driver attributes."""
    f = call.func
    return (
        isinstance(f, ast.Attribute)
        and f.attr.startswith("_")
        and isinstance(f.value, ast.Name)
    )


def _is_device_call(call: ast.Call) -> bool:
    return _is_jnp_call(call) or _is_jitwrapper_call(call)


def _jit_target(call: ast.Call) -> Optional[ast.AST]:
    """For a ``jax.jit(f, ...)`` call, the wrapped function node."""
    if _call_name(call) in ("jax.jit", "jit") and call.args:
        return call.args[0]
    return None


def _is_partial_of_jit(call: ast.Call) -> bool:
    """``partial(jax.jit, ...)`` (any name ending in 'partial')."""
    return (
        _call_name(call).endswith("partial")
        and bool(call.args)
        and _dotted(call.args[0]) in ("jax.jit", "jit")
    )


def _static_argnames(call: ast.Call) -> Set[str]:
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            try:
                v = ast.literal_eval(kw.value)
            except (ValueError, SyntaxError):
                return set()
            if isinstance(v, str):
                return {v}
            return set(v)
    return set()


def _has_kw(call: ast.Call, name: str) -> bool:
    return any(kw.arg == name for kw in call.keywords)


def _walk_shallow(func: ast.AST):
    """Walk a function body WITHOUT descending into nested def/class —
    every def gets its own visit from ``FileLint._functions``, so a deep
    walk would double-count nested findings.  Lambdas stay in scope
    (inline ``jax.jit(lambda ...)`` belongs to the enclosing def)."""
    stack = list(ast.iter_child_nodes(func))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
        ):
            stack.extend(ast.iter_child_nodes(node))


def _is_none_check(test: ast.AST) -> bool:
    """``x is None`` / ``x is not None`` (and `and`/`or`/`not` chains of
    them): identity-vs-None is a structural check, static under trace."""
    if isinstance(test, ast.BoolOp):
        return all(_is_none_check(v) for v in test.values)
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _is_none_check(test.operand)
    return (
        isinstance(test, ast.Compare)
        and all(isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops)
        and all(
            isinstance(c, ast.Constant) and c.value is None
            for c in test.comparators
        )
    )


def _inner_name(node: ast.AST) -> str:
    """Name of the function being jitted: Name / Attribute / partial(f,…)
    peeled recursively; lambdas are ''. """
    if isinstance(node, ast.Call) and _call_name(node).endswith("partial"):
        return _inner_name(node.args[0]) if node.args else ""
    name = _dotted(node)
    return name.rsplit(".", 1)[-1] if name else ""


# -- per-function device-taint tracking (JX001) -----------------------------


class _Taint:
    """Names assigned (in source order) from device-producing calls."""

    def __init__(self) -> None:
        self.names: Set[str] = set()

    def feed(self, stmt: ast.stmt) -> None:
        targets: List[ast.AST] = []
        value: Optional[ast.AST] = None
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
            targets = [stmt.target]
            value = stmt.value
        if value is None:
            return
        tainted = any(
            isinstance(n, ast.Call) and _is_device_call(n)
            for n in ast.walk(value)
        ) or bool(self.names & _names_in(value))
        # a host read LAUNDERS the value: np.asarray(x) yields host data
        for n in ast.walk(value):
            if isinstance(n, ast.Call) and _call_name(n) in (
                "np.asarray", "numpy.asarray", "jax.device_get"
            ):
                tainted = False
        if not tainted:
            return
        # only PLAIN names (incl. tuple/list unpacks) become tainted:
        # `self._x = jit(...)` must not taint `self` itself
        for t in targets:
            stack = [t]
            while stack:
                leaf = stack.pop()
                if isinstance(leaf, ast.Name):
                    self.names.add(leaf.id)
                elif isinstance(leaf, (ast.Tuple, ast.List)):
                    stack.extend(leaf.elts)
                elif isinstance(leaf, ast.Starred):
                    stack.append(leaf.value)

    def covers(self, expr: ast.AST) -> bool:
        if any(
            isinstance(n, ast.Call) and _is_device_call(n)
            for n in ast.walk(expr)
        ):
            return True
        return bool(self.names & _names_in(expr))


# -- the linter -------------------------------------------------------------


@dataclass
class FileLint:
    path: str            # repo-relative posix path
    tree: ast.Module
    suppressions: Dict[int, Dict[str, str]]
    violations: List[Violation] = field(default_factory=list)

    def run(self) -> List[Violation]:
        hot_module = bool(HOT_MODULE_RE.search(self.path))
        jitted = self._collect_jitted_defs()
        for func, qualname in self._functions():
            hot = hot_module and bool(HOT_FUNC_RE.match(func.name))
            if hot:
                self._check_host_sync(func, qualname)       # JX001
                self._check_loop_construction(func, qualname)  # JX004
            self._check_jit_sites(func, qualname)           # JX002
            if hot_module:
                self._check_jit_in_regrid_path(func, qualname)  # JX007
            if id(func) in jitted:
                self._check_traced_control_flow(            # JX003
                    func, qualname, jitted[id(func)]
                )
            self._check_timing_windows(func, qualname)      # JX006
            self._check_manual_timing(func, qualname)       # JX008
            self._check_wallclock_duration(func, qualname)  # JX014
            self._check_profiler_usage(func, qualname)      # JX012
            self._check_swallowed_exceptions(func, qualname)  # JX009
            if JX010_MODULE_RE.search(self.path) and bool(
                HOT_FUNC_RE.match(func.name)
            ):
                self._check_obstacle_staging(func, qualname)  # JX010
            if JX011_MODULE_RE.search(self.path):
                self._check_bf16_reduction(func, qualname)  # JX011
            if JX013_MODULE_RE.search(self.path):
                self._check_lane_device_loop(func, qualname)  # JX013
                self._check_batch_reassembly(func, qualname)  # JX015
                self._check_status_mutation(func, qualname)   # JX021
            if JX016_MODULE_RE.search(self.path):
                self._check_sharded_materialization(func, qualname)  # JX016
            if not JX017_EXEMPT_RE.search(self.path) and (
                JX017_PATH_RE.search(self.path)
                or JX017_FUNC_RE.search(func.name)
            ):
                self._check_hardware_peaks(func, qualname)  # JX017
            if (self.path.startswith("cup3d_tpu/")
                    and not JX018_EXEMPT_RE.search(self.path)):
                self._check_raw_collectives(func, qualname)  # JX018
            if (self.path.startswith("cup3d_tpu/")
                    and not JX019_EXEMPT_RE.search(self.path)):
                self._check_aot_seam(func, qualname)        # JX019
            if (self.path.startswith("cup3d_tpu/")
                    and not JX020_EXEMPT_RE.search(self.path)):
                self._check_raw_clock(func, qualname)       # JX020
        if (self.path.startswith("cup3d_tpu/")
                and not JX018_EXEMPT_RE.search(self.path)):
            self._check_raw_collectives(self.tree, "<module>")  # JX018
        if (self.path.startswith("cup3d_tpu/")
                and not JX019_EXEMPT_RE.search(self.path)):
            self._check_aot_seam(self.tree, "<module>")     # JX019
        if (self.path.startswith("cup3d_tpu/")
                and not JX020_EXEMPT_RE.search(self.path)):
            self._check_raw_clock(self.tree, "<module>")    # JX020
        self._check_dtype_literals()                        # JX005
        self._check_swallowed_exceptions(self.tree, "<module>")  # JX009
        self._check_wallclock_duration(self.tree, "<module>")  # JX014
        self._check_profiler_usage(self.tree, "<module>")   # JX012
        if JX011_MODULE_RE.search(self.path):
            self._check_bf16_reduction(self.tree, "<module>")  # JX011
        if JX013_MODULE_RE.search(self.path):
            self._check_lane_device_loop(self.tree, "<module>")  # JX013
            self._check_status_mutation(self.tree, "<module>")  # JX021
        if JX017_PATH_RE.search(self.path) and not JX017_EXEMPT_RE.search(
            self.path
        ):
            self._check_hardware_peaks(self.tree, "<module>")  # JX017
        return self.violations

    # -- plumbing ----------------------------------------------------------

    def _functions(self):
        """(FunctionDef, qualname) for every def, with class/def nesting."""
        out = []

        def visit(node: ast.AST, prefix: str) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    q = f"{prefix}{child.name}"
                    out.append((child, q))
                    visit(child, f"{q}.")
                elif isinstance(child, ast.ClassDef):
                    visit(child, f"{prefix}{child.name}.")
                else:
                    visit(child, prefix)

        visit(self.tree, "")
        return out

    def _emit(self, rule: str, node: ast.AST, func: str, msg: str) -> None:
        v = Violation(
            rule=rule, path=self.path, line=node.lineno,
            col=node.col_offset, func=func, message=msg,
        )
        reason = self.suppressions.get(node.lineno, {}).get(rule)
        if reason is not None:
            v.suppressed = True
            v.suppression_reason = reason or None
        self.violations.append(v)

    def _collect_jitted_defs(self) -> Dict[int, Set[str]]:
        """id(FunctionDef) -> static argnames, for defs that are jitted:
        decorated with jax.jit / partial(jax.jit, ...), or passed by name
        to a jax.jit(...) call anywhere in the module."""
        defs: Dict[str, ast.AST] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, node)
        jitted: Dict[int, Set[str]] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if _dotted(dec) in ("jax.jit", "jit"):
                        jitted[id(node)] = set()
                    elif isinstance(dec, ast.Call) and (
                        _dotted(dec.func) in ("jax.jit", "jit")
                        or _is_partial_of_jit(dec)
                    ):
                        jitted[id(node)] = _static_argnames(dec)
            elif isinstance(node, ast.Call):
                target = _jit_target(node)
                if target is not None:
                    name = _dotted(target)
                    if name in defs:
                        jitted[id(defs[name])] = _static_argnames(node)
        return jitted

    # -- JX001 -------------------------------------------------------------

    def _sanction_lookup(self, func: ast.AST):
        """line -> tag for `with sanctioned_transfer("tag"):` spans.

        The sanctioned block IS the designed-sync-point annotation — the
        runtime guard and the lint (JX001/JX010) agree on the same
        marker, so a site is never annotated twice."""
        sanctioned: List[Tuple[int, int, str]] = []
        for node in _walk_shallow(func):
            if isinstance(node, ast.With):
                for item in node.items:
                    c = item.context_expr
                    if isinstance(c, ast.Call) and _call_name(c).endswith(
                        "sanctioned_transfer"
                    ):
                        tag = ""
                        if c.args and isinstance(c.args[0], ast.Constant):
                            tag = str(c.args[0].value)
                        sanctioned.append(
                            (node.lineno, node.end_lineno or node.lineno,
                             tag)
                        )

        def sanction_tag(line: int) -> Optional[str]:
            for lo, hi, tag in sanctioned:
                if lo <= line <= hi:
                    return tag or "sanctioned"
            return None

        return sanction_tag

    def _check_host_sync(self, func: ast.AST, qualname: str) -> None:
        taint = _Taint()
        for stmt in _walk_shallow(func):
            if isinstance(stmt, ast.stmt):
                taint.feed(stmt)
        sanction_tag = self._sanction_lookup(func)
        for node in _walk_shallow(func):
            if not isinstance(node, ast.Call):
                continue
            tag = sanction_tag(node.lineno)
            if tag is not None:
                n_before = len(self.violations)
                self._try_host_sync_call(node, qualname, taint)
                for v in self.violations[n_before:]:
                    v.suppressed = True
                    v.suppression_reason = (
                        f"sanctioned_transfer({tag!r})"
                    )
                continue
            self._try_host_sync_call(node, qualname, taint)

    def _try_host_sync_call(
        self, node: ast.Call, qualname: str, taint: "_Taint"
    ) -> None:
        name = _call_name(node)
        if name == "jax.device_get":
            self._emit("JX001", node, qualname,
                       "jax.device_get blocks on a device->host read")
        elif (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "item"
            and not node.args
        ):
            self._emit("JX001", node, qualname,
                       ".item() blocks on a device->host read")
        elif name in SYNC_BUILTINS and len(node.args) == 1:
            if _is_host_metadata(node.args[0]):
                return
            if taint.covers(node.args[0]):
                self._emit(
                    "JX001", node, qualname,
                    f"{name}() on a device value blocks the dispatch "
                    "stream for a host round trip",
                )
        elif name in ("np.asarray", "numpy.asarray") and node.args:
            if taint.covers(node.args[0]):
                self._emit(
                    "JX001", node, qualname,
                    "np.asarray() of a device value is a blocking "
                    "device->host transfer",
                )

    # -- JX010 -------------------------------------------------------------

    def _check_obstacle_staging(self, func: ast.AST, qualname: str) -> None:
        """{np,jnp}.asarray on a ``self.X``/``ob.X``/``s.X`` attribute
        inside a step-loop function: the same obstacle/driver mirror
        crosses the host boundary again every step.  Precision-first like
        the rest of the linter — only attribute reads off the
        conventional state receivers fire, and ``sanctioned_transfer``
        blocks suppress just as they do for JX001."""
        sanction_tag = self._sanction_lookup(func)
        for node in _walk_shallow(func):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            name = _call_name(node)
            if name not in ASARRAY_NAMES:
                continue
            arg = node.args[0]
            if not isinstance(arg, ast.Attribute) or _is_host_metadata(arg):
                continue
            root = arg
            while isinstance(root, ast.Attribute):
                root = root.value
            if not (isinstance(root, ast.Name)
                    and root.id in JX010_STATE_ROOTS):
                continue
            direction = (
                "host->device upload"
                if name.split(".", 1)[0].lstrip("_") in ("jnp", "jax")
                else "device->host read"
            )
            n_before = len(self.violations)
            self._emit(
                "JX010", node, qualname,
                f"{name}({_dotted(arg)}) re-stages loop-carried "
                f"obstacle/driver state every step ({direction}); cache "
                "the mirror identity-keyed, derive it on device, or "
                "carry it in the scan state",
            )
            tag = sanction_tag(node.lineno)
            if tag is not None:
                for v in self.violations[n_before:]:
                    if not v.suppressed:
                        v.suppressed = True
                        v.suppression_reason = (
                            f"sanctioned_transfer({tag!r})"
                        )

    # -- JX002 -------------------------------------------------------------

    def _check_jit_sites(self, func: ast.AST, qualname: str) -> None:
        # assignment-target text per jit call, so `self._step = jax.jit(f)`
        # is step-shaped even when f's own name is opaque
        targets: Dict[int, str] = {}
        for stmt in _walk_shallow(func):
            if isinstance(stmt, ast.Assign):
                t = " ".join(_dotted(x) for x in stmt.targets)
                for sub in ast.walk(stmt.value):
                    if isinstance(sub, ast.Call):
                        targets[id(sub)] = t
        for node in _walk_shallow(func):
            if not isinstance(node, ast.Call):
                continue
            wrapped = _jit_target(node)
            if wrapped is None:
                continue
            step_shaped = (
                STEP_SHAPE_RE.search(_inner_name(wrapped))
                or STEP_SHAPE_RE.search(targets.get(id(node), ""))
                or STEP_SHAPE_RE.search(qualname)
            )
            if step_shaped and not _has_kw(node, "donate_argnums"):
                self._emit(
                    "JX002", node, qualname,
                    "step-shaped jax.jit without donate_argnums: the "
                    "state buffers are copied instead of updated in "
                    "place",
                )

    # -- JX007 -------------------------------------------------------------

    def _check_jit_in_regrid_path(self, func: ast.AST, qualname: str) -> None:
        """jax.jit construction per-regrid or per-loop-pass: the exact
        bug class capacity bucketing removes (sim/amr.py compiled-step
        cache).  Fires on a jit-construction call that is (a) inside a
        loop/comprehension body, or (b) anywhere in a function whose
        qualname marks it as an adaptation-path rebuild."""
        in_adapt = bool(ADAPT_FUNC_RE.search(qualname))

        def is_jit_construction(node: ast.AST) -> bool:
            return (
                isinstance(node, ast.Call)
                and (_jit_target(node) is not None
                     or _is_partial_of_jit(node))
            )

        loop_hits: Set[int] = set()
        for loop in _walk_shallow(func):
            if not isinstance(loop, LOOP_NODES):
                continue
            stack = list(ast.iter_child_nodes(loop))
            while stack:
                node = stack.pop()
                if isinstance(
                    node, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)
                ):
                    continue  # nested defs get their own visit
                if is_jit_construction(node):
                    loop_hits.add(id(node))
                    self._emit(
                        "JX007", node, qualname,
                        "jax.jit built inside a loop body creates a "
                        "fresh (cold-cache) jit object every pass; "
                        "hoist it and reuse, or cache by shape bucket",
                    )
                stack.extend(ast.iter_child_nodes(node))
        if not in_adapt:
            return
        for node in _walk_shallow(func):
            if is_jit_construction(node) and id(node) not in loop_hits:
                self._emit(
                    "JX007", node, qualname,
                    "jax.jit built on the adaptation path recompiles "
                    "every regrid even when shapes match (per-object "
                    "trace cache); build once and cache by bucket "
                    "(sim/amr.py compiled-step cache)",
                )

    # -- JX003 -------------------------------------------------------------

    def _check_traced_control_flow(
        self, func: ast.AST, qualname: str, static: Set[str]
    ) -> None:
        args = func.args
        params = {
            a.arg
            for a in (
                list(args.posonlyargs) + list(args.args)
                + list(args.kwonlyargs)
            )
        } - static - {"self"}
        for node in ast.walk(func):
            if isinstance(node, (ast.If, ast.While, ast.IfExp)):
                if _is_none_check(node.test):
                    continue  # `x is (not) None`: static under trace
                traced = params & _names_in(node.test)
                if traced:
                    kind = type(node).__name__.lower()
                    self._emit(
                        "JX003", node, qualname,
                        f"Python {kind} on traced argument(s) "
                        f"{sorted(traced)} inside a jitted body (implicit "
                        "__bool__ host sync or ConcretizationTypeError); "
                        "use lax.cond/lax.while_loop/jnp.where or mark "
                        "the argument static",
                    )

    # -- JX004 -------------------------------------------------------------

    def _check_loop_construction(self, func: ast.AST, qualname: str) -> None:
        for loop in _walk_shallow(func):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call):
                    continue
                name = _call_name(node)
                if (
                    name.split(".", 1)[0].lstrip("_") in ("jnp", "jax")
                    and "." in name
                    and name.rsplit(".", 1)[-1] in JNP_CONSTRUCTORS
                ):
                    self._emit(
                        "JX004", node, qualname,
                        f"{name}() inside a per-step Python loop "
                        "dispatches one host->device upload per "
                        "iteration; hoist or batch it",
                    )

    # -- JX005 -------------------------------------------------------------

    def _check_dtype_literals(self) -> None:
        if not re.search(r"cup3d_tpu/(sim|ops|grid|stream|models)/",
                         self.path):
            return
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Attribute) and node.attr == "float64":
                if _dotted(node) in ("jnp.float64", "jax.numpy.float64"):
                    self._emit(
                        "JX005", node, "<module>",
                        "jnp.float64 literal in device code; take the "
                        "dtype from the config (sim.dtype)",
                    )
            elif isinstance(node, ast.Call) and _is_jnp_call(node):
                for kw in node.keywords:
                    if kw.arg == "dtype" and (
                        (isinstance(kw.value, ast.Constant)
                         and kw.value.value == "float64")
                        or _dotted(kw.value) in (
                            "np.float64", "numpy.float64", "jnp.float64"
                        )
                    ):
                        self._emit(
                            "JX005", node, "<module>",
                            "float64 dtype literal in a jnp constructor",
                        )

    # -- JX006 -------------------------------------------------------------

    def _check_timing_windows(self, func: ast.AST, qualname: str) -> None:
        """Between consecutive perf_counter() reads (and from function
        start to the first one) there must be a sync: block_until_ready,
        a host read (float/int/np.asarray/.item), or nothing dispatched
        at all (no calls in the window)."""
        pc_lines: List[int] = []
        sync_lines: List[int] = []
        call_lines: List[int] = []
        for node in _walk_shallow(func):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            line = node.lineno
            if name.endswith("perf_counter"):
                pc_lines.append(line)
            elif (
                name in SYNC_BUILTINS
                or name in ("np.asarray", "numpy.asarray")
                or name.endswith("block_until_ready")
                or (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item")
            ):
                sync_lines.append(line)
            else:
                call_lines.append(line)
        if len(pc_lines) < 2:
            return
        pc_lines.sort()
        start = func.lineno
        for pc in pc_lines:
            window = (start, pc)
            dispatches = any(window[0] <= l <= window[1]
                             for l in call_lines)
            synced = any(window[0] <= l <= window[1] for l in sync_lines)
            if dispatches and not synced:
                v = Violation(
                    rule="JX006", path=self.path, line=pc, col=0,
                    func=qualname,
                    message=(
                        "perf_counter() read with dispatched device work "
                        "and no block_until_ready/host-read sync since "
                        f"line {window[0]}: the window times dispatch, "
                        "not device execution"
                    ),
                )
                reason = self.suppressions.get(pc, {}).get("JX006")
                if reason is not None:
                    v.suppressed = True
                    v.suppression_reason = reason or None
                self.violations.append(v)
            start = pc

    # -- JX008 -------------------------------------------------------------

    def _check_manual_timing(self, func: ast.AST, qualname: str) -> None:
        """``time.perf_counter()`` inside the package but outside the obs
        layer: a private timing channel the registry/trace/flight layer
        never sees.  One finding per function (the first read in source
        order), so one annotation covers a timed section; the obs layer
        itself is exempt by path, and so are bench.py/validation (they
        ARE timing harnesses, linted only for the other rules)."""
        if not self.path.startswith("cup3d_tpu/"):
            return
        if self.path.startswith("cup3d_tpu/obs/"):
            return
        first = None
        for node in _walk_shallow(func):
            if (isinstance(node, ast.Call)
                    and _call_name(node).endswith("perf_counter")):
                if first is None or node.lineno < first.lineno:
                    first = node
        if first is not None:
            self._emit(
                "JX008", first, qualname,
                "manual section timing outside cup3d_tpu/obs/: use obs "
                "spans (obs.trace.SpanTimer / the driver profiler) or "
                "obs metrics so the measurement reaches the registry "
                "and the step trace",
            )

    # -- JX014 -------------------------------------------------------------

    def _wallclock_call_names(self) -> Set[str]:
        """Dotted call names that read the WALL clock in this file,
        resolved from its imports: ``time.time`` under whatever alias
        the time module was imported as, the bare name ``from time
        import time [as X]`` leaves behind, and the datetime
        now/utcnow/today constructors."""
        cached = getattr(self, "_jx014_names", None)
        if cached is not None:
            return cached
        names: Set[str] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    alias = a.asname or a.name
                    if a.name == "time":
                        names.add(f"{alias}.time")
                    elif a.name == "datetime":
                        for attr in JX014_DATETIME_ATTRS:
                            names.add(f"{alias}.datetime.{attr}")
                            names.add(f"{alias}.date.{attr}")
            elif isinstance(node, ast.ImportFrom):
                if node.module == "time":
                    for a in node.names:
                        if a.name == "time":
                            names.add(a.asname or a.name)
                elif node.module == "datetime":
                    for a in node.names:
                        if a.name in ("datetime", "date"):
                            alias = a.asname or a.name
                            for attr in JX014_DATETIME_ATTRS:
                                names.add(f"{alias}.{attr}")
        self._jx014_names = names
        return names

    def _check_wallclock_duration(self, func: ast.AST,
                                  qualname: str) -> None:
        """Subtraction whose operands trace back to wall-clock reads:
        a duration computed from ``time.time()``/``datetime.now()``
        (directly, or through names/attributes assigned from them in
        this function).  Timestamp-only uses never subtract and stay
        silent; subtracting a numeric CONSTANT from a wall-clock read
        is timestamp arithmetic ("an hour ago") and stays silent too."""
        if not self.path.startswith("cup3d_tpu/"):
            return
        wall = self._wallclock_call_names()
        if not wall:
            return

        def is_wall_call(node: ast.AST) -> bool:
            return (isinstance(node, ast.Call)
                    and _call_name(node) in wall)

        # names/attributes assigned from a wall-clock read, iterated to
        # a fixpoint so t1 = time.time(); t2 = t1 taints t2 as well
        tainted: Set[str] = set()
        stmts = [n for n in _walk_shallow(func)
                 if isinstance(n, (ast.Assign, ast.AugAssign,
                                   ast.AnnAssign))]
        for _ in range(3):
            grew = False
            for stmt in stmts:
                value = stmt.value
                if value is None:
                    continue
                hit = any(is_wall_call(n) for n in ast.walk(value)) or any(
                    isinstance(n, (ast.Name, ast.Attribute))
                    and _dotted(n) in tainted
                    for n in ast.walk(value)
                )
                if not hit:
                    continue
                targets = (stmt.targets if isinstance(stmt, ast.Assign)
                           else [stmt.target])
                for t in targets:
                    for leaf in ast.walk(t):
                        name = _dotted(leaf)
                        if name and name not in tainted:
                            tainted.add(name)
                            grew = True
            if not grew:
                break

        def is_wallish(node: ast.AST) -> bool:
            if is_wall_call(node):
                return True
            return (isinstance(node, (ast.Name, ast.Attribute))
                    and _dotted(node) in tainted)

        for node in _walk_shallow(func):
            if not (isinstance(node, ast.BinOp)
                    and isinstance(node.op, ast.Sub)):
                continue
            l_wall, r_wall = is_wallish(node.left), is_wallish(node.right)
            if not (l_wall or r_wall):
                continue
            other = node.right if l_wall else node.left
            if isinstance(other, ast.Constant):
                continue  # timestamp arithmetic, not a duration
            self._emit(
                "JX014", node, qualname,
                "wall-clock subtraction used as a duration: "
                "time.time()/datetime.now() differences are NTP-"
                "slewed and can go negative — use the monotonic "
                "clock (obs.trace.now() at lifecycle seams, or obs "
                "spans/metrics) for durations",
            )

    # -- JX012 -------------------------------------------------------------

    def _check_profiler_usage(self, func: ast.AST, qualname: str) -> None:
        """Direct ``jax.profiler`` access — ``import``/``from`` imports
        or dotted ``jax.profiler.*`` chains — inside the package but
        outside the obs layer: a second, uncoordinated profiling channel
        (the profiler session is process-global).  Mirrors the JX008
        pattern: one finding per function/module (the first hit in
        source order, so one annotation covers a capture block); the obs
        layer owns the profiler and is exempt by path, and so are
        bench.py/validation harnesses (outside the package)."""
        if not self.path.startswith("cup3d_tpu/"):
            return
        if self.path.startswith("cup3d_tpu/obs/"):
            return
        first = None
        for node in _walk_shallow(func):
            hit = False
            if isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                hit = (mod == "jax.profiler"
                       or mod.startswith("jax.profiler."))
            elif isinstance(node, ast.Import):
                hit = any(
                    a.name == "jax.profiler"
                    or a.name.startswith("jax.profiler.")
                    for a in node.names
                )
            elif isinstance(node, ast.Attribute):
                name = _dotted(node)
                hit = (name == "jax.profiler"
                       or name.startswith("jax.profiler."))
            if hit and (first is None or node.lineno < first.lineno):
                first = node
        if first is not None:
            self._emit(
                "JX012", first, qualname,
                "direct jax.profiler use outside cup3d_tpu/obs/: use obs "
                "profile windows (obs.profile.CONTROLLER / "
                "CaptureController.capture()) and obs.trace.annotate() "
                "(profiler sections and blocking reads are annotations "
                "already) so captures coordinate and land on the merged "
                "host+device timeline",
            )

    # -- JX011 -------------------------------------------------------------

    def _dtype_aliases(self) -> Dict[str, str]:
        """Module-level ``_F32 = jnp.float32``-style aliases, so the
        idiomatic local dtype names resolve like the dotted originals."""
        aliases: Dict[str, str] = {}
        for node in ast.walk(self.tree):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                leaf = _dotted(node.value).rsplit(".", 1)[-1]
                if leaf in ("bfloat16", "float32", "float64"):
                    aliases[node.targets[0].id] = leaf
        return aliases

    def _dtype_leaf(self, node: ast.AST, aliases: Dict[str, str]) -> str:
        """'bfloat16'/'float32'/... for a dtype expression ('' unknown)."""
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value
        name = _dotted(node)
        if not name:
            return ""
        if "." not in name:
            return aliases.get(name, name)
        return name.rsplit(".", 1)[-1]

    def _cast_dtype(self, call: ast.Call, aliases: Dict[str, str]) -> str:
        """The dtype a call casts/constructs to: ``x.astype(D)`` or a jnp
        constructor/reduction with ``dtype=D`` ('' when neither)."""
        if (isinstance(call.func, ast.Attribute)
                and call.func.attr == "astype" and call.args):
            return self._dtype_leaf(call.args[0], aliases)
        if _is_jnp_call(call):
            for kw in call.keywords:
                if kw.arg == "dtype":
                    return self._dtype_leaf(kw.value, aliases)
        return ""

    def _check_bf16_reduction(self, func: ast.AST, qualname: str) -> None:
        """Reductions over bf16-tainted operands without an explicit
        accumulator dtype (JX011).  Precision-first: taint starts ONLY at
        an explicit bfloat16 cast/construction (``.astype(jnp.bfloat16)``,
        ``dtype=jnp.bfloat16``, module aliases included) and propagates
        through assignments; an f32/f64 re-cast launders.  A reduction
        call (jnp.sum/dot/vdot/...) whose operand is tainted and that
        names no ``dtype=``/``preferred_element_type=`` accumulator
        fires."""
        if not hasattr(self, "_jx011_aliases"):
            self._jx011_aliases = self._dtype_aliases()
        aliases = self._jx011_aliases

        def value_taint(value: ast.AST, tainted: Set[str]) -> bool:
            top = value
            if (isinstance(top, ast.Call)
                    and self._cast_dtype(top, aliases)
                    in ("float32", "float64")):
                return False  # explicit up-cast launders
            for n in ast.walk(value):
                if (isinstance(n, ast.Call)
                        and self._cast_dtype(n, aliases) == "bfloat16"):
                    return True
            return bool(tainted & _names_in(value))

        tainted: Set[str] = set()
        for stmt in _walk_shallow(func):
            targets: List[ast.AST] = []
            value: Optional[ast.AST] = None
            if isinstance(stmt, ast.Assign):
                targets, value = stmt.targets, stmt.value
            elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
                targets, value = [stmt.target], stmt.value
            if value is None:
                continue
            hit = value_taint(value, tainted)
            for t in targets:
                stack = [t]
                while stack:
                    leaf = stack.pop()
                    if isinstance(leaf, ast.Name):
                        (tainted.add if hit
                         else tainted.discard)(leaf.id)
                    elif isinstance(leaf, (ast.Tuple, ast.List)):
                        stack.extend(leaf.elts)
                    elif isinstance(leaf, ast.Starred):
                        stack.append(leaf.value)

        for node in _walk_shallow(func):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            root = name.split(".", 1)[0].lstrip("_")
            if (name.rsplit(".", 1)[-1] not in JX011_REDUCTIONS
                    or "." not in name
                    or root not in ("jnp", "jax", "lax", "np", "numpy")):
                continue
            if any(kw.arg in JX011_ACCUM_KWARGS for kw in node.keywords):
                continue
            if any(value_taint(a, tainted) for a in node.args):
                self._emit(
                    "JX011", node, qualname,
                    f"{name}() over bfloat16 operands reduces in storage "
                    "precision; name the f32 accumulator explicitly "
                    "(dtype=/preferred_element_type=) or up-cast the "
                    "operand first (ops/precision.py policy)",
                )

    # -- JX013 -------------------------------------------------------------

    def _check_lane_device_loop(self, func: ast.AST, qualname: str) -> None:
        """Python loop over the lane/scenario axis that dispatches device
        work per iteration (JX013, fleet/ only).  A loop 'walks the lane
        axis' when its target or any name in its iterable matches
        JX013_AXIS_RE (``lane``, ``lanes``, ``scenario``...); it fires
        when the loop body then makes a device call (jnp./jax. dotted
        call or a ``self._name(...)`` jitwrapper) — the B lanes exist to
        be advanced by ONE vmapped dispatch, not B host dispatches.
        Host-only lane loops (assembly, QoI fan-out) never fire."""
        for node in _walk_shallow(func):
            if not isinstance(node, LOOP_NODES) or isinstance(
                    node, ast.While):
                continue  # while has no axis target to classify
            if isinstance(node, ast.For):
                axis_src = [node.target, node.iter]
            else:  # comprehensions: every generator's target + iterable
                axis_src = [p for g in node.generators
                            for p in (g.target, g.iter)]
            names: Set[str] = set()
            for piece in axis_src:
                names |= _names_in(piece)
                names |= {a.attr for a in ast.walk(piece)
                          if isinstance(a, ast.Attribute)}
            if not any(JX013_AXIS_RE.search(n) for n in names):
                continue
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call) and _is_device_call(sub):
                    self._emit(
                        "JX013", sub, qualname,
                        f"`{_call_name(sub)}()` dispatches device work "
                        "per iteration of a lane/scenario-axis loop; "
                        "vectorize over the batch axis instead "
                        "(fleet/batch.py vmap advance, lane-masked "
                        "jnp.where selects)",
                    )
                    break

    # -- JX015 -------------------------------------------------------------

    def _check_batch_reassembly(self, func: ast.AST, qualname: str) -> None:
        """Full-batch host reassembly on the per-tick fleet fast path
        (JX015, fleet/ only).  Fires inside functions named like the
        K-boundary seam (JX015_FUNC_RE: tick/reseed/dispatch) on calls
        that restack the whole lane axis — ``jnp.stack``/``np.stack``/
        ``concatenate`` (any jnp/np/jax/lax root) or the assembly
        helpers ``stack_carries``/``stack_gaits`` under any dotted
        prefix.  A reseed must replace ONE lane through the jitted
        ``.at[lane].set`` upload (fleet/batch.py reseed_lane_carry /
        reseed_lane_gaits); rebuilding the B-lane pytree host-side
        every boundary is O(B) host work plus a full re-upload, and it
        breaks the bitwise-untouched guarantee for the other B-1
        lanes.  Batch construction (assemble/__init__) stacks
        legitimately and never matches the function-name gate."""
        if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return
        if not JX015_FUNC_RE.search(func.name):
            return
        for node in _walk_shallow(func):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            leaf = name.rsplit(".", 1)[-1]
            if leaf in JX015_ASSEMBLY_HELPERS:
                pass  # repo helpers stack by construction, any prefix
            elif leaf in JX015_STACKERS:
                root = name.split(".", 1)[0].lstrip("_")
                if "." not in name or root not in (
                        "jnp", "jax", "lax", "np", "numpy"):
                    continue  # bare/unknown-root stack(): not an array op
            else:
                continue
            self._emit(
                "JX015", node, qualname,
                f"`{name}()` reassembles the full lane-stacked batch "
                "inside a per-tick path; replace one lane via the "
                "jitted `.at[lane].set` upload instead "
                "(fleet/batch.py reseed_lane_carry/reseed_lane_gaits)",
            )

    # -- JX016 -------------------------------------------------------------

    def _check_sharded_materialization(
        self, func: ast.AST, qualname: str
    ) -> None:
        """Full-array materialization inside a sharded step path
        (JX016, sim|fleet|parallel only).  Fires inside functions named
        like the steady-state seam (JX016_FUNC_RE: step/advance/
        dispatch/megaloop) on ``jax.device_get``, ``np.asarray`` /
        ``np.array``, and the single-argument form of
        ``jax.device_put`` — each of which gathers a (possibly mesh-
        sharded) array whole onto one host or one device.
        ``device_put(x, sharding)`` with an explicit placement is the
        sanctioned way to move data and never matches; ``jnp.asarray``
        stays a device-side cast and is JX004/JX010's business.  Calls
        inside a ``with sanctioned_transfer(...)`` block are exempt —
        that context manager IS the designed-sync-point marker the
        runtime transfer guard audits (analysis/runtime.py)."""
        if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return
        if not JX016_FUNC_RE.search(func.name):
            return
        if JX016_BUILDER_RE.match(func.name):
            return
        sanctioned: Set[int] = set()
        for node in _walk_shallow(func):
            if isinstance(node, ast.With) and any(
                isinstance(it.context_expr, ast.Call)
                and _call_name(it.context_expr).rsplit(".", 1)[-1]
                == "sanctioned_transfer"
                for it in node.items
            ):
                for sub in ast.walk(node):
                    sanctioned.add(id(sub))
        for node in _walk_shallow(func):
            if not isinstance(node, ast.Call) or id(node) in sanctioned:
                continue
            name = _call_name(node)
            leaf = name.rsplit(".", 1)[-1]
            root = name.split(".", 1)[0].lstrip("_")
            if leaf == "device_get" and root in ("jax",):
                what = "pulls the full array to the host"
            elif (leaf in ("asarray", "array")
                  and root in ("np", "numpy")):
                what = "materializes the full array host-side"
            elif (leaf == "device_put" and root in ("jax",)
                    and len(node.args) == 1 and not node.keywords):
                what = ("re-places the full array onto the default "
                        "device (no explicit sharding)")
            else:
                continue
            self._emit(
                "JX016", node, qualname,
                f"`{name}()` {what} inside a sharded step path — a "
                "cross-shard gather under the 2-D mesh; slice shard-"
                "locally under shard_map or place with an explicit "
                "`device_put(x, sharding)`",
            )

    # -- JX018 -------------------------------------------------------------

    def _check_raw_collectives(self, func: ast.AST, qualname: str) -> None:
        """Raw communicating-collective call sites outside the
        ``cup3d_tpu/parallel/`` seam (JX018).  Matches ``lax.psum`` /
        ``jax.lax.ppermute`` / bare ``all_gather`` (from-import) style
        calls whose leaf name is one of JX018_COLLECTIVES; dotted
        prefixes other than jax/lax (e.g. a wrapper object's method)
        never fire.  ``axis_index`` is exempt by omission — it reads a
        shard-local coordinate and communicates nothing."""
        for node in _walk_shallow(func):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            leaf = name.rsplit(".", 1)[-1]
            if leaf not in JX018_COLLECTIVES:
                continue
            root = name.split(".", 1)[0]
            if "." in name and root not in ("jax", "lax"):
                continue
            self._emit(
                "JX018", node, qualname,
                f"raw collective `{name}()` outside cup3d_tpu/parallel/ "
                "— route it through the parallel/ seam (ring.ring_shift, "
                "collectives.all_gather_tiled/pmax_axis, ...) so the IR "
                "audit has one place to prove axis/permutation "
                "invariants",
            )

    # -- JX017 -------------------------------------------------------------

    def _check_hardware_peaks(self, func: ast.AST, qualname: str) -> None:
        """Hand-typed hardware peak/bandwidth literal in a roofline or
        bench reporting path (JX017).  A numeric constant >= 1e9 that
        is not an exact power of ten reads like a spec sheet
        (``197e12`` FLOP/s, ``819e9`` B/s) and bakes ONE device kind
        into math that runs on every backend — MFU and HBM fractions
        then silently lie on other hardware.  Exact powers of ten are
        unit conversions (``1e9`` for GB, ``1e12`` for T) and stay
        legal.  The sanctioned home for the literals is the
        provenance-annotated device-kind table in ``obs/costs.py``
        (path-exempt); consumers resolve the LIVE device through
        ``obs.costs.device_peaks()``."""
        for node in _walk_shallow(func):
            if not isinstance(node, ast.Constant):
                continue
            v = node.value
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            v = float(v)
            if v < JX017_MIN_MAGNITUDE or _is_power_of_ten(v):
                continue
            self._emit(
                "JX017", node, qualname,
                f"numeric literal {node.value!r} in a roofline/bench "
                "path looks like a hand-typed hardware peak — resolve "
                "the live device via obs.costs.device_peaks() (the "
                "obs/costs.py table is the one sanctioned home for "
                "spec-sheet numbers)",
            )

    # -- JX019 -------------------------------------------------------------

    def _check_aot_seam(self, func: ast.AST, qualname: str) -> None:
        """Direct AOT compile / jit-warmup call site outside the
        executable-store seam (JX019).  Two shapes fire: a chained
        ``fn.lower(...).compile()`` (Attribute ``compile`` called on a
        Call of Attribute ``lower``) and an immediately-invoked
        ``jit(f)(...)`` / ``jax.jit(f)(...)`` warmup.  Both compile an
        XLA executable the persistent store never sees — paid again
        every boot, invisible to the aot.* counters.  Split lowering
        (``lowered = fn.lower(...)`` then introspection, the
        analysis/audit.py pattern) never fires: IR-only reads are not
        warmups."""
        for node in _walk_shallow(func):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if (isinstance(f, ast.Attribute) and f.attr == "compile"
                    and isinstance(f.value, ast.Call)
                    and isinstance(f.value.func, ast.Attribute)
                    and f.value.func.attr == "lower"):
                self._emit(
                    "JX019", node, qualname,
                    "chained `.lower().compile()` outside the "
                    "cup3d_tpu/aot/ store seam — wrap the jitted "
                    "callable with aot.store_backed() and call "
                    ".warm()/.ensure_compiled() so previously-seen "
                    "signatures deserialize instead of recompiling",
                )
                continue
            if isinstance(f, ast.Call):
                name = _call_name(f)
                leaf = name.rsplit(".", 1)[-1]
                root = name.split(".", 1)[0]
                if leaf == "jit" and ("." not in name
                                      or root == "jax"):
                    self._emit(
                        "JX019", node, qualname,
                        f"immediately-invoked `{name}(...)(...)` "
                        "warmup compiles outside the cup3d_tpu/aot/ "
                        "store seam — bind the jit once, wrap it with "
                        "aot.store_backed(), and warm through the "
                        "wrapper",
                    )

    # -- JX020 -------------------------------------------------------------

    def _raw_clock_names(self) -> Set[str]:
        """Call names that read a raw ``time``-module clock in this
        file, resolved from its imports: ``time.monotonic`` (etc.)
        under whatever alias the module was imported as, plus the bare
        names ``from time import monotonic [as X]`` leaves behind."""
        cached = getattr(self, "_jx020_names", None)
        if cached is not None:
            return cached
        names: Set[str] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "time":
                        alias = a.asname or a.name
                        for attr in JX020_CLOCK_ATTRS:
                            names.add(f"{alias}.{attr}")
            elif isinstance(node, ast.ImportFrom):
                if node.module == "time":
                    for a in node.names:
                        if a.name in JX020_CLOCK_ATTRS:
                            names.add(a.asname or a.name)
        self._jx020_names = names
        return names

    def _check_raw_clock(self, func: ast.AST, qualname: str) -> None:
        """Raw ``time.monotonic()``/``time.time()``/``perf_counter()``
        (and ``*_ns`` variants) inside the package outside
        ``obs/trace.py``: a second clock domain.  The round-22 phase
        decomposition partitions end-to-end latency only because every
        lifecycle timestamp comes off the ONE monotonic clock behind
        ``obs.trace.now()``; wall stamps go through
        ``obs.trace.wall()``.  One finding per function (first read in
        source order) — one fix usually rewires the whole function."""
        clocks = self._raw_clock_names()
        if not clocks:
            return
        first = None
        for node in _walk_shallow(func):
            if isinstance(node, ast.Call) and _call_name(node) in clocks:
                if first is None or node.lineno < first.lineno:
                    first = node
        if first is not None:
            self._emit(
                "JX020", first, qualname,
                f"raw clock read `{_call_name(first)}()` outside "
                "cup3d_tpu/obs/trace.py splits the package across "
                "clock domains — use obs.trace.now() for monotonic "
                "reads or obs.trace.wall() for wall-time stamps",
            )

    # -- JX021 -------------------------------------------------------------

    def _check_status_mutation(self, func: ast.AST,
                               qualname: str) -> None:
        """Direct ``<job>.status = ...`` assignment outside the
        journal-logging seams (JX021, fleet/ only).  Every fleet job
        state transition must flow through a sanctioned seam
        (JX021_SANCTIONED_RE: first assembly, retire, reseed splice,
        cancel, prepare-failure, journal replay) — those are the
        functions whose transitions the round-23 write-ahead journal
        records, directly or via ``_job_terminal``/``mark``.  A status
        flip anywhere else is a lifecycle edge recovery can never
        replay: the job would be silently lost (or doubled) across a
        crash-restart.  One finding per assignment — each is its own
        unjournaled edge."""
        leaf = qualname.rsplit(".", 1)[-1]
        if JX021_SANCTIONED_RE.match(leaf):
            return
        for node in _walk_shallow(func):
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            for t in targets:
                if isinstance(t, ast.Attribute) and t.attr == "status":
                    self._emit(
                        "JX021", node, qualname,
                        "fleet job status mutated outside the "
                        "journal-logging seam — route the transition "
                        "through _job_terminal/mark (or a sanctioned "
                        "seam: " + JX021_SANCTIONED_RE.pattern + ") so "
                        "the write-ahead journal records it and "
                        "crash recovery can replay it",
                    )

    # -- JX009 -------------------------------------------------------------

    #: attribute names of log-like drop calls (log-and-drop handlers)
    _LOG_ATTRS = frozenset(
        {"warn", "warning", "error", "info", "debug", "exception"}
    )

    def _is_droppy_stmt(self, stmt: ast.stmt) -> bool:
        """A handler statement that drops the failure on the floor:
        pass/continue/break, a bare constant (docstring), or a pure
        log/print call.  Anything else — assignment, raise, return with
        a value, a counter ``.inc()`` — makes the handler observable."""
        if isinstance(stmt, (ast.Pass, ast.Continue, ast.Break)):
            return True
        if isinstance(stmt, ast.Expr):
            v = stmt.value
            if isinstance(v, ast.Constant):
                return True
            if isinstance(v, ast.Call):
                name = _call_name(v)
                if name == "print" or name.endswith("warnings.warn"):
                    return True
                if (isinstance(v.func, ast.Attribute)
                        and v.func.attr in self._LOG_ATTRS):
                    return True
        return False

    def _check_swallowed_exceptions(self, func: ast.AST,
                                    qualname: str) -> None:
        """``except`` handlers whose whole body drops the failure (JX009).
        Package scope only; ``cup3d_tpu/resilience/`` is exempt — its
        handlers ARE the degradation policy and carry their own
        counters."""
        if not self.path.startswith("cup3d_tpu/"):
            return
        if self.path.startswith("cup3d_tpu/resilience/"):
            return
        for node in _walk_shallow(func):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if node.body and all(self._is_droppy_stmt(s)
                                 for s in node.body):
                self._emit(
                    "JX009", node, qualname,
                    "exception swallowed (pass/log-and-drop): re-raise, "
                    "latch it into state, or bump an obs counter so the "
                    "failure is observable",
                )


# -- baseline ---------------------------------------------------------------


def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(__file__), "baseline.json")


def load_baseline(path: Optional[str]) -> Dict[Tuple[str, str, str], dict]:
    if path is None or not os.path.exists(path):
        return {}
    with open(path) as f:
        data = json.load(f)
    out = {}
    for e in data.get("entries", []):
        out[(e["rule"], e["path"], e["func"])] = {
            "reason": e.get("reason", ""),
            "count": int(e.get("count", 1)),
            "used": 0,
        }
    return out


def apply_baseline(
    violations: List[Violation],
    baseline: Dict[Tuple[str, str, str], dict],
) -> None:
    """Mark violations covered by the baseline (up to each entry's count —
    NEW violations in an already-baselined function still fail)."""
    for v in violations:
        if v.suppressed:
            continue
        entry = baseline.get(v.key())
        if entry is not None and entry["used"] < entry["count"]:
            entry["used"] += 1
            v.baselined = True


def write_baseline(violations: List[Violation], path: str) -> None:
    counts: Dict[Tuple[str, str, str], int] = {}
    for v in violations:
        if v.suppressed:
            continue
        counts[v.key()] = counts.get(v.key(), 0) + 1
    entries = [
        {"rule": r, "path": p, "func": f, "count": c,
         "reason": "TODO: justify or fix"}
        for (r, p, f), c in sorted(counts.items())
    ]
    with open(path, "w") as fh:
        json.dump({"version": 1, "entries": entries}, fh, indent=2)
        fh.write("\n")


# -- entry points -----------------------------------------------------------


def _iter_py_files(paths: Sequence[str]) -> Iterable[str]:
    for p in paths:
        if os.path.isfile(p):
            yield p
        else:
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in dirs if d != "__pycache__"]
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)


def repo_relative(path: str) -> str:
    """Normalize to a posix path rooted at the repo (the directory that
    contains the ``cup3d_tpu`` package), so baseline entries are stable
    regardless of the CWD the CLI runs from."""
    ap = os.path.abspath(path).replace(os.sep, "/")
    marker = "/cup3d_tpu/"
    idx = ap.rfind(marker)
    if idx >= 0:
        return ap[idx + 1:]
    return os.path.basename(ap)


def lint_source(
    source: str, path: str = "<string>"
) -> List[Violation]:
    """Lint one source string (fixture tests use this directly)."""
    tree = ast.parse(source)
    return FileLint(path, tree, parse_suppressions(source)).run()


def lint_paths(
    paths: Sequence[str],
    baseline_path: Optional[str] = None,
    rules: Optional[Set[str]] = None,
) -> List[Violation]:
    violations: List[Violation] = []
    for fpath in _iter_py_files(paths):
        with open(fpath, encoding="utf-8") as f:
            source = f.read()
        try:
            tree = ast.parse(source)
        except SyntaxError as e:
            violations.append(Violation(
                rule="JX000", path=repo_relative(fpath),
                line=e.lineno or 0, col=e.offset or 0, func="<module>",
                message=f"syntax error: {e.msg}",
            ))
            continue
        violations.extend(
            FileLint(repo_relative(fpath), tree,
                     parse_suppressions(source)).run()
        )
    if rules:
        violations = [v for v in violations if v.rule in rules]
    baseline = load_baseline(baseline_path)
    apply_baseline(violations, baseline)
    return violations


def failing(violations: Iterable[Violation]) -> List[Violation]:
    return [v for v in violations if not v.suppressed and not v.baselined]
