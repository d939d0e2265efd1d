"""Rule catalog for the JAX-aware lint (``cup3d_tpu.analysis.lint``).

Every hazard class that has actually cost this codebase wall-clock gets a
stable rule ID, so violations can be suppressed individually (inline
``# jax-lint: allow(JX00n, reason)``) or burned down against a checked-in
baseline (``analysis/baseline.json``) without ever turning the whole
checker off.

The catalog is the machine-checked half of the sanitizer contract in
VALIDATION.md ("Analysis subsystem: sanitizer contract"); the runtime
half (recompile counter, transfer guard) lives in ``analysis/runtime``.

Rule IDs are append-only: never renumber, never reuse.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple


@dataclass(frozen=True)
class Rule:
    id: str
    title: str
    rationale: str


RULES: Dict[str, Rule] = {
    r.id: r
    for r in (
        Rule(
            "JX001",
            "host sync in hot-path function",
            "float()/.item()/np.asarray()/jax.device_get() on device values "
            "inside step/solve-loop functions blocks the dispatch stream for "
            "a full device->host round trip: the dispatch queue stalls "
            "until the device has caught up.  PR 1 measured SyncQoI at 86% of the 256^3 fish step "
            "before these were hoisted onto the stream/ data-plane.  Every "
            "remaining sync must be a designed, annotated sync point.",
        ),
        Rule(
            "JX002",
            "step-shaped jax.jit without donate_argnums",
            "A steady-state step function that maps state -> state and is "
            "jitted without donating the state buffers doubles the field "
            "working set in HBM and forces XLA to copy instead of aliasing "
            "in-place.  At 256^3 the vel+p fields are ~400 MB; donation "
            "makes the update O(1) extra memory.",
        ),
        Rule(
            "JX003",
            "Python control flow on traced values in a jitted body",
            "`if`/`while` on a traced value inside a jitted function either "
            "raises a ConcretizationTypeError or — when the value is an "
            "argument that jit treats as dynamic — silently forces a "
            "trace-time host sync and a recompile per branch outcome.  Use "
            "lax.cond/lax.while_loop or jnp.where, or mark the argument "
            "static.",
        ),
        Rule(
            "JX004",
            "device array construction inside a per-step Python loop",
            "jnp.asarray/jnp.zeros/... inside a Python loop that runs every "
            "step dispatches one host->device upload per iteration per "
            "step.  Hoist the construction out of the loop, batch the "
            "uploads, or keep the data device-resident across steps.",
        ),
        Rule(
            "JX006",
            "perf_counter timing window without a device sync",
            "Timing a region that dispatches device work without a "
            "block_until_ready()/host-read sync before the perf_counter "
            "reads measures DISPATCH latency, not device execution: on an "
            "async backend the reported time can be off by orders of "
            "magnitude in either direction.  Every timed window must sync "
            "before its start and before its closing read.",
        ),
        Rule(
            "JX007",
            "jax.jit construction inside an adaptation/step loop",
            "Creating a jax.jit wrapper inside a loop, or inside a "
            "function that runs per mesh adaptation (rebuild/adapt "
            "paths), makes a FRESH jit object each pass — jax's trace "
            "cache is per-object, so every regrid recompiles every step "
            "function even when all shapes match.  Measured on amr_tgv: "
            "5.50 s max step against a 0.118 s median (BENCH_r05).  "
            "Build jits once and cache them keyed on the shape bucket "
            "(sim/amr.py compiled-step cache), or pass changing data as "
            "traced arguments.",
        ),
        Rule(
            "JX008",
            "manual section timing outside the obs layer",
            "time.perf_counter() section timing outside cup3d_tpu/obs/ "
            "builds a private, invisible telemetry channel: the wall it "
            "measures never reaches the metrics registry, the step trace, "
            "or the flight recorder, and the window repeats every JX006 "
            "sync-honesty hazard from scratch.  Use obs spans "
            "(obs.trace.SpanTimer / the driver profiler) or obs metrics; "
            "the annotated exceptions are the stream data-plane's "
            "stall/read splits, which ARE the registry's data source.",
        ),
        Rule(
            "JX009",
            "swallowed exception (drop without counter or re-raise)",
            "An `except: pass`/`continue` (or a log-and-drop handler) "
            "erases the only evidence of a failure: the round-10 "
            "resilience work found background checkpoint-write errors "
            "that vanished this way until the run ended with silent data "
            "loss.  A handler must re-raise, return a sentinel the "
            "caller checks, record the error into state, or at minimum "
            "bump an obs-registry counter so the drop is observable; "
            "deliberate capability probes are annotated inline.  The "
            "resilience/ subsystem (whose whole job is containing "
            "failures it has already counted) is exempt by path.",
        ),
        Rule(
            "JX010",
            "per-step host<->device staging of obstacle state",
            "np.asarray/jnp.asarray on a loop-carried obstacle/driver "
            "attribute (self.X / ob.X / s.X) inside a step-loop function "
            "re-stages the same mirror across the host boundary every "
            "step: construction-time constants (bForcedInSimFrame, "
            "bBlockRotation) and per-step scalars (lambda = DLM/dt) each "
            "cost a host->device upload per step, and np.asarray on a "
            "device-resident mirror blocks for the round trip.  BENCH_r05 "
            "measured the residue at ~28-43 ms/step on the fish configs.  "
            "Cache static mirrors identity-keyed on the obstacle "
            "(models/base.forced_mask_dev), derive per-step values on "
            "device from already-uploaded scalars "
            "(sim/data.lambda_device), or carry the state device-resident "
            "across steps (sim/megaloop.py).",
        ),
        Rule(
            "JX005",
            "float64 dtype literal in device code",
            "A bare float64 dtype in device code either doubles bandwidth "
            "and VMEM pressure on TPU or silently promotes downstream "
            "arithmetic.  Device-side dtypes must come from the config "
            "(sim.dtype); float64 is reserved for host-side mirrors and "
            "accumulations.",
        ),
        Rule(
            "JX011",
            "bf16 reduction without an explicit f32 accumulator",
            "jnp.sum/dot/vdot (or lax.dot) over bfloat16 operands without "
            "an explicit dtype=/preferred_element_type= accumulator "
            "reduces in storage precision on some backends: at 128^3 a "
            "bf16-accumulated dot product of the Krylov residual loses "
            "~8 of the ~11 significand bits the stopping test needs, so "
            "the solver reports convergence it does not have.  The round-"
            "12 mixed-precision policy (ops/precision.py) stores Krylov "
            "vectors in bf16 but ACCUMULATES in f32 everywhere — any "
            "reduction touching a bf16-cast value must name its f32 "
            "accumulator explicitly.",
        ),
        Rule(
            "JX013",
            "per-lane Python loop over the fleet scenario axis",
            "A Python loop that walks the lane/scenario axis AND "
            "dispatches device work per iteration inside cup3d_tpu/"
            "fleet/ undoes the entire fleet amortization: B lanes "
            "exist to be advanced by ONE vmapped dispatch "
            "(fleet/batch.py), so a per-lane device loop pays the "
            "~0.03 s/step host overhead B times over — exactly the "
            "floor BENCH_r04/r05 measured and the fleet was built to "
            "amortize.  The batch axis must stay vectorized (vmap / "
            "lane-masked selects); host-only Python loops over lanes "
            "are fine in assembly and fan-out code because they touch "
            "no device value.",
        ),
        Rule(
            "JX014",
            "wall-clock subtraction used as a duration",
            "Subtracting two time.time() (or datetime.now()) reads "
            "measures the WALL clock, which NTP slews and steps: a "
            "duration computed this way can come out negative, jump by "
            "whole seconds, and silently corrupts latency histograms "
            "and SLO burn rates (the round-16 job observatory gates on "
            "p99 completion latency, so a stepped clock is a paged "
            "on-call).  Durations must come from the monotonic clock — "
            "obs.trace.now() (perf_counter on the trace epoch) at "
            "lifecycle seams, or the obs span/metric primitives.  "
            "time.time() stays legitimate for TIMESTAMPS (history "
            "store rows, postmortem wall_time, /health time): the rule "
            "fires only on wall-clock SUBTRACTION.",
        ),
        Rule(
            "JX015",
            "per-tick host reassembly of full-batch arrays in fleet/",
            "A K-boundary fast-path function (tick/reseed/dispatch) in "
            "cup3d_tpu/fleet/ that restacks the whole lane axis — "
            "jnp.stack/np.stack/concatenate or the assembly helpers "
            "stack_carries/stack_gaits — turns an O(1)-lane reseed "
            "into O(B) host work plus a full-batch device upload at "
            "EVERY boundary, and the host-side rebuild breaks the "
            "round-14 bitwise-untouched guarantee for the other B-1 "
            "lanes (fresh ndarray round-trips are not bitwise-stable "
            "across pytrees that were never touched).  The round-17 "
            "continuous-batching contract is that a reseed replaces "
            "ONE lane through the jitted `.at[lane].set` upload path "
            "(fleet/batch.py reseed_lane_carry/reseed_lane_gaits, one "
            "compiled specialization for all lane indices).  Batch "
            "CONSTRUCTION (assemble/FleetBatch.__init__) stacks "
            "legitimately — the rule keys on per-tick function names.",
        ),
        Rule(
            "JX016",
            "full-array materialization in a sharded step path",
            "jax.device_get()/np.asarray()/np.array() — or a single-"
            "argument jax.device_put() — on a device value inside a "
            "step/advance/dispatch/megaloop function in cup3d_tpu/"
            "{sim,fleet,parallel}/ gathers the FULL array to one host "
            "or one device.  Under the round-18 2-D (lanes, x) mesh "
            "those arrays are shard-resident: the gather serializes "
            "every shard through a single host link (the exact "
            "scale-out ceiling the mesh removes), doubles peak memory "
            "on the target, and on multi-host topologies is an error.  "
            "Keep fields sharded: slice shard-locally under shard_map "
            "(lax.dynamic_slice + axis_index), move data with an "
            "explicit NamedSharding device_put(x, sharding), and stage "
            "host reads through the designed sync points "
            "(analysis/runtime.sanctioned_transfer).",
        ),
        Rule(
            "JX012",
            "direct jax.profiler use outside the obs layer",
            "jax.profiler.start_trace/stop_trace/TraceAnnotation called "
            "outside cup3d_tpu/obs/ opens a second, uncoordinated "
            "profiling channel: the profiler session is process-global, "
            "so an ad-hoc capture colliding with an obs window aborts "
            "one of them; ad-hoc annotations fall outside the cup3d: "
            "names the device-time attribution parser puts the device's "
            "idle gaps down to; and the resulting trace never reaches "
            "that parser or the merged host+device timeline.  Use obs "
            "profile windows (obs.profile.CONTROLLER / "
            "CaptureController.capture()) and obs.trace.annotate(): every "
            "profiler section, step and blocking read is an annotation "
            "already, with no switch.",
        ),
        Rule(
            "JX018",
            "raw collective call site outside cup3d_tpu/parallel/",
            "jax.lax.ppermute/psum/pmax/all_gather/all_to_all/... called "
            "directly outside cup3d_tpu/parallel/ scatters the SPMD "
            "communication surface across the tree: the IR audit "
            "(analysis/ir.py JP002) and the pod bring-up work need ONE "
            "seam where axis names, permutation structure, and mesh "
            "shape assumptions live.  Collectives go through the "
            "parallel/ layer (parallel/ring.py ring_shift/pad_slab, "
            "parallel/collectives.py all_gather_tiled/pmax_axis) so a "
            "mesh-axis rename or a topology change edits one module "
            "instead of every call site — the exact MPI-communicator "
            "discipline the reference C++ enforces by construction.",
        ),
        Rule(
            "JX019",
            "direct AOT compile / jit-warmup call site outside the "
            "executable-store seam",
            "A chained `fn.lower(...).compile()` or an immediately-"
            "invoked `jit(f)(...)` warmup compiles an XLA executable "
            "that the persistent store (cup3d_tpu/aot/store.py) never "
            "sees: the result is paid again on every process start — "
            "the exact cold-start tax round 21 eliminates — and the "
            "compile evades the aot.* hit/miss/compile-seconds "
            "telemetry.  Compile-producing call sites go through the "
            "store seam (aot.store_backed / StoreBackedExecutable."
            "warm/ensure_compiled) so previously-seen signatures "
            "deserialize instead of recompiling.  cup3d_tpu/aot/ IS "
            "the seam and obs/costs.py harvests cost analytics from "
            "an already-compiled object — both are path-exempt.",
        ),
        Rule(
            "JX020",
            "raw clock read inside cup3d_tpu/ outside obs/trace.py",
            "time.monotonic()/time.time()/time.perf_counter() (and the "
            "*_ns variants) called anywhere but obs/trace.py splits the "
            "package across clock domains: the round-22 latency "
            "provenance decomposes a job's end-to-end time into "
            "exclusive phases that sum back exactly, and that partition "
            "invariant only holds because every lifecycle timestamp — "
            "fleet marks, compile-service spans, flight-recorder stamps "
            "— comes off the ONE monotonic clock behind "
            "obs.trace.now().  A stray time.monotonic() in a subsystem "
            "is a second epoch: its intervals cannot be subtracted "
            "against trace timestamps without silent skew.  Monotonic "
            "reads route through obs.trace.now(); wall-time stamps "
            "(log/postmortem metadata, never durations — JX014) route "
            "through obs.trace.wall().  obs/trace.py IS the clock seam "
            "and is path-exempt.",
        ),
        Rule(
            "JX021",
            "fleet job status mutated outside the journal-logging seam",
            "A direct `<job>.status = ...` assignment in cup3d_tpu/"
            "fleet/ outside the sanctioned seams (FleetBatch.__init__, "
            "retire, reseed_lane, cancel, _prepare, "
            "_install_replayed_job) is a lifecycle transition the "
            "round-23 write-ahead journal never records: the sanctioned "
            "seams journal their transitions (place/terminal records) "
            "or funnel into _job_terminal, so FleetServer.recover() can "
            "replay every accepted job after a crash — terminal jobs "
            "remembered, queued re-admitted, running resumed from their "
            "snapshots.  An unjournaled status flip breaks that "
            "zero-lost-jobs guarantee silently: the job vanishes (or "
            "doubles) only when a server actually dies.  Route "
            "transitions through the seams, or extend "
            "JX021_SANCTIONED_RE when adding a new seam that itself "
            "journals.",
        ),
        Rule(
            "JP001",
            "donated buffer not aliased in the compiled executable",
            "jit(donate_argnums=...) is a PROMISE, not a guarantee: when "
            "XLA cannot alias a donated input to an output (shape/dtype "
            "mismatch, layout change, or an output that is not a pure "
            "update) it silently copies and the donation evaporates — "
            "the steady-state megaloop then carries 2x the field working "
            "set in HBM, exactly what donation exists to prevent (JX002 "
            "rationale, ~400 MB of vel+p at 256^3).  The audit traces "
            "the canonical executables and requires every donated leaf "
            "to appear in the compiled input_output_aliases (or the "
            "lowered tf.aliasing_output marks); an entry that documents "
            "a no-donation contract (fleet advance: rollback needs the "
            "pre-dispatch buffers) declares it and is checked for the "
            "ABSENCE of donation instead.",
        ),
        Rule(
            "JP002",
            "unsafe collective in a shard_map body",
            "A ppermute whose (src, dst) pairs are not a permutation "
            "(duplicate sources, duplicate destinations, or ids outside "
            "the mesh axis) and any collective naming an axis that does "
            "not exist in the enclosing mesh are exactly the class of "
            "bug that deadlocks or corrupts a multi-host pod at runtime "
            "— jax does NOT validate either at trace time.  The "
            "reference C++ relies on MPI runtime assertions here; the "
            "audit walks every shard_map body in the canonical jaxprs "
            "and proves the permutation/axis invariants before any "
            "jax.distributed run is real.",
        ),
        Rule(
            "JP003",
            "cross-shard materialization in a sharded step jaxpr",
            "An all_gather inside a mesh-sharded steady-state step "
            "reassembles a full axis on every shard, every step — the "
            "compiler-truth complement of AST rule JX016 (which can "
            "only see host-side gathers in source text).  A gather that "
            "is part of the design (the sharded megaloop's replicated "
            "coarse solve) is annotated at the registry entry with a "
            "reason; anything else is a scale-out ceiling hiding in "
            "the IR.",
        ),
        Rule(
            "JP004",
            "precision hazard visible in the jaxpr",
            "float64 avals or bf16-accumulated reductions (reduce_sum / "
            "dot_general producing bfloat16) in a hot jaxpr are the "
            "IR-grounded halves of JX005/JX011: dtype promotion "
            "introduced two helpers away from the call site is "
            "invisible to the AST linter but fully visible in the "
            "traced IR.  f64 doubles bandwidth and VMEM pressure on "
            "TPU; a bf16 accumulator loses ~8 of the ~11 significand "
            "bits the Krylov stopping test needs (the round-12 policy "
            "stores bf16 but accumulates f32 everywhere).",
        ),
        Rule(
            "JP005",
            "host callback op in a hot jaxpr",
            "pure_callback/io_callback/debug_callback inside a "
            "steady-state jaxpr inserts a host round trip into every "
            "step: the dispatch stream blocks on the Python interpreter "
            "(the JX001 hazard, but introduced at trace level where the "
            "AST linter cannot see it), and on a multi-host pod the "
            "callback runs per-process with unsynchronized side "
            "effects.  Debug prints and host-side physics must stay "
            "out of the megaloop; diagnostics ride the scan-stacked "
            "row outputs instead.",
        ),
        Rule(
            "JX017",
            "hand-typed hardware peak literal in a roofline/bench path",
            "A numeric constant >= 1e9 that is not an exact power of "
            "ten inside a bench*.py file or a roofline/peak-model "
            "function reads like a spec sheet (197e12 bf16 FLOP/s, "
            "819e9 HBM B/s) and hard-codes ONE device kind into math "
            "that runs on EVERY backend: the reported MFU and HBM "
            "fractions then silently lie on anything that is not that "
            "device — the round-19 bug class where bench.py divided by "
            "v5e ceilings regardless of hardware.  Hardware peaks live "
            "in the provenance-annotated device-kind table in "
            "obs/costs.py (the one path-exempt module; no entry, no "
            "ceiling — an unknown TPU kind raises, a CPU has none); "
            "consumers resolve the live "
            "backend with obs.costs.device_peaks().  Exact powers of "
            "ten (1e9, 1e12) are unit conversions and never fire.",
        ),
    )
}


@dataclass
class Violation:
    """One lint finding.  ``func`` is the enclosing function's qualname —
    the baseline matches on (rule, path, func) so entries survive line
    drift from unrelated edits."""

    rule: str
    path: str
    line: int
    col: int
    func: str
    message: str
    suppressed: bool = False
    suppression_reason: Optional[str] = None
    baselined: bool = False

    def key(self) -> Tuple[str, str, str]:
        return (self.rule, self.path, self.func)

    def format(self) -> str:
        tag = ""
        if self.suppressed:
            tag = f"  [allowed: {self.suppression_reason or 'no reason'}]"
        elif self.baselined:
            tag = "  [baselined]"
        rule = RULES.get(self.rule)
        title = rule.title if rule else "unknown rule"
        return (
            f"{self.path}:{self.line}:{self.col}: {self.rule} "
            f"({title}) in `{self.func}`: {self.message}{tag}"
        )
