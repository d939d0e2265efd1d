"""Device-time attribution: profiler capture windows + trace parsing
(ISSUE 9 — the device half of the obs/ telemetry).

The host spans of :mod:`cup3d_tpu.obs.trace` stop at the dispatch
boundary: a K-step megaloop or a fused BiCGSTAB solve is ONE opaque
block of host wall.  This module recovers where the device spent that
block:

1. :class:`CaptureController` — programmatic ``jax.profiler`` capture
   windows.  ``CUP3D_PROFILE=every:N`` opens a window every N steps
   (``once``/``once:S`` for a single window); the drivers call
   :meth:`CaptureController.on_step` at loop top — for the megaloop
   that is a K boundary, so a window brackets whole scan dispatches.
   Disabled (the default) the hook is one attribute load + branch; no
   jax import, no sync, nothing on the step loop.

2. The trace-event parser — loads the captured ``*.trace.json.gz``
   (gzipped Chrome trace-event JSON, the same format the sink's
   Perfetto export uses) and reads BOTH timelines by the one vocabulary
   the program writes into them.  Device: every executed operation
   carries the ``jax.named_scope`` path of the code that traced it (in
   the event's own arguments on a TPU; joined from the program's
   optimised HLO text, :func:`hlo_op_names`, where the backend leaves
   it out), and its SELF time goes to that path — by outermost operator
   scope (:data:`OPERATOR_SCOPES`), one level below for
   :data:`SPLIT_SCOPES`, ``other`` for what carries none — so the
   sections and ``other`` sum to the device's busy time.  Host: every
   instant the device idles goes to the innermost ``cup3d:`` annotation
   open at that instant (``obs/trace.py``: the profiler's sections,
   steps and blocking reads, always on).

3. The merge — each closed window lands (a) per-section gauges in the
   metrics registry (``profile.device_ms{section=...}``,
   ``profile.idle_ms{span=...}``), (b) a ``kind="device"`` auxiliary
   record in the step-trace JSONL, and (c) the device ops as
   pid-:data:`DEVICE_PID` events in the sink's Perfetto export, so host
   spans and device ops read off ONE timeline.

Everything here runs at window close on the host — never inside the
step loop — and every failure is counted, never raised (a profiler
hiccup must not kill a simulation).

Env knobs: ``CUP3D_PROFILE`` (plan), ``CUP3D_PROFILE_DIR`` (capture
directory), ``CUP3D_PROFILE_STEPS`` (window length in loop iterations,
default 1 — one megaloop dispatch or one plain step).

``python -m cup3d_tpu.obs.profile --selftest`` runs the synthetic
parser/merge round trip CI uses (tools/lint.sh), no TPU needed.
"""

from __future__ import annotations

import glob
import gzip
from bisect import bisect_right
import json
import os
import re
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from cup3d_tpu.obs import metrics as _metrics
from cup3d_tpu.obs import trace as obs_trace

#: pid the merged Perfetto export places device-stream ops on (host
#: spans are pid 1 — obs/trace.py)
DEVICE_PID = 2

#: process names marking a trace track as a DEVICE stream
_DEVICE_NAME_RE = re.compile(
    r"device|tpu|gpu|accelerator|/stream", re.IGNORECASE
)

#: the lines of a device process that hold its executed operations and
#: its executed programs (``benchmarks/lib/trace_reduce.py`` reads the same)
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"

#: the operator vocabulary: the drivers' profiler sections, which are
#: also the outermost ``jax.named_scope`` of the device code they issue
OPERATOR_SCOPES: Tuple[str, ...] = (
    "CreateObstacles", "AdvectionDiffusion", "UpdateObstacles",
    "Penalization", "PressureProjection", "ComputeForces", "AdaptMesh",
    "DtPolicy",
)

#: scopes that only ever sit below an operator (or alone, in a program
#: that is no step: a solve probe)
CHILD_SCOPES: Tuple[str, ...] = (
    "PoissonRHS", "PoissonSolve", "Gradient", "Laplacian",
    "Preconditioner", "TileSolve", "CoarseSolve", "Dots", "Halo",
    "FluxCorrection",
)

#: operators whose device time the summary also gives one level below
SPLIT_SCOPES: Tuple[str, ...] = ("PressureProjection", "AdvectionDiffusion")

_SCOPES = frozenset(OPERATOR_SCOPES + CHILD_SCOPES)

#: where a device event says which code traced it, in the order tried
_OP_NAME_ARGS: Tuple[str, ...] = ("tf_op", "op_name")

#: the gap of a device that no ``cup3d:`` annotation covers
NO_SPAN = "outside cup3d spans"


# -- capture plan ------------------------------------------------------------


def parse_plan(spec: Optional[str]) -> Optional[dict]:
    """``CUP3D_PROFILE`` -> plan dict, or None (profiling off).

    ``every:N``  one window every N steps (N >= 1);
    ``once``     one window at the first loop iteration;
    ``once:S``   one window at the first iteration with step >= S.
    Unset/empty/``0``/``off`` disable.  A malformed spec disables and
    bumps ``profile.bad_plan`` (a typo must not kill the run).
    """
    if not spec or spec in ("0", "off", "none"):
        return None
    try:
        if spec.startswith("every:"):
            n = int(spec.split(":", 1)[1])
            if n < 1:
                raise ValueError(spec)
            return {"mode": "every", "n": n}
        if spec == "once":
            return {"mode": "once", "at": 0}
        if spec.startswith("once:"):
            return {"mode": "once", "at": int(spec.split(":", 1)[1])}
        raise ValueError(spec)
    except ValueError:
        _metrics.counter("profile.bad_plan").inc()
        return None


def _default_start(logdir: str) -> None:
    """Device and host tracers on, Python's own off: it writes an event
    per Python call, which slows the very host whose spans the window is
    there to read and buries them (the benchmark's traced window starts
    its session the same way)."""
    import jax.profiler

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=options)


def _default_stop() -> None:
    import jax.profiler

    jax.profiler.stop_trace()


class CaptureController:
    """Opens/closes ``jax.profiler`` windows on a step cadence and
    harvests each closed window into a :class:`DeviceAttribution`.

    One process-global instance (:data:`CONTROLLER`) is wired into both
    drivers; a private instance with injected ``start_fn``/``stop_fn``
    is the test seam.  All state is host-side; ``on_step`` never touches
    a device value."""

    def __init__(self, plan=None, directory: Optional[str] = None,
                 window_steps: Optional[int] = None,
                 start_fn=None, stop_fn=None, sink=None):
        env = os.environ
        if isinstance(plan, str):
            plan = parse_plan(plan)
        self.plan = plan
        self.directory = (directory or env.get("CUP3D_PROFILE_DIR")
                          or "profile")
        self._dir_pinned = bool(directory or env.get("CUP3D_PROFILE_DIR"))
        try:
            self.window_steps = (int(env.get("CUP3D_PROFILE_STEPS", "1"))
                                 if window_steps is None else int(window_steps))
        except ValueError:
            self.window_steps = 1
        self.window_steps = max(1, self.window_steps)
        self._start = start_fn or _default_start
        self._stop = stop_fn or _default_stop
        self._sink = sink  # None -> the global TRACE at harvest time
        self.capturing = False
        self.windows = 0
        self.last_attribution: Optional["DeviceAttribution"] = None
        self._open_step: Optional[int] = None
        self._open_dir: Optional[str] = None
        self._last_step = 0
        self._next_open = self._first_open()
        self._g_capturing = _metrics.gauge("profile.capturing")

    @classmethod
    def from_env(cls) -> "CaptureController":
        return cls(plan=parse_plan(os.environ.get("CUP3D_PROFILE")))

    def _first_open(self) -> Optional[int]:
        if self.plan is None:
            return None
        if self.plan["mode"] == "once":
            return self.plan["at"]
        # every:N — skip the compile-heavy first steps: the first window
        # opens at step N, the next at open+N, ...
        return self.plan["n"]

    @property
    def sink(self) -> obs_trace.TraceSink:
        return self._sink if self._sink is not None else obs_trace.TRACE

    def default_directory(self, directory: str) -> None:
        """Driver hint (mirrors TraceSink.default_directory): capture
        under the run directory unless the user pinned a location."""
        if not self._dir_pinned and not self.capturing:
            self.directory = os.path.join(directory, "profile")

    # -- the driver hook (loop top / K boundary) ---------------------------

    def on_step(self, step: int) -> None:
        """Called at loop top with the CURRENT step index.  For the
        megaloop, consecutive calls differ by K — a window therefore
        brackets whole scan dispatches.  Disabled: one branch."""
        if self.plan is None:
            return
        self._last_step = step
        if self.capturing:
            if step >= self._open_step + self.window_steps:
                self._close_window(step)
            return
        if self._next_open is not None and step >= self._next_open:
            self._open_window(step)

    def finish(self) -> None:
        """Run end: close a still-open window (drivers call this from
        drain_streams; atexit backstops it)."""
        if self.capturing:
            self._close_window(self._last_step + 1)

    # -- window mechanics ---------------------------------------------------

    def _open_window(self, step: int) -> None:
        logdir = os.path.join(self.directory, f"window_{step:07d}")
        try:
            os.makedirs(logdir, exist_ok=True)
            self._start(logdir)
        except Exception:
            # a profiler that cannot start (unsupported backend, nested
            # session) disables the plan: counted, never raised, and
            # never retried every step
            _metrics.counter("profile.capture_errors").inc()
            self.plan = None
            return
        self.capturing = True
        self._open_step = step
        self._open_dir = logdir
        self._g_capturing.set(1.0)

    def _close_window(self, step: int) -> None:
        try:
            self._stop()
        except Exception:
            _metrics.counter("profile.capture_errors").inc()
            self.plan = None
            self.capturing = False
            self._g_capturing.set(0.0)
            return
        self.capturing = False
        self._g_capturing.set(0.0)
        self.windows += 1
        _metrics.counter("profile.windows").inc()
        window = (int(self._open_step), int(step))
        if self.plan is not None and self.plan["mode"] == "every":
            self._next_open = self._open_step + self.plan["n"]
        else:
            self._next_open = None
        self.harvest(self._open_dir, window=window)

    @contextmanager
    def capture(self, tag: str = "capture"):
        """One-shot programmatic window (bench/tools); yields the
        capture directory and harvests on exit."""
        if self.capturing:
            raise RuntimeError("a capture window is already open")
        logdir = os.path.join(self.directory, tag)
        os.makedirs(logdir, exist_ok=True)
        self._start(logdir)
        self.capturing = True
        self._g_capturing.set(1.0)
        try:
            yield logdir
        finally:
            self._stop()
            self.capturing = False
            self._g_capturing.set(0.0)
            self.windows += 1
            _metrics.counter("profile.windows").inc()
            self.harvest(logdir, window=(self._last_step, self._last_step))

    # -- harvest: parse + attribute + merge --------------------------------

    def harvest(self, logdir: str,
                window: Tuple[int, int] = (0, 0)
                ) -> Optional["DeviceAttribution"]:
        """Parse the newest capture under ``logdir``, attribute device
        time, and merge into metrics + the trace sink.  Any failure is
        counted into ``profile.parse_errors`` and swallowed."""
        attr = None
        for path in reversed(find_trace_files(logdir)):
            try:
                attr = attribute(load_chrome_trace(path), source=path)
                break
            except Exception:
                _metrics.counter("profile.parse_errors").inc()
        if attr is None:
            _metrics.counter("profile.empty_captures").inc()
            return None
        self.last_attribution = attr
        for name, ms in attr.sections.items():
            _metrics.gauge("profile.device_ms", section=name).set(ms)
        _metrics.gauge("profile.device_ms", section="other").set(attr.other_ms)
        _metrics.gauge("profile.device_total_ms").set(attr.total_ms)
        for name, ms in attr.gaps.items():
            _metrics.gauge("profile.idle_ms", span=name).set(ms)
        sink = self.sink
        if sink.enabled:
            merge_into_sink(sink, attr, window=window)
        return attr


#: the process-global controller (env-configured), wired into both
#: drivers like obs_trace.TRACE; finish() runs atexit so a window open
#: at interpreter exit still stops + harvests.
CONTROLLER = CaptureController.from_env()

import atexit  # noqa: E402  (registration must follow CONTROLLER)

atexit.register(CONTROLLER.finish)


# -- trace-event loading -----------------------------------------------------


def find_trace_files(logdir: str) -> List[str]:
    """Chrome-JSON capture files under ``logdir`` (the jax profiler
    writes ``plugins/profile/<run>/*.trace.json.gz``), oldest first."""
    pats = ("*.trace.json.gz", "*.trace.json", "perfetto_trace.json.gz")
    hits: List[str] = []
    for pat in pats:
        hits += glob.glob(os.path.join(logdir, "**", pat), recursive=True)
    return sorted(set(hits), key=lambda p: (os.path.getmtime(p), p))


def load_chrome_trace(path: str) -> dict:
    """Load one (optionally gzipped) Chrome trace-event JSON file."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        obj = json.load(f)
    if isinstance(obj, list):  # bare traceEvents array form
        obj = {"traceEvents": obj}
    if not isinstance(obj.get("traceEvents"), list):
        raise ValueError(f"{path}: no traceEvents")
    return obj


# -- attribution -------------------------------------------------------------


@dataclass
class DeviceAttribution:
    """One capture window read by scope.  ``paths`` holds the device's
    self time by full scope path (``"PressureProjection/PoissonSolve/
    Laplacian"``), ``sections`` the same time by outermost scope.
    Invariant: ``sum(sections.values()) + other_ms == total_ms`` — every
    device op's self time lands exactly once, and ``total_ms`` is the
    device's busy time.  ``gaps`` holds the device's idle time inside
    the window by host annotation, ``host`` the annotations' own self
    time (where the host thread was, device busy or not), ``programs``
    each executed program's (total, other) ms."""

    total_ms: float = 0.0
    sections: Dict[str, float] = field(default_factory=dict)
    paths: Dict[str, float] = field(default_factory=dict)
    other_ms: float = 0.0
    window_ms: float = 0.0
    gaps: Dict[str, float] = field(default_factory=dict)
    host: Dict[str, float] = field(default_factory=dict)
    programs: Dict[str, List[float]] = field(default_factory=dict)
    other_ops: Dict[str, float] = field(default_factory=dict)
    events: List[dict] = field(default_factory=list)
    source: str = ""

    def children(self, scope: str) -> Dict[str, float]:
        """``scope``'s time one level below; its own under ``self``."""
        out: Dict[str, float] = {}
        for path, ms in self.paths.items():
            parts = path.split("/")
            if parts[0] == scope:
                key = parts[1] if len(parts) > 1 else "self"
                out[key] = out.get(key, 0.0) + ms
        return out

    def summary(self) -> dict:
        r6 = lambda d: {k: round(v, 6) for k, v in sorted(d.items())}
        return {
            "total_device_ms": round(self.total_ms, 6),
            "device_sections": r6(self.sections),
            "other_ms": round(self.other_ms, 6),
            "device_children": {sc: r6(self.children(sc))
                                for sc in SPLIT_SCOPES
                                if sc in self.sections},
            "device_paths": r6(self.paths),
            "window_ms": round(self.window_ms, 6),
            "idle_gaps_ms": r6(self.gaps),
            "host_spans_ms": r6(self.host),
            "programs_total_other_ms": {
                k: [round(v[0], 6), round(v[1], 6)]
                for k, v in sorted(self.programs.items())},
            "other_top_ops_ms": r6(dict(sorted(
                self.other_ops.items(), key=lambda kv: -kv[1])[:10])),
            "source": self.source,
        }


def scope_path(op_name: str) -> Tuple[str, ...]:
    """The named scopes of the vocabulary in an HLO ``op_name``
    (``jit(megaloop)/while/body/closed_call/PressureProjection/
    PoissonSolve/while/body/Dots/reduce_sum``), outermost first; a scope
    entered again right inside itself counts once, and where the
    compiler writes a nested computation's whole path again behind its
    caller's the path starts over at the outermost scope."""
    out: List[str] = []
    for part in op_name.rstrip(":").split("/"):
        if part not in _SCOPES:
            continue
        if out and part == out[0]:
            out = [part]
        elif not out or out[-1] != part:
            out.append(part)
    return tuple(out)


_HLO_MODULE_RE = re.compile(r"^HloModule ([\w.\-]+)", re.MULTILINE)
_HLO_INSTR_RE = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"", re.MULTILINE)


def hlo_op_names(hlo_text: str) -> Dict[Tuple[str, str], str]:
    """``(module, instruction) -> op_name`` from one program's optimised
    HLO text (``jitted.lower(...).compile().as_text()``): what
    :func:`attribute` joins a device event to where the backend's trace
    names the instruction and leaves its ``op_name`` out (the CPU's)."""
    m = _HLO_MODULE_RE.search(hlo_text)
    module = m.group(1) if m else ""
    return {(module, instr): name
            for instr, name in _HLO_INSTR_RE.findall(hlo_text)}


def _track_names(events: List[dict]) -> Dict[int, str]:
    """pid -> process name from the metadata events."""
    names: Dict[int, str] = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            try:
                names[int(e["pid"])] = str(e.get("args", {}).get("name", ""))
            except (KeyError, TypeError, ValueError):
                _metrics.counter("profile.bad_metadata").inc()
    return names


def _thread_names(events: List[dict]) -> Dict[Tuple[int, int], str]:
    """(pid, tid) -> thread name from the metadata events."""
    names: Dict[Tuple[int, int], str] = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            try:
                names[(int(e["pid"]), int(e["tid"]))] = str(
                    e.get("args", {}).get("name", ""))
            except (KeyError, TypeError, ValueError):
                _metrics.counter("profile.bad_metadata").inc()
    return names


def _self_times(rows: List[dict]) -> None:
    """Set ``self`` on each event of ONE line: its duration less the
    events nested in it (a ``while`` holds its body's operations)."""
    stack: List[dict] = []
    for e in sorted(rows, key=lambda e: (e["ts"], -e["dur"])):
        while stack and stack[-1]["ts"] + stack[-1]["dur"] <= e["ts"]:
            stack.pop()
        e["self"] = e["dur"]
        if stack:
            stack[-1]["self"] -= e["dur"]
        stack.append(e)


def _union(intervals: List[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return out


def attribute(trace: dict, op_names: Optional[Dict[Tuple[str, str], str]]
              = None, source: str = "") -> DeviceAttribution:
    """Read one Chrome trace by scope (module docstring, point 2).

    Device operations are the events of a device process's ``XLA Ops``
    line (a TPU's) and, on any process, the events that name their HLO
    instruction (``args.hlo_op``: the CPU backend runs them on its
    executor threads).  The scope path of one comes from its own
    arguments (:data:`_OP_NAME_ARGS`) or, failing that, from
    ``op_names`` (:func:`hlo_op_names`) by module and instruction.
    Host spans are the ``cup3d:`` annotations on every other line; the
    window runs from the first to the last event of either kind."""
    events = trace["traceEvents"] if isinstance(trace, dict) else trace
    pnames = _track_names(events)
    tnames = _thread_names(events)
    device_pids = {pid for pid, name in pnames.items()
                   if _DEVICE_NAME_RE.search(name)}
    device_pids.add(DEVICE_PID)
    prefix = obs_trace.ANNOTATION_PREFIX

    lines: Dict[Tuple[int, int], List[dict]] = {}
    modules: List[dict] = []
    spans: List[dict] = []
    for e in events:
        dur = e.get("dur")
        if (e.get("ph") != "X" or not isinstance(dur, (int, float))
                or dur < 0 or not isinstance(e.get("name"), str)):
            continue
        key = (e.get("pid"), e.get("tid"))
        args = e.get("args") if isinstance(e.get("args"), dict) else {}
        tname = tnames.get(key, "")
        row = {"name": e["name"], "ts": float(e.get("ts", 0.0)),
               "dur": float(dur), "tid": int(e.get("tid", 0) or 0),
               "args": args, "line": key}
        if key[0] in device_pids:
            if tname == _MODULES_LINE:
                modules.append(row)
            elif tname in ("", _OPS_LINE):
                lines.setdefault(key, []).append(row)
        elif "hlo_op" in args:
            lines.setdefault(key, []).append(row)
        else:
            # the profiler's Chrome export cuts an annotation's name at
            # its first colon and keeps the whole under ``long_name``
            full = args.get("long_name", e["name"])
            if isinstance(full, str) and full.startswith(prefix):
                row["name"] = full
                spans.append(row)

    attr = DeviceAttribution(source=source)
    busy: List[Tuple[float, float]] = []
    modules.sort(key=lambda m: m["ts"])
    module_starts = [m["ts"] for m in modules]
    for rows in lines.values():
        _self_times(rows)
        for e in rows:
            args = e.pop("args")
            op_name = next((args[k] for k in _OP_NAME_ARGS
                            if isinstance(args.get(k), str)
                            and "/" in args[k]), "")
            module = str(args.get("hlo_module", ""))
            if not module and modules:
                # the program whose execution holds the op's midpoint
                mid = e["ts"] + e["dur"] / 2.0
                m = modules[max(bisect_right(module_starts, mid) - 1, 0)]
                if m["ts"] <= mid <= m["ts"] + m["dur"]:
                    module = m["name"]
            if not op_name and op_names:
                op_name = op_names.get(
                    (module, str(args.get("hlo_op", e["name"]))), "")
            path = "/".join(scope_path(op_name))
            ms = e["self"] / 1000.0
            attr.total_ms += ms
            prog = attr.programs.setdefault(module, [0.0, 0.0])
            prog[0] += ms
            if path:
                top = path.split("/", 1)[0]
                attr.paths[path] = attr.paths.get(path, 0.0) + ms
                attr.sections[top] = attr.sections.get(top, 0.0) + ms
            else:
                attr.other_ms += ms
                prog[1] += ms
                attr.other_ops[e["name"]] = (
                    attr.other_ops.get(e["name"], 0.0) + ms)
            e["section"], e["module"] = path or None, module
            attr.events.append(e)
            busy.append((e["ts"], e["ts"] + e["dur"]))

    # the device's idle time, every instant of it to the innermost
    # annotation open at that instant: a gap is cut where spans begin
    # and end
    merged = _union(busy)
    edges = [(r["ts"], r["ts"] + r["dur"]) for r in spans] + busy
    if not edges:
        return attr
    lo, hi = min(a for a, _ in edges), max(b for _, b in edges)
    attr.window_ms = (hi - lo) / 1000.0
    by_line: Dict[Tuple[int, int], List[dict]] = {}
    for r in spans:
        by_line.setdefault(r["line"], []).append(r)
    for rows in by_line.values():
        _self_times(rows)
        for r in rows:
            attr.host[r["name"]] = (attr.host.get(r["name"], 0.0)
                                    + r["self"] / 1000.0)
    spans.sort(key=lambda r: r["dur"])  # innermost first
    cur = lo
    for a, b in merged + [[hi, hi]]:
        if a > cur:
            over = [r for r in spans
                    if r["ts"] < a and r["ts"] + r["dur"] > cur]
            cuts = sorted({cur, a} | {t for r in over
                                      for t in (r["ts"], r["ts"] + r["dur"])
                                      if cur < t < a})
            for c0, c1 in zip(cuts, cuts[1:]):
                mid = 0.5 * (c0 + c1)
                name = next((r["name"] for r in over
                             if r["ts"] <= mid <= r["ts"] + r["dur"]),
                            NO_SPAN)
                attr.gaps[name] = attr.gaps.get(name, 0.0) + (c1 - c0) / 1000.0
        cur = max(cur, b)
    return attr


# -- merge into the host trace ----------------------------------------------


def merge_into_sink(sink: obs_trace.TraceSink, attr: DeviceAttribution,
                    window: Tuple[int, int] = (0, 0)) -> None:
    """Land one window's attribution in the sink: a ``kind="device"``
    JSONL record plus the device ops as pid-:data:`DEVICE_PID` events in
    the Perfetto export.  Device timestamps are shifted so the window
    ENDS at merge time on the sink's epoch — the capture's own clock is
    not the host span clock, so alignment is by window, not by tick."""
    rec = {"kind": "device", "step": int(window[1]),
           "window": [int(window[0]), int(window[1])]}
    rec.update(attr.summary())
    sink.aux(rec)
    if not attr.events:
        return
    now_us = (obs_trace.now() - sink.epoch) * 1e6
    end_us = max(e["ts"] + e["dur"] for e in attr.events)
    offset = now_us - end_us
    sink.events.append({
        "name": "process_name", "ph": "M", "pid": DEVICE_PID, "ts": 0,
        "args": {"name": "device (attributed capture)"},
    })
    for e in attr.events:
        sink.events.append({
            "name": e["name"], "ph": "X", "pid": DEVICE_PID,
            "tid": e["tid"], "ts": e["ts"] + offset, "dur": e["dur"],
            "args": {"section": e["section"] or "other"},
        })


# -- selftest (tools/lint.sh; also the test fixture generator) ---------------


def synthetic_trace() -> dict:
    """A deterministic Chrome trace shaped like a TPU capture of two
    steps: the program's ``cup3d:`` annotations on the host, and on the
    device's ``XLA Ops`` line operations that carry their scope path —
    a solve's ``while`` with its body nested in it, an operation with no
    scope (-> other) — with idle gaps under a blocking read, under a
    section, and between the steps."""
    ev = [
        {"name": "process_name", "ph": "M", "pid": 1, "ts": 0,
         "args": {"name": "/host:CPU"}},
        {"name": "thread_name", "ph": "M", "pid": 1, "tid": 1, "ts": 0,
         "args": {"name": "python"}},
        {"name": "process_name", "ph": "M", "pid": 7, "ts": 0,
         "args": {"name": "/device:TPU:0"}},
        {"name": "thread_name", "ph": "M", "pid": 7, "tid": 2, "ts": 0,
         "args": {"name": "XLA Ops"}},
        {"name": "thread_name", "ph": "M", "pid": 7, "tid": 3, "ts": 0,
         "args": {"name": "XLA Modules"}},
    ]
    def ann(full, ts, dur, **args):
        # as the profiler's Chrome export writes an annotation: the name
        # cut at its first colon, the whole under ``long_name``
        return {"name": full.split(":", 1)[1], "ph": "X", "pid": 1,
                "tid": 1, "ts": ts, "dur": dur,
                "args": {"long_name": full, **args}}

    for k, t0 in enumerate((0.0, 10000.0)):
        ev += [
            ann("cup3d:step", t0, 9000.0, step_num=str(k)),
            ann("cup3d:CreateObstacles", t0 + 100.0, 1900.0),
            ann("cup3d:AdvectionDiffusion", t0 + 2000.0, 200.0),
            ann("cup3d:SyncQoI", t0 + 2500.0, 6400.0),
            ann("cup3d:read:qoi-read", t0 + 2600.0, 6200.0),
            {"name": "jit_step(1)", "ph": "X", "pid": 7, "tid": 3,
             "ts": t0 + 2100.0, "dur": 6500.0},
        ]
        j = "jit(step)/"
        for name, ts, dur, op in (
            ("fusion.1", 2100.0, 900.0, j + "AdvectionDiffusion/add"),
            ("fusion.2", 3000.0, 400.0, j + "AdvectionDiffusion/Halo/pad"),
            ("fusion.3", 3500.0, 300.0,
             j + "PressureProjection/PoissonRHS/div"),
            ("while.4", 3900.0, 3000.0,
             j + "PressureProjection/PoissonSolve/while"),
            ("fusion.5", 4000.0, 1000.0, j + "PressureProjection/"
             "PoissonSolve/while/body/Laplacian/add"),
            ("fusion.6", 5000.0, 1200.0, j + "PressureProjection/"
             "PoissonSolve/while/body/Preconditioner/TileSolve/dot_general"),
            ("reduce.7", 6300.0, 500.0, j + "PressureProjection/"
             "PoissonSolve/while/body/Dots/reduce_sum"),
            ("fusion.8", 7000.0, 600.0,
             j + "PressureProjection/Gradient/sub"),
            ("copy.9", 8000.0, 600.0, ""),
        ):
            ev.append({"name": name, "ph": "X", "pid": 7, "tid": 2,
                       "ts": t0 + ts, "dur": dur,
                       "args": {"tf_op": op} if op else {}})
    return {"traceEvents": ev, "displayTimeUnit": "ms"}


def write_synthetic_capture(path: str) -> str:
    """Write the synthetic trace as a gzipped capture file (the checked-
    in tests/data fixture and the selftest round trip use this)."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    blob = json.dumps(synthetic_trace()).encode()
    # mtime=0 + empty FNAME: byte-identical output for the checked-in
    # fixture regardless of where or when it is regenerated
    with open(path, "wb") as raw:
        with gzip.GzipFile(filename="", fileobj=raw, mode="wb",
                           mtime=0) as f:
            f.write(blob)
    return path


def selftest() -> None:
    """Synthetic capture -> parse -> attribute -> merged export, all
    invariants asserted (CI via tools/lint.sh; no TPU, no sim)."""
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        cap = write_synthetic_capture(
            os.path.join(td, "plugins", "profile", "run",
                         "host.trace.json.gz"))
        found = find_trace_files(td)
        assert found == [cap], found
        attr = attribute(load_chrome_trace(cap), source=cap)
        assert set(attr.sections) == {"AdvectionDiffusion",
                                      "PressureProjection"}, attr.sections
        assert attr.other_ms > 0, "an op with no scope must go to other"
        total = sum(attr.sections.values()) + attr.other_ms
        assert abs(total - attr.total_ms) < 1e-9, (total, attr.total_ms)
        # busy + idle = the window; the wait under the read is the read's
        idle = sum(attr.gaps.values())
        assert abs(attr.total_ms + idle - attr.window_ms) < 1e-9
        assert attr.gaps["cup3d:CreateObstacles"] > 0, attr.gaps
        assert attr.gaps["cup3d:read:qoi-read"] > 0, attr.gaps
        assert attr.gaps[NO_SPAN] > 0, attr.gaps
        # capture-window cadence on injected start/stop
        calls: List[str] = []
        sink = obs_trace.TraceSink(enabled=True, directory=td)
        ctl = CaptureController(
            plan="every:4", directory=td, window_steps=2, sink=sink,
            start_fn=lambda d: calls.append("start"),
            stop_fn=lambda: calls.append("stop"),
        )
        for s in range(12):
            ctl.on_step(s)
        assert ctl.windows == 2 and calls == ["start", "stop"] * 2, (
            ctl.windows, calls)
        # merged export: device events + aux record validate
        merge_into_sink(sink, attr, window=(4, 6))
        dev = [e for e in sink.events
               if e.get("pid") == DEVICE_PID and e.get("ph") == "X"]
        assert len(dev) == len(attr.events), (len(dev), len(attr.events))
        assert all("section" in e["args"] for e in dev)
        sink.close()
        with open(sink.jsonl_path) as f:
            recs = [json.loads(x) for x in f if x.strip()]
        assert len(recs) == 1 and recs[0]["kind"] == "device", recs
        problems = obs_trace.validate_step_record(recs[0])
        assert not problems, problems
    print("profile selftest: OK")


if __name__ == "__main__":
    import sys

    if "--selftest" in sys.argv:
        selftest()
    elif len(sys.argv) > 1:
        # a capture, then the optimised HLO texts of the programs it ran
        # (only where the backend's trace leaves the op_name out)
        joined: Dict[Tuple[str, str], str] = {}
        for path in sys.argv[2:]:
            with open(path) as f:
                joined.update(hlo_op_names(f.read()))
        a = attribute(load_chrome_trace(sys.argv[1]), op_names=joined,
                      source=sys.argv[1])
        print(json.dumps(a.summary(), indent=1))
    else:
        print(__doc__)
