"""From a profiler trace (``*.xplane.pb``) to the numbers the benchmark
reports: device busy time, idle share, the device operations that took
most time, and the idle gaps named by the harness span that covers them.

Read with ``jax.profiler.ProfileData`` alone.  Device planes are the
planes whose name matches ``DEVICE_PLANE``; their ``XLA Ops`` line holds
one event per executed HLO operation (nested: a ``while`` holds its
body's operations), their ``XLA Modules`` line one event per executed
program.  Host spans are the ``TraceAnnotation`` events the harness
wrote, found by their name prefix on any other plane.  All planes share
one clock.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def start(directory):
    """Start the profiler the way every traced run does: device and host
    tracers on, Python's own tracer off (it writes an event per Python
    call, slows the host and makes the trace a hundred times larger)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=options)


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[..] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:80]


def union(intervals):
    """Merged, sorted list of (start, end)."""
    out = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals):
    return sum(b - a for a, b in intervals)


def gaps(busy, lo, hi):
    """Complement of a merged busy list inside [lo, hi]."""
    out, cur = [], lo
    for a, b in busy:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        out.append((cur, hi))
    return out


def self_times(events):
    """{name: seconds} with each nested event's time taken out of its
    parent: ``events`` are (start, end, name) on one line."""
    out = {}
    stack = []  # (end, name, child_time, start)

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, child, start = stack.pop()
            out[name] = out.get(name, 0.0) + (end - start) - child
            if stack:
                e, n, c, s = stack[-1]
                stack[-1] = (e, n, c + (end - start), s)

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        close(start)
        stack.append((end, name, 0.0, start))
    close(float("inf"))
    return out


def read_planes(path):
    """(device planes, host spans): device planes as
    ``{plane: {line: [(start_s, end_s, name)]}}``, host spans as
    ``[(start_s, end_s, name)]`` of every event on a non-device plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            rows = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                     e.name) for e in line.events]
            if is_dev:
                devices.setdefault(plane.name, {})[line.name] = rows
            else:
                host.extend(rows)
    return devices, host


def describe(path, per_line=5):
    """Planes, lines and a few events of each: for the look by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            out.append({"plane": plane.name, "line": line.name,
                        "events": len(evs),
                        "first": [[e.name[:80], e.start_ns, e.duration_ns]
                                  for e in evs[:per_line]]})
    return out


def reduce(devices, host, window, span_prefix, modules=()):
    spans = [s for s in host if s[2].startswith(span_prefix)]
    wins = [s for s in spans if s[2] == window]
    if not wins or not devices:
        return None
    lo, hi = wins[0][0], wins[0][1]
    inner = [s for s in spans if s[2] != window]
    busy_s, ops, gap_by = [], {}, {}
    module_s = {m: 0.0 for m in modules}
    module_n = {m: 0 for m in modules}
    for lines in devices.values():
        rows = lines.get(OPS_LINE, [])
        busy = union(clip([(a, b) for a, b, _ in rows], lo, hi))
        busy_s.append(total(busy))
        in_win = [(max(a, lo), min(b, hi), n) for a, b, n in rows
                  if min(b, hi) > max(a, lo)]
        for name, t in self_times(in_win).items():
            name = op_name(name)
            ops[name] = ops.get(name, 0.0) + t
        for a, b in gaps(busy, lo, hi):
            # the span that covers most of the gap; of several that
            # cover it alike (nested spans), the shortest
            best, key = "no harness span", (0.0, 0.0)
            for s0, s1, name in inner:
                c = min(b, s1) - max(a, s0)
                if c > 0 and (c, s0 - s1) > key:
                    best, key = name, (c, s0 - s1)
            gap_by[best] = gap_by.get(best, 0.0) + (b - a)
        for a, b, name in lines.get(MODULES_LINE, []):
            for m in modules:
                if m in name:
                    module_s[m] += b - a
                    module_n[m] += 1
    n = len(devices)
    top = lambda d: [[k, v / n] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"window_s": hi - lo, "busy_s": sum(busy_s) / n,
            "top_ops": top(ops), "top_gaps": top(gap_by),
            "module_s": {m: v / n for m, v in module_s.items()},
            "module_runs": {m: v // n for m, v in module_n.items()},
            "device_planes": sorted(devices)}


def reduce_dir(directory, window, span_prefix, modules=()):
    paths = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    devices, host = read_planes(paths[-1])
    return reduce(devices, host, window, span_prefix, modules)
