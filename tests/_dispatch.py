"""Programs executed and uploads made inside the program's own spans of
a call, counted the way the benchmark's traced runs see them: a profiler
trace at host tracer level 2 (``benchmarks/lib/trace_reduce.start``), the
executed programs (``PjRtCpuExecutable::Execute``) and the uploads
(``DevicePut*``) between the start and the end of each ``cup3d:``
annotation — the profiler sections, steps and blocking reads the program
writes into every trace (``cup3d_tpu/obs/trace.py``), and the spans a
test adds around a call of its own (``span``).  A count of dispatches
does not depend on the platform."""

import glob
import os

import jax

from benchmarks.lib import trace_reduce
from cup3d_tpu.obs import trace as obs_trace

PREFIX = obs_trace.ANNOTATION_PREFIX


def span(name):
    """One more annotation of the program's kind, around a test's call."""
    return obs_trace.annotate(PREFIX + name)


def advance_dispatches(sim, directory):
    """``dispatches`` of one more ``calc_max_timestep`` + ``advance`` of a
    driver: the call is the span ``advance``, every profiler section of
    it a span of its own name, each blocking read ``read:<site>``."""

    def one_more_advance():
        dt = sim.calc_max_timestep()
        with span("advance"):
            sim.advance(dt)
        jax.block_until_ready(sim.state["vel"])

    return dispatches(one_more_advance, directory)


def dispatches(run, directory):
    """{span: (programs executed, uploads)} of one ``run()``, which ends
    with the device idle.  A name that opens more than once (a section
    of several steps) holds the sum over its spans."""
    from jax.profiler import ProfileData

    trace_reduce.start(directory)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    spans, programs, uploads = [], [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name[len(PREFIX):]))
                elif e.name == "PjRtCpuExecutable::Execute":
                    programs.append(e.start_ns)
                elif e.name.startswith("DevicePut"):  # ...WithSharding
                    uploads.append(e.start_ns)
    assert programs and uploads, "the trace names its events otherwise"
    counts = {}
    for a, b, name in spans:
        p, u = counts.get(name, (0, 0))
        counts[name] = (p + sum(a <= t < b for t in programs),
                        u + sum(a <= t < b for t in uploads))
    return counts
