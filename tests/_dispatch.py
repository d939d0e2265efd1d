"""Programs executed and uploads made inside named spans of a call,
counted the way the benchmark's traced runs see them: a profiler trace
at host tracer level 2 (``benchmarks/lib/trace_reduce.start``), the
executed programs (``PjRtCpuExecutable::Execute``) and the uploads
(``DevicePut*``) between the start and the end of each
``TraceAnnotation`` whose name starts with ``op:``.  A count of
dispatches does not depend on the platform."""

import glob
import os
from contextlib import contextmanager

import jax

from benchmarks.lib import trace_reduce


def span(name):
    """The annotation ``dispatches`` counts under ``name``."""
    return jax.profiler.TraceAnnotation("op:" + name)


class SpannedProfiler:
    """A driver's profiler whose every section is also a counted span."""

    def __init__(self, profiler):
        self._profiler = profiler

    @contextmanager
    def __call__(self, name):
        with span(name), self._profiler(name):
            yield

    def __getattr__(self, name):
        return getattr(self._profiler, name)


def advance_dispatches(sim, directory):
    """``dispatches`` of one more ``calc_max_timestep`` + ``advance`` of a
    driver, the call a span ``advance`` and every profiler section of it
    a span of its own name."""
    profiler = sim.profiler
    sim.profiler = SpannedProfiler(profiler)

    def one_more_advance():
        dt = sim.calc_max_timestep()
        with span("advance"):
            sim.advance(dt)
        jax.block_until_ready(sim.state["vel"])

    try:
        return dispatches(one_more_advance, directory)
    finally:
        sim.profiler = profiler


def dispatches(run, directory):
    """{span: (programs executed, uploads)} of one ``run()``, which ends
    with the device idle."""
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
                if e.name.startswith("op:"):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name[3:]))
                elif e.name == "PjRtCpuExecutable::Execute":
                    programs.append(e.start_ns)
                elif e.name.startswith("DevicePut"):  # ...WithSharding
                    uploads.append(e.start_ns)
    assert programs and uploads, "the trace names its events otherwise"
    return {name: (sum(a <= t < b for t in programs),
                   sum(a <= t < b for t in uploads))
            for a, b, name in spans}
