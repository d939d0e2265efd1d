"""Unified telemetry (ISSUE 4): metrics registry, span traces, flight
recorder — the repo's cross-cutting nervous system.

- :mod:`cup3d_tpu.obs.metrics` — process-global counters / gauges /
  histograms with labels; ``snapshot()``/``delta()``/``reset()``; the
  stream data-plane, the analysis sanitizers, the bucket caches, and
  the solvers all report here.  Host scalars only: the hot path never
  syncs a device value for telemetry.
- :mod:`cup3d_tpu.obs.trace` — nested span timing (the engine behind
  ``io/logging.py``'s Profiler shim), per-step structured JSONL records
  (``CUP3D_TRACE=1`` -> ``trace.jsonl``), Chrome trace-event export
  (``trace.pfto.json``, Perfetto-loadable); every section, step and
  blocking read is also a ``jax.profiler`` annotation ``cup3d:…``,
  always (``annotate``).
- :mod:`cup3d_tpu.obs.flight` — fixed-size ring of recent step records
  + solver residual history; dumps a self-contained postmortem JSON on
  NaN/Inf velocity, dt collapse, or a Poisson solve at its iteration
  cap.

Observability v2 (ISSUE 9) — the device half:

- :mod:`cup3d_tpu.obs.profile` — programmatic ``jax.profiler`` capture
  windows (``CUP3D_PROFILE=every:N``) + the trace-event parser that
  puts device time down to the operator scopes in each op's ``op_name``
  and the device's idle gaps to the host's ``cup3d:`` annotations, and
  merges both into the step-trace JSONL and Perfetto export.
- :mod:`cup3d_tpu.obs.export` — zero-dependency background HTTP
  exporter: ``/metrics`` (Prometheus text from the registry snapshot)
  and ``/health`` (flight-recorder arm state, last-known-good step,
  recovery counters).  ``CUP3D_METRICS_PORT`` enables.
- :mod:`cup3d_tpu.obs.history` — append-only JSONL bench-history store
  with rolling-median regression detection (``tools/perfwatch.py``).

See README "Observability" / "Observability v2" for the metric catalog
and trace schema, and VALIDATION.md rounds 9 and 13 for the pinned
contracts.
"""

from cup3d_tpu.obs import (  # noqa: F401
    export,
    flight,
    history,
    metrics,
    profile,
    trace,
)
