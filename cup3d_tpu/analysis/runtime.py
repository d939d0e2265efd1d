"""Runtime sanitizers: the sound half of the analysis subsystem.

The AST lint (``analysis/lint``) is a precision-first heuristic; these
context managers check the same invariants at runtime, where device
placement is known exactly:

- :class:`RecompileCounter` — intercepts ``jax.jit`` so every jitted
  function created inside the context reports its compile count.  The
  steady-state contract (VALIDATION.md "Analysis subsystem") is that the
  step compiles EXACTLY ONCE per configuration: dt/lambda ride as traced
  scalars, so a second compile of the same function means a shape or
  dtype is leaking into the trace.
- :func:`no_implicit_transfers` — ``jax.transfer_guard("disallow")``
  scoped to a hot loop.  Every implicit device<->host transfer raises
  unless it happens inside :func:`sanctioned_transfer`, the allowlist
  hook that names the designed sync points (``umax-read``,
  ``qoi-read``, ``scalar-upload``, ...).  Sanctioned sites are recorded
  in :data:`TRANSFER_SITES` so tests can assert the allowlist is closed.
- :func:`blocking_read` — the one seam the designed blocking
  device-to-host reads go through (``qoi-read``, ``moments-read``,
  ``umax-read``, ``tags-read``, ``stream-read``): a sanctioned site that
  also keeps
  time (``transfers.wait_s`` / ``transfers.copy_s``) and writes itself
  into the profiler's trace.
- :func:`debug_nans` / :func:`tracer_leak_checks` — opt-in wrappers over
  the jax debug flags, scoped instead of global.

Typical use (tests/test_analysis.py runs exactly this)::

    with RecompileCounter() as rc:
        sim = Simulation(cfg); sim.init()
        with no_implicit_transfers():
            for _ in range(5):
                sim.advance(sim.calc_max_timestep())
    rc.assert_steady_state()
"""

from __future__ import annotations

import threading
from contextlib import contextmanager, nullcontext
from typing import Callable, Dict, Iterable, Optional, Set

#: every sanctioned transfer site that has EVER fired in this process:
#: tag -> fire count.  The documented allowlist lives in VALIDATION.md;
#: tests assert observed tags are a subset of it.  Mirrored into the obs
#: registry as ``transfers.sanctioned{site=tag}`` counters (round 9) so
#: one metrics snapshot carries the transfer picture too.
TRANSFER_SITES: Dict[str, int] = {}

_local = threading.local()


def _allowed_tags() -> Optional[Set[str]]:
    """None = no restriction (every sanctioned site may open the guard)."""
    return getattr(_local, "allowed_tags", None)


@contextmanager
def no_implicit_transfers(allow: Optional[Iterable[str]] = None):
    """Run the body under ``jax.transfer_guard("disallow")``: any device
    sync or host upload OUTSIDE a :func:`sanctioned_transfer` block
    raises immediately, with a traceback pointing at the hidden sync —
    the runtime teeth behind lint rule JX001.

    ``allow`` restricts which sanctioned tags may open the guard while
    this context is active (the allowlist hook); ``None`` admits every
    sanctioned site.  Unknown tags raise at the offending site, not
    here, so the failure names the call stack that transferred.
    """
    import jax

    prev = _allowed_tags()
    _local.allowed_tags = set(allow) if allow is not None else None
    try:
        with jax.transfer_guard("disallow"):
            yield
    finally:
        _local.allowed_tags = prev


@contextmanager
def sanctioned_transfer(tag: str):
    """Mark a DESIGNED sync point: re-allows transfers for the body and
    records the site under ``tag``.  Outside :func:`no_implicit_transfers`
    this costs one thread-local check and a counter bump (the guard
    context itself is cheap, but we skip it entirely when jax is not
    imported yet so import-light paths stay import-light)."""
    allowed = _allowed_tags()
    if allowed is not None and tag not in allowed:
        raise RuntimeError(
            f"transfer site {tag!r} is not in the active allowlist "
            f"{sorted(allowed)}; either the hot loop grew a new sync "
            "point (fix it) or the allowlist in the caller is stale"
        )
    TRANSFER_SITES[tag] = TRANSFER_SITES.get(tag, 0) + 1
    from cup3d_tpu.obs import metrics as obs_metrics

    obs_metrics.counter("transfers.sanctioned", site=tag).inc()
    import sys

    jax = sys.modules.get("jax")
    ctx = jax.transfer_guard("allow") if jax is not None else nullcontext()
    with ctx:
        yield


def blocking_read(site: str, x, dtype=None):
    """THE seam of every designed blocking device-to-host read: starts
    the host copy of ``x`` (an array, or a tuple of arrays read at one
    visit), waits for ``x``, then takes it as NumPy
    (``np.asarray(leaf, dtype)``), inside
    :func:`sanctioned_transfer` and under the profiler annotation
    ``cup3d:read:<site>``.  Beside the visit count it raises
    ``transfers.wait_s{site=…}`` by the wait — the device's unfinished
    work in front of the value, the host's idle time — and
    ``transfers.copy_s{site=…}`` by the copy.  What ``x`` is computed
    from is dispatched before the call, so the wait holds no dispatch."""
    import jax
    import numpy as np

    from cup3d_tpu.obs import metrics as obs_metrics
    from cup3d_tpu.obs import trace as obs_trace

    with sanctioned_transfer(site), \
            obs_trace.annotate(obs_trace.ANNOTATION_PREFIX + "read:" + site):
        # jax-lint: allow(JX008, the wait/copy split of a blocking read
        # is this seam's own counter pair: the spans it opens are the
        # annotation above and the caller's profiler section)
        t0 = obs_trace.now()
        # the copy queued behind the compute, as a bare ``np.asarray``
        # queues it: waiting first and asking for the copy after costs
        # one more round trip between host and device a read
        for leaf in jax.tree_util.tree_leaves(x):
            start = getattr(leaf, "copy_to_host_async", None)
            if start is not None:
                start()
        jax.block_until_ready(x)
        t1 = obs_trace.now()
        out = jax.tree_util.tree_map(lambda a: np.asarray(a, dtype), x)
        t2 = obs_trace.now()
    obs_metrics.counter("transfers.wait_s", site=site).inc(t1 - t0)
    obs_metrics.counter("transfers.copy_s", site=site).inc(t2 - t1)
    return out


class RecompileCounter:
    """Counts XLA compiles per jitted function.

    Entering the context monkeypatches ``jax.jit`` so every jit-wrapped
    function CREATED inside it is instrumented: each call compares the
    pjit cache size before and after, attributing cache growth to that
    function's name.  Functions jitted before the context opened (e.g.
    module-level ``@jax.jit`` decorations bound at import) are not
    counted — drivers construct their jits at __init__ time, so building
    the driver inside the context captures the full step.

    ``compiles`` maps function name -> number of distinct compiled
    specializations observed.  ``assert_steady_state()`` enforces the
    contract: every function compiled at most ``budget`` times (default
    1 — one trace per config, dt as a traced scalar)."""

    def __init__(self) -> None:
        self.compiles: Dict[str, int] = {}
        self.calls: Dict[str, int] = {}
        self._real_jit = None

    # -- counting ----------------------------------------------------------

    def _instrument(self, jitted, name: str):
        counter = self

        def wrapper(*args, **kwargs):
            try:
                before = jitted._cache_size()
            except Exception:
                before = None
            out = jitted(*args, **kwargs)
            counter.calls[name] = counter.calls.get(name, 0) + 1
            if before is not None:
                try:
                    grew = jitted._cache_size() - before
                except Exception:
                    grew = 0
                if grew > 0:
                    counter.compiles[name] = (
                        counter.compiles.get(name, 0) + grew
                    )
                    # compile events are rare by contract: mirror them
                    # into the obs registry (round 9) so a metrics
                    # snapshot answers "did anything retrace?"
                    from cup3d_tpu.obs import metrics as obs_metrics

                    obs_metrics.counter("jit.compiles", fn=name).inc(grew)
            return out

        wrapper.__name__ = f"counted({name})"
        wrapper.__wrapped__ = jitted
        # AOT/introspection passthrough for the odd caller that needs it
        wrapper.lower = getattr(jitted, "lower", None)
        wrapper._cache_size = getattr(jitted, "_cache_size", None)
        return wrapper

    def wrap(self, jitted, name: Optional[str] = None):
        """Instrument an existing jitted function explicitly."""
        return self._instrument(
            jitted, name or getattr(jitted, "__name__", repr(jitted))
        )

    # -- context -----------------------------------------------------------

    def __enter__(self) -> "RecompileCounter":
        import jax

        self._real_jit = jax.jit
        counter = self
        real = self._real_jit

        def counting_jit(fun=None, **kwargs):
            if fun is None:
                return lambda f: counting_jit(f, **kwargs)
            name = getattr(fun, "__name__", None)
            if name in (None, "<lambda>"):
                # partial(f, ...) and lambdas: dig for something stable
                inner = getattr(fun, "func", None)
                name = getattr(inner, "__name__", name) or repr(fun)
            return counter._instrument(real(fun, **kwargs), name)

        jax.jit = counting_jit
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.jit = self._real_jit
        self._real_jit = None

    # -- assertions --------------------------------------------------------

    @property
    def total_compiles(self) -> int:
        return sum(self.compiles.values())

    def assert_steady_state(self, budget: int = 1,
                            ignore: Iterable[str] = ()) -> None:
        """Every instrumented function compiled at most ``budget`` times.
        A failure names the offender — the usual cause is a Python scalar
        or shape reaching the trace as a fresh constant each step."""
        skip = set(ignore)
        bad = {
            name: n for name, n in self.compiles.items()
            if n > budget and name not in skip
        }
        if bad:
            raise AssertionError(
                f"steady-state recompile budget ({budget}) exceeded: "
                f"{bad} (calls: { {k: self.calls.get(k) for k in bad} })"
            )


@contextmanager
def debug_nans(enabled: bool = True):
    """Scoped ``jax_debug_nans``: every jitted op re-checks its output
    and raises AT the producing primitive instead of propagating NaNs
    into the abort path N steps later.  Opt-in: it disables fusion-level
    performance, so never leave it on in production loops."""
    import jax

    if not enabled:
        yield
        return
    old = jax.config.jax_debug_nans
    jax.config.update("jax_debug_nans", True)
    try:
        yield
    finally:
        jax.config.update("jax_debug_nans", old)


@contextmanager
def tracer_leak_checks(enabled: bool = True):
    """Scoped ``jax_check_tracer_leaks``: a traced value escaping its
    transform (stashed on self, closed over by a callback) raises at the
    leak site instead of surfacing later as an opaque
    UnexpectedTracerError."""
    import jax

    if not enabled:
        yield
        return
    old = jax.config.jax_check_tracer_leaks
    jax.config.update("jax_check_tracer_leaks", True)
    try:
        yield
    finally:
        jax.config.update("jax_check_tracer_leaks", old)


def device_scalar(value, dtype, tag: str = "scalar-upload"):
    """Upload one host scalar through a sanctioned site and return the
    device array.  Hot loops use this for the per-step dt so the upload
    is the ONLY host->device traffic the step pays — and the transfer
    guard can prove it."""
    import jax.numpy as jnp

    with sanctioned_transfer(tag):
        return jnp.asarray(value, dtype)
