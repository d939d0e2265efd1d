"""Per-executable XLA cost/memory accounting + the device peak table
(ISSUE 15, round 19).

Two jobs, both grounded in what the COMPILER says rather than what we
typed by hand:

1. **Device peak table** — every roofline in ``bench.py`` used to
   divide by hard-coded ``197e12`` / ``819e9`` (TPU v5e bf16 FLOP/s and
   HBM B/s) no matter what hardware actually ran, so MFU/HBM fractions
   silently lied on anything that wasn't a v5e.  :func:`device_peaks`
   resolves the live backend's ``device_kind`` against
   :data:`PEAK_TABLE` (public spec-sheet numbers, provenance in the
   table).  A TPU kind the table does not know raises, and off the TPU
   there is no ceiling at all (``None``): a CPU run reports its
   roofline shares as not measured, never against v5e numbers.  Lint
   rule JX017 keeps new hand-typed peaks out of roofline/bench paths;
   this module is the one sanctioned home for the literals.

2. **Cost/memory harvest** — :func:`harvest_compiled` pulls
   ``compiled.cost_analysis()`` (flops, bytes accessed) and
   ``compiled.memory_analysis()`` (argument/output/temp HBM) off an XLA
   executable; :func:`analyze_jitted` does the AOT
   ``lower(...).compile()`` dance for a jitted callable.  Availability
   is per-backend and per-version: every probe is guarded, failures are
   COUNTED (``costs.unavailable{what=...}``), never raised, and the row
   says what it could and couldn't get.  Rows land in :data:`_ROWS`
   (scrapeable via gauges ``xla.flops{executable=}`` /
   ``xla.bytes_accessed{executable=}`` / ``xla.peak_bytes{executable=}``)
   and ``bench.py`` appends them to the perfwatch history store, so a
   compile that doubles HBM traffic fails the history gate even when
   wall-clock noise hides it.

:func:`memory_watermarks` additionally samples
``device.memory_stats()`` into ``hbm.peak_bytes{device=}`` /
``hbm.bytes_in_use{device=}`` gauges (TPU backends report them; CPU
returns None — counted, skipped).

Hot-path rule (PR 9): nothing here runs per step.  Harvest happens at
bind/bench time (AOT lowering executes nothing and syncs nothing);
watermark sampling reads host-side allocator stats.  The module
imports neither jax nor numpy at module scope — jax is lazy so
import-light obs consumers stay import-light.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Optional

from cup3d_tpu.obs import metrics as _metrics


@dataclass(frozen=True)
class DevicePeaks:
    """One device kind's advertised ceilings (the roofline denominators)."""

    kind: str
    bf16_flops: float        # dense bf16 peak, FLOP/s per chip
    hbm_bytes_per_s: float   # HBM bandwidth, B/s per chip
    note: str = ""

    def as_dict(self) -> dict:
        return {"kind": self.kind, "bf16_flops": self.bf16_flops,
                "hbm_bytes_per_s": self.hbm_bytes_per_s,
                "note": self.note}


#: public spec-sheet peaks per ``device_kind`` substring (cloud.google
#: .com/tpu/docs/system-architecture-tpu-vm, v4/v5e/v5p/v6e pages).
#: Matching is normalized-substring (``"TPU v5 lite"`` -> v5e): order
#: matters, most specific first.
PEAK_TABLE = (
    DevicePeaks("TPU v6e", 918e12, 1640e9,
                note="Trillium: 918 TFLOP/s bf16, 1640 GB/s HBM"),
    DevicePeaks("TPU v5p", 459e12, 2765e9,
                note="459 TFLOP/s bf16, 2765 GB/s HBM"),
    DevicePeaks("TPU v5e", 197e12, 819e9,
                note="v5 lite: 197 TFLOP/s bf16, 819 GB/s HBM"),
    DevicePeaks("TPU v4", 275e12, 1228e9,
                note="275 TFLOP/s bf16, 1228 GB/s HBM"),
)

_KIND_ALIASES = {
    "tpu v5 lite": "TPU v5e",
    "tpu v5litepod": "TPU v5e",
    "tpu v6 lite": "TPU v6e",
}


def peaks_for_kind(kind: str) -> DevicePeaks:
    """Resolve a ``device_kind`` string against :data:`PEAK_TABLE`
    (normalized substring match, v5-lite aliases folded in).  A kind
    the table does not know is an error, not a default: a roofline
    against another chip's ceilings is a wrong number."""
    norm = str(kind).strip().lower()
    norm = _KIND_ALIASES.get(norm, norm).lower()
    for peaks in PEAK_TABLE:
        if peaks.kind.lower() in norm or norm in peaks.kind.lower():
            return peaks
    raise KeyError(
        f"device_kind {kind!r} has no entry in obs.costs.PEAK_TABLE: add "
        f"its published peaks (with their source) before reporting a "
        f"roofline on it")


def device_peaks(device=None) -> Optional[DevicePeaks]:
    """The live backend's peaks (``jax.devices()[0]`` unless a device
    is passed).  None off the TPU — a CPU run has no device ceiling,
    and its roofline fields read "not measured"; an unknown TPU
    ``device_kind`` raises (:func:`peaks_for_kind`)."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    return peaks_for_kind(device.device_kind)


# -- per-executable harvest --------------------------------------------------

#: name -> harvested row; append-only per process (re-harvest of the
#: same name overwrites — the newest compile wins)
_ROWS: Dict[str, dict] = {}


def _cost_analysis(compiled) -> Optional[dict]:
    """``compiled.cost_analysis()`` normalized to one flat dict (older
    jax returns a one-element list); None when the backend can't."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        _metrics.counter("costs.unavailable", what="cost_analysis").inc()
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        _metrics.counter("costs.unavailable", what="cost_analysis").inc()
        return None
    return ca


def _memory_analysis(compiled) -> Optional[object]:
    try:
        return compiled.memory_analysis()
    except Exception:
        _metrics.counter("costs.unavailable",
                         what="memory_analysis").inc()
        return None


def harvest_compiled(name: str, compiled) -> dict:
    """Harvest one XLA executable's compiler-counted cost/memory row.

    Always returns a row; the ``available`` sub-dict says which halves
    the backend actually produced.  ``peak_bytes`` is the static HBM
    footprint bound argument+output+temp (XLA's CompiledMemoryStats);
    live allocator watermarks come from :func:`memory_watermarks`."""
    row = {"name": str(name), "flops": None, "bytes_accessed": None,
           "argument_bytes": None, "output_bytes": None,
           "temp_bytes": None, "alias_bytes": None,
           "generated_code_bytes": None, "peak_bytes": None,
           "available": {"cost": False, "memory": False}}
    ca = _cost_analysis(compiled)
    if ca is not None:
        row["available"]["cost"] = True
        flops = ca.get("flops")
        nbytes = ca.get("bytes accessed")
        row["flops"] = float(flops) if flops is not None else None
        row["bytes_accessed"] = (
            float(nbytes) if nbytes is not None else None)
    ma = _memory_analysis(compiled)
    if ma is not None:
        try:
            arg = int(ma.argument_size_in_bytes)
            out = int(ma.output_size_in_bytes)
            tmp = int(ma.temp_size_in_bytes)
            row.update(
                argument_bytes=arg, output_bytes=out, temp_bytes=tmp,
                alias_bytes=int(ma.alias_size_in_bytes),
                generated_code_bytes=int(ma.generated_code_size_in_bytes),
                peak_bytes=arg + out + tmp,
            )
            row["available"]["memory"] = True
        except Exception:
            _metrics.counter("costs.unavailable",
                             what="memory_analysis").inc()
    _ROWS[row["name"]] = row
    if row["flops"] is not None:
        _metrics.gauge("xla.flops", executable=name).set(row["flops"])
    if row["bytes_accessed"] is not None:
        _metrics.gauge("xla.bytes_accessed",
                       executable=name).set(row["bytes_accessed"])
    if row["peak_bytes"] is not None:
        _metrics.gauge("xla.peak_bytes",
                       executable=name).set(float(row["peak_bytes"]))
    _metrics.counter("costs.harvests").inc()
    return row


def analyze_jitted(name: str, jitted, *args, **kwargs) -> Optional[dict]:
    """AOT-lower and compile ``jitted`` on ``args`` and harvest the
    executable's cost row.  Off the hot path by design: lowering
    executes nothing (no device sync, no donation — safe on functions
    with ``donate_argnums``), compiling costs one compile.  Returns
    None (counted) when the backend can't lower/compile here.

    Round 21 fix: a store-backed executable (aot/store.py) already
    holds — or knows how to load — its compiled object; harvesting
    through ``ensure_compiled`` reuses it instead of paying a
    duplicate lower+compile of a twin."""
    ensure = getattr(jitted, "ensure_compiled", None)
    if ensure is not None:
        try:
            compiled = ensure(*args, **kwargs)
        except Exception:
            compiled = None
        if compiled is not None:
            return harvest_compiled(name, compiled)
        # fallback state: harvest the plain jitted twin below
        jitted = getattr(jitted, "jitted", jitted)
    try:
        compiled = jitted.lower(*args, **kwargs).compile()
    except Exception:
        _metrics.counter("costs.unavailable", what="lower").inc()
        return None
    return harvest_compiled(name, compiled)


def rows() -> Dict[str, dict]:
    """Every harvested row this process, by executable name (copies)."""
    return {k: dict(v, available=dict(v["available"]))
            for k, v in _ROWS.items()}


def enabled() -> bool:
    """``CUP3D_COSTS=1`` arms the bind-point harvest in
    ``parallel/forest.py`` (one extra AOT compile per bound
    executable); bench/tests call :func:`analyze_jitted` explicitly."""
    return os.environ.get("CUP3D_COSTS", "0") not in ("0", "")


def harvest_on_first_call(jitted, name: str):
    """Wrap a jitted callable so its FIRST invocation also harvests the
    cost row (AOT lower+compile on the live operands, then the normal
    call).  Used by the forest/fleet bind points when
    :func:`enabled`; the steady-state path after the first call is the
    raw jitted function (the wrapper uninstalls itself logically via a
    flag — one bool test per call, no device work ever).

    The harvest runs BEFORE the wrapped call (lowering never donates,
    so the operands are still live); for a store-backed executable
    :func:`analyze_jitted` routes through its already-materialized
    compiled object, so the first call pays zero extra compiles."""
    state = {"done": False}

    def wrapper(*args, **kwargs):
        if not state["done"]:
            state["done"] = True
            analyze_jitted(name, jitted, *args, **kwargs)
        return jitted(*args, **kwargs)

    wrapper.__name__ = getattr(jitted, "__name__", name)
    wrapper.__wrapped__ = jitted
    wrapper.lower = getattr(jitted, "lower", None)
    return wrapper


# -- live allocator watermarks ----------------------------------------------

def memory_watermarks() -> Dict[str, dict]:
    """Sample ``device.memory_stats()`` on every local device into
    ``hbm.peak_bytes{device=}`` / ``hbm.bytes_in_use{device=}`` gauges.
    TPU/GPU backends report allocator stats; CPU returns None — both
    counted, never raised.  Returns {device_label: stats_subset}."""
    out: Dict[str, dict] = {}
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        _metrics.counter("costs.unavailable", what="devices").inc()
        return out
    for d in devices:
        label = f"{d.platform}:{d.id}"
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            _metrics.counter("costs.unavailable",
                             what="memory_stats").inc()
            continue
        sub = {}
        peak = stats.get("peak_bytes_in_use")
        in_use = stats.get("bytes_in_use")
        limit = stats.get("bytes_limit")
        if peak is not None:
            sub["peak_bytes_in_use"] = int(peak)
            _metrics.gauge("hbm.peak_bytes", device=label).set(float(peak))
        if in_use is not None:
            sub["bytes_in_use"] = int(in_use)
            _metrics.gauge("hbm.bytes_in_use",
                           device=label).set(float(in_use))
        if limit is not None:
            sub["bytes_limit"] = int(limit)
        if sub:
            out[label] = sub
    return out
