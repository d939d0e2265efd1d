"""Append-only bench-history store + rolling-baseline regression
detection (ISSUE 9).

``bench.py`` appends its COMPLETE summary (the untruncated object the
2000-char driver tail cuts mid-JSON — BENCH_r05's artifact) to a JSONL
store after every run; ``tools/perfwatch.py`` prints/gates the
trajectory.  One line per run::

    {"schema": 1, "ts": <unix>, "summary": {...the full bench out...}}

Regression detection is deliberately simple and robust: per tracked
metric, compare the newest value against the MEDIAN of the previous
``window`` values — the median ignores one bad day, and a
relative tolerance per metric direction separates drift from noise
(the tested bar: a 20% slowdown fires, ±2-3% run noise stays quiet).

The default metric set is the round-13 contract: ``cells_per_s``
(headline, higher is better), ``bicgstab_iter_device_ms`` (fused-solver
roofline, lower), ``wall_per_step_p95_s`` (tail latency, lower).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from statistics import median
from typing import Dict, List, Optional, Sequence, Tuple

from cup3d_tpu.obs import metrics as _metrics
from cup3d_tpu.obs import trace as _trace

STORE_SCHEMA = 1


@dataclass(frozen=True)
class MetricSpec:
    """One tracked metric: ``paths`` are dotted lookups into the bench
    summary, first hit wins (the fish block moves under ``detail`` on
    single-config runs)."""

    name: str
    paths: Tuple[Tuple[str, ...], ...]
    higher_is_better: bool = True
    rel_tol: float = 0.10


DEFAULT_SPECS: Tuple[MetricSpec, ...] = (
    MetricSpec("cells_per_s", (("value",),), higher_is_better=True),
    MetricSpec(
        "bicgstab_iter_device_ms",
        (("fish", "roofline", "bicgstab_iter_device_ms"),
         ("detail", "roofline", "bicgstab_iter_device_ms")),
        higher_is_better=False,
    ),
    MetricSpec(
        "wall_per_step_p95_s",
        (("fish", "wall_per_step_p95_s"),
         ("detail", "wall_per_step_p95_s")),
        higher_is_better=False,
    ),
    # fleet serving throughput: aggregate useful cells/s over all lanes
    # of the fleet32 config (bench.py), direction-aware higher-is-better
    MetricSpec(
        "fleet_cells_per_s",
        (("fleet32", "fleet_cells_per_s"),
         ("detail", "fleet_cells_per_s")),
        higher_is_better=True,
    ),
    # round 15 (fused AMR): the adaptive config's sustained throughput
    # and the forest BiCGSTAB device iteration (fused when the dispatch
    # gate is on, else the flat legacy number — same roofline block)
    MetricSpec(
        "amr_cells_per_s",
        (("amr_tgv", "cells_per_s"),),
        higher_is_better=True,
    ),
    MetricSpec(
        "amr_bicgstab_iter_device_ms",
        (("amr_tgv", "roofline", "fused", "bicgstab_iter_device_ms"),
         ("amr_tgv", "roofline", "bicgstab_iter_device_ms")),
        higher_is_better=False,
    ),
    # round 16 (serving observatory): p99 end-to-end job completion
    # latency of the seeded fleet_slo arrival trace (bench.py), from the
    # obs/metrics.py bucketed histograms — tail latency, lower is better
    MetricSpec(
        "fleet_job_p99_s",
        (("fleet_slo", "fleet_job_p99_s"),
         ("detail", "fleet_job_p99_s")),
        higher_is_better=False,
    ),
    # round 17 (continuous batching): lane occupancy of the seeded
    # heavy-tailed fleet_skew mix (bench.py) — busy-lane-steps over
    # total-lane-steps for the continuous serve window; a DROP means
    # the scheduler stopped reseeding freed lanes, so higher is better
    MetricSpec(
        "fleet_occupancy",
        (("fleet_skew", "fleet_occupancy"),
         ("detail", "fleet_occupancy")),
        higher_is_better=True,
    ),
    # round 18 (2-D mesh scale-out): sharded steady-state megaloop
    # throughput of the mesh2d config (bench.py) — the x-slab scan body
    # with ring halos; a DROP means the sharded path lost ground to the
    # solo loop (halo regression, retrace, fallback), higher is better
    MetricSpec(
        "mesh_cells_per_s",
        (("mesh2d", "mesh_cells_per_s"),
         ("detail", "mesh_cells_per_s")),
        higher_is_better=True,
    ),
    # round 19 (distributed observatory): COMPILER-counted HBM bytes of
    # one production BiCGSTAB iteration (xla cost_analysis via
    # obs/costs.py, bench._compiler_per_iter).  Deterministic per
    # (jax version, backend, config) — a rise means a compile started
    # moving more HBM traffic, caught even when wall-clock noise hides
    # it; lower is better
    MetricSpec(
        "fish_bicgstab_bytes_compiler",
        (("fish", "roofline", "legacy", "compiler", "bytes_per_iter"),
         ("detail", "roofline", "legacy", "compiler", "bytes_per_iter")),
        higher_is_better=False,
    ),
    # round 21 (zero cold start): boot-to-first-dispatch of a fresh
    # process against a WARMED executable store (bench.py cold_start,
    # subprocess-measured).  A rise means boot started recompiling —
    # the store stopped serving (fingerprint churn, key drift, a new
    # compile on the admission path); lower is better
    MetricSpec(
        "warm_start_s",
        (("cold_start", "warm_start_s"),
         ("detail", "warm_start_s")),
        higher_is_better=False,
    ),
    # round 22 (latency provenance): fraction of the fleet_skew
    # window's total phase-seconds spent in compile_wait — jobs parked
    # on background XLA builds.  A rise means the AOT store stopped
    # absorbing compiles (key drift, speculation miss, store churn);
    # the remedy is warming the store, NOT scaling out, which is
    # exactly why it is tracked separately from occupancy/p99;
    # lower is better
    MetricSpec(
        "fleet_compile_wait_frac",
        (("fleet_skew", "fleet_compile_wait_frac"),
         ("detail", "fleet_compile_wait_frac")),
        higher_is_better=False,
    ),
    # round 23 (durable fleet): crashed-server restart latency of the
    # bench.py durability drill — ``fleet recover`` CLI entry to the
    # restarted server's first dispatch (journal replay + driver
    # re-init + lane resume, subprocess-measured against a warm
    # executable store).  A rise means recovery started recompiling or
    # replaying slowly — the restart path stopped being cheap;
    # lower is better
    MetricSpec(
        "recover_restart_s",
        (("durability", "recover_restart_s"),
         ("detail", "recover_restart_s")),
        higher_is_better=False,
    ),
)


def default_path() -> str:
    """``CUP3D_BENCH_HISTORY`` or the validation-results store."""
    return (os.environ.get("CUP3D_BENCH_HISTORY")
            or os.path.join("validation", "results",
                            "bench_history.jsonl"))


def extract(summary: dict, spec: MetricSpec) -> Optional[float]:
    """The spec's value out of one bench summary (None when absent or
    non-numeric — a config that errored simply contributes no point)."""
    for path in spec.paths:
        node = summary
        for key in path:
            if not isinstance(node, dict) or key not in node:
                node = None
                break
            node = node[key]
        if isinstance(node, (int, float)) and not isinstance(node, bool):
            return float(node)
    return None


def rolling_baseline(series: Sequence[float], window: int = 5) -> float:
    """Median of the up-to-``window`` values PRECEDING the newest — the
    regression-detection baseline, factored out (round 22) so the fleet
    burn attribution (``fleet/server.py phase_attribution``) judges
    phase shares against the same median machinery the bench gate uses.
    With fewer than two points there is no "previous" to take a median
    of; the newest value (or 0.0 on empty) is its own baseline."""
    if len(series) < 2:
        return float(series[-1]) if series else 0.0
    return float(median(series[-(window + 1):-1]))


class HistoryStore:
    """Append-only JSONL store of bench summaries."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_path()

    def append(self, summary: dict, ts: Optional[float] = None) -> dict:
        wrapper = {"schema": STORE_SCHEMA,
                   "ts": _trace.wall() if ts is None else float(ts),
                   "summary": summary}
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        with open(self.path, "a") as f:
            f.write(json.dumps(wrapper) + "\n")
        _metrics.counter("history.appends").inc()
        return wrapper

    def load(self) -> List[dict]:
        """Every parseable wrapper, oldest first; unparseable lines are
        counted (``history.bad_lines``) and skipped — one truncated
        write must not orphan the whole trajectory."""
        if not os.path.exists(self.path):
            return []
        out = []
        with open(self.path) as f:
            for line in f:
                if not line.strip():
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    _metrics.counter("history.bad_lines").inc()
                    continue
                if isinstance(rec, dict) and isinstance(
                        rec.get("summary"), dict):
                    out.append(rec)
                else:
                    _metrics.counter("history.bad_lines").inc()
        return out

    def summaries(self) -> List[dict]:
        return [r["summary"] for r in self.load()]


def detect_regressions(summaries: Sequence[dict],
                       specs: Sequence[MetricSpec] = DEFAULT_SPECS,
                       window: int = 5) -> List[dict]:
    """Newest summary vs the median of the previous ``window`` values,
    per spec.  Returns one report dict per spec:

        {"metric", "n", "current", "baseline", "ratio", "regressed",
         "higher_is_better", "rel_tol"}         # or
        {"metric", "n", "regressed": False, "reason": ...}

    A metric regresses when the current/baseline ratio crosses the
    spec's relative tolerance AGAINST its direction."""
    reports = []
    for spec in specs:
        series = [v for v in (extract(s, spec) for s in summaries)
                  if v is not None]
        if len(series) < 2:
            reports.append({"metric": spec.name, "n": len(series),
                            "regressed": False,
                            "reason": "insufficient history (<2 points)"})
            continue
        current = series[-1]
        baseline = rolling_baseline(series, window=window)
        if baseline == 0:
            reports.append({"metric": spec.name, "n": len(series),
                            "regressed": False,
                            "reason": "zero baseline"})
            continue
        ratio = current / baseline
        if spec.higher_is_better:
            regressed = ratio < 1.0 - spec.rel_tol
        else:
            regressed = ratio > 1.0 + spec.rel_tol
        reports.append({
            "metric": spec.name, "n": len(series),
            "current": current, "baseline": baseline,
            "ratio": round(ratio, 4), "regressed": regressed,
            "higher_is_better": spec.higher_is_better,
            "rel_tol": spec.rel_tol,
        })
    return reports


def any_regressed(reports: Sequence[dict]) -> bool:
    return any(r.get("regressed") for r in reports)
