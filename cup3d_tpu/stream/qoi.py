"""Streaming QoI layer: grouped, deferred device->host reads with
counters, backpressure, and per-config pack slimming.

A blocking device->host read stalls the dispatch queue until the device
has caught up — so reading one QoI pack per step caps throughput at one
such stall per step.  Both drivers
instead emit per-step packs into a :class:`QoIStream`, which every
``read_every`` steps concatenates them ON DEVICE into one vector, starts
an ASYNC host copy, and consumes completed groups opportunistically.
Entries are applied strictly FIFO via the driver's consume callback, on
the main thread.

The stream is THREADLESS (round-4 redesign, VERDICT r3 item 4): the old
scheme fetched each group on a worker thread whose blocking
``np.asarray`` was starved by the main thread's dispatch loop (GIL) and
serialized with the dispatch traffic — seconds per group read while
stepping.  ``copy_to_host_async`` prefetches the value to host (a later
``np.asarray`` is then a local copy) and ``x.is_ready()`` is a local
poll.  So the stream keeps a FIFO
of in-flight async-copied batches and drains the completed prefix at
each emit; nothing blocks until ``max_inflight`` groups are outstanding,
and the only blocking wait is genuine backpressure (the device has
fallen ``max_inflight * read_every`` steps behind the host).

Host-mirror staleness is bounded by ~(1 + max_inflight) * read_every
steps; the drivers' device-resident dt chain (or, on the host-dt path,
their dt-growth bound and runaway abort) guards stability against the
stale max|u| (see VALIDATION.md, "stream subsystem contract").

Round-6 additions (the ``stream/`` subsystem, ISSUE 1):

- **counters** — every stream keeps ``stats`` (packs emitted, groups
  started/read, bytes streamed, stall/read seconds, peak groups in
  flight) surfaced in the bench JSON, so host-read cost is attributed
  explicitly instead of hiding inside whichever operator forces a sync;
- **stall attribution** — the backpressure wait (device behind host) is
  timed into ``stats['stall_s']`` and, when the stream is given a
  profiler, into its own ``StreamWait`` section: ``SyncQoI`` then
  measures the actual host work of emitting/consuming packs, not the
  device catch-up time;
- **pack slimming** — a :class:`PackPolicy` filters emitted parts by
  name/size so large host mirrors (full-field score vectors, debug
  mirrors) can be dropped per config while the QoI scalars always ship;
  at 256^3 the pack is scalars-only and nothing else rides the stream.
"""

from __future__ import annotations

import weakref
from contextlib import nullcontext
from typing import Callable, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from cup3d_tpu.analysis.runtime import blocking_read
from cup3d_tpu.obs import trace as _trace


class PackPolicy:
    """Which parts of a step's QoI pack ride the stream.

    ``max_part_elems`` drops any part larger than that many elements
    (0/None = keep all); ``drop`` drops parts by name.  Parts named in
    ``required`` always ship — the dt chain's ``umax`` and the rigid
    mirrors must never be slimmed away.  Dropped parts simply never
    leave the device: their device arrays are unreferenced and their
    bytes are counted in ``bytes_dropped``.
    """

    REQUIRED = ("umax", "rigid", "scan")

    def __init__(self, max_part_elems: int = 0, drop: Iterable[str] = (),
                 required: Iterable[str] = REQUIRED):
        self.max_part_elems = int(max_part_elems or 0)
        self.drop = frozenset(drop)
        self.required = frozenset(required)

    def admits(self, name: str, size: int) -> bool:
        if name in self.required:
            return True
        if name in self.drop:
            return False
        if self.max_part_elems and size > self.max_part_elems:
            return False
        return True

    @classmethod
    def for_cells(cls, ncells: int, slim_at: int = 2**24) -> "PackPolicy":
        """Per-config slimming: at 256^3-class resolutions (>= ``slim_at``
        cells, default 2^24 = 256^3) ship only QoI scalars and small host
        mirrors — any full-field part (scores, debug mirrors) stays on
        device.  Below that, everything rides (the transfers are cheap
        relative to the step)."""
        if ncells >= slim_at:
            return cls(max_part_elems=4096)
        return cls()


class QoIStream:
    """Grouped async device->host QoI reader (the promoted
    ``sim/pack.GroupedPackReader``).

    entries: dicts with a ``pack`` device vector and a ``layout`` of
    (name, size) pairs; ``consume(entry)`` is called with
    ``entry['vals']`` filled, in emission order.
    """

    def __init__(self, consume: Callable[[dict], None], read_every: int = 4,
                 max_inflight: int = 2,
                 policy: Optional[PackPolicy] = None,
                 profiler=None, name: str = "qoi"):
        self.consume = consume
        self.read_every = read_every
        self.max_inflight = max_inflight
        self.policy = policy or PackPolicy()
        self.profiler = profiler
        self.name = name
        self.queue: List[dict] = []
        self._inflight: List[dict] = []  # {batch, group} FIFO
        self.stats = self._zero_stats()
        # the per-instance stats dict stays the single store (tests pin
        # its exact per-stream counts); the process-global registry sees
        # it through a weakref collector, so `obs.metrics.snapshot()`
        # carries every live stream's counters under stream.*{stream=name}
        # and equal-named streams SUM (obs/metrics.py)
        from cup3d_tpu.obs import metrics as obs_metrics

        def _collect(ref=weakref.ref(self)):
            st = ref()
            if st is None:
                return {}
            return {
                f"stream.{k}{{stream={st.name}}}": v
                for k, v in st.snapshot().items()
            }

        obs_metrics.register_collector(_collect, owner=self)

    @staticmethod
    def _zero_stats() -> dict:
        return {
            "packs_emitted": 0,
            "packs_consumed": 0,
            "packs_abandoned": 0,
            "groups_started": 0,
            "groups_read": 0,
            "parts_dropped": 0,
            "bytes_streamed": 0,
            "bytes_dropped": 0,
            "bytes_staged": 0,
            "stall_s": 0.0,
            "read_s": 0.0,
            "inflight_peak": 0,
            "kicks": 0,
        }

    def reset_stats(self) -> None:
        """Zero the counters (bench timed-window boundaries)."""
        self.stats = self._zero_stats()

    def snapshot(self) -> dict:
        """Counters plus instantaneous queue state, for the bench JSON."""
        out = dict(self.stats)
        out["groups_inflight"] = len(self._inflight)
        out["packs_queued"] = len(self.queue)
        return out

    def __bool__(self):
        return bool(self.queue or self._inflight)

    # -- emission ----------------------------------------------------------

    def pack_parts(self, parts: Sequence[Tuple[str, "object"]], dtype,
                   **meta) -> dict:
        """(name, device vector) parts -> one emitted entry, applying the
        slimming policy BEFORE the device concat so dropped parts never
        leave the device.  Returns the entry (callers on the non-pipelined
        path hand it straight to their consume callback)."""
        import jax.numpy as jnp

        kept = []
        for name, arr in parts:
            if self.policy.admits(name, int(arr.shape[0])):
                kept.append((name, arr))
            else:
                self.stats["parts_dropped"] += 1
                self.stats["bytes_dropped"] += int(
                    arr.shape[0]) * jnp.dtype(dtype).itemsize
        pack = jnp.concatenate([a.astype(dtype) for _, a in kept])
        try:
            pack.copy_to_host_async()
        # jax-lint: allow(JX009, capability probe: platforms without
        # async copies fall back to the blocking read downstream)
        except Exception:
            pass
        entry = {"layout": [(n, int(a.shape[0])) for n, a in kept],
                 "pack": pack}
        entry.update(meta)
        return entry

    def emit(self, entry: dict) -> None:
        from cup3d_tpu.resilience import faults

        # stream.stall injection seam (resilience/faults.py): a
        # simulated transfer stall lands in the stream's own stall
        # accounting; the unarmed probe is one tuple scan
        faults.maybe_stall(step=entry.get("step"))
        self.queue.append(entry)
        self.stats["packs_emitted"] += 1
        self.poll()
        if len(self.queue) >= self.read_every:
            if len(self._inflight) >= self.max_inflight:
                # backpressure: the device has fallen a full window behind
                # the host.  This wait is device catch-up, not host-read
                # cost — attribute it to its own profiler section (and the
                # stall counter) so SyncQoI stays an honest dispatch cost.
                ctx = (self.profiler("StreamWait")
                       if self.profiler is not None else nullcontext())
                with ctx:
                    while len(self._inflight) >= self.max_inflight:
                        self._consume_one()  # bounded staleness
            self.kick()

    def kick(self) -> None:
        """Group everything queued NOW into one device batch and start its
        async host copy.  Called by emit() at the regular cadence, and by
        drivers that need fresher mirrors than the cadence provides (e.g.
        the collision pre-check when obstacles approach contact).  A kick
        at the max_inflight limit is skipped — emit()'s backpressure is
        the only place allowed to wait, so the retained device batches
        stay bounded even when a driver kicks every step."""
        import jax.numpy as jnp

        if not self.queue or len(self._inflight) >= self.max_inflight:
            return
        group, self.queue = self.queue, []
        batch = jnp.concatenate([e["pack"] for e in group])
        try:
            batch.copy_to_host_async()
        # jax-lint: allow(JX009, capability probe: platforms without
        # async copies fall back to the blocking asarray downstream)
        except Exception:
            pass
        self._inflight.append({"batch": batch, "group": group})
        self.stats["kicks"] += 1
        self.stats["groups_started"] += 1
        self.stats["bytes_streamed"] += int(batch.size) * batch.dtype.itemsize
        self.stats["inflight_peak"] = max(
            self.stats["inflight_peak"], len(self._inflight)
        )

    # -- staging (non-pack device->host traffic) ---------------------------

    def stage(self, x):
        """Start an async host copy of ``x`` and account its bytes to this
        stream (scores prefetch, ad-hoc mirrors).  Returns ``x``; the
        caller reads it later with ``np.asarray`` (~free once landed)."""
        try:
            x.copy_to_host_async()
        # jax-lint: allow(JX009, capability probe: platforms without
        # async copies fall back to the caller's blocking asarray)
        except Exception:
            pass
        try:
            self.stats["bytes_staged"] += int(x.size) * x.dtype.itemsize
        # jax-lint: allow(JX009, best-effort byte accounting on duck-
        # typed staged values; the stage itself already succeeded)
        except Exception:
            pass
        return x

    # -- consumption -------------------------------------------------------

    def _consume_one(self) -> None:
        """Read the oldest in-flight batch (blocking only if its compute /
        transfer has not landed yet) and apply its entries FIFO.  The
        read is timed into ``read_s`` when the batch had landed and
        ``stall_s`` when it had not (or its readiness was unknowable),
        and — when the stream has a profiler — into a ``StreamRead`` /
        ``StreamWait`` section, so a blocking read can never hide
        inside whichever driver section happened to enclose it (the
        BENCH_r05 fish256 SyncQoI regression: unattributed device
        catch-up billed as pack-read host work)."""
        holder = self._inflight.pop(0)
        was_ready = self._ready(holder["batch"]) is True
        ctx = (self.profiler("StreamRead" if was_ready else "StreamWait")
               if self.profiler is not None else nullcontext())
        # jax-lint: allow(JX006, the pre-window calls are host
        # bookkeeping (FIFO pop + readiness poll); the timed np.asarray
        # read IS the sync, and stall_s/read_s split on was_ready)
        # jax-lint: allow(JX008, the stall_s/read_s split is the stream's
        # native counter — it feeds the obs registry via the collector
        # registered in __init__; the StreamWait/StreamRead spans above
        # are exactly the obs attribution the rule asks for)
        t0 = _trace.now()
        with ctx:
            vals = blocking_read("stream-read", holder["batch"], np.float64)
        elapsed = _trace.now() - t0
        self.stats["stall_s" if not was_ready else "read_s"] += elapsed
        self.stats["groups_read"] += 1
        off = 0
        for entry in holder["group"]:
            size = sum(s for _, s in entry["layout"])
            entry["vals"] = vals[off:off + size]
            off += size
            self.consume(entry)
            self.stats["packs_consumed"] += 1

    @staticmethod
    def _ready(batch):
        """True / False from the platform's readiness probe, or None
        when the probe itself fails.  None means "unknowable", NOT
        "ready": poll() treating a probe failure as ready turned every
        opportunistic drain into a BLOCKING read of an unfinished batch
        — serializing the dispatch loop with device compute once per
        emit cadence (the fish256 SyncQoI regression, BENCH_r05)."""
        try:
            return bool(batch.is_ready())
        # jax-lint: allow(JX009, capability probe: duck-typed batches
        # without is_ready report unknowable readiness; blocking
        # consumers proceed, the opportunistic poll() skips)
        except Exception:
            return None

    def poll(self) -> None:
        """Consume completed reads without blocking (strictly FIFO: stop
        at the first batch whose computation hasn't landed or whose
        readiness cannot be probed)."""
        while self._inflight and self._ready(self._inflight[0]["batch"]) is True:
            self._consume_one()

    def join(self) -> None:
        """Consume ALL in-flight group reads (blocking)."""
        while self._inflight:
            self._consume_one()

    def flush(self) -> None:
        """Drain everything: in-flight reads, then still-queued packs."""
        self.join()
        while self.queue:
            entry = self.queue.pop(0)
            self.consume(entry)
            self.stats["packs_consumed"] += 1

    def abandon(self) -> None:
        """Drop every queued pack and in-flight group WITHOUT consuming
        them — recovery rollback (resilience/recovery.py): mirrors from
        the abandoned trajectory must never apply to the restored
        state.  Counted in ``packs_abandoned``."""
        n = len(self.queue) + sum(len(h["group"]) for h in self._inflight)
        self.queue = []
        self._inflight = []
        self.stats["packs_abandoned"] += n
