"""Zero-cold-start acceptance (cup3d_tpu/aot/; VALIDATION.md "Round 21"):

- Store round trip: a deserialized executable returns bitwise-identical
  results to the fresh compile that produced it, and to an independent
  compile of the same function.
- Rejection is never a wrong load: a fingerprint-mismatched, truncated,
  or bit-flipped artifact is rejected (counted by reason, file removed)
  and the caller transparently recompiles — correct results either way.
- Warm boot is compile-free: a second FleetServer against a warmed
  store dispatches previously-seen signatures with ZERO advance
  compiles (RecompileCounter-verified), where the no-store control
  provably recompiles.
- Cross-process reuse: a fresh ``python -m cup3d_tpu aot probe``
  subprocess boots from the store written by a prior subprocess with
  zero advance compiles and bitwise-identical QoI rows.
- Background compile: an admission-signature miss queues a build off
  the dispatch thread (miss -> queue -> serve lifecycle), and the
  speculative ladder pre-compiles a neighboring lane rung.
- GC: the store stays under its byte bound, evicting oldest-first.
"""

import hashlib
import json
import os
import pickle
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cup3d_tpu.aot import store as aot_store
from cup3d_tpu.aot.compiler import CompileService
from cup3d_tpu.aot.store import ExecutableStore, StoreBackedExecutable
from cup3d_tpu.obs import metrics as M
from tests._cases import delta, tgv_spec


def _f(x):
    return jnp.sin(x) * 2.0 + x**2


def _wrapper(store, sig=("test", 1), name="test-exec"):
    return StoreBackedExecutable(jax.jit(_f), sig, name=name, store=store)


# -- store round trip -------------------------------------------------------


def test_store_roundtrip_bitwise(tmp_path):
    """write -> read-back returns bitwise-identical results to both the
    producing compile and an independent fresh compile."""
    store = ExecutableStore(str(tmp_path / "store"))
    x = jnp.linspace(-1.0, 1.0, 64, dtype=jnp.float32)
    before = M.snapshot()

    w1 = _wrapper(store)
    y1 = np.asarray(w1(x))
    assert delta(before, "aot.store_writes") == 1
    assert store.contains(("test", 1))

    w2 = _wrapper(store)  # fresh wrapper, same sig: loads, no compile
    y2 = np.asarray(w2(x))
    assert delta(before, "aot.store_hits") == 1
    assert y1.tobytes() == y2.tobytes()

    y_fresh = np.asarray(jax.jit(_f)(x))
    assert y1.tobytes() == y_fresh.tobytes()


def test_store_backed_is_identity_without_store():
    jitted = jax.jit(_f)
    assert aot_store.store_backed(jitted, ("s",), store=None) is jitted


# -- rejection: never a wrong load ------------------------------------------


def _tamper_record(path, mutate):
    """Rewrite one entry with a mutated record and a VALID checksum —
    exercising the semantic guards, not the integrity ones."""
    with open(path, "rb") as f:
        blob = f.read()
    inner = blob[len(aot_store.MAGIC):].split(b"\n", 1)[1]
    rec = pickle.loads(inner)
    mutate(rec)
    inner = pickle.dumps(rec, protocol=4)
    with open(path, "wb") as f:
        f.write(aot_store.MAGIC
                + hashlib.blake2s(inner).hexdigest().encode()
                + b"\n" + inner)


def test_fingerprint_mismatch_rejected(tmp_path):
    """An entry stamped by a different jax/device world MISSES (reason
    counted, file removed) and the caller recompiles correctly."""
    store = ExecutableStore(str(tmp_path / "store"))
    x = jnp.ones(8, dtype=jnp.float32)
    y0 = np.asarray(_wrapper(store)(x))
    path = store.path_for(("test", 1))

    def wrong_world(rec):
        rec["fingerprint"] = dict(rec["fingerprint"], jax="0.0.0")

    _tamper_record(path, wrong_world)
    before = M.snapshot()
    y1 = np.asarray(_wrapper(store)(x))  # transparent recompile
    assert delta(before, "aot.store_rejects{reason=fingerprint}") == 1
    assert y0.tobytes() == y1.tobytes()
    assert not os.path.exists(path) or store.contains(("test", 1))


def test_sig_collision_rejected(tmp_path):
    store = ExecutableStore(str(tmp_path / "store"))
    _wrapper(store)(jnp.ones(8, dtype=jnp.float32))
    path = store.path_for(("test", 1))
    _tamper_record(path, lambda rec: rec.update(sig="('other', 99)"))
    before = M.snapshot()
    assert store.get(("test", 1)) is None
    assert delta(before, "aot.store_rejects{reason=sig-collision}") == 1


@pytest.mark.parametrize("damage,reason", [
    (lambda blob: blob[: len(blob) // 2], "checksum"),
    (lambda blob: blob[:15], "truncated"),  # MAGIC intact, header cut
    (lambda blob: b"garbage" + blob[7:], "magic"),
    (lambda blob: blob[:-20] + bytes(20), "checksum"),
])
def test_corrupt_artifact_rejected(tmp_path, damage, reason):
    """Truncated/bit-flipped entries are rejected by reason, removed,
    and the wrapper recompiles — never crashes, never a wrong load."""
    store = ExecutableStore(str(tmp_path / "store"))
    x = jnp.ones(8, dtype=jnp.float32)
    y0 = np.asarray(_wrapper(store)(x))
    path = store.path_for(("test", 1))
    with open(path, "rb") as f:
        blob = f.read()
    with open(path, "wb") as f:
        f.write(damage(blob))
    before = M.snapshot()
    y1 = np.asarray(_wrapper(store)(x))
    key = "aot.store_rejects{reason=%s}" % reason
    assert delta(before, key) == 1
    assert y0.tobytes() == y1.tobytes()


def test_verify_rejects_defects(tmp_path):
    store = ExecutableStore(str(tmp_path / "store"))
    _wrapper(store)(jnp.ones(8, dtype=jnp.float32))
    _wrapper(store, sig=("test", 2))(jnp.ones(8, dtype=jnp.float32))
    path = store.path_for(("test", 2))
    with open(path, "ab") as f:
        f.write(b"trailing garbage")
    report = store.verify()
    assert report["ok"] == 1 and report["rejected"] == 1
    assert not os.path.exists(path)


# -- warm boot: zero advance compiles ---------------------------------------


@pytest.mark.slow
def test_warm_boot_zero_advance_compiles(tmp_path, monkeypatch):
    """Server 2 against the store server 1 warmed dispatches its jobs
    with ZERO advance compiles; the no-store control recompiles —
    proving the assertion bites."""
    from cup3d_tpu.analysis.runtime import RecompileCounter
    from cup3d_tpu.fleet.server import FleetServer

    monkeypatch.setenv("CUP3D_AOT_STORE", str(tmp_path / "store"))
    srv1 = FleetServer(workdir=str(tmp_path / "wd1"))
    for i in range(2):
        srv1.submit(f"t{i}", tgv_spec())
    srv1.drain()
    store = aot_store.active_store()
    assert store.state()["files"] >= 1

    before = M.snapshot()
    with RecompileCounter() as rc:
        srv2 = FleetServer(workdir=str(tmp_path / "wd2"))
        ids = [srv2.submit(f"t{i}", tgv_spec()) for i in range(2)]
        srv2.drain()
    assert all(srv2._jobs[j].status == "done" for j in ids)
    advance = {k: v for k, v in rc.compiles.items() if "advance" in k}
    assert not advance, advance
    assert delta(before, "aot.store_hits") >= 1

    # control: the same boot WITHOUT a store recompiles the advance
    monkeypatch.delenv("CUP3D_AOT_STORE")
    with RecompileCounter() as rc_cold:
        srv3 = FleetServer(workdir=str(tmp_path / "wd3"))
        ids = [srv3.submit(f"t{i}", tgv_spec()) for i in range(2)]
        srv3.drain()
    assert all(srv3._jobs[j].status == "done" for j in ids)
    assert any("advance" in k for k in rc_cold.compiles), rc_cold.compiles


@pytest.mark.slow
def test_compile_wait_phase_cold_then_warm(tmp_path, monkeypatch):
    """Round-22 provenance through the AOT seam: a cold background
    build parks its jobs in a nonzero compile_wait phase and leaves a
    pid-5 compile-service span flow-linked to the jobs' lane spans; a
    warm boot against the same store never opens the phase at all."""
    from cup3d_tpu.fleet.server import FleetServer
    from cup3d_tpu.obs import trace as OT

    monkeypatch.setenv("CUP3D_AOT_STORE", str(tmp_path / "store"))
    td = str(tmp_path / "trace")
    OT.TRACE.configure(enabled=True, directory=td)
    try:
        srv1 = FleetServer(workdir=str(tmp_path / "wd1"))
        ids = [srv1.submit(f"t{i}", tgv_spec()) for i in range(2)]
        srv1.drain()
        OT.TRACE.close()
    finally:
        OT.TRACE.configure(enabled=False)
    assert all(srv1._jobs[j].status == "done" for j in ids)
    cold = {j: srv1._jobs[j].phases().get("compile_wait", 0.0)
            for j in ids}
    assert max(cold.values()) > 0, cold
    # the decomposition still partitions e2e with the new phase present
    for j in ids:
        phases = srv1._jobs[j].phases()
        times = [t for _, t in srv1._jobs[j].events]
        assert sum(phases.values()) == pytest.approx(
            times[-1] - times[0], rel=1e-9, abs=1e-12)
    # cross-subsystem flow: compile-service span on pid 5, flow start
    # ("s") at the build, flow finish ("f") on a waiting job's lane span
    with open(os.path.join(td, "trace.pfto.json")) as f:
        events = json.load(f)["traceEvents"]
    compile_track = [e for e in events if e.get("pid") == OT.COMPILE_PID]
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in compile_track)
    spans = [e for e in compile_track if e["ph"] == "X"]
    assert spans and all(e["args"]["outcome"] == "done" for e in spans)
    starts = {e["id"] for e in events
              if e.get("ph") == "s" and e.get("cat") == "flow"}
    finishes = {e["id"] for e in events
                if e.get("ph") == "f" and e.get("cat") == "flow"}
    waited = {j for j, v in cold.items() if v > 0}
    assert waited <= starts and finishes <= starts
    assert finishes & waited  # at least one arrow lands on a lane span

    # warm boot: the signature deserializes — nobody waits on a compile
    srv2 = FleetServer(workdir=str(tmp_path / "wd2"))
    ids2 = [srv2.submit(f"t{i}", tgv_spec()) for i in range(2)]
    srv2.drain()
    assert all(srv2._jobs[j].status == "done" for j in ids2)
    for j in ids2:
        assert srv2._jobs[j].phases().get("compile_wait", 0.0) == 0.0
        assert srv2._jobs[j].event_time("compile_wait") is None


@pytest.mark.slow
def test_health_reports_aot_state(tmp_path, monkeypatch):
    from cup3d_tpu.fleet.server import FleetServer

    monkeypatch.setenv("CUP3D_AOT_STORE", str(tmp_path / "store"))
    srv = FleetServer(workdir=str(tmp_path / "wd"))
    srv.submit("t", tgv_spec())
    srv.drain()
    aot = srv.health()["aot"]
    assert aot["store"]["files"] >= 1
    assert aot["service"]["queue_depth"] == 0


# -- cross-process reuse ----------------------------------------------------


@pytest.mark.slow
def test_cross_process_store_reuse(tmp_path):
    """The real next-boot experience: two fresh subprocesses share only
    the on-disk store — the second dispatches with zero advance
    compiles and bitwise-identical QoI rows."""
    spec_path = tmp_path / "spec.json"
    spec_path.write_text(json.dumps(
        [dict(kind="tgv", n=16, nsteps=4, cfl=0.3, tenant="x")]))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("CUP3D_AOT_STORE", None)

    def probe(tag):
        out = subprocess.run(
            [sys.executable, "-m", "cup3d_tpu", "aot", "probe",
             "--scenarios", str(spec_path),
             "--store", str(tmp_path / "store"),
             "--workdir", str(tmp_path / f"wd-{tag}")],
            capture_output=True, text=True, env=env, timeout=120)
        assert out.returncode == 0, out.stderr[-500:]
        return json.loads(out.stdout)

    cold = probe("cold")
    warm = probe("warm")
    assert cold["advance_compiles"] >= 1
    assert warm["advance_compiles"] == 0
    assert warm["aot_counters"].get("aot.store_hits", 0) >= 1
    assert cold["rows_blake2s"] == warm["rows_blake2s"]
    assert all(s == "done" for s in warm["jobs"].values())


# -- background compile service ---------------------------------------------


def test_compile_service_lifecycle():
    """submit -> (pending|running) -> done -> take, with dedup and the
    queue-depth gauge returning to zero."""
    svc = CompileService()
    svc.submit("k1", lambda: "built-1", name="one")
    svc.submit("k1", lambda: "NEVER", name="dup")  # deduplicated
    assert svc.drain(timeout=30)
    assert svc.status("k1") == "done"
    assert svc.take("k1") == "built-1"
    assert svc.take("k1") is None  # result consumed, record remains
    assert svc.status("k1") == "done"
    assert svc.depth() == 0

    # a failing build lands FAILED and can be resubmitted
    svc.submit("k2", lambda: 1 / 0, name="boom")
    assert svc.drain(timeout=30)
    assert svc.status("k2") == "failed"
    svc.submit("k2", lambda: "retry-ok", name="boom")
    assert svc.drain(timeout=30)
    assert svc.take("k2") == "retry-ok"


@pytest.mark.slow
def test_background_miss_queue_serve(tmp_path, monkeypatch):
    """A cold admission signature compiles off the dispatch thread:
    jobs queue while the build runs, install on completion, and every
    job still finishes (miss -> queue -> serve)."""
    from cup3d_tpu.fleet.server import FleetServer

    monkeypatch.setenv("CUP3D_AOT_STORE", str(tmp_path / "store"))
    before = M.snapshot()
    srv = FleetServer(workdir=str(tmp_path / "wd"))
    ids = [srv.submit(f"t{i}", tgv_spec()) for i in range(2)]
    srv.drain()
    assert all(srv._jobs[j].status == "done" for j in ids)
    assert delta(before, "aot.compile_submits{kind=demand}") >= 1
    assert delta(before, "aot.background_compiles") >= 1
    assert delta(before, "aot.background_installs") >= 1
    assert delta(before, "aot.store_writes") >= 1


@pytest.mark.slow
def test_speculative_rung_precompile(tmp_path, monkeypatch):
    """The ±1 capacity rungs pre-compile speculatively: after a cold
    drain at rung 2, the store also holds a neighboring-rung
    executable it was never asked to dispatch."""
    from cup3d_tpu.fleet.server import FleetServer

    monkeypatch.setenv("CUP3D_AOT_STORE", str(tmp_path / "store"))
    monkeypatch.setenv("CUP3D_AOT_SPECULATE", "1")
    before = M.snapshot()
    srv = FleetServer(workdir=str(tmp_path / "wd"))
    ids = [srv.submit(f"t{i}", tgv_spec()) for i in range(2)]
    srv.drain()
    assert all(srv._jobs[j].status == "done" for j in ids)
    assert delta(before, "aot.compile_submits{kind=speculative}") >= 1
    assert delta(before, "aot.speculative_compiles") >= 1
    # the speculative executable landed on disk for the next boot
    store = aot_store.active_store()
    assert store.state()["files"] >= 2


def test_speculation_disabled_by_env(tmp_path, monkeypatch):
    from cup3d_tpu.fleet.server import FleetServer

    monkeypatch.setenv("CUP3D_AOT_STORE", str(tmp_path / "store"))
    monkeypatch.setenv("CUP3D_AOT_SPECULATE", "0")
    before = M.snapshot()
    srv = FleetServer(workdir=str(tmp_path / "wd"))
    ids = [srv.submit(f"t{i}", tgv_spec()) for i in range(2)]
    srv.drain()
    assert all(srv._jobs[j].status == "done" for j in ids)
    assert delta(before, "aot.compile_submits{kind=speculative}") == 0


# -- GC bound ---------------------------------------------------------------


def test_gc_keeps_store_under_bound(tmp_path):
    """The store never exceeds max_bytes: oldest-touched entries evict
    first and the survivors stay loadable."""
    store = ExecutableStore(str(tmp_path / "store"))
    x = jnp.ones(16, dtype=jnp.float32)
    sigs = [("gc", i) for i in range(3)]
    for i, sig in enumerate(sigs):
        w = StoreBackedExecutable(
            jax.jit(lambda x, i=i: x + float(i)), sig,
            name=f"gc-{i}", store=store)
        w(x)
        os.utime(store.path_for(sig), (i + 1.0, i + 1.0))
    assert store.state()["files"] == 3
    one = os.path.getsize(store.path_for(sigs[0]))

    before = M.snapshot()
    store.max_bytes = 2 * one + one // 2  # room for two entries
    store.gc()
    assert store.total_bytes() <= store.max_bytes
    assert delta(before, "aot.store_gc_evictions") >= 1
    assert not store.contains(sigs[0])  # oldest went first
    assert store.contains(sigs[2])
    assert store.get(sigs[2], name="gc-2") is not None
