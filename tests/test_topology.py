"""2-D (lane x space) mesh topology acceptance (parallel/topology.py,
fleet 2-D wiring, per-slice elastic recovery; VALIDATION.md "Round 18"):

- Factory: shape resolution (explicit args, CUP3D_MESH env, the
  (ndevices, 1) auto default), the loud ValueError on shapes that do
  not multiply out, and placement determinism — two constructions of
  the same mesh agree on every placement entry.
- Sharded megaloop equivalence: the x-slab TGV megaloop is BITWISE
  against the solo loop under the canonical compile
  (--xla_disable_hlo_passes=fusion, in a subprocess: XLA CPU fusion is
  shape-dependent, see VALIDATION.md), and tight-allclose (~1 ulp)
  in-process under the default compile; the sharded fish stays within
  the 1e-6 relative-KE contract.
- Fleet on the 2-D mesh: a sharded drain reproduces the unsharded
  drain bitwise (per-lane scan bodies have no cross-lane coupling),
  and a shard loss mid-drain requeues the lost lanes' jobs onto the
  survivors — every job completes with QoI bytes matching a
  never-failed run, the dead lanes stay fenced, and the counters /
  /health mesh section record what happened.
- Zero steady-state retraces: the sharded megaloop serves every
  dispatch from one trace (RecompileCounter budget 1).
- No quiet solo runs: an unshardable fleet request degrades with a
  warning and a counter (fleet.mesh_fallbacks); a megaloop mesh that
  was asked for (CUP3D_MESH_X) and cannot be had raises.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from cup3d_tpu.obs import metrics as M
from cup3d_tpu.parallel import topology as topo
from cup3d_tpu.resilience import faults
from cup3d_tpu.sim.simulation import Simulation
from tests._cases import fish_cfg, mean_ke, simulate, tgv_cfg


pytestmark = pytest.mark.usefixtures("clean_faults")


# -- factory + placement ---------------------------------------------------


def test_mesh_factory_shapes_env_and_errors(monkeypatch):
    monkeypatch.delenv("CUP3D_MESH", raising=False)
    nd = len(jax.devices())
    assert nd == 8  # conftest forces the 8-device virtual CPU mesh
    # auto default: the old 1-D lanes mesh with a unit x axis
    m = topo.make_mesh2d()
    assert m.axis_names == ("lanes", "x")
    assert m.devices.shape == (nd, 1)
    # explicit shapes, and one-axis derivation
    assert topo.make_mesh2d(lanes=2, x=4).devices.shape == (2, 4)
    assert topo.make_mesh2d(x=2).devices.shape == (4, 2)
    assert topo.make_mesh2d(lanes=4).devices.shape == (4, 2)
    assert topo.mesh_axis_size(topo.make_mesh2d(lanes=2, x=4), "x") == 4
    # CUP3D_MESH="LxX" resolves the auto shape; malformed falls back
    monkeypatch.setenv("CUP3D_MESH", "2x4")
    assert topo.make_mesh2d().devices.shape == (2, 4)
    monkeypatch.setenv("CUP3D_MESH", "bogus")
    assert topo.make_mesh2d().devices.shape == (nd, 1)
    monkeypatch.delenv("CUP3D_MESH")
    # shapes that do not multiply out raise loudly
    with pytest.raises(ValueError):
        topo.make_mesh2d(lanes=3)
    with pytest.raises(ValueError):
        topo.make_mesh2d(lanes=2, x=2)


def test_placement_map_is_deterministic():
    mk = lambda: topo.make_mesh2d(lanes=2, x=4)  # noqa: E731
    pm = topo.placement_map(mk())
    assert pm == topo.placement_map(mk())  # pure function of devices
    # row-major over the (lanes, x) array, device order sorted
    assert [(e["lane_shard"], e["x_shard"]) for e in pm] == [
        (i // 4, i % 4) for i in range(8)]
    ids = [e["device_id"] for e in pm]
    assert ids == sorted(ids)
    st = topo.mesh_state(mk(), fallbacks=3)
    assert st["active"] and st["shape"] == [2, 4]
    assert st["devices"] == 8 and st["fallbacks"] == 3
    assert st["placement"] == pm and "dist" in st
    off = topo.mesh_state(None)
    assert not off["active"] and off["devices"] == 0


def test_shard_carry_places_fields_on_x():
    mesh = topo.make_mesh2d(lanes=1, x=4,
                            devices=topo.device_order()[:4])
    carry = {"vel": jnp.zeros((8, 8, 8, 3), jnp.float32),
             "time": jnp.float32(0.0)}
    out = topo.shard_carry(carry, mesh)
    assert isinstance(out["vel"].sharding, NamedSharding)
    assert out["vel"].sharding.spec == P("x")
    assert out["time"].sharding.spec == P()


# -- loud fallbacks --------------------------------------------------------


def test_megaloop_mesh_gate_raises_when_unavailable(monkeypatch, tmp_path):
    monkeypatch.delenv("CUP3D_MESH_X", raising=False)
    assert topo.megaloop_mesh() is None
    monkeypatch.setenv("CUP3D_MESH_X", "4")
    m = topo.megaloop_mesh()
    assert m is not None and m.devices.shape == (1, 4)
    # no mesh was asked for: off, malformed, <2
    monkeypatch.setenv("CUP3D_MESH_X", "bogus")
    assert topo.megaloop_mesh() is None
    monkeypatch.setenv("CUP3D_MESH_X", "1")
    assert topo.megaloop_mesh() is None
    # more slabs than devices: the mesh cannot be had, so it raises —
    # never an unsharded run under the mesh's name
    monkeypatch.setenv("CUP3D_MESH_X", "16")
    with pytest.raises(RuntimeError, match="CUP3D_MESH_X=16"):
        topo.megaloop_mesh()
    # a solver with no slab form raises at the sharded build as well
    monkeypatch.setenv("CUP3D_MESH_X", "4")
    sim = Simulation(tgv_cfg(tmp_path, scan_k=8,
                              poissonSolver="iterative"))
    sim.init()
    with pytest.raises(NotImplementedError, match="spectral"):
        sim._scan_ready()


def test_fleet_mesh_gate_and_loud_fallback(monkeypatch):
    from cup3d_tpu.fleet import batch as FB

    monkeypatch.delenv("CUP3D_FLEET_MESH", raising=False)
    assert topo.fleet_mesh2d() is None
    monkeypatch.setenv("CUP3D_FLEET_MESH", "1")
    m = topo.fleet_mesh2d()
    assert m is not None and m.devices.size == len(jax.devices())
    # a lane count that cannot shard evenly degrades to unsharded vmap
    # with the warning + counter (and None recorded as the live state)
    mesh = topo.make_mesh2d(lanes=2, x=2, devices=topo.device_order()[:4])
    assert FB.resolve_fleet_mesh(8, mesh) is mesh
    before = M.counter("fleet.mesh_fallbacks").value
    with pytest.warns(UserWarning, match="unsharded"):
        assert FB.resolve_fleet_mesh(3, mesh) is None
    assert M.counter("fleet.mesh_fallbacks").value == before + 1


# -- sharded megaloop equivalence ------------------------------------------


def _run_canonical(tmp_path, source, **env_extra):
    """Run ``source`` (argv[1] = tmp_path) in a process of its own under
    the canonical compile, where the bitwise gates hold: XLA CPU fusion
    is shape-dependent, XLA_FLAGS must be set before the CPU client
    exists, and this process's client is long since alive."""
    script = tmp_path / "canonical.py"
    script.write_text(source)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, **env_extra)
    env.pop("CUP3D_MESH_X", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        "--xla_disable_hlo_passes=fusion")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, str(script), str(tmp_path)],
        capture_output=True, text=True, timeout=180, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "BITWISE-OK" in proc.stdout


def test_sharded_tgv_bitwise_under_canonical_compile(tmp_path):
    """Solo-vs-sharded TGV is BITWISE when XLA's shape-dependent CPU
    fusion is pinned off (the canonical compile the Round-18 contract
    is stated under — see VALIDATION.md)."""
    _run_canonical(
        tmp_path,
        "import os, sys\n"
        "import numpy as np\n"
        "from cup3d_tpu.config import SimulationConfig\n"
        "from cup3d_tpu.sim.simulation import Simulation\n"
        "def cfg(path):\n"
        "    return SimulationConfig(\n"
        "        bpdx=2, bpdy=2, bpdz=2, levelMax=1, levelStart=0,\n"
        "        extent=2 * np.pi, CFL=0.3, nu=0.02, nsteps=8,\n"
        "        tend=0.0, rampup=0, initCond='taylorGreen',\n"
        "        pipelined=True, verbose=False, freqDiagnostics=0,\n"
        "        scan_k=8, path4serialization=path)\n"
        "def run(path):\n"
        "    sim = Simulation(cfg(path))\n"
        "    sim.init()\n"
        "    sim.simulate()\n"
        "    return np.asarray(sim.sim.state['vel']), sim\n"
        "os.environ.pop('CUP3D_MESH_X', None)\n"
        "solo, _ = run(sys.argv[1] + '/solo')\n"
        "os.environ['CUP3D_MESH_X'] = '4'\n"
        "shd, s = run(sys.argv[1] + '/shd')\n"
        "assert s._scan_mesh is not None, 'sharded build fell back'\n"
        "assert (solo == shd).all(), float(np.abs(solo - shd).max())\n"
        "print('BITWISE-OK')\n"
    )


def test_sharded_tgv_matches_solo_inprocess(tmp_path, monkeypatch):
    """Under the default compile the fused carry chain may differ by
    ~1 ulp (shape-dependent fusion rounding): tight-allclose here, the
    bitwise gate lives in the subprocess test above.  The sharded loop
    also serves every dispatch from one trace."""
    from cup3d_tpu.analysis import runtime as R

    monkeypatch.delenv("CUP3D_MESH_X", raising=False)
    a = simulate(tgv_cfg(tmp_path / "solo", scan_k=8))
    monkeypatch.setenv("CUP3D_MESH_X", "4")
    with R.RecompileCounter() as rc:
        b = simulate(tgv_cfg(tmp_path / "shd", scan_k=8))
    assert b._scan_mesh is not None  # really sharded, not a fallback
    assert a.sim.step == b.sim.step == 16
    va = np.asarray(a.sim.state["vel"])
    vb = np.asarray(b.sim.state["vel"])
    np.testing.assert_allclose(vb, va, rtol=1e-5, atol=1e-6)
    ke_a, ke_b = mean_ke(va), mean_ke(vb)
    assert abs(ke_a - ke_b) <= 1e-6 * max(abs(ke_a), 1e-12)
    # zero steady-state retraces: 16 steps / K=8 -> 2 dispatches, one
    # compiled specialization per function
    rc.assert_steady_state(budget=1)


def test_sharded_fish_ke(tmp_path, monkeypatch):
    """The fish megaloop adds rigid/qint/chi/udef to the carry; the
    x-slab build must hold the same 1e-6 relative-KE contract as the
    K-equivalence gate (test_megaloop.py)."""
    monkeypatch.delenv("CUP3D_MESH_X", raising=False)
    a = simulate(fish_cfg(tmp_path / "solo", scan_k=8))
    monkeypatch.setenv("CUP3D_MESH_X", "4")
    b = simulate(fish_cfg(tmp_path / "shd", scan_k=8))
    assert b._scan_mesh is not None
    assert a.sim.step == b.sim.step == 8
    ke_a, ke_b = mean_ke(a.sim.state["vel"]), mean_ke(b.sim.state["vel"])
    assert abs(ke_a - ke_b) <= 1e-6 * max(abs(ke_a), 1e-12)
    np.testing.assert_allclose(
        a.sim.obstacles[0].position, b.sim.obstacles[0].position,
        rtol=0, atol=1e-6)


# -- fleet on the 2-D mesh -------------------------------------------------


def _fleet_drain(mesh, workdir, arm_shard=None):
    from cup3d_tpu.fleet.server import FleetServer

    faults.clear()
    if arm_shard is not None:
        faults.arm("fleet.shard_loss", step=arm_shard, count=1)
    srv = FleetServer(max_lanes=8, mesh=mesh, workdir=workdir)
    spec = dict(kind="tgv", n=16, nsteps=10, cfl=0.3)
    jids = [srv.submit(f"t{i}", dict(spec)) for i in range(4)]
    srv.drain()
    out = {f"t{i}": (srv._jobs[j].status, int(srv._jobs[j].steps_done),
                     srv._jobs[j].qoi_bytes())
           for i, j in enumerate(jids)}
    return srv, out


def test_fleet_sharded_drain_and_shard_loss(tmp_path):
    """The three drains below, under the canonical compile like the
    megaloop's bitwise gate above: under the default compile the
    sharded drain's QoI bytes are one bit off the unsharded one's
    (fusion rounds the (2 lanes x 2) program's shapes differently)."""
    _run_canonical(
        tmp_path,
        "import sys\n"
        "from tests.test_topology import fleet_drain_three_ways\n"
        "fleet_drain_three_ways(sys.argv[1])\n"
        "print('BITWISE-OK')\n",
        CUP3D_SCAN_K="4")


def fleet_drain_three_ways(tmp):
    """One seeded 4-job TGV mix, drained three ways: unsharded vmap,
    sharded over the (2 lanes x 2) mesh, and sharded with a shard loss
    injected mid-drain.  The sharded drain must be BITWISE against the
    unsharded one (per-lane scan bodies, no cross-lane coupling), and
    the shard-loss drain must still complete every job with the SAME
    QoI bytes — the requeued jobs restart from their spec on surviving
    lanes, and a job's trajectory does not depend on which lane ran
    it."""
    _, base = _fleet_drain(None, os.path.join(tmp, "base"))
    assert all(st == "done" and n == 10 for st, n, _ in base.values())

    mesh = topo.make_mesh2d(lanes=2, x=2, devices=topo.device_order()[:4])
    srv, shard = _fleet_drain(mesh, os.path.join(tmp, "shard"))
    for k in base:
        assert shard[k][:2] == base[k][:2]
        assert shard[k][2] == base[k][2], f"{k}: sharded QoI differs"
    h = srv.health()["mesh"]
    assert h["active"] and h["devices"] == 4 and h["dead_lanes"] == []

    # shard loss at the first K-boundary: shard 1's running jobs are
    # requeued (fleet.elastic_requeues), its lanes fenced, and every
    # job completes with bytes matching the never-failed run
    losses0 = M.counter("fleet.shard_losses").value
    req0 = M.counter("fleet.elastic_requeues").value
    srv2, lost = _fleet_drain(mesh, os.path.join(tmp, "loss"), arm_shard=1)
    assert M.counter("fleet.shard_losses").value == losses0 + 1
    assert M.counter("fleet.elastic_requeues").value >= req0 + 1
    for k in base:
        assert lost[k][:2] == (base[k][0], base[k][1])
        assert lost[k][2] == base[k][2], f"{k}: post-loss QoI differs"
    h2 = srv2.health()["mesh"]
    assert h2["shard_losses"] >= 1 and h2["dead_lanes"]
    # the fenced lanes never serve again
    assert all(ln in srv2.batches[0].dead_lanes
               for ln in h2["dead_lanes"])
