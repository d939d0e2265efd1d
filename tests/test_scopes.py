"""One vocabulary on both timelines (PR 38).

Device: the programs of the uniform driver carry, in the ``op_name`` of
what they lower to, the ``jax.named_scope`` of every operator they run
(``obs/profile.OPERATOR_SCOPES`` and their children), written once in the
functions all three step bodies share.  Host: with no environment
variable set, a profiler session around a driver's steps holds a
``cup3d:`` annotation per profiler section, one ``cup3d:step`` with the
step number and one ``cup3d:read:<site>`` per blocking read.  The seam
those reads go through returns what ``np.asarray`` returns and keeps
time; ``obs/profile.attribute`` reads a recorded trace by those names;
the benchmark's three readers count what the seam raises.  The forest's
programs: ``tests/test_amr_fish.py``.
"""

import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import spec, trace_reduce
from cup3d_tpu.analysis import runtime as R
from cup3d_tpu.models.fish import stefanfish
from cup3d_tpu.obs import metrics as M
from cup3d_tpu.obs import profile as P
from cup3d_tpu.obs import trace as T
from cup3d_tpu.ops.surface import force_integrals_probe_uniform
from cup3d_tpu.sim import megaloop as ml
from cup3d_tpu.sim.simulation import Simulation
from tests._cases import fish_cfg, scope_paths, simulate, tgv_cfg

ITERATIVE = dict(poissonSolver="iterative", poissonTol=1e-6,
                 poissonTolRel=1e-4)


@pytest.fixture(scope="module")
def fish(tmp_path_factory):
    """A 32^3 fish driven per step through the iterative solve, two
    steps in (every program compiled, the QoI pack flowing)."""
    tmp = tmp_path_factory.mktemp("fish")
    driver = Simulation(fish_cfg(tmp, pipelined=False, nsteps=10 ** 6,
                                 **ITERATIVE))
    driver.init()
    for _ in range(2):
        driver.advance(driver.calc_max_timestep())
    return driver


SOLVE = {"PressureProjection/PoissonRHS", "PressureProjection/PoissonRHS/Halo",
         "PressureProjection/PoissonSolve",
         "PressureProjection/PoissonSolve/Laplacian",
         "PressureProjection/PoissonSolve/Preconditioner",
         "PressureProjection/PoissonSolve/Preconditioner/TileSolve",
         "PressureProjection/PoissonSolve/Preconditioner/CoarseSolve",
         "PressureProjection/PoissonSolve/Dots",
         "PressureProjection/Gradient"}


def _uniform_program(driver, name):
    """(thunk, scope paths it must hold, top scopes it may hold) of one
    program of the per-step path, called as its operator calls it."""
    s = driver.sim
    ops = {op.name: op for op in driver.pipeline}
    st, ob = s.state, s.obstacles[0]
    dt = jnp.asarray(s.dt, s.dtype)
    cms = jnp.asarray(ob.centerOfMass, s.dtype)[None]
    if name == "AdvectionDiffusion":
        return (lambda: ops[name]._step(st["vel"], dt=dt,
                                        uinf=s.uinf_device()),
                {"AdvectionDiffusion", "AdvectionDiffusion/Halo"})
    if name == "PressureProjection":
        return (lambda: ops[name]._project(st["vel"], st["chi"], st["udef"],
                                           dt, st["p"]), SOLVE)
    if name == "Penalization":
        op = ops[name]
        return (lambda: (op._penalize(st["vel"], st["chi"], st["udef"],
                                      s.lambda_device(dt), dt),
                         op._penal_force(st["vel"], st["vel"], (ob.chi,),
                                         dt, cms),
                         s._ubody_fn(ob.udef, cms[0], cms[0], cms[0])),
                {"Penalization"})
    if name == "UpdateObstacles":
        op = ops[name]
        return (lambda: op._rigid(
            op._moments((ob.chi,), st["vel"], cms)[0],
            ob.rigid_state_dev(s.dtype), ob.forced_mask_dev(),
            ob.block_mask_dev(), s.uinf_device(), dt),
            {"UpdateObstacles"})
    if name == "CreateObstacles":
        return (lambda: stefanfish._create_dense(
            *ob._dense_inputs(), s.grid, ob._window_shape, ob._raster_box,
            True),
            {"CreateObstacles", "CreateObstacles/Halo"})
    if name == "ComputeForces":
        return (lambda: force_integrals_probe_uniform(
            s.grid, ob, st["vel"], st["p"], ob.chi, ob.sdf, ob.udef, s.nu,
            cms[0], cms[0], cms[0]), {"ComputeForces"})
    assert name == "DtPolicy"
    return (lambda: driver._max_u(st["vel"], s.uinf_device()), {"DtPolicy"})


@pytest.mark.parametrize("name", [
    "CreateObstacles", "AdvectionDiffusion", "UpdateObstacles",
    "Penalization", "PressureProjection", "ComputeForces", "DtPolicy"])
def test_each_program_of_the_per_step_path_carries_its_operator(fish, name):
    thunk, want = _uniform_program(fish, name)
    got = scope_paths(thunk)
    assert want <= got, sorted(want - got)
    # and nothing of another operator's: one program, one owner
    assert {p.split("/")[0] for p in got} == {name}, sorted(got)


def test_the_scan_body_carries_every_operator_it_runs(tmp_path):
    """``make_body_step`` has no scope of its own: the names come from
    the functions it shares with the per-step path and the forest, and
    the solve's children sit under PressureProjection."""
    driver = Simulation(fish_cfg(tmp_path, nsteps=10 ** 6, **ITERATIVE))
    driver.init()
    s = driver.sim
    ob = s.obstacles[0]
    from cup3d_tpu.models.fish.device_midline import freeze_gait

    gait = freeze_gait(ob, s.time, s.dtype)
    one_step = ml.make_body_step(s, ob)
    carry = ml.init_body_carry(s, ob)
    got = scope_paths(lambda: one_step(gait, carry, jnp.asarray(0.3, s.dtype)))
    want = SOLVE | {
        "DtPolicy", "CreateObstacles", "CreateObstacles/Halo",
        "AdvectionDiffusion", "AdvectionDiffusion/Halo", "UpdateObstacles",
        "Penalization", "ComputeForces"}
    assert want <= got, sorted(want - got)
    assert {p.split("/")[0] for p in got} <= set(P.OPERATOR_SCOPES), got
    for child in ("PoissonRHS", "PoissonSolve", "Gradient", "Laplacian",
                  "Preconditioner", "TileSolve", "CoarseSolve", "Dots"):
        assert all(p.startswith("PressureProjection/") for p in got
                   if child in p.split("/")), child


# -- the host's annotations, with no environment variable set ----------------


def host_events(run, directory):
    """[(name, start_ns, end_ns, stats)] of the ``cup3d:`` annotations of
    one ``run()`` under a profiler session like the benchmark's."""
    from jax.profiler import ProfileData

    assert not any(k.startswith("CUP3D_TRACE") for k in os.environ)
    trace_reduce.start(directory)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return [(e.name[len(T.ANNOTATION_PREFIX):], e.start_ns,
             e.start_ns + e.duration_ns, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name.startswith(T.ANNOTATION_PREFIX)]


def test_a_traced_advance_holds_sections_step_and_reads(fish, tmp_path):
    s = fish.sim
    step = int(s.step)
    before = dict(s.profiler.counts)

    def run():
        fish.advance(fish.calc_max_timestep())
        jax.block_until_ready(s.state["vel"])

    events = host_events(run, str(tmp_path))
    names = [e[0] for e in events]
    # one annotation per profiler section that opened
    opened = {k for k, v in s.profiler.counts.items()
              if v > before.get(k, 0)}
    assert opened >= {"CreateObstacles", "AdvectionDiffusion",
                      "UpdateObstacles", "Penalization",
                      "PressureProjection", "ComputeForces", "SyncQoI"}
    for section in opened:
        assert names.count(section) == 1, (section, names)
    # one step, with its number; one blocking read, inside SyncQoI
    (step_ev,) = [e for e in events if e[0] == "step"]
    assert int(step_ev[3]["step_num"]) == step
    reads = [e for e in events if e[0].startswith("read:")]
    assert [e[0] for e in reads] == ["read:qoi-read"]
    (sync,) = [e for e in events if e[0] == "SyncQoI"]
    assert sync[1] <= reads[0][1] and reads[0][2] <= sync[2]
    assert all(step_ev[1] <= e[1] and e[2] <= step_ev[2] for e in events)


def test_a_traced_scan_dispatch_is_one_step_with_its_length(tmp_path):
    driver = Simulation(fish_cfg(tmp_path / "run", scan_k=4, nsteps=4))
    driver.init()
    events = host_events(driver.simulate, str(tmp_path / "trace"))
    steps = [e for e in events if e[0] == "step"]
    assert len(steps) == 1 and int(steps[0][3]["step_num"]) == 0
    assert int(steps[0][3]["scan_k"]) == 4
    assert [e[0] for e in events].count("Megaloop") == 1
    # its rows are read at the flush: one packed read, annotated
    assert [e[0] for e in events if e[0].startswith("read:")] == [
        "read:qoi-read"]


def test_the_stream_s_grouped_reads_go_through_the_seam(tmp_path):
    """Sixteen pipelined steps, one dispatch each: the stream groups
    their packs, and every group it consumes is one visit of the site
    ``stream-read``, timed like the others."""
    before = M.snapshot()
    driver = simulate(tgv_cfg(tmp_path, scan_k=1))
    d = M.delta(before)
    groups = driver._pack_reader.stats["groups_read"]
    assert groups >= 1
    assert d["transfers.sanctioned{site=stream-read}"] == groups
    assert d["transfers.wait_s{site=stream-read}"] > 0
    assert d["transfers.copy_s{site=stream-read}"] > 0


# -- the seam ---------------------------------------------------------------


def test_the_seam_returns_what_asarray_returns_and_keeps_time():
    x = jnp.arange(12.0, dtype=jnp.float32).reshape(3, 4) / 7.0
    before = M.snapshot()
    got = R.blocking_read("qoi-read", x, np.float64)
    want = np.asarray(x, np.float64)
    assert got.dtype == want.dtype and np.array_equal(got, want)
    a, b = R.blocking_read("umax-read", (x[0, 1], x[2, 3]))
    assert (float(a), float(b)) == (float(x[0, 1]), float(x[2, 3]))
    d = M.delta(before)
    for site in ("qoi-read", "umax-read"):
        assert d[f"transfers.sanctioned{{site={site}}}"] == 1
        assert d[f"transfers.wait_s{{site={site}}}"] > 0
        assert d[f"transfers.copy_s{{site={site}}}"] > 0


def test_the_seam_is_a_sanctioned_site_under_the_transfer_guard():
    y = jnp.arange(8.0) + 1.0
    with R.no_implicit_transfers(allow=["umax-read"]):
        assert R.blocking_read("umax-read", y).shape == (8,)
        before = M.snapshot()
        with pytest.raises(RuntimeError, match="qoi-read"):
            R.blocking_read("qoi-read", y)
        # a refused visit is not counted, timed or read
        assert not any(v for k, v in M.delta(before).items()
                       if k.startswith("transfers."))


# -- attribute on a recorded trace -------------------------------------------


def test_attribute_on_a_recorded_trace(tmp_path):
    """A real CPU capture: two scoped programs, a sleep inside a
    ``cup3d:`` span between them.  The CPU's trace names module and
    instruction only, so the scopes come from the join with each
    program's optimised HLO; they sum with ``other`` to the busy time,
    and the gap under the sleep is the span's."""

    @jax.jit
    def advect(x):
        with jax.named_scope("AdvectionDiffusion"):
            y = jnp.tanh(x) * 2.0
            with jax.named_scope("Halo"):
                y = jnp.roll(y, 1, 0) + y
        return y

    # (inside one program XLA may fuse an unscoped operation into a
    # scoped neighbour, and a fusion carries its root's name)
    scale = jax.jit(lambda x: x * 1.5)  # no scope: other

    @jax.jit
    @jax.named_scope("PressureProjection")
    def solve(x):
        def body(_, z):
            with jax.named_scope("Laplacian"):
                return z + 0.1 * (jnp.roll(z, 1, 1) - z)
        with jax.named_scope("PoissonSolve"):
            return jax.lax.fori_loop(0, 4, body, x)

    x = jnp.ones((512, 512), jnp.float32)
    names = {}
    for fn in (advect, scale, solve):
        names.update(P.hlo_op_names(fn.lower(x).compile().as_text()))
        fn(x).block_until_ready()
    ctl = P.CaptureController(plan=None, directory=str(tmp_path),
                              sink=T.TraceSink(enabled=False))
    timer = T.SpanTimer(sink=T.TraceSink(enabled=False))
    with ctl.capture("window") as logdir:
        with T.annotate("cup3d:step", step_num=3):
            with timer("AdvectionDiffusion"):
                y = scale(advect(x))
            with timer("SyncQoI"):
                y.block_until_ready()
                time.sleep(0.05)
            with timer("PressureProjection"):
                solve(y).block_until_ready()
    (path,) = P.find_trace_files(logdir)
    attr = P.attribute(P.load_chrome_trace(path), op_names=names)
    assert {"AdvectionDiffusion", "PressureProjection"} <= set(attr.sections)
    assert "AdvectionDiffusion/Halo" in attr.paths
    assert "PressureProjection/PoissonSolve/Laplacian" in attr.paths
    assert attr.other_ms > 0
    assert sum(attr.sections.values()) + attr.other_ms == pytest.approx(
        attr.total_ms)
    assert sum(attr.paths.values()) == pytest.approx(
        sum(attr.sections.values()))
    assert set(attr.programs) >= {"jit_advect", "jit_solve"}
    # the sleep: at least 50 ms of idle device under the section's name
    assert attr.gaps["cup3d:SyncQoI"] >= 50.0
    assert attr.gaps["cup3d:SyncQoI"] == max(attr.gaps.values())
    # the controller's own harvest read the same file, without the join
    assert ctl.last_attribution.total_ms == pytest.approx(attr.total_ms)


# -- the benchmark's three readers -------------------------------------------


BENCH = spec.load_benchmark()


def reader(name):
    return spec.load_reader(BENCH, name).read


SEAM = {
    "transfers.sanctioned{site=qoi-read}": 20, "transfers.wait_s{site=qoi-read}": 0.5,
    "transfers.copy_s{site=qoi-read}": 0.002,
    "transfers.sanctioned{site=moments-read}": 20,
    "transfers.wait_s{site=moments-read}": 0.1,
    "transfers.copy_s{site=moments-read}": 0.001,
    "transfers.sanctioned{site=tags-read}": 1, "transfers.wait_s{site=tags-read}": 0.06,
    "transfers.copy_s{site=tags-read}": 0.0005,
    # uploads are visits of sanctioned sites too, and no reads
    "transfers.sanctioned{site=dt-upload}": 20,
    "transfers.sanctioned{site=scalar-upload}": 40,
}


@pytest.mark.parametrize("name, obs, steps, want", [
    ("stream.reads_per_step", SEAM, 20, 41 / 20),
    ("stream.read_wait_ms_per_step", SEAM, 20, 660.0 / 20),
    ("stream.read_copy_ms_per_step", SEAM, 20, 3.5 / 20),
    # a window that read nothing through the seam reads 0.0, never None
    ("stream.reads_per_step", {}, 20, 0.0),
    ("stream.read_wait_ms_per_step", {}, 20, 0.0),
    ("stream.read_copy_ms_per_step", {"stream.stall_s{stream=qoi}": 1.0}, 20,
     0.0),
    # a program from before the seam counts its visits and times nothing
    ("stream.reads_per_step",
     {"transfers.sanctioned{site=qoi-read}": 40}, 20, 2.0),
    ("stream.read_wait_ms_per_step",
     {"transfers.sanctioned{site=qoi-read}": 40}, 20, None),
    ("stream.read_copy_ms_per_step",
     {"transfers.sanctioned{site=qoi-read}": 40}, 20, None),
    ("stream.reads_per_step", SEAM, 0, None),
])
def test_the_three_readers(name, obs, steps, want):
    got = reader(name)({"window": {"steps": steps}, "obs": obs})
    assert got == (want if want is None else pytest.approx(want))


def test_the_readers_read_what_a_driver_s_window_raises(fish):
    """One more step of the per-step path: one packed read through the
    seam, its wait and copy on the counters the readers sum."""
    before = M.snapshot()
    fish.advance(fish.calc_max_timestep())
    ctx = {"window": {"steps": 1}, "obs": M.delta(before)}
    assert reader("stream.reads_per_step")(ctx) == 1.0
    assert reader("stream.read_wait_ms_per_step")(ctx) > 0.0
    assert 0.0 < reader("stream.read_copy_ms_per_step")(ctx) < 50.0
