"""Analysis subsystem: AST lint (cup3d_tpu/analysis/lint.py) self-tests
on synthetic fixtures, the whole-package gate, and the runtime sanitizers
(recompile counter + transfer guard) on a live uniform-grid sim.

The whole-package test IS the CI gate the ISSUE asks for: the shipped
tree must lint clean (every finding annotated with a reason or baselined,
baseline <= 15 entries)."""

import json
import subprocess
import sys

import numpy as np
import pytest

from cup3d_tpu.analysis import lint as L
from cup3d_tpu.analysis import runtime as R
from cup3d_tpu.analysis.rules import RULES

HOT = "cup3d_tpu/sim/fixture.py"  # path inside the hot-module scope


def _failing(src, path=HOT):
    return L.failing(L.lint_source(src, path))


def _rules(vs):
    return {v.rule for v in vs}


def _cli(*args):
    """``python -m cup3d_tpu.analysis`` in a process of its own (the
    whole package lints in ~5 s; the limit is for a hung child)."""
    return subprocess.run(
        [sys.executable, "-m", "cup3d_tpu.analysis", *args],
        capture_output=True, text=True, timeout=120)


# -- per-rule fixtures: firing and suppressed ------------------------------


def test_jx001_host_sync_fires_and_suppresses():
    src = (
        "import jax.numpy as jnp\n"
        "class D:\n"
        "    def advance(self, dt):\n"
        "        v = self._step(self.v, dt)\n"
        "        return float(jnp.sum(v))\n"
    )
    vs = _failing(src)
    assert _rules(vs) == {"JX001"} and vs[0].func == "D.advance"
    ok = src.replace(
        "        return float(",
        "        # jax-lint: allow(JX001, designed sync point)\n"
        "        return float(",
    )
    all_vs = L.lint_source(ok, HOT)
    assert not L.failing(all_vs)
    assert any(v.rule == "JX001" and v.suppressed and
               v.suppression_reason == "designed sync point"
               for v in all_vs)


def test_jx001_not_fired_outside_hot_scope():
    src = (
        "import jax.numpy as jnp\n"
        "def advance(v):\n"
        "    return float(jnp.sum(v))\n"
    )
    assert not _failing(src, "cup3d_tpu/models/fixture.py")
    # hot module, but a cold function name
    src2 = src.replace("def advance", "def postprocess")
    assert not _failing(src2, HOT)


def test_jx001_sanctioned_transfer_is_the_annotation():
    """A `with sanctioned_transfer(tag):` block suppresses JX001 inside
    it — the lint and the runtime guard share one marker."""
    src = (
        "import jax.numpy as jnp\n"
        "from cup3d_tpu.analysis.runtime import sanctioned_transfer\n"
        "class D:\n"
        "    def advance(self, dt):\n"
        "        v = self._step(self.v, dt)\n"
        "        with sanctioned_transfer('umax-read'):\n"
        "            return float(jnp.sum(v))\n"
    )
    vs = L.lint_source(src, HOT)
    assert not L.failing(vs)
    hit = [v for v in vs if v.rule == "JX001"]
    assert hit and all("umax-read" in v.suppression_reason for v in hit)


def test_jx002_jit_without_donation_fires_and_suppresses():
    src = (
        "import jax\n"
        "def build(f):\n"
        "    step = jax.jit(f)\n"
        "    return step\n"
    )
    vs = _failing(src)
    assert _rules(vs) == {"JX002"}
    fixed = src.replace("jax.jit(f)", "jax.jit(f, donate_argnums=(0,))")
    assert not _failing(fixed)
    allowed = src.replace(
        "    step = jax.jit(f)",
        "    # jax-lint: allow(JX002, restore path reuses the input)\n"
        "    step = jax.jit(f)",
    )
    assert not _failing(allowed)


def test_jx003_traced_branch_fires_and_static_is_clean():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def step(x, dt):\n"
        "    if dt > 0:\n"
        "        x = x + dt\n"
        "    return x\n"
    )
    vs = _failing(src)
    assert _rules(vs) == {"JX003"}
    # static argname or an `is None` structural check are both fine
    static = src.replace("@jax.jit",
                         "@partial(jax.jit, static_argnames=('dt',))")
    static = "from functools import partial\n" + static
    assert not _failing(static)
    none_chk = src.replace("if dt > 0:", "if dt is not None:")
    assert not _failing(none_chk)


def test_jx004_loop_construction_fires_and_suppresses():
    src = (
        "import jax.numpy as jnp\n"
        "class D:\n"
        "    def advance(self, obs):\n"
        "        outs = []\n"
        "        for item in obs:\n"
        "            outs.append(jnp.asarray(item.slots))\n"
        "        return outs\n"
    )
    vs = _failing(src)
    assert _rules(vs) == {"JX004"}
    allowed = src.replace(
        "            outs.append(",
        "            # jax-lint: allow(JX004, n_obs <= 2 (tiny upload))\n"
        "            outs.append(",
    )
    all_vs = L.lint_source(allowed, HOT)
    assert not L.failing(all_vs)
    # nested parens survive in the recorded reason
    assert any(v.suppression_reason == "n_obs <= 2 (tiny upload)"
               for v in all_vs)


def test_jx005_float64_literal_fires_and_suppresses():
    src = (
        "import jax.numpy as jnp\n"
        "TBL = jnp.zeros((4, 4), dtype=jnp.float64)\n"
    )
    vs = _failing(src)
    assert _rules(vs) == {"JX005"}
    allowed = src.replace(
        "TBL = ",
        "# jax-lint: allow(JX005, host-side accumulation table)\n"
        "TBL = ",
    )
    assert not _failing(allowed)
    # host-side modules (io/) are out of scope for JX005
    assert not _failing(src, "cup3d_tpu/io/fixture.py")


def test_jx007_jit_in_loop_fires_and_suppresses():
    src = (
        "import jax\n"
        "class D:\n"
        "    def _prepare(self, fns):\n"
        "        outs = []\n"
        "        for f in fns:\n"
        "            outs.append(jax.jit(f))\n"
        "        return outs\n"
    )
    vs = _failing(src)
    assert _rules(vs) == {"JX007"}
    # comprehensions are loops too (the order_dispatch shape)
    comp = (
        "import jax\n"
        "class D:\n"
        "    def _prepare(self, f):\n"
        "        return [jax.jit(f, static_argnums=(1,)) for _ in (0, 1)]\n"
    )
    assert _rules(_failing(comp)) == {"JX007"}
    allowed = src.replace(
        "            outs.append(jax.jit(f))",
        "            # jax-lint: allow(JX007, built once at init)\n"
        "            outs.append(jax.jit(f))",
    )
    assert not _failing(allowed)
    # cold module scope: no finding
    assert not _failing(src, "cup3d_tpu/io/fixture.py")


def test_jx007_jit_in_rebuild_fires_and_cached_builder_is_clean():
    """An adaptation-path function (rebuild/adapt names) may not build
    jits even outside a lexical loop; a cache-keyed builder is clean."""
    src = (
        "import jax\n"
        "class D:\n"
        "    def _rebuild(self):\n"
        "        self._step = jax.jit(self._step_impl, "
        "donate_argnums=(0,))\n"
    )
    vs = _failing(src)
    assert _rules(vs) == {"JX007"} and vs[0].func == "D._rebuild"
    clean = src.replace("def _rebuild", "def _build_bucket_executables")
    assert not _failing(clean)


def test_jx006_unsynced_timing_fires_and_sync_is_clean():
    src = (
        "import time\n"
        "def run(advance):\n"
        "    t0 = time.perf_counter()\n"
        "    advance()\n"
        "    t1 = time.perf_counter()\n"
        "    return t1 - t0\n"
    )
    # in-package manual timing also trips JX008 (round 9) — scope the
    # JX006 assertions to that rule
    vs = _failing(src, "cup3d_tpu/io/fixture.py")
    assert "JX006" in _rules(vs)
    synced = src.replace(
        "    t1 = ",
        "    jax.block_until_ready(state)\n    t1 = ",
    )
    assert not any(v.rule == "JX006"
                   for v in _failing(synced, "cup3d_tpu/io/fixture.py"))


def test_jx008_manual_timing_fires_suppresses_and_scopes():
    src = (
        "import time\n"
        "def run(advance):\n"
        "    t0 = time.perf_counter()\n"
        "    advance()\n"
        "    jax.block_until_ready(state)\n"
        "    t1 = time.perf_counter()\n"
        "    return t1 - t0\n"
    )
    # one finding per function, at the FIRST perf_counter read
    # (JX020 also fires — perf_counter is double-jeopardy by design)
    vs = [v for v in _failing(src, "cup3d_tpu/io/fixture.py")
          if v.rule == "JX008"]
    assert [v.rule for v in vs] == ["JX008"] and vs[0].line == 3
    assert "obs spans" in vs[0].message
    # annotation suppresses it
    ok = src.replace(
        "    t0 = ",
        "    # jax-lint: allow(JX008, native counter feeding the obs "
        "registry)\n    t0 = ",
    )
    assert not any(v.rule == "JX008"
                   for v in _failing(ok, "cup3d_tpu/io/fixture.py"))
    # the obs layer itself is exempt — it IS the span implementation
    assert not any(v.rule == "JX008"
                   for v in _failing(src, "cup3d_tpu/obs/fixture.py"))
    # bench.py / validation harnesses (outside the package) are exempt
    assert not any(v.rule == "JX008" for v in _failing(src, "bench.py"))


def test_jx009_swallowed_exception_fires_and_suppresses():
    src = (
        "def stage(x):\n"
        "    try:\n"
        "        x.copy_to_host_async()\n"
        "    except Exception:\n"
        "        pass\n"
        "    return x\n"
    )
    vs = _failing(src)
    assert _rules(vs) == {"JX009"}
    # log-and-drop is still a drop
    logged = src.replace("        pass", "        print('copy failed')")
    assert _rules(_failing(logged)) == {"JX009"}
    # module-level handlers are in scope too
    mod = (
        "try:\n"
        "    import fastpath\n"
        "except ImportError:\n"
        "    pass\n"
    )
    vs = _failing(mod, "cup3d_tpu/io/fixture.py")
    assert _rules(vs) == {"JX009"} and vs[0].func == "<module>"
    # annotation suppresses it with a reason
    ok = src.replace(
        "    except Exception:",
        "    # jax-lint: allow(JX009, capability probe: the blocking\n"
        "    # read downstream is the fallback)\n"
        "    except Exception:",
    )
    all_vs = L.lint_source(ok, HOT)
    assert not L.failing(all_vs)
    assert any(v.rule == "JX009" and "capability probe" in
               (v.suppression_reason or "") for v in all_vs)


def test_jx009_observable_handlers_and_resilience_are_clean():
    # a counter bump makes the drop observable: clean
    counted = (
        "def stage(x, c):\n"
        "    try:\n"
        "        x.copy_to_host_async()\n"
        "    except Exception:\n"
        "        c.inc()\n"
        "    return x\n"
    )
    assert not _failing(counted)
    # latching into state is observable too
    latched = counted.replace("        c.inc()", "        self._err = 1")
    assert not _failing(latched)
    # re-raise and sentinel-return are handling, not dropping
    reraised = counted.replace("        c.inc()", "        raise")
    assert not _failing(reraised)
    sentinel = counted.replace("        c.inc()", "        return None")
    assert not _failing(sentinel)
    # the resilience subsystem is exempt by path (its handlers ARE the
    # counted degradation policy), and so is code outside the package
    dropped = counted.replace("        c.inc()", "        pass")
    assert _rules(_failing(dropped)) == {"JX009"}
    assert not _failing(dropped, "cup3d_tpu/resilience/fixture.py")
    assert not _failing(dropped, "bench.py")


def test_jx010_obstacle_staging_fires_and_suppresses():
    """Per-step re-staging of a loop-carried obstacle/driver attribute
    ({np,jnp}.asarray on self.X/ob.X/s.X in a step-loop function)."""
    src = (
        "import jax.numpy as jnp\n"
        "class Penalization:\n"
        "    def __call__(self, dt):\n"
        "        s = self.sim\n"
        "        return jnp.asarray(s.lambda_penal, s.dtype)\n"
    )
    # models/ is INSIDE the JX010 scope (the operator __call__s are the
    # per-step obstacle path) even though it is outside HOT_MODULE_RE
    vs = _failing(src, "cup3d_tpu/models/fixture.py")
    assert _rules(vs) == {"JX010"}
    assert vs[0].func == "Penalization.__call__"
    assert "host->device upload" in vs[0].message
    # the device->host direction fires too, scoped to JX010
    host = src.replace("jnp.asarray(s.lambda_penal, s.dtype)",
                       "np.asarray(ob.transVel)")
    vs = _failing(host, "cup3d_tpu/models/fixture.py")
    assert _rules(vs) == {"JX010"}
    assert "device->host read" in vs[0].message
    # annotation suppresses with the reason recorded
    ok = src.replace(
        "        return jnp.asarray(",
        "        # jax-lint: allow(JX010, host fallback path: the mirror\n"
        "        # is fresh by construction)\n"
        "        return jnp.asarray(",
    )
    all_vs = L.lint_source(ok, "cup3d_tpu/models/fixture.py")
    assert not L.failing(all_vs)
    assert any(v.rule == "JX010" and "host fallback" in
               (v.suppression_reason or "") for v in all_vs)


def test_jx010_scoping_and_precision():
    src = (
        "import jax.numpy as jnp\n"
        "class D:\n"
        "    def advance(self, dt):\n"
        "        return jnp.asarray(self.lam, self.dtype)\n"
    )
    # hot sim/ scope fires; io/ (outside the obstacle pipeline) and a
    # cold function name do not
    assert _rules(_failing(src)) == {"JX010"}
    assert not _failing(src, "cup3d_tpu/io/fixture.py")
    cold = src.replace("def advance", "def checkpoint_restore")
    assert not _failing(cold)
    # precision: a local value is not loop-carried state, and host
    # metadata reads never cross the boundary
    local = src.replace("jnp.asarray(self.lam, self.dtype)",
                        "jnp.asarray(dt, self.dtype)")
    assert not _failing(local)
    meta = src.replace("jnp.asarray(self.lam, self.dtype)",
                       "jnp.asarray(self.chi.shape)")
    assert not _failing(meta)


def test_jx010_sanctioned_transfer_is_the_annotation():
    """A `with sanctioned_transfer(tag):` block is the shared designed-
    transfer marker for JX010 exactly as for JX001."""
    src = (
        "import jax.numpy as jnp\n"
        "from cup3d_tpu.analysis.runtime import sanctioned_transfer\n"
        "class D:\n"
        "    def advance(self, dt):\n"
        "        with sanctioned_transfer('scalar-upload'):\n"
        "            return jnp.asarray(self.lam, self.dtype)\n"
    )
    vs = L.lint_source(src, HOT)
    assert not L.failing(vs)
    hit = [v for v in vs if v.rule == "JX010"]
    assert hit and all("scalar-upload" in v.suppression_reason for v in hit)


def test_jx011_bf16_reduction_fires_and_suppresses():
    """A reduction over bf16-tainted operands with no explicit
    accumulator dtype (the round-12 mixed-precision hazard)."""
    src = (
        "import jax.numpy as jnp\n"
        "def residual_norm(r):\n"
        "    rb = r.astype(jnp.bfloat16)\n"
        "    return jnp.sum(rb * rb)\n"
    )
    vs = _failing(src, "cup3d_tpu/ops/fixture.py")
    assert _rules(vs) == {"JX011"}
    assert vs[0].func == "residual_norm"
    # module-level dtype aliases (_BF = jnp.bfloat16) taint too
    alias = (
        "import jax.numpy as jnp\n"
        "_BF = jnp.bfloat16\n"
        "def dot(a, b):\n"
        "    return jnp.vdot(a.astype(_BF), b)\n"
    )
    assert _rules(_failing(alias, "cup3d_tpu/ops/fixture.py")) == {"JX011"}
    # annotation suppresses with the reason recorded
    ok = src.replace(
        "    return jnp.sum(",
        "    # jax-lint: allow(JX011, diagnostic dump, never feeds the\n"
        "    # stopping test)\n"
        "    return jnp.sum(",
    )
    all_vs = L.lint_source(ok, "cup3d_tpu/ops/fixture.py")
    assert not L.failing(all_vs)
    assert any(v.rule == "JX011" and "diagnostic dump" in
               (v.suppression_reason or "") for v in all_vs)


def test_jx011_explicit_accumulator_and_scope_are_clean():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def residual_norm(S, r):\n"
        "    rb = r.astype(jnp.bfloat16)\n"
        "    a = jnp.sum(rb * rb, dtype=jnp.float32)\n"
        "    b = jnp.dot(S, rb, preferred_element_type=jnp.float32)\n"
        "    r32 = rb.astype(jnp.float32)\n"
        "    c = jnp.sum(r32 * r32)\n"
        "    return a, b, c\n"
    )
    # named accumulator, and an f32 re-cast launders the taint
    assert not _failing(src, "cup3d_tpu/ops/fixture.py")
    # pure-f32 code never fires
    f32 = (
        "import jax.numpy as jnp\n"
        "def residual_norm(r):\n"
        "    return jnp.sum(r * r)\n"
    )
    assert not _failing(f32, "cup3d_tpu/ops/fixture.py")
    # scope: only cup3d_tpu/ops/ carries the mixed-precision policy
    bf_elsewhere = (
        "import jax.numpy as jnp\n"
        "def residual_norm(r):\n"
        "    rb = r.astype(jnp.bfloat16)\n"
        "    return jnp.sum(rb * rb)\n"
    )
    assert not _failing(bf_elsewhere, HOT)


def test_jx012_profiler_outside_obs_fires_suppresses_and_scopes():
    """Direct jax.profiler use outside cup3d_tpu/obs/ (round 13): the
    profiler session is process-global, so ad-hoc captures collide with
    obs windows and never reach the attribution parser."""
    src = (
        "import jax\n"
        "def capture(fn):\n"
        "    jax.profiler.start_trace('/tmp/t')\n"
        "    fn()\n"
        "    jax.profiler.stop_trace()\n"
    )
    # one finding per function, at the FIRST profiler touch
    vs = _failing(src)
    assert [v.rule for v in vs] == ["JX012"] and vs[0].line == 3
    assert "obs" in vs[0].message
    # imports fire too — module-level and from-imports
    imp = "import jax.profiler\n"
    vs = _failing(imp, "cup3d_tpu/sim/fixture.py")
    assert _rules(vs) == {"JX012"} and vs[0].func == "<module>"
    frm = (
        "from jax.profiler import TraceAnnotation\n"
        "def mark(name):\n"
        "    return TraceAnnotation(name)\n"
    )
    assert _rules(_failing(frm)) == {"JX012"}
    # annotation suppresses with the reason recorded
    ok = src.replace(
        "    jax.profiler.start_trace",
        "    # jax-lint: allow(JX012, standalone capture tool, no obs\n"
        "    # window can be open here)\n"
        "    jax.profiler.start_trace",
    )
    all_vs = L.lint_source(ok, HOT)
    assert not L.failing(all_vs)
    assert any(v.rule == "JX012" and "standalone capture" in
               (v.suppression_reason or "") for v in all_vs)
    # the obs layer OWNS the profiler — exempt by path
    assert not _failing(src, "cup3d_tpu/obs/profile.py")
    # bench.py / tools (outside the package) are exempt
    assert not any(v.rule == "JX012" for v in _failing(src, "bench.py"))
    assert not any(v.rule == "JX012"
                   for v in _failing(src, "tools/capture.py"))


def test_jx012_obs_channel_use_is_clean():
    """Going through the obs channel never fires: CONTROLLER windows
    and obs.trace.annotate are the sanctioned path."""
    src = (
        "from cup3d_tpu.obs import profile as obs_profile\n"
        "from cup3d_tpu.obs import trace as obs_trace\n"
        "def capture(fn):\n"
        "    with obs_profile.CONTROLLER.capture('bench'):\n"
        "        ann = obs_trace.annotate('cup3d:Megastep')\n"
        "        fn()\n"
    )
    assert not any(v.rule == "JX012" for v in _failing(src))


def test_jx013_lane_loop_fires_suppresses_and_scopes():
    """Per-lane device dispatch inside a scenario-axis loop in fleet/
    (round 14): B lanes exist to be advanced by ONE vmapped dispatch;
    a per-lane device loop pays the host overhead B times over."""
    FLEET = "cup3d_tpu/fleet/fixture.py"
    src = (
        "import jax.numpy as jnp\n"
        "class Batch:\n"
        "    def fixup(self):\n"
        "        for lane in range(self.nlanes):\n"
        "            self.carry[lane] = jnp.where(self.mask, 0.0, 1.0)\n"
    )
    vs = _failing(src, FLEET)
    assert _rules(vs) == {"JX013"}
    assert "vectorize" in vs[0].message
    # comprehensions over the lane axis fire too
    comp = (
        "import jax.numpy as jnp\n"
        "def kes(lane_carries):\n"
        "    return [jnp.sum(c) for c in lane_carries]\n"
    )
    assert _rules(_failing(comp, FLEET)) == {"JX013"}
    # jitwrapper-convention calls (self._advance(...)) count as device
    wrap = (
        "class Batch:\n"
        "    def run(self):\n"
        "        for lane in range(self.nlanes):\n"
        "            self.carry = self._advance(self.carry, lane)\n"
    )
    assert _rules(_failing(wrap, FLEET)) == {"JX013"}
    # annotation suppresses with the reason recorded
    ok = src.replace(
        "            self.carry[lane]",
        "            # jax-lint: allow(JX013, one-off debug dump, not a\n"
        "            # dispatch path)\n"
        "            self.carry[lane]",
    )
    all_vs = L.lint_source(ok, FLEET)
    assert not L.failing(all_vs)
    assert any(v.rule == "JX013" and "debug dump" in
               (v.suppression_reason or "") for v in all_vs)
    # scoped to fleet/: the same loop elsewhere is other rules' business
    assert not any(v.rule == "JX013" for v in _failing(src, HOT))


def test_jx013_host_only_lane_loops_are_clean():
    """Assembly and fan-out loops touch no device value — never fire;
    nor do device calls in loops over non-axis names."""
    FLEET = "cup3d_tpu/fleet/fixture.py"
    host = (
        "import numpy as np\n"
        "class Batch:\n"
        "    def fanout(self):\n"
        "        for lane, job in enumerate(self.jobs):\n"
        "            job.record(lane, np.asarray(self.rows[lane]))\n"
    )
    assert not any(v.rule == "JX013" for v in _failing(host, FLEET))
    other_axis = (
        "import jax.numpy as jnp\n"
        "def pad(blocks):\n"
        "    return [jnp.zeros(3) for _ in range(len(blocks))]\n"
    )
    assert not any(v.rule == "JX013"
                   for v in _failing(other_axis, FLEET))


def test_jx015_batch_reassembly_fires_suppresses_and_scopes():
    """Per-tick host reassembly of the full lane-stacked batch in
    fleet/ (round 17): a reseed must replace ONE lane via the jitted
    .at[lane].set upload, not restack the whole B-lane pytree."""
    FLEET = "cup3d_tpu/fleet/fixture.py"
    src = (
        "import jax.numpy as jnp\n"
        "class Batch:\n"
        "    def reseed_lane(self, lane, solo):\n"
        "        self.u = jnp.stack([c['u'] for c in self.parts])\n"
    )
    vs = _failing(src, FLEET)
    assert _rules(vs) == {"JX015"}
    assert ".at[lane].set" in vs[0].message
    # the repo's own assembly helpers stack by construction — any
    # dotted prefix fires inside a tick/reseed/dispatch function
    helper = (
        "from cup3d_tpu.fleet import batch as FB\n"
        "class Batch:\n"
        "    def tick(self):\n"
        "        self.carry = FB.stack_carries(self.solos)\n"
    )
    assert _rules(_failing(helper, FLEET)) == {"JX015"}
    # np.concatenate in a dispatch path is the same hazard
    cat = (
        "import numpy as np\n"
        "def dispatch_all(rows):\n"
        "    return np.concatenate(rows)\n"
    )
    assert _rules(_failing(cat, FLEET)) == {"JX015"}
    # annotation suppresses with the reason recorded
    ok = src.replace(
        "        self.u = jnp.stack",
        "        # jax-lint: allow(JX015, one-shot debug snapshot, not\n"
        "        # the reseed upload path)\n"
        "        self.u = jnp.stack",
    )
    all_vs = L.lint_source(ok, FLEET)
    assert not L.failing(all_vs)
    assert any(v.rule == "JX015" and "debug snapshot" in
               (v.suppression_reason or "") for v in all_vs)
    # scoped to fleet/: the same code elsewhere is other rules' business
    assert not any(v.rule == "JX015" for v in _failing(src, HOT))


def test_jx015_construction_and_upload_paths_are_clean():
    """Batch CONSTRUCTION stacks legitimately (assemble/__init__ don't
    match the per-tick name gate), the jitted per-lane upload is the
    sanctioned path, and bare non-array stack() calls never fire."""
    FLEET = "cup3d_tpu/fleet/fixture.py"
    build = (
        "import jax.numpy as jnp\n"
        "from cup3d_tpu.fleet import batch as FB\n"
        "class Batch:\n"
        "    def __init__(self, solos):\n"
        "        self.carry = FB.stack_carries(solos)\n"
        "    def assemble(self, parts):\n"
        "        return jnp.stack(parts)\n"
    )
    assert not any(v.rule == "JX015" for v in _failing(build, FLEET))
    upload = (
        "class Batch:\n"
        "    def reseed_lane(self, lane, solo):\n"
        "        self.carry = {k: self.carry[k].at[lane].set(solo[k])\n"
        "                      for k in solo}\n"
    )
    assert not any(v.rule == "JX015" for v in _failing(upload, FLEET))
    # a bare/unknown-root stack() is not an array op
    bare = (
        "def tick(frames, stack):\n"
        "    return stack(frames)\n"
    )
    assert not any(v.rule == "JX015" for v in _failing(bare, FLEET))


def test_jx016_sharded_materialization_fires_suppresses_and_scopes():
    """Full-array materialization in a sharded step path (round 18):
    device_get / np.asarray / bare single-arg device_put inside a
    step/advance/dispatch/megaloop function of sim|fleet|parallel is a
    cross-shard gather under the 2-D mesh."""
    PAR = "cup3d_tpu/parallel/fixture.py"
    src = (
        "import jax\n"
        "class Driver:\n"
        "    def advance_megaloop(self):\n"
        "        rows = jax.device_get(self.carry['vel'])\n"
        "        return rows\n"
    )
    vs = _failing(src, PAR)
    assert _rules(vs) == {"JX016"}
    assert "cross-shard gather" in vs[0].message
    pull = (
        "import numpy as np\n"
        "class Batch:\n"
        "    def dispatch(self):\n"
        "        return np.asarray(self.carry['vel'])\n"
    )
    assert _rules(_failing(pull, "cup3d_tpu/fleet/fixture.py")) == {
        "JX016"}
    # single-arg device_put re-places onto the default device — a
    # gather when the input was sharded; the explicit-sharding form
    # is the sanctioned placement and stays clean
    put = (
        "import jax\n"
        "def step(carry):\n"
        "    return jax.device_put(carry)\n"
    )
    assert _rules(_failing(put, "cup3d_tpu/parallel/fixture.py")) == {
        "JX016"}
    placed = put.replace("jax.device_put(carry)",
                         "jax.device_put(carry, sharding)")
    assert not any(v.rule == "JX016"
                   for v in _failing(placed, "cup3d_tpu/parallel/f.py"))
    # annotation suppresses with the reason recorded
    ok = src.replace(
        "        rows = jax.device_get",
        "        # jax-lint: allow(JX016, designed postmortem read)\n"
        "        rows = jax.device_get",
    )
    all_vs = L.lint_source(ok, PAR)
    assert not L.failing(all_vs)
    assert any(v.rule == "JX016" and "postmortem" in
               (v.suppression_reason or "") for v in all_vs)
    # scoped: the same pull outside sim|fleet|parallel never fires
    assert not any(v.rule == "JX016"
                   for v in _failing(src, "cup3d_tpu/obs/fixture.py"))


def test_jx016_sanctioned_and_builder_paths_are_clean():
    """The designed sync points (sanctioned_transfer blocks) and the
    once-per-topology builder factories (make_*/build_*) are exempt;
    inner step closures of a builder stay covered."""
    sanctioned = (
        "import numpy as np\n"
        "from cup3d_tpu.analysis.runtime import sanctioned_transfer\n"
        "class Driver:\n"
        "    def advance(self):\n"
        "        with sanctioned_transfer('qoi-read'):\n"
        "            vals = np.asarray(self.pack)\n"
        "        return vals\n"
    )
    assert not any(v.rule == "JX016" for v in _failing(sanctioned, HOT))
    builder = (
        "import numpy as np\n"
        "def make_tgv_step(s):\n"
        "    h = np.asarray(s.grid.h)\n"
        "    def step(carry, cfl):\n"
        "        return carry\n"
        "    return step\n"
    )
    assert not any(v.rule == "JX016" for v in _failing(builder, HOT))
    leaky = builder.replace(
        "        return carry\n",
        "        return np.asarray(carry)\n",
    )
    assert any(v.rule == "JX016" for v in _failing(leaky, HOT))


def test_jx017_hardware_peak_fires_suppresses_and_scopes():
    """Hand-typed hardware peak literal in a roofline/bench path
    (round 19): a spec-sheet constant (197e12, 819e9) in a bench*.py
    file or a roofline/peak-named function bakes one device kind into
    MFU/HBM math that runs on every backend."""
    src = (
        "def report(flops, bytes_, t):\n"
        "    return {'mfu': flops / t / 197e12,\n"
        "            'hbm': bytes_ / t / 819e9}\n"
    )
    # fires by PATH scope: any bench*.py, module and function level
    vs = _failing(src, "bench.py")
    assert _rules(vs) == {"JX017"} and len(vs) == 2
    assert "device_peaks" in vs[0].message
    # fires by FUNCTION-name scope anywhere in the package
    fn = src.replace("def report", "def roofline_place")
    assert _rules(_failing(fn, HOT)) == {"JX017"}
    # out of scope: same literal in a plain function off the bench path
    assert not any(v.rule == "JX017" for v in _failing(src, HOT))
    # exact powers of ten are unit conversions, never hardware claims
    units = (
        "def roofline_place(flops, t):\n"
        "    return {'gflops': flops / t / 1e9,\n"
        "            'tflops': flops / t / 1e12}\n"
    )
    assert not any(v.rule == "JX017" for v in _failing(units, HOT))
    # the sanctioned home: obs/costs.py is path-exempt even for
    # peak-named functions
    assert not any(v.rule == "JX017"
                   for v in _failing(fn, "cup3d_tpu/obs/costs.py"))
    # annotation suppresses with the reason recorded
    ok = src.replace(
        "    return {'mfu': flops / t / 197e12,\n"
        "            'hbm': bytes_ / t / 819e9}\n",
        "    # jax-lint: allow(JX017, documented reference ceiling)\n"
        "    return {'mfu': flops / t / 197e12,\n"
        "            'hbm': bytes_ / t / 819e9}\n",
    )
    all_vs = L.lint_source(ok, "bench.py")
    fails = [v for v in L.failing(all_vs) if v.rule == "JX017"]
    # the allow-comment binds to its line: the first literal's line is
    # annotated, the second still fails — both behaviors on record
    assert len(fails) == 1 and any(
        v.rule == "JX017" and v.suppressed for v in all_vs)


def test_jx017_in_tree_roofline_paths_are_clean():
    """The burn-down stays burned down: bench.py and the obs/tools
    trees carry no unannotated hardware-peak literal (the peak table in
    obs/costs.py is path-exempt by design)."""
    out = _cli("--rules", "JX017", "bench.py", "cup3d_tpu/", "tools/", "-q")
    assert out.returncode == 0, out.stdout + out.stderr


def test_jx018_raw_collective_fires_suppresses_and_scopes():
    """Raw communicating collective outside the parallel/ seam (round
    20): every psum/ppermute/all_gather call site must live in
    cup3d_tpu/parallel/ so the IR audit has ONE seam to prove axis and
    permutation invariants on."""
    src = (
        "import jax\n"
        "def halo(x):\n"
        "    y = jax.lax.ppermute(x, 'x', [(0, 1)])\n"
        "    return jax.lax.psum(y, 'x')\n"
    )
    vs = _failing(src)
    assert _rules(vs) == {"JX018"} and len(vs) == 2
    assert "parallel/ seam" in vs[0].message
    # bare from-import names fire too
    bare = (
        "from jax.lax import all_gather\n"
        "def widen(x):\n"
        "    return all_gather(x, 'x', axis=0, tiled=True)\n"
    )
    assert _rules(_failing(bare)) == {"JX018"}
    # the sanctioned home: any parallel/ module is exempt by path
    assert not _failing(src, "cup3d_tpu/parallel/ring.py")
    assert not _failing(src, "cup3d_tpu/parallel/collectives.py")
    # a wrapper object's method with a colliding leaf name never fires
    wrapped = (
        "def widen(coll, x):\n"
        "    return coll.all_gather(x)\n"
    )
    assert not _failing(wrapped)
    # axis_index communicates nothing and is exempt by omission
    idx = (
        "import jax\n"
        "def lane(x):\n"
        "    return jax.lax.axis_index('lanes')\n"
    )
    assert not _failing(idx)
    # annotation suppresses with the reason recorded
    ok = src.replace(
        "    y = jax.lax.ppermute",
        "    # jax-lint: allow(JX018, staging for parallel/ migration)\n"
        "    y = jax.lax.ppermute",
    )
    all_vs = L.lint_source(ok, HOT)
    fails = [v for v in L.failing(all_vs) if v.rule == "JX018"]
    assert len(fails) == 1 and any(
        v.rule == "JX018" and v.suppressed and
        v.suppression_reason == "staging for parallel/ migration"
        for v in all_vs)


def test_jx019_aot_seam_fires_suppresses_and_scopes():
    """Direct AOT compile / jit-warmup outside the store seam (round
    21): a chained ``.lower().compile()`` or an immediately-invoked
    ``jit(f)(...)`` produces an executable the persistent store never
    sees — recompiled every boot, invisible to aot.* telemetry."""
    chain = (
        "def warm(fn, x):\n"
        "    return fn.lower(x).compile()\n"
    )
    vs = _failing(chain)
    assert _rules(vs) == {"JX019"} and len(vs) == 1
    assert "store seam" in vs[0].message
    # immediately-invoked jit warmups fire, dotted and bare
    warmup = (
        "import jax\n"
        "def warm(f, x):\n"
        "    return jax.jit(f)(x)\n"
    )
    assert _rules(_failing(warmup)) == {"JX019"}
    bare = (
        "from jax import jit\n"
        "def warm(f, x):\n"
        "    return jit(f)(x)\n"
    )
    assert _rules(_failing(bare)) == {"JX019"}
    # the seam itself and the cost-harvest module are path-exempt
    assert not _failing(chain, "cup3d_tpu/aot/store.py")
    assert not _failing(chain, "cup3d_tpu/obs/costs.py")
    # split lowering (audit.py IR introspection) never fires
    split = (
        "def audit(fn, x):\n"
        "    lowered = fn.lower(x)\n"
        "    return lowered.as_text()\n"
    )
    assert not _failing(split)
    # a bound jit called later is the normal (legal) pattern
    bound = (
        "import jax\n"
        "def bind(f, x):\n"
        "    g = jax.jit(f)\n"
        "    return g(x)\n"
    )
    assert not _failing(bound)
    # str.lower() chains never fire (no .compile() on the result call)
    strings = (
        "def norm(s):\n"
        "    return s.strip().lower()\n"
    )
    assert not _failing(strings)
    # annotation suppresses with the reason recorded
    ok = chain.replace(
        "    return fn.lower",
        "    # jax-lint: allow(JX019, one-shot debug harness)\n"
        "    return fn.lower",
    )
    all_vs = L.lint_source(ok, HOT)
    assert not [v for v in L.failing(all_vs) if v.rule == "JX019"]
    assert any(
        v.rule == "JX019" and v.suppressed and
        v.suppression_reason == "one-shot debug harness"
        for v in all_vs)


def test_jx020_raw_clock_fires_suppresses_and_scopes():
    """Raw clock read outside obs/trace.py (round 22): a stray
    time.monotonic() is a second clock domain — its intervals cannot
    be subtracted against trace timestamps without silent skew, which
    would break the phase-decomposition partition invariant."""
    mono = (
        "import time\n"
        "def f():\n"
        "    return time.monotonic()\n"
    )
    vs = _failing(mono)
    assert _rules(vs) == {"JX020"} and len(vs) == 1
    assert "obs.trace.now()" in vs[0].message
    # bare names from `from time import ...` resolve, aliased or not
    bare = (
        "from time import monotonic as mono\n"
        "def f():\n"
        "    return mono()\n"
    )
    assert _rules(_failing(bare)) == {"JX020"}
    # an aliased module import and the *_ns variants resolve too
    ns = (
        "import time as T\n"
        "def f():\n"
        "    return T.time_ns()\n"
    )
    assert _rules(_failing(ns)) == {"JX020"}
    # perf_counter is double-jeopardy by design: JX008 (private timing
    # channel) and JX020 (clock domain) both fire
    pc = (
        "import time\n"
        "def f():\n"
        "    return time.perf_counter()\n"
    )
    assert "JX020" in _rules(_failing(pc))
    # one finding per function: the first read covers the section
    two = (
        "import time\n"
        "def f():\n"
        "    t0 = time.monotonic()\n"
        "    work()\n"
        "    return time.monotonic() - t0\n"
    )
    assert len([v for v in _failing(two) if v.rule == "JX020"]) == 1
    # module-level reads fire too
    toplevel = "import time\nSTART = time.monotonic()\n"
    assert "JX020" in _rules(_failing(toplevel))
    # the clock seam itself is path-exempt; outside the package the
    # rule never engages (bench.py is a timing harness)
    assert not _failing(mono, "cup3d_tpu/obs/trace.py")
    assert not _failing(mono, "bench.py")
    # the sanctioned route never fires (no time-module read at all)
    sanctioned = (
        "from cup3d_tpu.obs import trace as OT\n"
        "def f():\n"
        "    return OT.now()\n"
    )
    assert not _failing(sanctioned)
    # annotation suppresses with the reason recorded
    ok = mono.replace(
        "    return time.monotonic()",
        "    # jax-lint: allow(JX020, third-party API needs its epoch)\n"
        "    return time.monotonic()",
    )
    all_vs = L.lint_source(ok, HOT)
    assert not [v for v in L.failing(all_vs) if v.rule == "JX020"]
    assert any(
        v.rule == "JX020" and v.suppressed and
        v.suppression_reason == "third-party API needs its epoch"
        for v in all_vs)


def test_jx021_status_mutation_fires_suppresses_and_scopes():
    """Fleet job status mutated outside the journal-logging seam
    (round 23): a transition the write-ahead journal never records is
    a job a crash-restart can silently lose or double."""
    FLEET = "cup3d_tpu/fleet/fixture.py"
    src = (
        "class S:\n"
        "    def poke(self, job):\n"
        "        job.status = 'done'\n"
    )
    vs = _failing(src, FLEET)
    assert _rules(vs) == {"JX021"} and len(vs) == 1
    assert "_job_terminal" in vs[0].message
    # every sanctioned seam stays clean — those are the functions whose
    # transitions the journal records (directly or via _job_terminal)
    for seam in ("__init__", "retire", "reseed_lane", "cancel",
                 "_prepare", "_install_replayed_job"):
        clean = (
            "class S:\n"
            f"    def {seam}(self, job):\n"
            "        job.status = 'running'\n"
        )
        assert not _failing(clean, FLEET), seam
    # one finding PER assignment: each is its own unjournaled edge
    two = (
        "def swap(a, b):\n"
        "    a.status = 'done'\n"
        "    b.status = 'failed'\n"
    )
    assert len([v for v in _failing(two, FLEET)
                if v.rule == "JX021"]) == 2
    # annotated and augmented assignment forms resolve too
    ann = (
        "def poke(job):\n"
        "    job.status: str = 'done'\n"
    )
    assert _rules(_failing(ann, FLEET)) == {"JX021"}
    # module-level mutations fire
    toplevel = "JOB.status = 'done'\n"
    assert "JX021" in _rules(_failing(toplevel, FLEET))
    # a plain local named status is not a job transition
    local = (
        "def poke(job):\n"
        "    status = 'done'\n"
        "    return status\n"
    )
    assert not _failing(local, FLEET)
    # the rule is scoped to fleet/ — sim code has no fleet jobs
    assert not _failing(src, HOT)
    # annotation suppresses with the reason recorded
    ok = src.replace(
        "        job.status = 'done'",
        "        # jax-lint: allow(JX021, test fixture freezes state)\n"
        "        job.status = 'done'",
    )
    all_vs = L.lint_source(ok, FLEET)
    assert not [v for v in L.failing(all_vs) if v.rule == "JX021"]
    assert any(
        v.rule == "JX021" and v.suppressed and
        v.suppression_reason == "test fixture freezes state"
        for v in all_vs)


@pytest.mark.parametrize("rule", ["JX018", "JX019", "JX020", "JX021"])
def test_package_is_clean_with_empty_baseline(rule):
    """The burn-downs stay burned down, with the baseline EMPTY for
    each rule: no raw collective call site outside
    parallel/collectives.py (JX018); every compile-producing call site
    through cup3d_tpu/aot/ or the exempt obs/costs.py harvest (JX019);
    every clock read through obs.trace.now()/wall() (JX020); every
    fleet status transition through a journal-logging seam (JX021)."""
    out = _cli("--rules", rule, "--no-baseline", "cup3d_tpu/", "-q")
    assert out.returncode == 0, out.stdout + out.stderr


def test_jx014_wallclock_duration_fires_and_suppresses():
    """Wall-clock subtraction used as a duration (round 16): NTP slews
    and steps time.time(), so a latency computed from it can go
    negative and corrupts the SLO histograms."""
    direct = (
        "import time\n"
        "def f(t0):\n"
        "    return time.time() - t0\n"
    )
    vs = [v for v in _failing(direct) if v.rule == "JX014"]
    assert _rules(vs) == {"JX014"}
    assert "monotonic" in vs[0].message
    # names assigned from wall-clock reads are tainted transitively
    tainted = (
        "import time\n"
        "def f():\n"
        "    t0 = time.time()\n"
        "    work()\n"
        "    t1 = time.time()\n"
        "    return t1 - t0\n"
    )
    assert "JX014" in _rules(_failing(tainted))
    # `from time import time` leaves a bare name behind; still resolved
    bare = (
        "from time import time\n"
        "def f(start):\n"
        "    return time() - start\n"
    )
    assert "JX014" in _rules(_failing(bare))
    # datetime.now() differences are the same hazard
    dt = (
        "import datetime\n"
        "def f(prev):\n"
        "    return datetime.datetime.now() - prev\n"
    )
    assert _rules(_failing(dt)) == {"JX014"}
    # attribute targets taint too (self.t0 = time.time())
    attr = (
        "import time\n"
        "class C:\n"
        "    def f(self):\n"
        "        self.t0 = time.time()\n"
        "        return time.time() - self.t0\n"
    )
    assert "JX014" in _rules(_failing(attr))
    # annotation suppresses with the reason recorded
    ok = direct.replace(
        "    return time.time() - t0",
        "    # jax-lint: allow(JX014, test fixture, not a latency)\n"
        "    return time.time() - t0",
    )
    all_vs = L.lint_source(ok, HOT)
    assert not [v for v in L.failing(all_vs) if v.rule == "JX014"]
    assert any(v.rule == "JX014" and "test fixture" in
               (v.suppression_reason or "") for v in all_vs)


def test_jx014_timestamps_and_monotonic_clocks_are_clean():
    """time.time() as a TIMESTAMP (no subtraction), constant-offset
    timestamp arithmetic, and perf_counter durations never fire."""
    stamp = (
        "import time\n"
        "def f():\n"
        "    return {'wall_time': time.time()}\n"
    )
    assert not any(v.rule == "JX014" for v in _failing(stamp))
    # "an hour ago" is timestamp arithmetic, not a duration
    offset = (
        "import time\n"
        "def f():\n"
        "    return time.time() - 3600\n"
    )
    assert not any(v.rule == "JX014" for v in _failing(offset))
    # the monotonic clock is the SANCTIONED duration source
    mono = (
        "import time\n"
        "def f(t0):\n"
        "    return time.perf_counter() - t0\n"
    )
    assert not any(v.rule == "JX014" for v in _failing(mono))
    # scoped to the package: tooling outside cup3d_tpu/ is exempt
    direct = (
        "import time\n"
        "def f(t0):\n"
        "    return time.time() - t0\n"
    )
    assert not any(v.rule == "JX014"
                   for v in _failing(direct, "tools/fixture.py"))


def test_wrapped_annotation_comment_blocks_parse():
    """A multi-line (wrapped) annotation applies to the next code line."""
    src = (
        "import jax.numpy as jnp\n"
        "class D:\n"
        "    def advance(self, v):\n"
        "        v = self._step(v)\n"
        "        # jax-lint: allow(JX001, a reason long enough that the\n"
        "        # author had to wrap it over two comment lines)\n"
        "        return float(jnp.sum(v))\n"
    )
    vs = L.lint_source(src, HOT)
    assert not L.failing(vs)
    assert any("wrap it over two comment lines" in (v.suppression_reason
               or "") for v in vs)


# -- baseline mechanism ----------------------------------------------------


def test_baseline_roundtrip_and_count_cap(tmp_path):
    src = (
        "import jax.numpy as jnp\n"
        "class D:\n"
        "    def advance(self, dt):\n"
        "        v = self._step(self.v, dt)\n"
        "        a = float(jnp.sum(v))\n"
        "        b = float(jnp.max(v))\n"
        "        return a + b\n"
    )
    vs = L.lint_source(src, HOT)
    assert len(L.failing(vs)) == 2
    bp = str(tmp_path / "baseline.json")
    L.write_baseline(vs, bp)
    data = json.loads(open(bp).read())
    assert data["entries"][0]["count"] == 2

    fresh = L.lint_source(src, HOT)
    L.apply_baseline(fresh, L.load_baseline(bp))
    assert not L.failing(fresh)

    # a NEW violation in the same function exceeds the baselined count
    grown = src.replace("return a + b",
                        "c = float(jnp.min(v))\n        return a + b + c")
    regress = L.lint_source(grown, HOT)
    L.apply_baseline(regress, L.load_baseline(bp))
    assert len(L.failing(regress)) == 1


# -- the whole-package gate ------------------------------------------------


def _package_root():
    import cup3d_tpu

    return cup3d_tpu.__path__[0]


def test_package_lints_clean_with_reasons():
    """The shipped tree has zero non-baselined violations, every inline
    annotation carries a reason, and the baseline stays small (<= 15
    entries, each justified) — the ISSUE acceptance gate."""
    bp = L.default_baseline_path()
    vs = L.lint_paths([_package_root()], baseline_path=bp)
    bad = L.failing(vs)
    assert not bad, "\n".join(v.format() for v in bad)
    for v in vs:
        if v.suppressed:
            assert v.suppression_reason, f"reason-less annotation: {v.format()}"
    entries = json.load(open(bp))["entries"]
    assert len(entries) <= 15
    assert all(e.get("reason", "").strip() and "TODO" not in e["reason"]
               for e in entries)


def test_cli_exits_zero_on_package():
    proc = _cli(_package_root(), "-q")
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_lists_rules():
    proc = _cli("--list-rules")
    assert proc.returncode == 0
    for rid in RULES:
        assert rid in proc.stdout


# -- runtime sanitizers ----------------------------------------------------


def test_transfer_guard_blocks_and_sanction_allows():
    import jax
    import jax.numpy as jnp

    x = jnp.arange(8.0)
    with R.no_implicit_transfers():
        with pytest.raises(Exception):
            np.asarray(x + 1.0)  # implicit device->host read
        with R.sanctioned_transfer("qoi-read"):
            assert np.asarray(x).shape == (8,)
    # allowlist: an unknown tag raises AT the site, naming the tag
    with R.no_implicit_transfers(allow=["umax-read"]):
        with pytest.raises(RuntimeError, match="qoi-read"):
            with R.sanctioned_transfer("qoi-read"):
                pass
    del jax


def test_recompile_counter_flags_per_step_retrace():
    import jax
    import jax.numpy as jnp

    with R.RecompileCounter() as rc:
        f = jax.jit(lambda x, n: x * n)
        x = jnp.ones(4)
        for n in range(3):
            f(x, float(n))  # fresh WEAK-TYPE constant: OK, same trace
        assert rc.compiles.get("<lambda>", 0) <= 1

        g = jax.jit(lambda x: x + 1)
        for n in range(1, 4):
            g(jnp.ones(n))  # shape leak: one compile per step
    assert rc.compiles["<lambda>"] >= 3
    with pytest.raises(AssertionError, match="recompile budget"):
        rc.assert_steady_state()


def _tgv_cfg(tmp_path, **kw):
    from cup3d_tpu.config import SimulationConfig

    base = dict(
        bpdx=2, bpdy=2, bpdz=2, levelMax=1, levelStart=0,
        extent=2 * np.pi, CFL=0.3, nu=0.02, nsteps=5, rampup=0,
        initCond="taylorGreen", verbose=False, freqDiagnostics=0,
        path4serialization=str(tmp_path),
    )
    base.update(kw)
    return SimulationConfig(**base)


#: the documented steady-state allowlist for the uniform driver
#: (VALIDATION.md "Analysis subsystem: sanitizer contract")
UNIFORM_ALLOWLIST = ("umax-read", "dt-upload", "uinf-upload", "qoi-read")


def test_uniform_step_compiles_once_and_runs_transfer_clean(tmp_path):
    """The ISSUE acceptance case: a uniform-grid sim steps 5+ times with
    EXACTLY one compile per jitted step function (dt rides as a traced
    scalar) and the loop is clean under jax.transfer_guard('disallow')
    with the documented allowlist."""
    with R.RecompileCounter() as rc:
        from cup3d_tpu.sim.simulation import Simulation

        sim = Simulation(_tgv_cfg(tmp_path))
        sim.init()
        # first step compiles every kernel once
        sim.advance(sim.calc_max_timestep())
        with R.no_implicit_transfers(allow=UNIFORM_ALLOWLIST):
            for _ in range(5):
                sim.advance(sim.calc_max_timestep())
    assert rc.compiles, "counter saw no jitted functions"
    rc.assert_steady_state(budget=1)
    # the step really ran through the instrumented kernels every step
    assert max(rc.calls.values()) >= 6
    # and only documented transfer sites fired
    assert set(R.TRANSFER_SITES) <= set(UNIFORM_ALLOWLIST) | {
        "scalar-upload", "moments-read", "uinf-upload",
        # device-dt AMR runs under recovery sync once per snapshot
        # cadence (resilience/recovery.py; VALIDATION.md round 10)
        "resilience-snapshot",
        # megaloop carry seeding: once per entry into scan mode, never
        # per step (sim/simulation.py advance_megaloop; round 11)
        "scan-carry-upload",
        # the stream's grouped reads and the adaptation pass's tags go
        # through the blocking-read seam since PR 38 (pipelined runs,
        # the forest): sites of other drivers' loops, never this one's
        "stream-read", "tags-read",
    }


def test_debug_modes_scope_and_restore():
    import jax

    old_nan = jax.config.jax_debug_nans
    old_leak = jax.config.jax_check_tracer_leaks
    with R.debug_nans():
        assert jax.config.jax_debug_nans
    assert jax.config.jax_debug_nans == old_nan
    with R.tracer_leak_checks():
        assert jax.config.jax_check_tracer_leaks
    assert jax.config.jax_check_tracer_leaks == old_leak
