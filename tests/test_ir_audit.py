"""IR audit (cup3d_tpu/analysis/ir.py + audit.py) self-tests.

Each JP rule gets a deliberately-broken fixture asserting it FIRES and a
registry-level ``allow`` annotation asserting it is SUPPRESSIBLE (the IR
analogue of the linter's inline ``# jax-lint: allow`` — IR findings have
no source line, so the annotation lives on the EntryPoint).  The
whole-registry test is the CI gate: every canonical executable must
audit clean (baseline EMPTY, the two designed sharded-solve gathers
annotated with reasons) and JP001 must prove the donated carries of the
uniform, AMR, fleet, and mesh-sharded entries are actually aliased —
or, for the fleet's documented no-donation contract, actually NOT.
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cup3d_tpu.analysis import audit as A
from cup3d_tpu.analysis import ir as IR
from cup3d_tpu.analysis import lint as L
from cup3d_tpu.analysis.runtime import RecompileCounter


def _entry(name, fn, args, donate=(), **kw):
    ep = A.EntryPoint(name, lambda: A.Built(fn, args, donate), **kw)
    with warnings.catch_warnings():
        # the JP001 fixtures donate unaliasable buffers ON PURPOSE;
        # jax's lowering warns about exactly that
        warnings.simplefilter("ignore")
        return A.audit_entry(ep)


def _rules(vs):
    return {v.rule for v in vs}


# -- JP001: donation audit --------------------------------------------------


def _donated_but_copied():
    """A jit whose donated input CANNOT alias any output (dtype
    narrows), so the donation is a silent copy."""
    fn = jax.jit(lambda x: x.astype(jnp.float16), donate_argnums=(0,))
    return fn, (jnp.ones((8, 8), jnp.float32),)


def test_jp001_donated_but_copied_fires():
    fn, args = _donated_but_copied()
    vs, meta = _entry("fixture_jp001", fn, args, donate=(0,))
    bad = [v for v in L.failing(vs) if v.rule == "JP001"]
    # both readings agree: no tf.aliasing_output mark in the lowered
    # module AND no input_output_alias entry in the compiled header
    assert len(bad) == 2, [v.message for v in vs]
    assert meta["donated_params"] == [0]
    assert "tf.aliasing_output" in bad[0].message
    assert "input_output_alias" in bad[1].message


def test_jp001_suppressible():
    fn, args = _donated_but_copied()
    vs, _ = _entry("fixture_jp001", fn, args, donate=(0,),
                   allow={"JP001": "fixture: copy is intended"})
    assert not L.failing(vs)
    assert all(v.suppressed and
               v.suppression_reason == "fixture: copy is intended"
               for v in vs if v.rule == "JP001")


def test_jp001_no_donation_contract_violation_fires():
    """An entry DECLARING the fleet's no-donation contract while its
    executable aliases anyway must fail — contract and IR disagree."""
    fn = jax.jit(lambda x: x * 2.0, donate_argnums=(0,))
    vs, _ = _entry("fixture_contract", fn,
                   (jnp.ones((8, 8), jnp.float32),), donate=(0,),
                   expect_no_donation=True)
    bad = [v for v in L.failing(vs) if v.rule == "JP001"]
    assert bad and "no-donation contract" in bad[0].message


def test_jp001_offset_bookkeeping_pinned():
    """donated_leaf_indices must match jit's left-to-right flattening:
    a 2-leaf donated dict ahead of an undonated scalar aliases flat
    params [0, 1] in BOTH the lowered marks and the compiled header."""
    carry = {"a": jnp.ones((4,), jnp.float32),
             "b": jnp.ones((4, 4), jnp.float32)}
    fn = jax.jit(lambda c, s: {k: v * s for k, v in c.items()},
                 donate_argnums=(0,))
    args = (carry, jnp.float32(2.0))
    assert IR.donated_leaf_indices(args, (0,)) == [0, 1]
    lo = fn.lower(*args)
    assert IR.aliased_params_from_lowered(lo.as_text()) == [0, 1]
    assert IR.aliased_params_from_compiled(
        lo.compile().as_text()) == [0, 1]


# -- JP002: collective safety -----------------------------------------------


def _mesh1d(n=4):
    from jax.sharding import Mesh

    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    return Mesh(np.asarray(jax.devices()[:n]), ("x",))


def _shardmapped(body, mesh):
    from jax.sharding import PartitionSpec as P

    from cup3d_tpu.parallel.compat import shard_map

    return jax.jit(shard_map(body, mesh, in_specs=(P("x"),),
                             out_specs=P("x"), check_vma=False))


def _ring_jaxpr_with_perm(perm):
    """Trace the valid full-cycle ring, then rewrite the ppermute perm
    in place.  jax itself rejects duplicate pairs at trace time and
    crashes .lower() on out-of-range ids, so the broken shapes can only
    reach IR through a hand-edited lowering or a future jax that stops
    validating — exactly the drift JP002 exists to catch."""
    mesh = _mesh1d()
    fn = _shardmapped(
        lambda x: jax.lax.ppermute(
            x, "x", [(i, (i + 1) % 4) for i in range(4)]), mesh)
    closed = jax.make_jaxpr(fn)(jnp.ones((8,), jnp.float32))

    def mutate(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "ppermute":
                eqn.params["perm"] = tuple(perm)
                return True
            for sub in IR._sub_jaxprs(eqn.params):
                if mutate(IR._as_jaxpr(sub)):
                    return True
        return False

    assert mutate(closed.jaxpr)
    return closed


def test_jp002_duplicate_source_fires():
    # shard 0 sends twice, shard 2 receives twice, shard 1 never
    # receives — the pod deadlock shape
    closed = _ring_jaxpr_with_perm([(0, 1), (0, 2), (1, 2), (3, 0)])
    msgs = [v.message for v in IR.audit_jaxpr(closed, "fixture_jp002")
            if v.rule == "JP002"]
    assert any("duplicate source" in m for m in msgs), msgs
    assert any("duplicate destination" in m for m in msgs), msgs


def test_jp002_out_of_range_fires_and_suppresses():
    closed = _ring_jaxpr_with_perm([(0, 7), (1, 0), (2, 1), (3, 2)])
    vs = IR.audit_jaxpr(closed, "fixture_jp002b")
    bad = [v for v in L.failing(vs) if v.rule == "JP002"]
    assert bad and "outside axis x of size 4" in bad[0].message
    # suppressible through the registry-allow path (jaxpr-only entry)
    ep = A.EntryPoint("fixture_jp002b",
                      lambda: A.Built(None, (), jaxpr=closed),
                      allow={"JP002": "fixture"})
    vs2, meta = A.audit_entry(ep)
    assert not L.failing(vs2)
    assert [v.rule for v in vs2] == ["JP002"] and vs2[0].suppressed
    assert meta["donated_params"] == [] and not meta["compiled"]


def test_jp002_valid_ring_is_clean():
    """The parallel/ring.py full-cycle permute — the shape every real
    halo exchange in the tree lowers to — must NOT fire."""
    mesh = _mesh1d()
    fn = _shardmapped(
        lambda x: jax.lax.ppermute(
            x, "x", [(i, (i + 1) % 4) for i in range(4)]), mesh)
    vs, _ = _entry("fixture_ring", fn, (jnp.ones((8,), jnp.float32),))
    assert not [v for v in L.failing(vs) if v.rule == "JP002"]


def test_jp002_unknown_axis_fake_eqn():
    """The missing-axis branch: jax refuses to TRACE an unbound axis
    name, so the walker is exercised on a minimal stub jaxpr — the
    shape of the bug a hand-edited lowering or a future jax version
    could let through."""

    class _Prim:
        name = "psum2"

    class _Eqn:
        primitive = _Prim()
        params = {"axes": ("ghost", 2)}
        invars = ()
        outvars = ()

    class _Jaxpr:
        eqns = [_Eqn()]

    vs = IR.audit_jaxpr(_Jaxpr(), "fixture_axis")
    assert [v.rule for v in vs] == ["JP002"]
    assert "ghost" in vs[0].message


# -- JP004: precision audit -------------------------------------------------


def test_jp004_bf16_reduction_fires_and_suppresses():
    # jnp.sum quietly upcasts to an f32 accumulator even with
    # dtype=bfloat16 (convert -> f32 reduce_sum -> convert), so the
    # genuinely hazardous shape is a contraction that ACCUMULATES in
    # bf16: dot_general with bf16 operands and a bf16 output
    fn = jax.jit(lambda a, b: jax.lax.dot(a, b))
    args = (jnp.ones((8, 8), jnp.bfloat16), jnp.ones((8, 8), jnp.bfloat16))
    vs, _ = _entry("fixture_jp004", fn, args)
    bad = [v for v in L.failing(vs) if v.rule == "JP004"]
    assert bad and "bfloat16" in bad[0].message
    vs2, _ = _entry("fixture_jp004", fn, args,
                    allow={"JP004": "fixture"})
    assert not L.failing(vs2)


def test_jp004_bf16_storage_without_accumulation_is_clean():
    fn = jax.jit(lambda x: (x * 2).astype(jnp.bfloat16))
    vs, _ = _entry("fixture_bf16_store", fn,
                   (jnp.ones((64,), jnp.float32),))
    assert not [v for v in L.failing(vs) if v.rule == "JP004"]


def test_jp004_f64_fires():
    from jax import enable_x64

    with enable_x64():
        fn = jax.jit(lambda x: x * 2.0)
        vs, _ = _entry("fixture_f64", fn,
                       (jnp.ones((8,), jnp.float64),))
    bad = [v for v in L.failing(vs) if v.rule == "JP004"]
    assert bad and "float64" in bad[0].message


# -- JP005: host callbacks --------------------------------------------------


def test_jp005_pure_callback_fires_and_suppresses():
    def step(x):
        y = jax.pure_callback(
            lambda a: np.asarray(a) * 2.0,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y + 1.0

    fn = jax.jit(step)
    args = (jnp.ones((8,), jnp.float32),)
    vs, _ = _entry("fixture_jp005", fn, args)
    bad = [v for v in L.failing(vs) if v.rule == "JP005"]
    assert bad and "pure_callback" in bad[0].message
    vs2, _ = _entry("fixture_jp005", fn, args,
                    allow={"JP005": "fixture"})
    assert not L.failing(vs2)


# -- JP003: sharded materialization -----------------------------------------


def test_jp003_all_gather_fires_only_inside_shard_map():
    mesh = _mesh1d()
    fn = _shardmapped(
        lambda x: jax.lax.all_gather(x, "x", axis=0, tiled=True), mesh)
    vs, _ = _entry("fixture_jp003", fn, (jnp.ones((8,), jnp.float32),))
    assert [v.rule for v in L.failing(vs)] == ["JP003"]
    vs2, _ = _entry("fixture_jp003", fn, (jnp.ones((8,), jnp.float32),),
                    allow={"JP003": "fixture"})
    assert not L.failing(vs2)


# -- the whole-tree gate ----------------------------------------------------


def test_registry_audits_clean_and_donations_aliased():
    """The CI gate (the lint.sh audit stage in test form): the full
    entry-point registry runs with ZERO failing findings against the
    EMPTY shipped baseline, JP001 proves every donated carry leaf of
    the uniform/AMR/mesh-sharded executables aliased (and the fleet's
    documented no-donation contract honored), and the audit itself
    dispatches no steady-state device work (RecompileCounter sees no
    compile through the jit call path — tracing and AOT lowering only).
    """
    with RecompileCounter() as rc:
        violations, metas = A.run_audit(
            baseline_path=A.default_baseline_path())
    assert not L.failing(violations), [
        v.format() for v in L.failing(violations)]
    # the shipped baseline is EMPTY: nothing may be baselined
    assert not any(v.baselined for v in violations)
    # every annotation carries a reason
    assert all(v.suppression_reason for v in violations if v.suppressed)

    by_name = {m["entry"]: m for m in metas}
    donated_entries = ("uniform_tgv_megaloop", "uniform_fish_megaloop",
                      "amr_tgv_megastep", "sharded_tgv_megaloop")
    for name in donated_entries:
        assert not by_name[name]["skipped"], name
        assert by_name[name]["donated_params"], name
    for name in ("fleet_advance", "fleet_reseed_upload"):
        assert not by_name[name]["skipped"], name
        assert by_name[name]["donated_params"] == [], name
    # compiled-header cross-check ran where promised
    assert by_name["uniform_tgv_megaloop"]["compiled"]
    assert by_name["amr_tgv_megastep"]["compiled"]
    assert by_name["sharded_tgv_megaloop"]["compiled"]
    # the gate is trace/AOT only: the audited executables never RUN.
    # Sim construction legitimately executes a couple of tiny one-time
    # helpers (the AMR builder's 'tags' jit); none of the megaloop /
    # advance / upload / solve entries may appear in the call path.
    assert rc.total_compiles <= 2, rc.compiles
    hot = ("megaloop", "advance", "upload", "solve", "step")
    assert not [n for n in rc.compiles
                if any(h in n for h in hot)], rc.compiles


def test_summary_line_shape():
    vs, metas = _entry("fixture_sum",
                       jax.jit(lambda x: x + 1),
                       (jnp.ones((4,), jnp.float32),))
    import json

    line = A.summary_line(vs, [metas], A.default_baseline_path())
    d = json.loads(line)
    assert d["audit"] == "ir" and d["baseline_size"] == 0
    assert d["failing"] == 0
