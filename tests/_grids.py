"""Grids, forests and random fields that more than one test file builds,
and the one way the tests dispatch an operator on sharded tables: under
``jit``, as every driver does."""

import jax
import jax.numpy as jnp
import numpy as np

from cup3d_tpu.analysis.ir import iter_eqns
from cup3d_tpu.grid.blocks import BlockGrid
from cup3d_tpu.grid.octree import Octree, TreeConfig
from cup3d_tpu.grid.uniform import BC, UniformGrid
from cup3d_tpu.ops import amr_ops
from cup3d_tpu.parallel.forest import (
    ShardedForest,
    bind_step_executable,
    make_block_mesh,
)

BS = 8

#: every octant of the base level refined, then one of level 1: 3 levels
THREE_LEVEL = (
    (0, 0, 0, 0), (0, 1, 0, 0), (0, 0, 1, 0), (0, 0, 0, 1),
    (0, 1, 1, 0), (0, 1, 0, 1), (0, 0, 1, 1), (0, 1, 1, 1),
    (1, 1, 1, 1),
)


def unit_cube(bc, n=32):
    return UniformGrid((n, n, n), (1.0, 1.0, 1.0), (bc,) * 3)


def two_level_grid(extent=1.0):
    """2x2x2 periodic blocks, one octant refined: 7 coarse + 8 fine."""
    t = Octree(TreeConfig((2, 2, 2), 2, (True,) * 3), 0)
    t.refine((0, 0, 0, 0))
    t.assert_balanced()
    return BlockGrid(t, (float(extent),) * 3, (BC.periodic,) * 3, bs=BS)


def mixed_grid(bc=(BC.periodic,) * 3,
               refine=((0, 0, 0, 0), (0, 1, 1, 1))):
    """The forest tests' grid: 2x2x2 blocks, up to three levels."""
    tree = Octree(
        TreeConfig((2, 2, 2), 3, tuple(b == BC.periodic for b in bc)), 0
    )
    for k in refine:
        tree.refine(k)
    tree.assert_balanced()
    return BlockGrid(tree, (1.0, 1.0, 1.0), bc)


def forest(g, n=8):
    return ShardedForest(g, make_block_mesh(jax.devices()[:n]))


def randn(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def rand(g, ncomp=0, seed=0):
    """A random scalar (or ``ncomp``-vector) field on ``g``'s blocks."""
    return randn(np.random.default_rng(seed), g.nb, BS, BS, BS,
                 *((ncomp,) if ncomp else ()))


def assemble(tab, kind, field, *comp):
    """One halo assembly (``kind``: scalar, vector, component) as ONE
    program, bound the way sim/amr.py binds its steps.  Called eagerly,
    an operator on sharded tables compiles and dispatches every
    primitive as an 8-device program of its own: 74 s for one 15-block
    assemble, 0.7 s jitted."""
    return bind_step_executable(
        lambda a: getattr(tab, "assemble_" + kind)(a, BS, *comp))(field)


def laplacian(geom, field, tab, ftab):
    """The refluxed Laplacian as one program.  The tables are this
    call's arguments, so the forest's lazy ``fo.flux_tables`` is built
    here and not inside the trace.  Jitted on both sides of a
    comparison the sharded result is bitwise the single-device one; an
    eager and a jitted one are an ulp apart."""
    return bind_step_executable(
        lambda a, *tabs: amr_ops.laplacian_blocks(geom, a, *tabs),
        tab, ftab)(field)


def assert_dots_highest(jaxpr, at_least):
    """Every dot_general of the program asks for Precision.HIGHEST: on
    the TPU the default rounds float32 operands to bfloat16, which no
    CPU run can see."""
    dots = [eqn for eqn, _, _ in iter_eqns(jaxpr)
            if eqn.primitive.name == "dot_general"]
    assert len(dots) >= at_least
    for eqn in dots:
        p = eqn.params["precision"]
        assert p is not None, eqn
        for side in p if isinstance(p, tuple) else (p,):
            assert side == jax.lax.Precision.HIGHEST, eqn
