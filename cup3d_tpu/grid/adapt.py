"""Mesh adaptation: tag -> 2:1 validation -> refine/compress -> reshard
(reference MeshAdaptation, main.cpp:5023-5583).

TPU-native shape: adaptation is a *layout change*.  The host tags blocks
from per-block scores, enforces the reference's 2:1/octet rules
(ValidStates, main.cpp:5330-5492), builds a new Octree + BlockGrid, and
emits a TransferPlan of static index arrays.  Device data moves through
three batched primitives:

- copy: gather surviving blocks into their new slots;
- refine: quadratic tensor-product prolongation of each refined block's
  1-ghost lab into 8 children (reference RefineBlocks' 2nd-order Taylor
  stencil, main.cpp:5493-5565, expressed as three dense matmuls);
- compress: 2x2x2 average of 8 children into the parent (main.cpp:5272-5328).

This replaces the reference's in-place surgery + LoadBalancer block
migration (main.cpp:4660-5022): the new Hilbert-ordered layout IS the
balanced partition, and XLA moves the bytes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from cup3d_tpu.grid.blocks import BlockGrid, assemble_scalar_lab, assemble_vector_lab
from cup3d_tpu.grid.octree import Key, Octree, TreeConfig

_HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# tagging + 2:1 validation (host)
# ---------------------------------------------------------------------------


def tag_states(
    grid: BlockGrid,
    score: np.ndarray,
    rtol: float,
    ctol: float,
    level_max_block: Optional[np.ndarray] = None,
) -> Dict[Key, str]:
    """Per-leaf desired state from per-block scores (TagLoadedBlock,
    main.cpp:5566-5582): 'R' if score > rtol, 'C' if score < ctol, else 'L'.
    level_max_block: optional per-block cap on refinement level (the
    levelMaxVorticity mechanism, main.cpp:8540-8602)."""
    states: Dict[Key, str] = {}
    lm = grid.tree.cfg.level_max
    for s, key in enumerate(grid.keys):
        lvl = key[0]
        cap = lm - 1 if level_max_block is None else int(level_max_block[s])
        if score[s] > rtol and lvl < cap:
            states[key] = "R"
        elif score[s] < ctol and lvl > 0:
            states[key] = "C"
        else:
            states[key] = "L"
    return states


@jax.named_scope("AdaptMesh")
def device_tags(
    vort: jnp.ndarray,
    near: jnp.ndarray,
    level: jnp.ndarray,
    rtol: float,
    ctol: float,
    level_max: int,
    level_max_vort: int,
    chi_inf: bool,
) -> jnp.ndarray:
    """Jitted mirror of tag_states: per-block int8 tag (1=R, -1=C, 0=L).

    Inputs are per-slot arrays over the padded bucket: `vort` the
    vorticity score, `near` the grad-chi mask, `level` the octree level
    of each slot (padding slots carry level 0 and score 0, so they tag
    'L').  Composition matches sim/amr.py adapt_mesh exactly: the
    per-block level cap is levelMax-1 near the body and
    levelMaxVorticity-1 away from it (always), while the force-refine
    score -> inf near the body applies only under bAdaptChiGradient
    (`chi_inf`).  Comparisons are strict and refine wins over coarsen,
    matching tag_states' elif chain, so host and device tags agree
    bitwise whenever rtol/ctol are exactly representable in the score
    dtype.
    """
    score = vort.astype(jnp.float32)
    nearb = near.astype(bool)
    if chi_inf:
        score = jnp.where(nearb, jnp.inf, score)
    cap = jnp.where(nearb, level_max - 1, level_max_vort - 1)
    refine = (score > rtol) & (level < cap)
    coarsen = (score < ctol) & (level > 0)
    return jnp.where(refine, 1, jnp.where(coarsen, -1, 0)).astype(jnp.int8)


def states_from_tags(grid: BlockGrid, tags: np.ndarray) -> Dict[Key, str]:
    """Decode device_tags output (host-side) into the {key: 'R'/'C'/'L'}
    dict that valid_states/adapt consume."""
    sym = {1: "R", -1: "C", 0: "L"}
    return {key: sym[int(tags[s])] for s, key in enumerate(grid.keys)}


def valid_states(tree: Octree, states: Dict[Key, str]) -> Dict[Key, str]:
    """Enforce refinement/compression legality (ValidStates,
    main.cpp:5330-5492):

    1. refinement propagates: a leaf one level coarser next to a refining
       block must refine too (keeps 26-neighbor 2:1 after refinement);
    2. a refining or finer neighbor vetoes a neighbor's compression;
    3. compression requires the full octet of same-level sibling leaves,
       all marked 'C'.
    """
    st = dict(states)
    levels = sorted({k[0] for k in tree.leaves}, reverse=True)

    # 1: sweep fine -> coarse so forced refinements cascade downward
    for l in levels:
        for key in [k for k in tree.leaves if k[0] == l and st.get(k) == "R"]:
            _, i, j, k_ = key
            for dk in (-1, 0, 1):
                for dj in (-1, 0, 1):
                    for di in (-1, 0, 1):
                        if di == dj == dk == 0:
                            continue
                        w = tree.wrap(l, (i + di, j + dj, k_ + dk))
                        if w is None:
                            continue
                        parent = (l - 1, w[0] // 2, w[1] // 2, w[2] // 2)
                        if l > 0 and parent in tree.leaves:
                            st[parent] = "R"

    # 2+3: compression legality
    for key in list(tree.leaves):
        if st.get(key) != "C":
            continue
        l, i, j, k_ = key
        ok = True
        sibs = tree.siblings(key)
        for s in sibs:
            if s not in tree.leaves or st.get(s) != "C":
                ok = False
                break
        if ok:
            # neighbors of the parent region must end up <= level l
            for dk in (-1, 0, 1):
                for dj in (-1, 0, 1):
                    for di in (-1, 0, 1):
                        if not ok:
                            break
                        if di == dj == dk == 0:
                            continue
                        for s in sibs:
                            w = tree.wrap(l, (s[1] + di, s[2] + dj, s[3] + dk))
                            if w is None:
                                continue
                            nk = (l, *w)
                            if nk in [tuple(x) for x in sibs]:
                                continue
                            # finer coverage (at any depth — the reference's
                            # CheckFiner, main.cpp:5381, is tree state), or a
                            # same-level neighbor that will refine, vetoes
                            if tree.covered_finer(nk):
                                ok = False
                                break
                            if nk in tree.leaves and st.get(nk) == "R":
                                ok = False
                                break
        if not ok:
            for s in sibs:
                if s in tree.leaves and st.get(s) == "C":
                    st[s] = "L"
    return st


# ---------------------------------------------------------------------------
# transfer plan + device data movement
# ---------------------------------------------------------------------------


@dataclass
class TransferPlan:
    new_grid: BlockGrid
    copy_src: jnp.ndarray  # (ncopy,)
    copy_dst: jnp.ndarray
    ref_src: jnp.ndarray  # (nref,) old slots to prolong
    ref_dst: jnp.ndarray  # (nref, 8) new child slots (octant-ordered)
    com_src: jnp.ndarray  # (ncom, 8) old child slots (octant-ordered)
    com_dst: jnp.ndarray  # (ncom,) new parent slots
    refine_w: jnp.ndarray  # (2*bs, bs+2) prolongation matrix


def _octant_children(key: Key) -> List[Key]:
    """Children ordered so octant index = di*4 + dj*2 + dk."""
    l, i, j, k = key
    return [
        (l + 1, 2 * i + di, 2 * j + dj, 2 * k + dk)
        for di in (0, 1)
        for dj in (0, 1)
        for dk in (0, 1)
    ]


def adapt(grid: BlockGrid, states: Dict[Key, str]) -> Optional[TransferPlan]:
    """Build the new grid + transfer plan; None if nothing changes."""
    states = valid_states(grid.tree, states)
    refining = [k for k, s in states.items() if s == "R"]
    compressing = {k for k, s in states.items() if s == "C"}
    if not refining and not compressing:
        return None

    new_tree = Octree(grid.tree.cfg, 0)
    new_tree.leaves.clear()
    ref_children: Dict[Key, List[Key]] = {}
    done_octets: Set[Key] = set()
    com_groups: List[Tuple[Key, List[Key]]] = []  # (parent, children)

    for key in grid.keys:
        s = states.get(key, "L")
        if s == "R":
            kids = _octant_children(key)
            ref_children[key] = kids
            for c in kids:
                new_tree.leaves[c] = None
        elif s == "C":
            l, i, j, k = key
            parent = (l - 1, i // 2, j // 2, k // 2)
            if parent in done_octets:
                continue
            done_octets.add(parent)
            kids = _octant_children(parent)
            com_groups.append((parent, kids))
            new_tree.leaves[parent] = None
        else:
            new_tree.leaves[key] = None

    new_tree.assert_balanced()
    new_grid = BlockGrid(new_tree, grid.extent, grid.bc, grid.bs)

    copy_src, copy_dst = [], []
    for key in grid.keys:
        if states.get(key, "L") == "L" and key in new_grid.slot:
            copy_src.append(grid.slot[key])
            copy_dst.append(new_grid.slot[key])

    ref_src = [grid.slot[k] for k in ref_children]
    ref_dst = [[new_grid.slot[c] for c in kids] for kids in ref_children.values()]

    com_src = [[grid.slot[c] for c in kids] for _, kids in com_groups]
    com_dst = [new_grid.slot[p] for p, _ in com_groups]

    bs = grid.bs
    W = np.zeros((2 * bs, bs + 2), np.float32)
    from cup3d_tpu.grid.blocks import _WQ

    for f in range(2 * bs):
        p = f // 2 + 1  # lab coordinate of the parent cell (1-ghost lab)
        for d, wq in zip((-1, 0, 1), _WQ[f & 1]):
            W[f, p + d] += wq

    as_i32 = lambda a, shape: jnp.asarray(
        np.asarray(a, np.int64).reshape(shape), jnp.int32
    )
    return TransferPlan(
        new_grid=new_grid,
        copy_src=as_i32(copy_src, (-1,)),
        copy_dst=as_i32(copy_dst, (-1,)),
        ref_src=as_i32(ref_src, (-1,)),
        ref_dst=as_i32(ref_dst, (-1, 8)),
        com_src=as_i32(com_src, (-1, 8)),
        com_dst=as_i32(com_dst, (-1,)),
        refine_w=jnp.asarray(W),
    )


def _upsample3(lab: jnp.ndarray, W: jnp.ndarray) -> jnp.ndarray:
    """(n, bs+2,bs+2,bs+2) labs -> (n, 2bs,2bs,2bs)."""
    out = lab
    for axis in (1, 2, 3):
        out = jnp.moveaxis(
            jnp.tensordot(out, W, axes=([axis], [1]), precision=_HI), -1, axis
        )
    return out


def transfer_field(
    grid: BlockGrid, plan: TransferPlan, field: jnp.ndarray
) -> jnp.ndarray:
    """Move a scalar (nb,bs,bs,bs) or vector (nb,bs,bs,bs,3) field onto the
    adapted layout."""
    if field.ndim == 5:
        comps = [
            _transfer_scalar(grid, plan, field[..., c], comp=c) for c in range(3)
        ]
        return jnp.stack(comps, axis=-1)
    return _transfer_scalar(grid, plan, field)


def _transfer_scalar(grid, plan: TransferPlan, field, comp: Optional[int] = None):
    bs = grid.bs
    ng = plan.new_grid
    out = jnp.zeros((ng.nb, bs, bs, bs), field.dtype)
    out = out.at[plan.copy_dst].set(field[plan.copy_src])

    if plan.ref_src.shape[0]:
        tab = grid.lab_tables(1)
        lab = (
            assemble_scalar_lab(field, tab, bs)
            if comp is None
            else _component_lab(field, tab, bs, comp)
        )
        fine = _upsample3(lab[plan.ref_src], plan.refine_w)  # (r, 2bs,2bs,2bs)
        for o in range(8):
            di, dj, dk = o >> 2 & 1, o >> 1 & 1, o & 1
            child = fine[
                :,
                di * bs : (di + 1) * bs,
                dj * bs : (dj + 1) * bs,
                dk * bs : (dk + 1) * bs,
            ]
            out = out.at[plan.ref_dst[:, o]].set(child)

    if plan.com_src.shape[0]:
        kids = field[plan.com_src]  # (c, 8, bs,bs,bs)
        half = bs // 2
        avg = (
            kids.reshape(-1, 8, half, 2, half, 2, half, 2)
            .mean(axis=(3, 5, 7))
        )  # (c, 8, half,half,half)
        parent = jnp.zeros((avg.shape[0], bs, bs, bs), field.dtype)
        for o in range(8):
            di, dj, dk = o >> 2 & 1, o >> 1 & 1, o & 1
            parent = parent.at[
                :,
                di * half : (di + 1) * half,
                dj * half : (dj + 1) * half,
                dk * half : (dk + 1) * half,
            ].set(avg[:, o])
        out = out.at[plan.com_dst].set(parent)
    return out


def _component_lab(comp_field, tab, bs, comp):
    from cup3d_tpu.grid.blocks import _assemble_vec_comp

    return _assemble_vec_comp(comp_field, tab, bs, comp)
