"""Bytes and floating-point operations one BiCGSTAB iteration needs, from
the grid's shapes alone.

ONE count, whatever implements the iteration (lane layout, fused Pallas
stages, bf16 storage, two-level or tile preconditioner, forest or
uniform): it is the work of the algorithm on float32 fields of ``cells``
unknowns with a 7-point operator.  It is the STRICT count: within each
phase that a global reduction separates from the next, every distinct
operand is read once and every result written once, and the
preconditioner is charged one read and one write per application.  No
implementation can move fewer bytes than that without changing the
algorithm, so no roofline share built on it can pass 100 %.

Preconditioned BiCGSTAB, per iteration (vectors x, r, r0, p, v, s, t and
the preconditioned y = M^-1 p, z = M^-1 s):

  phase 1  p = r + beta (p - omega v); y = M^-1 p; v = A y; <r0, v>
           reads r, p, v, r0                      writes p, y, v
  phase 2  s = r - alpha v; z = M^-1 s; t = A z; <t, s>, <t, t>
           reads r, v                             writes s, z, t
  phase 3  x += alpha y + omega z; r = s - omega t; <r0, r>, <r, r>
           reads x, y, z, s, t, r0                writes x, r

20 vectors of ``cells`` float32 values: 80 bytes per unknown.
"""

BYTES_PER_VALUE = 4  # the configuration states float32

#: (reads, writes) of whole vectors per phase, as tabulated above
PHASES = ((4, 3), (2, 3), (6, 2))

#: flops per unknown: 2 operator applies (7-point: 7 mul-add = 13),
#: 4 axpy-like updates of 2 flops per term (p: 4, s: 2, x: 4, r: 2),
#: 5 dot products (2 each); the preconditioner is charged nothing
FLOPS_PER_CELL = 2 * 13 + (4 + 2 + 4 + 2) + 5 * 2


def bicgstab_iteration(cells: int) -> dict:
    vectors = sum(r + w for r, w in PHASES)
    return {"bytes": vectors * cells * BYTES_PER_VALUE,
            "flops": FLOPS_PER_CELL * cells, "vectors": vectors}


def forest_bicgstab_iteration(cells: int, bs: int) -> dict:
    """The same iteration on the leaves of an octree of ``bs^3`` blocks
    (``krylov.bicgstab`` with ``laplacian_blocks`` as the operator): the
    vectors above (``s.x``, ``s.r``, ``rhat``, ``p``, ``v``, ``svec``,
    ``t``, ``y``, ``z``), and beside them what a block cannot have from
    its own cells: the halo of the operator's input.  ``v = A y`` and ``t
    = A z`` each read, per block, the face planes of ``y`` or ``z`` that
    its neighbours hold: the 7-point operator reaches one cell across
    each of the six faces and no edge or corner, so ``6 bs^2`` values per
    ``bs^3`` block, ``6 / bs`` of a vector per apply.  (The lab the
    program assembles, ``(bs + 2)^3`` per block, holds edges and corners
    too: 95 % of a vector more where ``bs = 8``.  The strict count leaves
    out what no 7-point stencil reads.)  Left out as well, so that the
    count stays a lower bound: the restriction pyramid under finer
    neighbours, the interpolation under coarser ones, the flux tables
    (``grid/faces.py``, ``grid/flux.py``: each a few per cent of the
    cells) and the per-block spacing."""
    halo_vectors = 2 * 6.0 / bs
    vectors = sum(r + w for r, w in PHASES) + halo_vectors
    return {"bytes": vectors * cells * BYTES_PER_VALUE,
            "flops": FLOPS_PER_CELL * cells, "vectors": vectors}


def roofline_seconds(work: dict, chip: dict) -> dict:
    """Least time the chip could take and which bound it is."""
    t_mem = work["bytes"] / chip["hbm_bytes_per_s"]
    # float32 work: the bf16 MXU peak is the most generous compute peak
    t_flop = work["flops"] / chip["bf16_flops_per_s"]
    return {"seconds": max(t_mem, t_flop),
            "bound": "hbm" if t_mem >= t_flop else "flops"}
