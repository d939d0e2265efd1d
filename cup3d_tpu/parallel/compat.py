"""One call surface for ``jax.shard_map`` in the sharded layers.

The forest/faces/ring kernels and the sharded megaloop pass the mesh
positionally and default the replication check off; this thin wrapper
keeps that spelling in one place.
"""

from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check_vma=False):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )
