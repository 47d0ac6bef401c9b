"""Small jax API shims (single home, imported lazily)."""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with replication checking off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
