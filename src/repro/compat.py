"""The one shard_map spelling the weather path uses (jax 0.9)."""

from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs):
    """`jax.shard_map` without replication checking."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


__all__ = ["shard_map"]
