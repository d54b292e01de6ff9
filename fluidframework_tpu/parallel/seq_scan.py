"""Sequence-parallel prefix sum via shard_map.

For documents whose segment table is sharded along the capacity axis
('sp'), position resolution needs a cross-shard exclusive prefix sum. The
decomposition is the standard two-level scan (How to Scale Your Model's
collective-scan recipe): each shard cumsums locally, shard totals are
all-gathered (tiny: one scalar per shard), and each shard adds the sum of
its predecessors. Cost: one psum-sized collective per scan instead of
serializing the whole axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map


def sharded_cumsum(x: jnp.ndarray, mesh: Mesh, axis_name: str = "sp",
                   exclusive: bool = False) -> jnp.ndarray:
    """Cumsum along the last axis of [B_local..., C] with C sharded over
    `axis_name`; batch axes may be sharded over 'dp'."""

    def local(block):
        c = jnp.cumsum(block, axis=-1)
        total = c[..., -1:]
        # Exclusive scan of shard totals: all-gather totals, mask my prefix.
        totals = jax.lax.all_gather(total, axis_name, axis=-1,
                                    tiled=True)  # [..., S]
        idx = jax.lax.axis_index(axis_name)
        mask = jnp.arange(totals.shape[-1]) < idx
        offset = jnp.sum(jnp.where(mask, totals, 0), axis=-1, keepdims=True)
        out = c + offset
        if exclusive:
            out = out - block
        return out

    spec = P(*(["dp"] + [None] * (x.ndim - 2) + [axis_name]))
    return shard_map(local, mesh=mesh, in_specs=(spec,), out_specs=spec)(x)
