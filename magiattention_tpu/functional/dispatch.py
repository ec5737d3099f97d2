"""Dispatch / undispatch ops (ref: magi_attention/functional/dispatch.py:193-224).

dispatch permutes the global sequence into the load-balanced chunk order and
shards it over the cp axis; undispatch inverts. Implemented as plain gathers
with sharding constraints: XLA inserts the all-gather / reduce-scatter
(forward / transpose) collectives — the reference's hand-written
all_gather_v + unpermute (+ `_UndispatchPartialGradFunc` reduce-scatter
backward, ref :70-189) fall out of AD.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def dispatch_func(
    x: jax.Array,
    position_ids: np.ndarray,
    mesh: Mesh,
    cp_axis: str,
) -> jax.Array:
    """Global (natural order) -> dispatched (chunk-permuted, cp-sharded).

    Args:
        x: ``(total_seqlen, ...)`` in natural order (any sharding).
        position_ids: ``(cp, shard)`` host array — global row of each local row.

    Returns:
        ``(total_seqlen, ...)`` permuted so rank r's shard is rows
        ``position_ids[r]``, sharded P(cp_axis) on dim 0.
    """
    idx = jnp.asarray(np.asarray(position_ids).reshape(-1))
    y = jnp.take(x, idx, axis=0)
    return jax.lax.with_sharding_constraint(
        y, NamedSharding(mesh, P(cp_axis, *([None] * (x.ndim - 1))))
    )


def undispatch_func(
    y: jax.Array,
    unpermute_index: np.ndarray,
    mesh: Mesh,
    cp_axis: str,
) -> jax.Array:
    """Dispatched -> global natural order (inverse permutation)."""
    idx = jnp.asarray(np.asarray(unpermute_index))
    x = jnp.take(y, idx, axis=0)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(cp_axis, *([None] * (y.ndim - 1))))
    )
