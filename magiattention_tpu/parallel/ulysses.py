"""Ulysses (head-sharded) sequence parallelism baseline.

Ref: exps/dist_attn/baselines/ulysess.py — DeepSpeed-SP style: all_to_all
converts sequence sharding into head sharding, every rank computes full-
sequence attention for its head subset with the *global* (static) slice
metadata, and an inverse all_to_all restores sequence sharding. Requires
``n_kv_heads % cp == 0``.
"""

from __future__ import annotations

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..kernels.ffa import ffa_attn


def ulysses_attn(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_ranges: np.ndarray,
    k_ranges: np.ndarray,
    attn_type_map: np.ndarray,
    mesh: Mesh,
    cp_axis: str = "cp",
    softmax_scale: float | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Sequence-sharded in, sequence-sharded out.

    Args:
        q/k/v: ``(S, h, d)`` natural order, sharded P(cp_axis) on dim 0.
        q_ranges/k_ranges/attn_type_map: concrete global slice metadata.

    Returns:
        (out ``(S, hq, dv)``, lse ``(S, hq)``), same sharding.
    """
    cp = mesh.shape[cp_axis]
    S, hq, dh = q.shape
    _, hk, dv = v.shape
    if hq % cp or hk % cp:
        raise ValueError(f"ulysses requires heads divisible by cp ({hq},{hk},{cp})")

    def f(q, k, v):
        # (shard, h, d) -> (S, h/cp, d): split heads, gather sequence
        qg = jax.lax.all_to_all(q, cp_axis, split_axis=1, concat_axis=0, tiled=True)
        kg = jax.lax.all_to_all(k, cp_axis, split_axis=1, concat_axis=0, tiled=True)
        vg = jax.lax.all_to_all(v, cp_axis, split_axis=1, concat_axis=0, tiled=True)
        out_g, lse_g = ffa_attn(
            qg, kg, vg, q_ranges, k_ranges, attn_type_map,
            softmax_scale=softmax_scale,
        )
        out = jax.lax.all_to_all(
            out_g, cp_axis, split_axis=0, concat_axis=1, tiled=True
        )
        lse = jax.lax.all_to_all(
            lse_g[..., None], cp_axis, split_axis=0, concat_axis=1, tiled=True
        )[..., 0]
        return out, lse

    fn = shard_map(
        f, mesh=mesh,
        in_specs=(P(cp_axis), P(cp_axis), P(cp_axis)),
        out_specs=(P(cp_axis), P(cp_axis)),
        check_vma=False,
    )
    return fn(q, k, v)
