"""Ring attention baseline family (P2P rotation + AllGather variants).

Ref: exps/dist_attn/baselines/ring_attn.py — the reference ships two
executors (RingAttnP2P :1668, RingAttnAllGather :1460), both over *zigzag*
sequence sharding (shard.py:486): the sequence splits into 2*cp chunks and
rank r owns chunks r and 2cp-1-r, so causal masks load-balance exactly.
TPU redesign:

- P2P: kv rotates one hop per step (``jax.lax.ppermute``); each rank
  computes its q block against the visiting kv block and partials merge
  with the lse identity (functional/dist_attn._multi_ffa). Arbitrary
  band-slice masks are supported by clipping the global metadata to every
  (q owner, kv owner) chunk pair on the host — the zigzag half-chunk
  causal skips (ref loongtrain.py "q, k0, v0" step specialization) fall
  out of the plan for free: empty pairs produce no work items.
- AllGather: KV is all-gathered up front (one collective instead of cp-1
  hops — the latency-bound regime the reference's AG variant targets),
  reordered zigzag->natural with a static gather, and each rank runs ONE
  merged-plan FFA of its q block against the full sequence. jax AD
  transposes the all_gather + take into scatter-add + reduce-scatter,
  which is exactly the reference's dkv reduce-scatter backward.

Backward everywhere reuses the multi-part merged VJP; the ppermute chain
transposes automatically under AD.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..functional.dist_attn import _multi_ffa
from ..kernels.ffa import default_blocks
from ._utils import (
    band_meta,
    baseline_params,
    block_plan,
    check_zigzag_geometry,
    clip_to_segs,
    ring_step_plans,
    stack_step_plans,
    zigzag_inv_perm,
    zigzag_perm,
    zigzag_ring_step_plans,
    zigzag_segs,
)


def ring_dispatch(x: jax.Array, cp: int, sharding: str = "zigzag") -> jax.Array:
    """Natural global order -> the layout ``ring_attn`` shards (host-side
    permutation, ref shard.py zigzag_dispatch). Identity for contiguous."""
    if sharding == "contig":
        return x
    return jnp.take(x, jnp.asarray(zigzag_perm(x.shape[0], cp)), axis=0)


def ring_undispatch(x: jax.Array, cp: int, sharding: str = "zigzag") -> jax.Array:
    """Inverse of :func:`ring_dispatch` (ref shard.py zigzag_undispatch)."""
    if sharding == "contig":
        return x
    return jnp.take(x, jnp.asarray(zigzag_inv_perm(x.shape[0], cp)), axis=0)


def ring_attn(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_ranges: np.ndarray,
    k_ranges: np.ndarray,
    attn_type_map: np.ndarray,
    mesh: Mesh,
    cp_axis: str = "cp",
    softmax_scale: float | None = None,
    sharding: str = "zigzag",
) -> tuple[jax.Array, jax.Array]:
    """P2P ring attention (ref RingAttnP2P).

    Args:
        q/k/v: ``(S, h, d)`` in ``ring_dispatch(x, cp, sharding)`` layout,
            sharded P(cp_axis) on dim 0.
        sharding: ``zigzag`` (reference layout, causal load-balanced) or
            ``contig`` (naive contiguous blocks).

    Returns:
        (out ``(S, hq, dv)``, lse ``(S, hq)``), same layout/sharding —
        ``ring_undispatch`` restores natural order.
    """
    cp = mesh.shape[cp_axis]
    S, hq, dh = q.shape
    _, hk, dv = v.shape
    shard = S // cp
    scale = float(dh) ** -0.5 if softmax_scale is None else softmax_scale

    qr, kr, lo, hi = band_meta(q_ranges, k_ranges, attn_type_map)

    bq, bk = default_blocks(shard, shard)
    if sharding == "zigzag":
        plans = zigzag_ring_step_plans(qr, kr, lo, hi, shard, cp, bq, bk)
    elif sharding == "contig":
        plans = ring_step_plans(qr, kr, lo, hi, shard, cp, bq, bk)
    else:
        raise ValueError(f"unknown ring sharding: {sharding!r}")
    stacked, w, wt = stack_step_plans(plans)
    params = baseline_params(plans[0][0], w, wt, bq, bk, scale, hq, hk)
    params_list = tuple([params] * cp)
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def f(q, k, v, step_arrays):
        ks, vs = [k], [v]
        for s in range(1, cp):
            ks.append(jax.lax.ppermute(ks[-1], cp_axis, perm))
            vs.append(jax.lax.ppermute(vs[-1], cp_axis, perm))
        arrays_list = tuple(
            tuple(a[0] for a in step_arrays[s]) for s in range(cp)
        )
        return _multi_ffa(q, tuple(ks), tuple(vs), arrays_list, params_list)[:2]

    fn = shard_map(
        f, mesh=mesh,
        in_specs=(P(cp_axis), P(cp_axis), P(cp_axis),
                  [tuple(P(cp_axis) for _ in st) for st in stacked]),
        out_specs=(P(cp_axis), P(cp_axis)),
        check_vma=False,
    )
    return fn(q, k, v, stacked)


def ring_attn_allgather(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_ranges: np.ndarray,
    k_ranges: np.ndarray,
    attn_type_map: np.ndarray,
    mesh: Mesh,
    cp_axis: str = "cp",
    softmax_scale: float | None = None,
    sharding: str = "zigzag",
) -> tuple[jax.Array, jax.Array]:
    """AllGather ring attention (ref RingAttnAllGather): one up-front KV
    all_gather + a single merged-plan kernel per rank; dkv reduce-scatters
    through the AD transpose. Same layout contract as :func:`ring_attn`.
    """
    cp = mesh.shape[cp_axis]
    S, hq, dh = q.shape
    _, hk, dv = v.shape
    shard = S // cp
    scale = float(dh) ** -0.5 if softmax_scale is None else softmax_scale

    qr, kr, lo, hi = band_meta(q_ranges, k_ranges, attn_type_map)
    bq, bk = default_blocks(shard, S)

    # per-rank merged plan: q = this rank's segments, k = full natural seq
    per_rank = []
    for r in range(cp):
        if sharding == "zigzag":
            check_zigzag_geometry(shard, cp)
            q_segs = zigzag_segs(r, cp, shard // 2)
        elif sharding == "contig":
            q_segs = [(r * shard, (r + 1) * shard, 0)]
        else:
            raise ValueError(f"unknown ring sharding: {sharding!r}")
        slices = clip_to_segs(qr, kr, lo, hi, q_segs, [(0, S, 0)])
        per_rank.append(block_plan(slices, shard, S, bq, bk))
    stacked, w, wt = stack_step_plans([per_rank])
    params = baseline_params(per_rank[0], w, wt, bq, bk, scale, hq, hk)

    # gathered KV arrives in dispatch layout (rank-major shards); this
    # static gather restores natural order (ref
    # gather_with_reorder_before_attn, ring_attn.py:76)
    if sharding == "zigzag":
        reorder = jnp.asarray(zigzag_inv_perm(S, cp))
    else:
        reorder = None

    def f(q, k, v, arrays):
        k_all = jax.lax.all_gather(k, cp_axis, axis=0, tiled=True)
        v_all = jax.lax.all_gather(v, cp_axis, axis=0, tiled=True)
        if reorder is not None:
            k_all = jnp.take(k_all, reorder, axis=0)
            v_all = jnp.take(v_all, reorder, axis=0)
        local = tuple(a[0] for a in arrays[0])
        return _multi_ffa(
            q, (k_all,), (v_all,), (local,), (params,)
        )[:2]

    fn = shard_map(
        f, mesh=mesh,
        in_specs=(P(cp_axis), P(cp_axis), P(cp_axis),
                  [tuple(P(cp_axis) for _ in st) for st in stacked]),
        out_specs=(P(cp_axis), P(cp_axis)),
        check_vma=False,
    )
    return fn(q, k, v, stacked)
