"""Megatron HybridCP and Ring-AllGather context-parallel baselines.

Ref: exps/dist_attn/baselines/hybrid_dcp.py (hybrid) and the allgather
variants in ring_attn.py — two KV-replication strategies:

- ``allgather_attn``: every rank all-gathers the full K/V over the cp axis
  and computes its q block against the global sequence with clipped global
  metadata. One collective, maximal memory — the "Ring AllGather" baseline.
- ``hybrid_cp_attn``: 2-level. K/V is all-gathered over the *intra* axis
  (cheap, high-bandwidth ICI), forming one super-block per intra group; the
  super-blocks then ring over the *inter* axis (ppermute), so the expensive
  axis carries ring traffic while the cheap axis pays one gather.
"""

from __future__ import annotations

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..functional.dist_attn import _multi_ffa
from ..kernels.ffa import default_blocks
from ._utils import (
    band_meta,
    baseline_params,
    block_plan,
    clip_to_blocks,
    stack_step_plans,
)


def allgather_attn(
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
    """All-gather-KV attention: seq-sharded in/out over ``P(cp_axis)``."""
    cp = mesh.shape[cp_axis]
    S, hq, dh = q.shape
    _, hk, dv = v.shape
    shard = S // cp
    scale = float(dh) ** -0.5 if softmax_scale is None else softmax_scale
    qr, kr, lo, hi = band_meta(q_ranges, k_ranges, attn_type_map)

    bq, bk = default_blocks(shard, S)
    per_rank = [
        block_plan(
            clip_to_blocks(qr, kr, lo, hi, r * shard, (r + 1) * shard, 0, S),
            shard, S, bq, bk,
        )
        for r in range(cp)
    ]
    stacked, w, wt = stack_step_plans([per_rank])

    params = baseline_params(per_rank[0], w, wt, bq, bk, scale, hq, hk)

    def f(q, k, v, arrays):
        k_all = jax.lax.all_gather(k, cp_axis, axis=0, tiled=True)
        v_all = jax.lax.all_gather(v, cp_axis, axis=0, tiled=True)
        local = tuple(a[0] for a in arrays[0])
        return _multi_ffa(q, (k_all,), (v_all,), (local,), (params,))[:2]

    spec = P(cp_axis)
    fn = shard_map(
        f, mesh=mesh,
        in_specs=(spec, spec, spec, [tuple(spec for _ in st) for st in stacked]),
        out_specs=(spec, spec),
        check_vma=False,
    )
    return fn(q, k, v, stacked)


def hybrid_cp_attn(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_ranges: np.ndarray,
    k_ranges: np.ndarray,
    attn_type_map: np.ndarray,
    mesh: Mesh,
    inter_axis: str = "cp_inter",
    intra_axis: str = "cp_intra",
    softmax_scale: float | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Hybrid 2-level CP: all-gather KV intra, ring inter.

    q/k/v: ``(S, h, d)``, dim 0 sharded ``P((inter_axis, intra_axis))`` —
    rank ``(io, ii)`` owns contiguous block ``io*I + ii``; the intra group of
    ``io`` jointly owns super-block ``[io*S/O, (io+1)*S/O)``.
    """
    O = mesh.shape[inter_axis]
    I = mesh.shape[intra_axis]
    cp = O * I
    S, hq, dh = q.shape
    _, hk, dv = v.shape
    shard = S // cp
    super_blk = S // O
    scale = float(dh) ** -0.5 if softmax_scale is None else softmax_scale
    qr, kr, lo, hi = band_meta(q_ranges, k_ranges, attn_type_map)

    bq, bk = default_blocks(shard, super_blk)
    # plans[o][global rank b]: q block b vs super-block of inter rank (io-o)%O
    plans = []
    for o in range(O):
        per_rank = []
        for io in range(O):
            for ii in range(I):
                b = io * I + ii
                src = (io - o) % O
                slices = clip_to_blocks(
                    qr, kr, lo, hi,
                    b * shard, (b + 1) * shard,
                    src * super_blk, (src + 1) * super_blk,
                )
                per_rank.append(block_plan(slices, shard, super_blk, bq, bk))
        plans.append(per_rank)
    stacked, w, wt = stack_step_plans(plans)

    params = baseline_params(plans[0][0], w, wt, bq, bk, scale, hq, hk)
    params_list = tuple([params] * O)
    perm_out = [(i, (i + 1) % O) for i in range(O)]

    def f(q, k, v, step_arrays):
        k_g = jax.lax.all_gather(k, intra_axis, axis=0, tiled=True)
        v_g = jax.lax.all_gather(v, intra_axis, axis=0, tiled=True)
        ks, vs = [k_g], [v_g]
        for _ in range(1, O):
            ks.append(jax.lax.ppermute(ks[-1], inter_axis, perm_out))
            vs.append(jax.lax.ppermute(vs[-1], inter_axis, perm_out))
        arrays_list = tuple(
            tuple(a[0] for a in step_arrays[o]) for o in range(O)
        )
        return _multi_ffa(q, tuple(ks), tuple(vs), arrays_list, params_list)[:2]

    spec = P((inter_axis, intra_axis))
    fn = shard_map(
        f, mesh=mesh,
        in_specs=(spec, spec, spec,
                  [tuple(spec for _ in st) for st in stacked]),
        out_specs=(spec, spec),
        check_vma=False,
    )
    return fn(q, k, v, stacked)
