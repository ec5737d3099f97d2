"""LoongTrain (2D-attention + double-ring) context-parallel baseline.

Ref: exps/dist_attn/baselines/loongtrain.py — LoongTrain composes two
mechanisms on a flat world of ``U * O * I`` ranks:

- **2D attention** (head x context): a Ulysses process group of size ``U``
  converts sequence sharding to head sharding with an all_to_all
  (ParallelMode.ULYSESS, ref :1173), and the remaining ``R = O * I`` ranks
  form the context ring (ParallelMode.RING).
- **Double ring**: the context ring is decomposed into inner windows of
  size ``I`` (ParallelMode.INTRA_WINDOW — intra-node on GPU) and an outer
  ring of size ``O`` over windows (INTER_WINDOW): ``I-1`` cheap hops per
  outer round, one expensive hop per round. KV visiting ring rank
  ``(io, ii)`` at step ``(o, s)`` originates from ring rank
  ``((io-o) % O) * I + ((ii-s) % I)`` (ref :148 window_offset).
- **Zigzag sharding** on the ring dim (shard.py zigzag_dispatch): ring
  rank r owns chunks ``r`` and ``2R-1-r`` of ``2R``, so causal masks
  load-balance; the reference's per-step half-chunk specializations
  ("q, k0, v0" branches, ref :1216-1228) fall out of the band-slice plan
  clipping for free — empty chunk pairs produce no work items.

**Head-first vs context-first placement** (the paper's two process-group
constructions) is which logical role varies fastest over the flat device
order; on TPU that is the *mesh construction*, not the attention code —
use :func:`make_loongtrain_mesh`.

TPU redesign notes: process groups -> mesh axes; P2P send/recv ->
``jax.lax.ppermute``; the double-buffered comm/compute overlap ->
XLA async collective scheduling; backward -> AD through the multi-part
merged VJP (functional/dist_attn._multi_ffa).
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
    zigzag_ring_step_plans,
)


def make_loongtrain_mesh(
    devices,
    ulysses: int,
    outer: int,
    inner: int,
    placement: str = "head_first",
) -> Mesh:
    """Build the LoongTrain mesh with the requested rank placement.

    head_first (ref default): the Ulysses group takes adjacent ranks
    (fastest-varying) — head a2a rides the cheapest links; the inner ring
    is next. context_first: the inner-window ring takes adjacent ranks —
    ring hops ride the cheapest links. Axis names are always
    ("rp_out", "rp_in", "sp") roles regardless of placement.
    """
    devs = np.asarray(devices).reshape(-1)[: ulysses * outer * inner]
    if placement == "head_first":
        arr = devs.reshape(outer, inner, ulysses)
        return Mesh(arr, axis_names=("rp_out", "rp_in", "sp"))
    if placement == "context_first":
        arr = devs.reshape(ulysses, outer, inner).transpose(1, 2, 0)
        return Mesh(arr, axis_names=("rp_out", "rp_in", "sp"))
    raise ValueError(f"unknown placement: {placement!r}")


def loongtrain_attn(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_ranges: np.ndarray,
    k_ranges: np.ndarray,
    attn_type_map: np.ndarray,
    mesh: Mesh,
    outer_axis: str = "rp_out",
    inner_axis: str = "rp_in",
    ulysses_axis: str | None = None,
    softmax_scale: float | None = None,
    sharding: str = "zigzag",
) -> tuple[jax.Array, jax.Array]:
    """Sequence-sharded in/out over the (outer, inner[, ulysses]) axes.

    Args:
        q/k/v: ``(S, h, d)``, dim 0 sharded over all given axes; in
            :func:`..ring.ring_dispatch` layout over the ``R = O*I`` ring
            ranks when ``sharding='zigzag'`` (ring rank ``io*I + ii`` owns
            zigzag chunks ``r`` and ``2R-1-r``).
        ulysses_axis: when set, 2D attention — heads split over this axis
            with an a2a, so only ``hq % U == 0`` is required (not the full
            world size).

    Returns:
        (out ``(S, hq, dv)``, lse ``(S, hq)`` fp32), same sharding.
    """
    O = mesh.shape[outer_axis]
    I = mesh.shape[inner_axis]
    U = mesh.shape[ulysses_axis] if ulysses_axis else 1
    R = O * I
    S, hq, dh = q.shape
    _, hk, dv = v.shape
    if ulysses_axis and (hq % U or hk % U):
        raise ValueError(
            f"loongtrain 2D attention needs heads divisible by the "
            f"ulysses size ({hq},{hk},{U})"
        )
    shard = S // R
    scale = float(dh) ** -0.5 if softmax_scale is None else softmax_scale

    qr, kr, lo, hi = band_meta(q_ranges, k_ranges, attn_type_map)

    bq, bk = default_blocks(shard, shard)

    def src_of(b: int, t: int) -> int:
        io, ii = divmod(b, I)
        o, s = divmod(t, I)
        return ((io - o) % O) * I + ((ii - s) % I)

    if sharding == "zigzag":
        plans = zigzag_ring_step_plans(
            qr, kr, lo, hi, shard, R, bq, bk, ring_rank_of=src_of
        )
    elif sharding == "contig":
        plans = []
        for t in range(R):
            per_rank = []
            for b in range(R):
                src = src_of(b, t)
                slices = clip_to_blocks(
                    qr, kr, lo, hi,
                    b * shard, (b + 1) * shard,
                    src * shard, (src + 1) * shard,
                )
                per_rank.append(block_plan(slices, shard, shard, bq, bk))
            plans.append(per_rank)
    else:
        raise ValueError(f"unknown loongtrain sharding: {sharding!r}")
    stacked, w, wt = stack_step_plans(plans)

    params = baseline_params(plans[0][0], w, wt, bq, bk, scale, hq, hk)
    params_list = tuple([params] * R)
    perm_in = [(i, (i + 1) % I) for i in range(I)]
    perm_out = [(i, (i + 1) % O) for i in range(O)]

    def a2a(x, split_axis, concat_axis):
        return jax.lax.all_to_all(
            x, ulysses_axis, split_axis=split_axis,
            concat_axis=concat_axis, tiled=True,
        )

    def f(q, k, v, step_arrays):
        if ulysses_axis:
            # 2D attention: seq shard -> head shard within the ring block
            q, k, v = (a2a(t, 1, 0) for t in (q, k, v))
        ks, vs = [], []
        k_base, v_base = k, v
        for o in range(O):
            if o > 0:
                k_base = jax.lax.ppermute(k_base, outer_axis, perm_out)
                v_base = jax.lax.ppermute(v_base, outer_axis, perm_out)
            k_cur, v_cur = k_base, v_base
            for s in range(I):
                if s > 0:
                    k_cur = jax.lax.ppermute(k_cur, inner_axis, perm_in)
                    v_cur = jax.lax.ppermute(v_cur, inner_axis, perm_in)
                ks.append(k_cur)
                vs.append(v_cur)
        arrays_list = tuple(
            tuple(a[0] for a in step_arrays[t]) for t in range(R)
        )
        out, lse, _ = _multi_ffa(q, tuple(ks), tuple(vs), arrays_list,
                                 params_list)
        if ulysses_axis:
            out = a2a(out, 0, 1)
            lse = a2a(lse[..., None], 0, 1)[..., 0]
        return out, lse

    data_axes = (
        (outer_axis, inner_axis, ulysses_axis)
        if ulysses_axis else (outer_axis, inner_axis)
    )
    spec = P(data_axes)
    ring_spec = P((outer_axis, inner_axis))
    fn = shard_map(
        f, mesh=mesh,
        in_specs=(spec, spec, spec,
                  [tuple(ring_spec for _ in st) for st in stacked]),
        out_specs=(spec, spec),
        check_vma=False,
    )
    return fn(q, k, v, stacked)
