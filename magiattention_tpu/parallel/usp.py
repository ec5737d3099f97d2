"""USP (Ulysses x Ring 2D) sequence-parallel baseline.

Ref: exps/dist_attn/baselines/usp.py — a 2D CP decomposition: the inner
``ulysses`` mesh axis converts sequence sharding to head sharding with an
all_to_all, and the outer ``ring`` axis rotates KV blocks ppermute-style.
Total context parallelism = ulysses_size * ring_size with the head-count
divisibility requirement reduced to the ulysses axis only.

Layout: q/k/v are sharded over BOTH axes on dim 0 via ``P((ring, ulysses))``
so that, after the in-shard_map all_to_all over the ulysses axis, each ring
rank holds the contiguous sequence block ``[r*S/R, (r+1)*S/R)`` for its head
subset — exactly the ring baseline's layout with ``1/U`` of the heads.
"""

from __future__ import annotations

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..functional.dist_attn import _multi_ffa
from ..kernels.ffa import default_blocks
from ._utils import band_meta, baseline_params, ring_step_plans, stack_step_plans


def usp_attn(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_ranges: np.ndarray,
    k_ranges: np.ndarray,
    attn_type_map: np.ndarray,
    mesh: Mesh,
    ring_axis: str = "rp",
    ulysses_axis: str = "sp",
    softmax_scale: float | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Sequence-sharded in/out over ``P((ring_axis, ulysses_axis))``.

    Args:
        q/k/v: ``(S, h, d)`` natural order, dim 0 sharded over both axes.

    Returns:
        (out ``(S, hq, dv)``, lse ``(S, hq)`` fp32), same sharding.
    """
    R = mesh.shape[ring_axis]
    U = mesh.shape[ulysses_axis]
    S, hq, dh = q.shape
    _, hk, dv = v.shape
    if hq % U or hk % U:
        raise ValueError(
            f"usp requires heads divisible by ulysses size ({hq},{hk},{U})"
        )
    ring_shard = S // R
    scale = float(dh) ** -0.5 if softmax_scale is None else softmax_scale

    qr, kr, lo, hi = band_meta(q_ranges, k_ranges, attn_type_map)

    bq, bk = default_blocks(ring_shard, ring_shard)
    plans = ring_step_plans(qr, kr, lo, hi, ring_shard, R, bq, bk)
    stacked, w, wt = stack_step_plans(plans)

    params = baseline_params(plans[0][0], w, wt, bq, bk, scale, hq, hk)
    params_list = tuple([params] * R)
    perm = [(i, (i + 1) % R) for i in range(R)]

    def a2a(x, split_axis, concat_axis):
        return jax.lax.all_to_all(
            x, ulysses_axis, split_axis=split_axis,
            concat_axis=concat_axis, tiled=True,
        )

    def f(q, k, v, step_arrays):
        # ulysses phase: seq shard -> head shard within the ring block
        qg, kg, vg = (a2a(t, 1, 0) for t in (q, k, v))
        # ring phase over the ring axis
        ks, vs = [kg], [vg]
        for _ in range(1, R):
            ks.append(jax.lax.ppermute(ks[-1], ring_axis, perm))
            vs.append(jax.lax.ppermute(vs[-1], ring_axis, perm))
        arrays_list = tuple(
            tuple(a[0] for a in step_arrays[s]) for s in range(R)
        )
        out_g, lse_g, _ = _multi_ffa(
            qg, tuple(ks), tuple(vs), arrays_list, params_list
        )
        out = a2a(out_g, 0, 1)
        lse = a2a(lse_g, 0, 1)
        return out, lse

    spec = P((ring_axis, ulysses_axis))
    fn = shard_map(
        f, mesh=mesh,
        in_specs=(spec, spec, spec,
                  [tuple(P(ring_axis) for _ in st) for st in stacked]),
        out_specs=(spec, spec),
        check_vma=False,
    )
    return fn(q, k, v, stacked)
