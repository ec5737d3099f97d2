"""NSA (native sparse attention) CP baselines.

Ref: exps/dist_attn/baselines/nsa.py (VarlenNSA) and usp_nsa.py
(USPAllGatherNSA). Three branches per query, mixed by a learned sigmoid
gate:

  cmp — attention over MLP-compressed KV blocks (length ``l_cmp``,
        stride ``d``), dense softmax per varlen segment;
  slc — attention over the ``slc_top_k`` *selected* KV blocks (length
        ``l_slc``), chosen per (kv-head, q-block) from the compressed
        scores (summed over GQA heads and q-block rows, ref
        compute_gqa_p_slc / compute_blockq_p_slc);
  win — sliding-window attention per segment.

TPU-first re-design: all block bookkeeping (block starts, segment masks,
the cmp->slc aggregation matrix) is static host metadata derived from
``cu_seqlens``, so the whole forward is one fused XLA program — top-k is
the only data-dependent op and its indices are block-granular (q-block x
kv-head), keeping gathers MXU-friendly. The distributed variant follows the
reference's all-gather design (usp_nsa.py:747 USPAllGatherNSA): ulysses
all_to_all head-shards, the ring axis all-gathers KV — a ring P2P loop
would fight XLA's static shapes for no bandwidth win on ICI.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .. import telemetry
from ..kernels.block_sparse import block_sparse_attn, modeled_slc_bytes
from ..kernels.ffa import ffa_attn
from ..kernels.mask_utils import BAND_INF

NEG_INF = float("-inf")


def init_nsa_params(
    key: jax.Array, head_dim: int, l_cmp: int, dtype=jnp.float32
) -> dict:
    """Learned parameters: block compressors (ref cmp_linear_k/v) and the
    3-way branch gate (ref gate_proj)."""
    k1, k2, k3 = jax.random.split(key, 3)
    s = l_cmp ** -0.5
    return {
        "w_cmp_k": jax.random.uniform(k1, (l_cmp,), dtype, -s, s),
        "b_cmp_k": jnp.zeros((), dtype),
        "w_cmp_v": jax.random.uniform(k2, (l_cmp,), dtype, -s, s),
        "b_cmp_v": jnp.zeros((), dtype),
        "w_gate": jax.random.uniform(
            k3, (head_dim, 3), dtype, -(head_dim ** -0.5), head_dim ** -0.5
        ),
        "b_gate": jnp.zeros((3,), dtype),
    }


def _block_layout(cu_seqlens: list[int], l: int, d: int):
    """Per-segment stride-d window starts (host). Returns (starts (n,),
    seg_id (n,), counts per segment)."""
    starts, seg_ids, counts = [], [], []
    for s in range(len(cu_seqlens) - 1):
        a, b = cu_seqlens[s], cu_seqlens[s + 1]
        n = max(0, (b - a - l) // d + 1)
        counts.append(n)
        for j in range(n):
            starts.append(a + j * d)
            seg_ids.append(s)
    return (
        np.asarray(starts, dtype=np.int32),
        np.asarray(seg_ids, dtype=np.int32),
        counts,
    )


def _p_slc_matrix(
    counts_cmp: list[int], counts_slc: list[int], l_slc: int, l_cmp: int,
    d: int,
) -> np.ndarray:
    """(n_cmp_total, n_slc_total) aggregation weights: P_slc = P_cmp @ M.

    BOTH block families come from :func:`_block_layout`, i.e. both are
    anchored at stride ``d``: cmp block i covers d-chunks ``[i, i + beta)``
    and slc block j covers ``[j, j + alpha)`` (alpha = l_slc/d, beta =
    l_cmp/d). The weight is their chunk-overlap count — the number of
    stride-d chunks the two windows share:

        M[i, j] = max(0, min(i + beta, j + alpha) - max(i, j))

    a small-integer count, exact in f32. At alpha == beta == 1 this is the
    identity, matching the ``p_slc = p_cmp`` shortcut in :func:`nsa_attn`.

    (An earlier revision anchored slc blocks at stride ``l_slc`` — the
    non-overlapping layout of the reference ``compute_p_slc`` — while
    ``_block_layout`` emits stride-``d`` windows; for l_slc=2d, l_cmp=d
    that scored slc block j from cmp blocks {2j-1, 2j} instead of the
    overlapping {j, j+1}, so top-k selected windows that missed the very
    keys that scored them. The misaligned-stride parity test pins this.)
    """
    alpha, beta = l_slc // d, l_cmp // d
    n_cmp, n_slc = sum(counts_cmp), sum(counts_slc)
    M = np.zeros((n_cmp, n_slc), dtype=np.float32)
    co = so = 0
    for nc, ns in zip(counts_cmp, counts_slc):
        t = np.arange(nc)[:, None] - np.arange(ns)[None, :]  # i - j
        cnt = np.minimum(alpha, t + beta) - np.maximum(0, t)
        M[co:co + nc, so:so + ns] = np.maximum(cnt, 0).astype(np.float32)
        co += nc
        so += ns
    return M


def nsa_attn(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    params: dict,
    cu_seqlens: list[int],
    *,
    l_cmp: int = 32,
    l_slc: int = 64,
    d_stride: int = 32,
    block_size_q: int = 16,
    slc_top_k: int = 2,
    window: tuple[int, int] = (128, 0),
    causal: bool = True,
    softmax_scale: float | None = None,
) -> jax.Array:
    """Single-device NSA forward (``(S, h, dh)`` packed varlen layout).

    cu_seqlens / block geometry are static host metadata; every segment
    must satisfy ``len >= l_slc``, ``block_size_q | len``, ``d | start``,
    and hold at least ``slc_top_k`` selection blocks (ref asserts the same).
    """
    S, hq, dh = q.shape
    _, hk, _ = k.shape
    g = hq // hk
    scale = dh ** -0.5 if softmax_scale is None else softmax_scale
    cu = list(cu_seqlens)
    assert cu[0] == 0 and cu[-1] == S

    # ---- static layout ---------------------------------------------------
    cmp_starts, cmp_seg, cmp_counts = _block_layout(cu, l_cmp, d_stride)
    slc_starts, slc_seg, slc_counts = _block_layout(cu, l_slc, d_stride)
    n_cmp, n_slc = len(cmp_starts), len(slc_starts)
    assert min(slc_counts) >= slc_top_k, (
        f"every segment needs >= slc_top_k={slc_top_k} blocks"
    )
    row_seg = np.zeros(S, dtype=np.int32)
    for s in range(len(cu) - 1):
        row_seg[cu[s]: cu[s + 1]] = s
        assert (cu[s + 1] - cu[s]) % block_size_q == 0
    n_qb = S // block_size_q
    qb_seg = row_seg.reshape(n_qb, block_size_q)[:, 0]

    # ---- compressed KV ---------------------------------------------------
    def blocks_of(x, starts, l):  # (S, h, dh) -> (n, l, h, dh)
        idx = starts[:, None] + np.arange(l)[None, :]
        return jnp.take(x, jnp.asarray(idx.reshape(-1)), axis=0).reshape(
            len(starts), l, *x.shape[1:]
        )

    k_cmp_blk = blocks_of(k, cmp_starts, l_cmp)  # (n_cmp, l, hk, dh)
    v_cmp_blk = blocks_of(v, cmp_starts, l_cmp)
    k_cmp = (
        jnp.einsum("nlhd,l->nhd", k_cmp_blk, params["w_cmp_k"])
        + params["b_cmp_k"]
    )
    v_cmp = (
        jnp.einsum("nlhd,l->nhd", v_cmp_blk, params["w_cmp_v"])
        + params["b_cmp_v"]
    )

    # ---- cmp branch (dense per-segment softmax, ref :119-126) ------------
    seg_mask = jnp.asarray(row_seg[:, None] == cmp_seg[None, :])  # (S, n_cmp)
    # GQA: each q head attends its kv head's compressed blocks
    qg = q.reshape(S, hk, g, dh)
    logits = jnp.einsum("shgd,nhd->shgn", qg, k_cmp).astype(jnp.float32) * scale
    logits = jnp.where(seg_mask[:, None, None, :], logits, NEG_INF)
    p_cmp = jax.nn.softmax(logits, axis=-1)  # (S, hk, g, n_cmp)
    out_cmp = jnp.einsum(
        "shgn,nhd->shgd", p_cmp.astype(q.dtype), v_cmp
    ).reshape(S, hq, dh)

    # ---- selection scores (ref compute_p_slc/gqa/blockq) -----------------
    if l_slc == l_cmp == d_stride:
        p_slc = p_cmp  # (S, hk, g, n_slc)
    else:
        M = jnp.asarray(_p_slc_matrix(cmp_counts, slc_counts, l_slc, l_cmp,
                                      d_stride))
        p_slc = jnp.einsum("shgn,nm->shgm", p_cmp, M)
    # sum over GQA heads and q-block rows -> (hk, n_qb, n_slc)
    score = p_slc.sum(axis=2).reshape(n_qb, block_size_q, hk, n_slc).sum(1)
    score = score.transpose(1, 0, 2)  # (hk, n_qb, n_slc)
    qb_mask = jnp.asarray(qb_seg[:, None] == slc_seg[None, :])
    score = jnp.where(qb_mask[None], score, NEG_INF)
    _, idx = jax.lax.top_k(score, slc_top_k)  # (hk, n_qb, K)

    # ---- slc branch: registry decision — gather-free block-sparse kernel
    # (kernels/block_sparse.py streams the selected blocks through the
    # prefetched index table) vs the gathered-dense reference ---------------
    slc_feasible = (
        S % d_stride == 0
        and l_slc % d_stride == 0
        and (d_stride <= 128 or d_stride % 128 == 0)
        and not (slc_starts % d_stride).any()
    )
    if slc_feasible:
        from ..kernels import registry as _registry

        slc_backend = _registry.nsa_slc_backend(
            key=(hk, g, n_qb, slc_top_k, l_slc, d_stride)
        )
    else:
        slc_backend = "gathered_dense"
    if slc_backend == "block_sparse_pallas":
        out_slc, _ = block_sparse_attn(
            q, k, v, idx, slc_starts,
            block_len=l_slc, d_stride=d_stride,
            block_size_q=block_size_q, softmax_scale=scale,
        )
    else:
        # gathered-dense reference: materialize the top-k blocks, dense
        # softmax over the concatenated selection
        k_slc_blk = (
            k_cmp_blk if l_slc == l_cmp else blocks_of(k, slc_starts, l_slc)
        )  # (n_slc, l, hk, dh)
        v_slc_blk = (
            v_cmp_blk if l_slc == l_cmp else blocks_of(v, slc_starts, l_slc)
        )
        # (hk, n_qb, K, l, dh)
        k_sel = jnp.take_along_axis(
            k_slc_blk.transpose(2, 0, 1, 3)[:, None],  # (hk, 1, n_slc, l, dh)
            idx[..., None, None],
            axis=2,
        )
        v_sel = jnp.take_along_axis(
            v_slc_blk.transpose(2, 0, 1, 3)[:, None], idx[..., None, None],
            axis=2,
        )
        L = slc_top_k * k_sel.shape[-2]
        k_sel = k_sel.reshape(hk, n_qb, L, dh)
        v_sel = v_sel.reshape(hk, n_qb, L, dh)
        qb = q.reshape(n_qb, block_size_q, hk, g, dh)
        s_logits = (
            jnp.einsum("bqhgd,hbld->hbgql", qb, k_sel).astype(jnp.float32)
            * scale
        )
        p_s = jax.nn.softmax(s_logits, axis=-1)
        out_slc = (
            jnp.einsum("hbgql,hbld->bqhgd", p_s.astype(q.dtype), v_sel)
            .reshape(S, hq, dh)
        )
    if telemetry.enabled():
        slc_bytes = modeled_slc_bytes(
            hk=hk, n_qb=n_qb, top_k=slc_top_k, block_len=l_slc,
            d_stride=d_stride, block_size_q=block_size_q, g=g, d=dh,
            dv=dh, itemsize=q.dtype.itemsize,
        )
        telemetry.record_event(
            "nsa_step",
            slc_backend=slc_backend,
            top_k=slc_top_k,
            hk=hk,
            n_qb=n_qb,
            l_slc=l_slc,
            d_stride=d_stride,
            executed_bytes=slc_bytes["streamed_bytes"],
            gathered_bytes=slc_bytes["gathered_bytes"],
        )

    # ---- win branch: banded FFA per segment (ref flash varlen + window) --
    wl, wr = window
    d_hi = 0 if causal else (wr if wr >= 0 else BAND_INF)
    d_lo = -wl if wl >= 0 else -BAND_INF
    qr = np.array([[cu[s], cu[s + 1]] for s in range(len(cu) - 1)], np.int32)
    out_win, _ = ffa_attn(
        q, k, v, qr, qr.copy(), None,
        softmax_scale=scale,
        d_lo=np.full(len(qr), d_lo, np.int32),
        d_hi=np.full(len(qr), d_hi, np.int32),
    )

    # ---- gate mix (ref gate_proj + sigmoid) ------------------------------
    gate = jax.nn.sigmoid(
        jnp.einsum("shd,dc->shc", q.astype(jnp.float32),
                   params["w_gate"].astype(jnp.float32))
        + params["b_gate"]
    ).astype(q.dtype)
    out = (
        gate[..., 0:1] * out_cmp
        + gate[..., 1:2] * out_slc
        + gate[..., 2:3] * out_win
    )
    return out


def usp_nsa_attn(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    params: dict,
    cu_seqlens: list[int],
    mesh: Mesh,
    ring_axis: str = "rp",
    ulysses_axis: str = "sp",
    **nsa_kwargs,
) -> jax.Array:
    """USP-sharded NSA (ref usp_nsa.py:747 USPAllGatherNSA).

    q/k/v: ``(S, h, dh)`` natural order, dim 0 sharded P((ring, ulysses)).
    ulysses a2a -> head sharding; ring all-gather -> full sequence; each
    rank computes NSA for its head subset on its ring block's queries.
    """
    R = mesh.shape[ring_axis]
    U = mesh.shape[ulysses_axis]
    S, hq, dh = q.shape
    _, hk, _ = k.shape
    if hq % U or hk % U:
        raise ValueError(f"usp_nsa needs heads divisible by U ({hq},{hk},{U})")
    blk = S // R

    # head-subset params are identical on every rank; the gate/compressors
    # act per-head-dim so no parameter sharding is needed
    def f(q, k, v):
        # (S/(R*U), h) -> (S/R, h/U)
        qa = jax.lax.all_to_all(q, ulysses_axis, 1, 0, tiled=True)
        ka = jax.lax.all_to_all(k, ulysses_axis, 1, 0, tiled=True)
        va = jax.lax.all_to_all(v, ulysses_axis, 1, 0, tiled=True)
        # full sequence for the head subset
        qf = jax.lax.all_gather(qa, ring_axis, axis=0, tiled=True)
        kf = jax.lax.all_gather(ka, ring_axis, axis=0, tiled=True)
        vf = jax.lax.all_gather(va, ring_axis, axis=0, tiled=True)
        out_f = nsa_attn(qf, kf, vf, params, cu_seqlens, **nsa_kwargs)
        r = jax.lax.axis_index(ring_axis)
        out_blk = jax.lax.dynamic_slice_in_dim(out_f, r * blk, blk, axis=0)
        return jax.lax.all_to_all(out_blk, ulysses_axis, 0, 1, tiled=True)

    spec = P((ring_axis, ulysses_axis))
    return shard_map(
        f, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
