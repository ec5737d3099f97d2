"""Gather-free block-sparse FFA kernel for the NSA selected branch.

The NSA baseline (:mod:`..parallel.nsa`) picks ``slc_top_k`` KV blocks per
(kv-head, q-block) and then *materializes* them with ``jnp.take_along_axis``
followed by a dense, non-online softmax — full HBM gather traffic plus O(L)
logits memory. This kernel attends straight out of the resident K/V instead:
the per-(kv-head, q-block) block index table rides as scalar prefetch and the
K/V ``BlockSpec`` index maps read it directly, so each grid step DMAs exactly
one selected chunk in place (the ``paged_decode.py`` page-table idiom — FSA's
"selected branch as a first-class sparse kernel", PAPERS.md arXiv:2508.18224).

Design notes (shared idiom with ``ffa.py`` / ``paged_decode.py`` — same
online-softmax algebra, same Mosaic compatibility rules):

- the selected-block space is re-tiled into **chunks** of ``d_stride`` rows:
  NSA blocks overlap when ``d_stride < block_len`` (stride-``d`` sliding
  windows), but their *starts* are stride-aligned, so every selected block is
  exactly ``block_len // d_stride`` consecutive chunks. Chunking makes the
  streamed unit uniform; duplicate chunks in a row's list reproduce the
  gathered reference's duplicated softmax mass term for term.
- grid ``(hk, n_qb, n_chunk_steps)`` with the chunk axis innermost and
  ``arbitrary``: all chunks of one (head, q-block) are consecutive grid steps
  accumulating into f32 m/l/acc VMEM scratch; the output tile is written once
  at the end of the run (the FFA run-ordering contract, rule K2).
- blocks produced by ``nsa._block_layout`` lie fully inside their segment, so
  no length mask is needed in-kernel and no row can be empty (every q row
  attends ``top_k * block_len`` live keys). The LSE output merges with the
  cmp/win branches via the existing host-side LSE-merge.
- backward is a fused one-pass custom_vjp: **dq** accumulates in VMEM scratch
  over the same chunk table and flushes once per (head, q-block); **dk/dv**
  use revisit-accumulation into *indexed* output windows — the PR 7 fused
  backward first-visit/last-visit discipline, except the first-visit flags
  come from a second scalar-prefetch array (a chunk may be selected by many
  q-blocks; its first visitor zero-inits the window, later visitors ``+=``)
  and no last-visit flush is needed (dv is unscaled; dk's ``ln2`` correction
  is a host-side multiply). The zero background rides as aliased inputs.
- q is pre-scaled by ``softmax_scale * log2(e)`` on the host and the softmax
  runs in the exp2 domain (the softcap-free fwd-kernel fast path).

This module is deliberately env-free (rule K5): the gather-free vs gathered
choice is a registry decision (``nsa_slc``) resolved in ``parallel/nsa.py``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _named
from .ffa import (
    _lane_tile,
    _should_interpret,
    EMPTY_THRESH,
    LN2,
    LOG2E,
    MASK_VALUE,
    NEG_INF,
    NUM_LANES,
)

__all__ = [
    "block_sparse_attn",
    "first_visit_flags",
    "modeled_slc_bytes",
    "validate_block_table",
    "PALLAS_CONTRACTS",
]


def _bsp_fwd_kernel(
    tbl_ref,
    q_ref,
    k_ref,
    v_ref,
    out_ref,
    lse_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    ds: int,
):
    c_idx = pl.program_id(2)
    num_chunks_grid = pl.num_programs(2)
    is_first = jnp.int32(c_idx == 0)
    is_last = jnp.int32(c_idx == num_chunks_grid - 1)

    @pl.when(is_first == 1)
    def _():
        m_scr[:] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]  # (r, d), pre-scaled by softmax_scale * log2e
    k = k_ref[0, :, 0, :]  # (ds, d)
    v = v_ref[0, :, 0, :]  # (ds, dv)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (r, ds) — every chunk row is live (blocks lie inside their segment)

    m_prev = m_scr[...]  # (r, NUM_LANES)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
    p = jnp.exp2(s - _lane_tile(m_new, ds))
    alpha = jnp.exp2(m_prev - m_new)
    l_scr[:] = l_scr[...] * alpha + jnp.sum(p, axis=1)[:, None]
    pv = jax.lax.dot_general(
        p.astype(v_ref.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_scr[:] = acc_scr[:] * _lane_tile(alpha, acc_scr.shape[-1]) + pv
    m_scr[:] = m_new

    @pl.when(is_last == 1)
    def _():
        m = m_scr[...]
        l = l_scr[...]
        empty = m <= EMPTY_THRESH
        l_safe = jnp.where(empty | (l == 0.0), 1.0, l)
        o = acc_scr[:] / _lane_tile(l_safe, acc_scr.shape[-1])
        o = jnp.where(_lane_tile(empty, o.shape[-1]), 0.0, o)
        out_ref[0, 0] = o.astype(out_ref.dtype)
        lse_ref[0, 0] = jnp.where(
            empty, MASK_VALUE, (m + jnp.log2(l_safe)) * LN2
        ).astype(jnp.float32)


def _bsp_fwd_pallas(chunk_tbl, q_r, k_c, v_c, scale: float, interpret: bool):
    """q_r: ``(hk, n_qb, r, d)`` UNscaled; k/v_c ``(n_chunks, ds, hk, *)``;
    chunk_tbl ``(hk, n_qb, C)`` int32 chunk indices, every entry in-range.

    Returns (out ``(hk, n_qb, r, dv)`` q dtype, lse ``(hk, n_qb, r,
    NUM_LANES)`` fp32 natural-log, MASK_VALUE flags on empty rows).
    """
    hk, n_qb, r, d = q_r.shape
    n_chunks, ds, _, dv = v_c.shape
    C = chunk_tbl.shape[2]
    q_r = (q_r.astype(jnp.float32) * (scale * LOG2E)).astype(q_r.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(hk, n_qb, C),
        in_specs=[
            pl.BlockSpec(
                (1, 1, r, d),
                lambda h, b, c, tbl: (h, b, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, ds, 1, d),
                lambda h, b, c, tbl: (tbl[h, b, c], 0, h, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, ds, 1, dv),
                lambda h, b, c, tbl: (tbl[h, b, c], 0, h, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, r, dv),
                lambda h, b, c, tbl: (h, b, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, r, NUM_LANES),
                lambda h, b, c, tbl: (h, b, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((r, NUM_LANES), jnp.float32),
            pltpu.VMEM((r, NUM_LANES), jnp.float32),
            pltpu.VMEM((r, dv), jnp.float32),
        ],
    )
    kernel = partial(_bsp_fwd_kernel, ds=ds)
    out, lse = _named.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hk, n_qb, r, dv), q_r.dtype),
            jax.ShapeDtypeStruct((hk, n_qb, r, NUM_LANES), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * hk * n_qb * C * r * ds * (d + dv),
            bytes_accessed=(
                q_r.size * q_r.dtype.itemsize
                + hk * n_qb * C * ds * (d + dv) * k_c.dtype.itemsize
                + hk * n_qb * r * dv * q_r.dtype.itemsize
            ),
            transcendentals=hk * n_qb * C * r * ds,
        ),
    )(chunk_tbl, q_r, k_c, v_c)
    return out, lse


def _bsp_bwd_kernel(
    tbl_ref,
    fvis_ref,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dkz_ref,
    dvz_ref,
    dq_ref,
    dk_ref,
    dv_ref,
    dq_scr,
    *,
    scale: float,
):
    h_idx = pl.program_id(0)
    b_idx = pl.program_id(1)
    c_idx = pl.program_id(2)
    num_chunks_grid = pl.num_programs(2)
    is_first = jnp.int32(c_idx == 0)
    is_last = jnp.int32(c_idx == num_chunks_grid - 1)
    del dkz_ref, dvz_ref  # aliased zero background only; never read in-kernel

    # first-visit flag for the (head, chunk) window this step accumulates
    # into: 1 exactly on the earliest grid step (in b-major, c-minor visit
    # order) that maps onto this chunk for this head
    fvis = fvis_ref[h_idx, b_idx, c_idx]

    @pl.when(is_first == 1)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(fvis == 1)
    def _():
        dk_ref[0, :, 0] = jnp.zeros(dk_ref.shape[1:2] + dk_ref.shape[3:],
                                    jnp.float32)
        dv_ref[0, :, 0] = jnp.zeros(dv_ref.shape[1:2] + dv_ref.shape[3:],
                                    jnp.float32)

    q = q_ref[0, 0]  # (r, d), pre-scaled by softmax_scale * log2e
    k = k_ref[0, :, 0, :]  # (ds, d)
    v = v_ref[0, :, 0, :]  # (ds, dv)
    do_blk = do_ref[0, 0]  # (r, dv)
    # lse is stored in natural log; the recompute runs in the exp2 domain
    lse2 = lse_ref[0, 0][:, :1] * LOG2E  # (r, 1)
    delta_c = delta_ref[0, 0][:, :1]  # (r, 1)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (r, ds) exp2-domain logits
    p = jnp.exp2(s - lse2)  # exact softmax weights (no running max needed)

    dv_ref[0, :, 0] += jax.lax.dot_general(
        p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (ds, dv)

    dp = jax.lax.dot_general(
        do_blk, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (r, ds)
    ds_mat = p * (dp - delta_c)

    # dk accumulates against the PRE-scaled q: the extra scale*log2e factor
    # is corrected on the host by a single * ln2 (ln2 * log2e == 1, leaving
    # exactly the softmax_scale the math wants) — the ffa fused-bwd algebra
    dk_ref[0, :, 0] += jax.lax.dot_general(
        ds_mat.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (ds, d)

    dq_scr[:] += jax.lax.dot_general(
        ds_mat.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (r, d) against UNscaled k; the flush applies softmax_scale

    @pl.when(is_last == 1)
    def _():
        dq_ref[0, 0] = (dq_scr[:] * scale).astype(jnp.float32)


def _bsp_bwd_pallas(chunk_tbl, q_r, k_c, v_c, do_r, lse_r, delta_r,
                    scale: float, interpret: bool):
    """Fused one-pass backward over the same chunk table as the forward.

    q_r UNscaled ``(hk, n_qb, r, d)``; do_r ``(hk, n_qb, r, dv)``; lse_r /
    delta_r ``(hk, n_qb, r, NUM_LANES)`` fp32 (lane-broadcast). Returns
    (dq ``(hk, n_qb, r, d)``, dk ``(n_chunks, ds, hk, d)``, dv
    ``(n_chunks, ds, hk, dv)``), all fp32.
    """
    hk, n_qb, r, d = q_r.shape
    n_chunks, ds, _, dv = v_c.shape
    C = chunk_tbl.shape[2]
    q_r = (q_r.astype(jnp.float32) * (scale * LOG2E)).astype(q_r.dtype)
    fvis = first_visit_flags(chunk_tbl, n_chunks)

    # zero background for the revisit-accumulated dk/dv windows: donated to
    # the outputs via input_output_aliases, fetched by a CONSTANT index map
    # (never streamed per step, never read in-kernel)
    dkz = jnp.zeros((n_chunks, ds, hk, d), jnp.float32)
    dvz = jnp.zeros((n_chunks, ds, hk, dv), jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(hk, n_qb, C),
        in_specs=[
            pl.BlockSpec(
                (1, 1, r, d),
                lambda h, b, c, tbl, fv: (h, b, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, ds, 1, d),
                lambda h, b, c, tbl, fv: (tbl[h, b, c], 0, h, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, ds, 1, dv),
                lambda h, b, c, tbl, fv: (tbl[h, b, c], 0, h, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, r, dv),
                lambda h, b, c, tbl, fv: (h, b, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, r, NUM_LANES),
                lambda h, b, c, tbl, fv: (h, b, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, r, NUM_LANES),
                lambda h, b, c, tbl, fv: (h, b, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, ds, 1, d),
                lambda h, b, c, tbl, fv: (0, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, ds, 1, dv),
                lambda h, b, c, tbl, fv: (0, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, r, d),
                lambda h, b, c, tbl, fv: (h, b, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, ds, 1, d),
                lambda h, b, c, tbl, fv: (tbl[h, b, c], 0, h, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, ds, 1, dv),
                lambda h, b, c, tbl, fv: (tbl[h, b, c], 0, h, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((r, d), jnp.float32),
        ],
    )
    kernel = partial(_bsp_bwd_kernel, scale=scale)
    dq, dk, dv_out = _named.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hk, n_qb, r, d), jnp.float32),
            jax.ShapeDtypeStruct((n_chunks, ds, hk, d), jnp.float32),
            jax.ShapeDtypeStruct((n_chunks, ds, hk, dv), jnp.float32),
        ],
        interpret=interpret,
        # operands 8/9 (dkz/dvz, counting the 2 scalar-prefetch args) donate
        # their zeroed buffers to outputs 1/2 (dk/dv)
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            # the chunk axis must be sequential (scratch accumulation) AND
            # the q-block axis too: dk/dv windows are revisited across
            # q-blocks of the same head, in b-major grid order
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=8 * hk * n_qb * C * r * ds * (d + dv) // 2,
            bytes_accessed=(
                2 * q_r.size * q_r.dtype.itemsize
                + 3 * hk * n_qb * C * ds * (d + dv) * k_c.dtype.itemsize
            ),
            transcendentals=hk * n_qb * C * r * ds,
        ),
    )(chunk_tbl, fvis, q_r, k_c, v_c, do_r, lse_r, delta_r, dkz, dvz)
    # the kernel accumulated ds^T @ (q * scale * log2e); * ln2 leaves scale
    dk = dk * LN2
    return dq, dk, dv_out


def first_visit_flags(chunk_tbl: jax.Array, n_chunks: int) -> jax.Array:
    """Per-head first-visit flags for the backward's revisit windows.

    For each kv head, grid steps visit chunk-table entries in row-major
    ``(q_block, slot)`` order; entry (b, c) is flagged 1 iff it is the FIRST
    step whose index map lands on its chunk. Works on traced tables (the
    table may come from an in-graph top-k); shape ``(hk, n_qb, C)`` int32.
    """
    hk, n_qb, C = chunk_tbl.shape

    def per_head(tbl_h):
        e = tbl_h.reshape(-1).astype(jnp.int32)  # (n_qb * C,)
        pos = jnp.arange(e.shape[0], dtype=jnp.int32)
        big = jnp.int32(e.shape[0])
        first = jnp.full((n_chunks,), big, jnp.int32).at[e].min(pos)
        return (first[e] == pos).astype(jnp.int32).reshape(n_qb, C)

    return jax.vmap(per_head)(chunk_tbl)


def validate_block_table(block_idx: np.ndarray, n_blocks: int) -> None:
    """R5-style index-table audit (host, concrete tables only): every
    prefetched block index must be in-range and each (kv-head, q-block)
    row's top-k picks must be pairwise distinct — a duplicate would double
    that block's softmax mass silently."""
    tbl = np.asarray(block_idx)
    if tbl.size == 0:
        raise ValueError("block_idx is empty")
    if tbl.min() < 0 or tbl.max() >= n_blocks:
        raise ValueError(
            f"block_idx out of range: min={tbl.min()} max={tbl.max()} "
            f"valid=[0, {n_blocks})"
        )
    srt = np.sort(tbl, axis=-1)
    if (srt[..., 1:] == srt[..., :-1]).any():
        raise ValueError(
            "block_idx has duplicate block picks within a "
            "(kv-head, q-block) row"
        )


def modeled_slc_bytes(
    *,
    hk: int,
    n_qb: int,
    top_k: int,
    block_len: int,
    d_stride: int,
    block_size_q: int,
    g: int,
    d: int,
    dv: int,
    itemsize: int,
) -> dict:
    """Modeled HBM bytes for the slc branch: gather-free streaming vs the
    gathered-dense reference. The gathered path pays the streamed traffic
    PLUS a write+read round trip of the materialized ``take_along_axis``
    K/V selections (``top_k * block_len`` rows per (head, q-block))."""
    r = block_size_q * g
    C = top_k * (block_len // d_stride)
    q_bytes = hk * n_qb * r * d * itemsize
    out_bytes = hk * n_qb * r * dv * itemsize
    streamed_kv = hk * n_qb * C * d_stride * (d + dv) * itemsize
    streamed = q_bytes + out_bytes + streamed_kv
    gathered = streamed + 2 * hk * n_qb * top_k * block_len * (d + dv) * itemsize
    return {"streamed_bytes": streamed, "gathered_bytes": gathered}


@dataclass(frozen=True, eq=False)
class BSPParams:
    """Static kernel parameters (hashable by identity for custom_vjp)."""

    softmax_scale: float
    interpret: bool


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _bsp_core(q_r, k_c, v_c, chunk_tbl, params: BSPParams):
    out, lse = _bsp_fwd_pallas(
        chunk_tbl, q_r, k_c, v_c, params.softmax_scale, params.interpret
    )
    return out, lse


def _bsp_core_fwd(q_r, k_c, v_c, chunk_tbl, params: BSPParams):
    out, lse = _bsp_fwd_pallas(
        chunk_tbl, q_r, k_c, v_c, params.softmax_scale, params.interpret
    )
    return (out, lse), (q_r, k_c, v_c, chunk_tbl, out, lse)


def _bsp_core_bwd(params: BSPParams, res, cts):
    do, _ = cts  # lse cotangent discarded (lse feeds merges, not losses)
    q_r, k_c, v_c, chunk_tbl, out, lse = res
    delta = jnp.sum(
        out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1
    )  # (hk, n_qb, r)
    delta_r = jnp.broadcast_to(delta[..., None], lse.shape).astype(jnp.float32)
    dq, dk, dv = _bsp_bwd_pallas(
        chunk_tbl, q_r, k_c, v_c, do.astype(q_r.dtype), lse, delta_r,
        params.softmax_scale, params.interpret,
    )
    return (
        dq.astype(q_r.dtype),
        dk.astype(k_c.dtype),
        dv.astype(v_c.dtype),
        None,  # int chunk table: no cotangent
    )


_bsp_core.defvjp(_bsp_core_fwd, _bsp_core_bwd)


def block_sparse_attn(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_idx: jax.Array,
    block_starts,
    *,
    block_len: int,
    block_size_q: int,
    d_stride: int | None = None,
    softmax_scale: float | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Gather-free block-sparse attention over a top-k block index table.

    Each q-block of ``block_size_q`` rows attends, per kv head, exactly the
    KV blocks named by its ``block_idx`` row — streamed from HBM in place
    through the prefetched chunk table, never gathered.

    Args:
        q: ``(S, hq, d)``; k: ``(S, hk, d)``; v: ``(S, hk, dv)``.
        block_idx: ``(hk, n_qb, top_k)`` int — selected block ids per
            (kv-head, q-block). May be traced (in-graph top-k); concrete
            tables are audited (in-range + per-row deduplicated).
        block_starts: ``(n_blocks,)`` int row offsets of each selectable
            block (``nsa._block_layout`` starts); every start must be
            ``d_stride``-aligned and every block fully inside its segment.
        block_len: rows per selectable block; ``d_stride`` (default
            ``block_len``) is the block-start stride — blocks overlap when
            it is smaller, and it is the streamed-chunk granularity.
        block_size_q: q rows per table row; must divide ``S``.
        softmax_scale: defaults to ``d ** -0.5``.
        interpret: force/deny Pallas interpret mode (defaults to the shared
            env/backend heuristic).

    Returns:
        (out ``(S, hq, dv)`` in q's dtype, lse ``(S, hq)`` fp32 natural-log,
        ``-inf`` on never-attending rows — none exist for valid tables).
    """
    S, hq, d = q.shape
    _, hk, dv = v.shape
    if hq % hk:
        raise ValueError(f"hq={hq} not a multiple of kv heads hk={hk}")
    if d_stride is None:
        d_stride = block_len
    ds = int(d_stride)
    if block_len % ds:
        raise ValueError(f"block_len={block_len} not a multiple of {ds=}")
    if S % ds:
        raise ValueError(f"S={S} not a multiple of d_stride={ds}")
    if S % block_size_q:
        raise ValueError(f"S={S} not a multiple of {block_size_q=}")
    if not (ds <= NUM_LANES or ds % NUM_LANES == 0):
        raise ValueError(
            f"d_stride={ds} must be <= {NUM_LANES} or a multiple of it "
            f"(lane-tiling rule shared with ffa.default_blocks)"
        )
    g = hq // hk
    n_qb = S // block_size_q
    n_chunks = S // ds
    alpha = block_len // ds
    if softmax_scale is None:
        softmax_scale = float(d) ** -0.5
    if interpret is None:
        interpret = _should_interpret()

    starts_arr = block_starts
    if not isinstance(block_idx, jax.core.Tracer):
        n_blocks = int(np.asarray(starts_arr).shape[0])
        validate_block_table(np.asarray(block_idx), n_blocks)
    if not isinstance(starts_arr, jax.core.Tracer):
        starts_np = np.asarray(starts_arr)
        if (starts_np % ds).any():
            raise ValueError(
                f"block_starts must be d_stride={ds} aligned"
            )
        if starts_np.size and int(starts_np.max()) + block_len > S:
            raise ValueError("a block extends past the sequence end")

    starts = jnp.asarray(starts_arr, jnp.int32)
    ctbl = (
        (starts // ds)[block_idx][..., None]
        + jnp.arange(alpha, dtype=jnp.int32)
    ).reshape(hk, n_qb, -1).astype(jnp.int32)

    # (S, hq, d) -> (hk, n_qb, bq*g, d): q heads [h*g, (h+1)*g) share kv
    # head h (nsa's `reshape(S, hk, g, dh)` grouping); within a tile, row
    # q_row * g + gi
    q_r = (
        q.reshape(n_qb, block_size_q, hk, g, d)
        .transpose(2, 0, 1, 3, 4)
        .reshape(hk, n_qb, block_size_q * g, d)
    )
    k_c = k.reshape(n_chunks, ds, hk, d)
    v_c = v.reshape(n_chunks, ds, hk, dv)

    params = BSPParams(softmax_scale=float(softmax_scale),
                       interpret=bool(interpret))
    out_r, lse_r = _bsp_core(q_r, k_c, v_c, ctbl, params)

    out = (
        out_r.reshape(hk, n_qb, block_size_q, g, dv)
        .transpose(1, 2, 0, 3, 4)
        .reshape(S, hq, dv)
    )
    lse_raw = (
        lse_r[..., 0]
        .reshape(hk, n_qb, block_size_q, g)
        .transpose(1, 2, 0, 3)
        .reshape(S, hq)
    )
    lse = jnp.where(lse_raw <= EMPTY_THRESH, NEG_INF, lse_raw)
    return out, lse


# Static kernel-contract declarations consumed by analysis/kernel_check
# (K2/K4 source rules + K1/K3/K4 capture checks). The chunk-axis guards bind
# from pl.program_id; the backward's dk/dv windows are revisit-accumulated
# (scatter targets indexed by the chunk table) with first-visit init bound
# from the fvis scalar-prefetch array and NO flush (dv is exact as
# accumulated; dk's ln2 correction is a host-side multiply).
PALLAS_CONTRACTS: dict = {
    "_bsp_fwd_kernel": dict(
        wrapper="_bsp_fwd_pallas",
        scratch=("m_scr", "l_scr", "acc_scr"),
        outputs=("out_ref", "lse_ref"),
        out_dtypes=("input", "f32"),
        init_guard="is_first",
        flush_guard="is_last",
        init_binding="c_idx == 0",
        flush_binding="num_chunks_grid - 1",
        group_inner=None,
    ),
    "_bsp_bwd_kernel": dict(
        wrapper="_bsp_bwd_pallas",
        scratch=("dq_scr",),
        outputs=("dq_ref", "dk_ref", "dv_ref"),
        out_dtypes=("f32", "f32", "f32"),
        init_guard="is_first",
        flush_guard="is_last",
        init_binding="c_idx == 0",
        flush_binding="num_chunks_grid - 1",
        group_inner=None,
        revisit=[
            dict(out="dk_ref", init_guard="fvis", init_binding="fvis_ref",
                 flush_guard=None),
            dict(out="dv_ref", init_guard="fvis", init_binding="fvis_ref",
                 flush_guard=None),
        ],
    ),
}
