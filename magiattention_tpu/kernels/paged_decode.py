"""Ragged paged-decode attention Pallas kernel (serving decode step).

The gather+FFA path in :mod:`paged_kv` materializes ``max_pages * page_size``
contiguous rows per sequence before attending — fine for prefill chunks, but
a decode step reads ONE query row per sequence, so the gather dominates. This
kernel attends straight out of the paged cache instead, in the Ragged Paged
Attention shape (PAPERS.md): a single query tile per sequence (the GQA group
rows of one kv head), a KV-page-major grid, and the per-sequence page table
as scalar prefetch so each grid step DMAs exactly one page.

Design notes (shared idiom with ``ffa.py`` — same online-softmax algebra,
same Mosaic compatibility rules):

- grid ``(hk, max_seqs, pages_per_seq)`` with the page axis innermost and
  ``arbitrary``: all pages of one (head, seq) are consecutive grid steps
  accumulating into VMEM scratch; the output tile is written once at the end
  of the run (the FFA run-ordering contract, rule K2).
- the page-table row is prefetch state consumed by the k/v index maps;
  unallocated entries (-1) clamp to page 0 and the length mask turns the
  whole page into exact no-op contributions (masked ``p`` underflows to 0.0,
  never-live rows are discarded by the finalize empty threshold), so dead
  pages need no control flow — matching ``gather_kv``'s clamp semantics.
- lengths are traced values (NOT host constants): one lowered kernel serves
  every step of a serving loop, which is the whole point vs ``paged_attn``'s
  host-static ``kv_len`` plan parameterization.
- q is pre-scaled by ``softmax_scale * log2(e)`` on the host and the softmax
  runs in the exp2 domain (the softcap-free fwd-kernel fast path; decode has
  no softcap rung today).
- no ``-inf`` arithmetic in-kernel: masking uses ``MASK_VALUE``; fully-empty
  slots (length 0) are flagged at ``EMPTY_THRESH`` and converted to
  (out=0, lse=-inf) on the host, exactly like ``_fwd_kernel``.

This module is deliberately env-free (rule K5): routing decisions (decode
kernel vs gather+FFA vs dense) live in ``serving/decode.py``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec

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
from .paged_kv import PagedKVCache

__all__ = [
    "paged_decode_attn",
    "paged_decode_attn_int8",
    "paged_decode_attn_sharded",
    "paged_decode_attn_spec",
    "PALLAS_CONTRACTS",
]


def _paged_decode_kernel(
    table_ref,
    lengths_ref,
    q_ref,
    k_ref,
    v_ref,
    out_ref,
    lse_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    ps: int,
):
    s_idx = pl.program_id(1)
    p_idx = pl.program_id(2)
    num_pages_grid = pl.num_programs(2)
    is_first = jnp.int32(p_idx == 0)
    is_last = jnp.int32(p_idx == num_pages_grid - 1)

    @pl.when(is_first == 1)
    def _():
        m_scr[:] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]  # (g, d), pre-scaled by softmax_scale * log2e
    k = k_ref[0, :, 0, :]  # (ps, d)
    v = v_ref[0, :, 0, :]  # (ps, dv)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (g, ps)
    # ragged length mask: page p covers rows [p*ps, (p+1)*ps) of the
    # sequence; rows at or past lengths[s] are dead (incl. every row of a
    # clamped -1 page, whose coverage lies entirely past the length)
    cols = p_idx * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols < lengths_ref[s_idx], s, MASK_VALUE)

    m_prev = m_scr[...]  # (g, NUM_LANES)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
    p = jnp.exp2(s - _lane_tile(m_new, ps))
    alpha = jnp.exp2(m_prev - m_new)  # == 1 while empty
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


def _paged_decode_pallas(page_table, lengths, q_hds, k_pages, v_pages,
                         interpret: bool):
    """q_hds: ``(hk, S, g, d)`` pre-scaled; k/v_pages ``(num_pages, ps, hk, *)``.

    Returns (out ``(hk, S, g, dv)`` q dtype, lse ``(hk, S, g, NUM_LANES)``
    fp32 with MASK_VALUE flags on empty slots).
    """
    hk, S, g, d = q_hds.shape
    num_pages, ps, _, dv = v_pages.shape
    P = page_table.shape[1]

    lse_spec = pl.BlockSpec(
        (1, 1, g, NUM_LANES),
        lambda h, s, p, table, lens: (h, s, 0, 0),
        memory_space=pltpu.VMEM,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(hk, S, P),
        in_specs=[
            pl.BlockSpec(
                (1, 1, g, d),
                lambda h, s, p, table, lens: (h, s, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, ps, 1, d),
                lambda h, s, p, table, lens: (
                    jnp.maximum(table[s, p], 0), 0, h, 0
                ),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, ps, 1, dv),
                lambda h, s, p, table, lens: (
                    jnp.maximum(table[s, p], 0), 0, h, 0
                ),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, g, dv),
                lambda h, s, p, table, lens: (h, s, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            lse_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((g, NUM_LANES), jnp.float32),
            pltpu.VMEM((g, NUM_LANES), jnp.float32),
            pltpu.VMEM((g, dv), jnp.float32),
        ],
    )
    kernel = partial(_paged_decode_kernel, ps=ps)
    out, lse = _named.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hk, S, g, dv), q_hds.dtype),
            jax.ShapeDtypeStruct((hk, S, g, NUM_LANES), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * hk * S * P * g * ps * d,
            bytes_accessed=(
                q_hds.size * q_hds.dtype.itemsize
                + S * P * ps * (d + dv) * k_pages.dtype.itemsize
            ),
            transcendentals=hk * S * P * g * ps,
        ),
    )(page_table, lengths, q_hds, k_pages, v_pages)
    return out, lse


def paged_decode_attn(
    q: jax.Array,
    cache: PagedKVCache,
    softmax_scale: float | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One batched decode step: each sequence slot's single query token
    attends over its own paged KV rows ``[0, lengths[slot])``.

    Args:
        q: ``(max_seqs, hq, d)`` — one query row per slot. Slots with
            ``lengths == 0`` are inactive and yield (out=0, lse=-inf).
        cache: the paged cache; ``page_table``/``lengths`` ride as scalar
            prefetch, so they may be traced (jit-safe serving loop).
        softmax_scale: defaults to ``d ** -0.5``.
        interpret: force/deny Pallas interpret mode (defaults to the env/
            backend heuristic shared with FFA).

    Returns:
        (out ``(max_seqs, hq, dv)`` in q's dtype, lse ``(max_seqs, hq)``
        fp32, ``-inf`` on inactive slots).
    """
    S, hq, d = q.shape
    num_pages, ps, hk, dv = cache.v_pages.shape
    if hq % hk:
        raise ValueError(f"hq={hq} not a multiple of kv heads hk={hk}")
    if not (ps <= NUM_LANES or ps % NUM_LANES == 0):
        raise ValueError(
            f"page_size={ps} must be <= {NUM_LANES} or a multiple of it "
            f"(lane-tiling rule shared with ffa.default_blocks)"
        )
    g = hq // hk
    if softmax_scale is None:
        softmax_scale = float(d) ** -0.5
    if interpret is None:
        interpret = _should_interpret()

    q_scale = softmax_scale * LOG2E
    q = (q.astype(jnp.float32) * q_scale).astype(q.dtype)
    # (S, hq, d) -> (hk, S, g, d): q heads [h*g, (h+1)*g) share kv head h,
    # the same grouping as ffa's `h // g` k index map
    q_hds = q.reshape(S, hk, g, d).transpose(1, 0, 2, 3)

    out_hds, lse_hds = _paged_decode_pallas(
        cache.page_table, cache.lengths, q_hds,
        cache.k_pages, cache.v_pages, interpret,
    )
    out = out_hds.transpose(1, 0, 2, 3).reshape(S, hq, dv)
    lse_raw = lse_hds[..., 0].transpose(1, 0, 2).reshape(S, hq)
    lse = jnp.where(lse_raw <= EMPTY_THRESH, NEG_INF, lse_raw)
    return out, lse


def paged_decode_attn_sharded(
    q: jax.Array,
    cache: PagedKVCache,
    num_shards: int,
    softmax_scale: float | None = None,
    interpret: bool | None = None,
    devices=None,
) -> tuple[jax.Array, jax.Array]:
    """Mesh-sharded decode step: ``shard_map`` over the kv-head axis, one
    kernel launch per shard (the SNIPPETS ``sharded_paged_attention``
    pattern). Each shard runs the *same* ``_paged_decode_pallas`` body over
    its ``hk // num_shards`` heads — per-(head, seq) accumulation is
    untouched, so shard output is bitwise-equal to the single-device run.

    page_table/lengths are replicated (every shard walks the same pages);
    k/v pages are split on their head axis, q on its leading kv-head axis.
    No new ``pallas_call`` site: the audited single-device contract covers
    the sharded path exactly.
    """
    S, hq, d = q.shape
    num_pages, ps, hk, dv = cache.v_pages.shape
    if hq % hk:
        raise ValueError(f"hq={hq} not a multiple of kv heads hk={hk}")
    if hk % num_shards:
        raise ValueError(
            f"hk={hk} not divisible by num_shards={num_shards}; the kv-head "
            f"axis is the shard axis"
        )
    if not (ps <= NUM_LANES or ps % NUM_LANES == 0):
        raise ValueError(
            f"page_size={ps} must be <= {NUM_LANES} or a multiple of it "
            f"(lane-tiling rule shared with ffa.default_blocks)"
        )
    if devices is None:
        devices = jax.devices()[:num_shards]
    if len(devices) < num_shards:
        raise ValueError(
            f"need {num_shards} devices for the kv mesh, have {len(devices)}"
        )
    g = hq // hk
    if softmax_scale is None:
        softmax_scale = float(d) ** -0.5
    if interpret is None:
        interpret = _should_interpret()

    q_scale = softmax_scale * LOG2E
    q = (q.astype(jnp.float32) * q_scale).astype(q.dtype)
    q_hds = q.reshape(S, hk, g, d).transpose(1, 0, 2, 3)

    mesh = Mesh(np.asarray(devices), ("kv",))
    spec_kv_heads = PartitionSpec(None, None, "kv")
    sharded = shard_map(
        lambda table, lens, qh, kp, vp: _paged_decode_pallas(
            table, lens, qh, kp, vp, interpret
        ),
        mesh=mesh,
        in_specs=(
            PartitionSpec(),  # page_table: replicated
            PartitionSpec(),  # lengths: replicated
            PartitionSpec("kv"),  # q_hds (hk, S, g, d)
            spec_kv_heads,  # k_pages (num_pages, ps, hk, d)
            spec_kv_heads,  # v_pages (num_pages, ps, hk, dv)
        ),
        out_specs=(PartitionSpec("kv"), PartitionSpec("kv")),
        check_vma=False,
    )
    out_hds, lse_hds = sharded(
        cache.page_table, cache.lengths, q_hds, cache.k_pages, cache.v_pages
    )
    # Re-materialize as uncommitted single-device arrays: the shard_map
    # outputs are laid out across the mesh, and downstream eager ops (the
    # model's projections) on sharded operands would pick partitioned
    # reduction orders that drift ~1e-7 from the single-device run.
    # Gathering here keeps the whole serving loop bitwise-equal to the
    # unsharded rung; uncommitted (vs device_put to a mesh device) so the
    # next tick's inputs can feed the mesh again.
    out_hds = jnp.asarray(jax.device_get(out_hds))
    lse_hds = jnp.asarray(jax.device_get(lse_hds))
    out = out_hds.transpose(1, 0, 2, 3).reshape(S, hq, dv)
    lse_raw = lse_hds[..., 0].transpose(1, 0, 2).reshape(S, hq)
    lse = jnp.where(lse_raw <= EMPTY_THRESH, NEG_INF, lse_raw)
    return out, lse


def _paged_decode_spec_kernel(
    table_ref,
    lengths_ref,
    q_ref,
    k_ref,
    v_ref,
    out_ref,
    lse_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    ps: int,
    spec_k: int,
    g: int,
):
    """Multi-token speculative-verify variant: the q tile holds the GQA
    group rows of ``spec_k`` consecutive draft tokens (``spec_k * g`` rows),
    already appended to the cache, with a per-row causal horizon — row
    ``r`` verifies draft token ``t = r // g`` sitting at absolute position
    ``lengths - spec_k + t``, so it may attend columns ``< lengths -
    (spec_k - 1 - t)``. Everything else (page walk, online softmax,
    init/flush discipline) is the base decode kernel."""
    s_idx = pl.program_id(1)
    p_idx = pl.program_id(2)
    num_pages_grid = pl.num_programs(2)
    is_first = jnp.int32(p_idx == 0)
    is_last = jnp.int32(p_idx == num_pages_grid - 1)

    @pl.when(is_first == 1)
    def _():
        m_scr[:] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]  # (spec_k * g, d), pre-scaled by softmax_scale * log2e
    k = k_ref[0, :, 0, :]  # (ps, d)
    v = v_ref[0, :, 0, :]  # (ps, dv)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (spec_k * g, ps)
    cols = p_idx * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    # per-row ragged causal horizon: draft token t = row // g ends at
    # absolute position lengths - spec_k + t (inclusive)
    limit = lengths_ref[s_idx] - (spec_k - 1 - rows // g)
    s = jnp.where(cols < limit, s, MASK_VALUE)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
    p = jnp.exp2(s - _lane_tile(m_new, ps))
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


def _paged_decode_spec_pallas(page_table, lengths, q_hds, k_pages, v_pages,
                              spec_k: int, g: int, interpret: bool):
    """q_hds: ``(hk, S, spec_k * g, d)`` pre-scaled; same page walk as the
    base decode pallas wrapper, taller q/out/scratch tiles."""
    hk, S, kg, d = q_hds.shape
    num_pages, ps, _, dv = v_pages.shape
    P = page_table.shape[1]

    lse_spec = pl.BlockSpec(
        (1, 1, kg, NUM_LANES),
        lambda h, s, p, table, lens: (h, s, 0, 0),
        memory_space=pltpu.VMEM,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(hk, S, P),
        in_specs=[
            pl.BlockSpec(
                (1, 1, kg, d),
                lambda h, s, p, table, lens: (h, s, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, ps, 1, d),
                lambda h, s, p, table, lens: (
                    jnp.maximum(table[s, p], 0), 0, h, 0
                ),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, ps, 1, dv),
                lambda h, s, p, table, lens: (
                    jnp.maximum(table[s, p], 0), 0, h, 0
                ),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, kg, dv),
                lambda h, s, p, table, lens: (h, s, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            lse_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((kg, NUM_LANES), jnp.float32),
            pltpu.VMEM((kg, NUM_LANES), jnp.float32),
            pltpu.VMEM((kg, dv), jnp.float32),
        ],
    )
    kernel = partial(_paged_decode_spec_kernel, ps=ps, spec_k=spec_k, g=g)
    out, lse = _named.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hk, S, kg, dv), q_hds.dtype),
            jax.ShapeDtypeStruct((hk, S, kg, NUM_LANES), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * hk * S * P * kg * ps * d,
            bytes_accessed=(
                q_hds.size * q_hds.dtype.itemsize
                + S * P * ps * (d + dv) * k_pages.dtype.itemsize
            ),
            transcendentals=hk * S * P * kg * ps,
        ),
    )(page_table, lengths, q_hds, k_pages, v_pages)
    return out, lse


def paged_decode_attn_spec(
    q: jax.Array,
    cache: PagedKVCache,
    softmax_scale: float | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Speculative verify step: each slot's ``spec_k`` draft-token query
    rows (already appended to the cache, so ``lengths`` includes them)
    attend their own causal prefixes in one launch.

    Args:
        q: ``(max_seqs, spec_k, hq, d)`` — draft token ``t`` of a slot sits
            at absolute position ``lengths[slot] - spec_k + t``. Slots with
            ``lengths == 0`` are inactive and yield (out=0, lse=-inf).

    Returns:
        (out ``(max_seqs, spec_k, hq, dv)`` in q's dtype,
        lse ``(max_seqs, spec_k, hq)`` fp32, ``-inf`` on inactive slots).
    """
    S, spec_k, hq, d = q.shape
    num_pages, ps, hk, dv = cache.v_pages.shape
    if hq % hk:
        raise ValueError(f"hq={hq} not a multiple of kv heads hk={hk}")
    if spec_k < 1:
        raise ValueError(f"spec_k={spec_k} must be >= 1")
    if not (ps <= NUM_LANES or ps % NUM_LANES == 0):
        raise ValueError(
            f"page_size={ps} must be <= {NUM_LANES} or a multiple of it "
            f"(lane-tiling rule shared with ffa.default_blocks)"
        )
    g = hq // hk
    if softmax_scale is None:
        softmax_scale = float(d) ** -0.5
    if interpret is None:
        interpret = _should_interpret()

    q_scale = softmax_scale * LOG2E
    q = (q.astype(jnp.float32) * q_scale).astype(q.dtype)
    # (S, spec_k, hq, d) -> (hk, S, spec_k * g, d): token-major rows within
    # a kv head, so kernel row r = t * g + group_row
    q_hds = (
        q.reshape(S, spec_k, hk, g, d)
        .transpose(2, 0, 1, 3, 4)
        .reshape(hk, S, spec_k * g, d)
    )

    out_hds, lse_hds = _paged_decode_spec_pallas(
        cache.page_table, cache.lengths, q_hds,
        cache.k_pages, cache.v_pages, spec_k, g, interpret,
    )
    out = (
        out_hds.reshape(hk, S, spec_k, g, dv)
        .transpose(1, 2, 0, 3, 4)
        .reshape(S, spec_k, hq, dv)
    )
    lse_raw = (
        lse_hds[..., 0]
        .reshape(hk, S, spec_k, g)
        .transpose(1, 2, 0, 3)
        .reshape(S, spec_k, hq)
    )
    lse = jnp.where(lse_raw <= EMPTY_THRESH, NEG_INF, lse_raw)
    return out, lse


def _paged_decode_int8_kernel(
    table_ref,
    lengths_ref,
    q_ref,
    k_ref,
    v_ref,
    ks_ref,
    vs_ref,
    out_ref,
    lse_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    ps: int,
):
    """int8-KV variant: k/v pages arrive as int8 codes plus one f32 scale
    per (page, kv head), routed by the same page-table prefetch as the page
    itself (a (1, 1) block of the ``(num_pages, hk)`` scale arrays).
    Dequant happens in-kernel right after the DMA; all accumulation stays
    f32 (rule K4), so the only precision loss is the storage quantization."""
    s_idx = pl.program_id(1)
    p_idx = pl.program_id(2)
    num_pages_grid = pl.num_programs(2)
    is_first = jnp.int32(p_idx == 0)
    is_last = jnp.int32(p_idx == num_pages_grid - 1)

    @pl.when(is_first == 1)
    def _():
        m_scr[:] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (g, d), pre-scaled
    # dequant: codes are symmetric int8, scale is per (page, kv head)
    k = k_ref[0, :, 0, :].astype(jnp.float32) * ks_ref[0, 0]  # (ps, d)
    v = v_ref[0, :, 0, :].astype(jnp.float32) * vs_ref[0, 0]  # (ps, dv)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (g, ps)
    cols = p_idx * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols < lengths_ref[s_idx], s, MASK_VALUE)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
    p = jnp.exp2(s - _lane_tile(m_new, ps))
    alpha = jnp.exp2(m_prev - m_new)
    l_scr[:] = l_scr[...] * alpha + jnp.sum(p, axis=1)[:, None]
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
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


def _paged_decode_int8_pallas(page_table, lengths, q_hds, k_pages, v_pages,
                              k_scales, v_scales, interpret: bool):
    """q_hds ``(hk, S, g, d)`` pre-scaled; k/v_pages int8
    ``(num_pages, ps, hk, *)``; k/v_scales f32 ``(num_pages, hk)`` — the
    scale blocks ride the same page-table index map as their pages."""
    hk, S, g, d = q_hds.shape
    num_pages, ps, _, dv = v_pages.shape
    P = page_table.shape[1]

    lse_spec = pl.BlockSpec(
        (1, 1, g, NUM_LANES),
        lambda h, s, p, table, lens: (h, s, 0, 0),
        memory_space=pltpu.VMEM,
    )
    scale_spec = pl.BlockSpec(
        (1, 1),
        lambda h, s, p, table, lens: (jnp.maximum(table[s, p], 0), h),
        memory_space=pltpu.VMEM,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(hk, S, P),
        in_specs=[
            pl.BlockSpec(
                (1, 1, g, d),
                lambda h, s, p, table, lens: (h, s, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, ps, 1, d),
                lambda h, s, p, table, lens: (
                    jnp.maximum(table[s, p], 0), 0, h, 0
                ),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, ps, 1, dv),
                lambda h, s, p, table, lens: (
                    jnp.maximum(table[s, p], 0), 0, h, 0
                ),
                memory_space=pltpu.VMEM,
            ),
            scale_spec,
            scale_spec,
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, g, dv),
                lambda h, s, p, table, lens: (h, s, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            lse_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((g, NUM_LANES), jnp.float32),
            pltpu.VMEM((g, NUM_LANES), jnp.float32),
            pltpu.VMEM((g, dv), jnp.float32),
        ],
    )
    kernel = partial(_paged_decode_int8_kernel, ps=ps)
    out, lse = _named.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hk, S, g, dv), q_hds.dtype),
            jax.ShapeDtypeStruct((hk, S, g, NUM_LANES), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * hk * S * P * g * ps * d,
            bytes_accessed=(
                q_hds.size * q_hds.dtype.itemsize
                + S * P * ps * (d + dv)  # int8: 1 byte/elem
                + S * P * 2 * 4  # per-page scales
            ),
            transcendentals=hk * S * P * g * ps,
        ),
    )(page_table, lengths, q_hds, k_pages, v_pages, k_scales, v_scales)
    return out, lse


def paged_decode_attn_int8(
    q: jax.Array,
    cache: PagedKVCache,
    softmax_scale: float | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One batched decode step over a quantized (int8 + per-page-scale)
    cache. Same contract as :func:`paged_decode_attn`; requires
    ``cache.k_scales``/``cache.v_scales`` (see ``PagedKVCache.create`` with
    ``dtype=jnp.int8``)."""
    if cache.k_scales is None or cache.v_scales is None:
        raise ValueError(
            "paged_decode_attn_int8 needs a quantized cache "
            "(PagedKVCache.create(..., dtype=jnp.int8))"
        )
    S, hq, d = q.shape
    num_pages, ps, hk, dv = cache.v_pages.shape
    if hq % hk:
        raise ValueError(f"hq={hq} not a multiple of kv heads hk={hk}")
    if not (ps <= NUM_LANES or ps % NUM_LANES == 0):
        raise ValueError(
            f"page_size={ps} must be <= {NUM_LANES} or a multiple of it "
            f"(lane-tiling rule shared with ffa.default_blocks)"
        )
    g = hq // hk
    if softmax_scale is None:
        softmax_scale = float(d) ** -0.5
    if interpret is None:
        interpret = _should_interpret()

    q_scale = softmax_scale * LOG2E
    q = (q.astype(jnp.float32) * q_scale).astype(q.dtype)
    q_hds = q.reshape(S, hk, g, d).transpose(1, 0, 2, 3)

    out_hds, lse_hds = _paged_decode_int8_pallas(
        cache.page_table, cache.lengths, q_hds,
        cache.k_pages, cache.v_pages,
        cache.k_scales, cache.v_scales, interpret,
    )
    out = out_hds.transpose(1, 0, 2, 3).reshape(S, hq, dv)
    lse_raw = lse_hds[..., 0].transpose(1, 0, 2).reshape(S, hq)
    lse = jnp.where(lse_raw <= EMPTY_THRESH, NEG_INF, lse_raw)
    return out, lse


# Static kernel-contract declarations consumed by analysis/kernel_check
# (K2/K4 source rules + K1/K3/K4 capture checks). The page-axis guards bind
# from pl.program_id instead of plan meta columns — init_binding /
# flush_binding carry the expected binding substrings.
PALLAS_CONTRACTS: dict = {
    "_paged_decode_kernel": dict(
        wrapper="_paged_decode_pallas",
        scratch=("m_scr", "l_scr", "acc_scr"),
        outputs=("out_ref", "lse_ref"),
        out_dtypes=("input", "f32"),
        init_guard="is_first",
        flush_guard="is_last",
        init_binding="p_idx == 0",
        flush_binding="num_pages_grid - 1",
        group_inner=None,
    ),
    "_paged_decode_spec_kernel": dict(
        wrapper="_paged_decode_spec_pallas",
        scratch=("m_scr", "l_scr", "acc_scr"),
        outputs=("out_ref", "lse_ref"),
        out_dtypes=("input", "f32"),
        init_guard="is_first",
        flush_guard="is_last",
        init_binding="p_idx == 0",
        flush_binding="num_pages_grid - 1",
        group_inner=None,
    ),
    "_paged_decode_int8_kernel": dict(
        wrapper="_paged_decode_int8_pallas",
        scratch=("m_scr", "l_scr", "acc_scr"),
        outputs=("out_ref", "lse_ref"),
        out_dtypes=("input", "f32"),
        init_guard="is_first",
        flush_guard="is_last",
        init_binding="p_idx == 0",
        flush_binding="num_pages_grid - 1",
        group_inner=None,
    ),
}
