"""Flex-flash-attention Pallas TPU kernels (fwd + bwd).

TPU-native counterpart of the reference FFA CUDA kernel
(magi_attention/csrc/flexible_flash_attention/ — fwd/bwd mainloops, tile
schedulers, mask.h). Design differences, deliberate and TPU-first:

- The device-side persistent tile scheduler is replaced by a host-side plan
  (:mod:`ffa_plan`) + ``PrefetchScalarGridSpec``: the grid is exactly the list
  of non-empty (q_tile, k_tile, slice) work items, so fully-masked tiles cost
  nothing and no dynamic control flow reaches the MXU. Plan *contents* may be
  traced arrays (per-CP-rank metadata under shard_map); only the work counts
  and tile geometry are static.
- The atomic-reduce epilogues (epilogue_fwd.hpp / epilogue_bwd.hpp) are
  replaced by run-ordering: all work items of one output tile are consecutive
  grid steps accumulating into VMEM scratch; the tile is written once at the
  end of its run. dq uses the q-major plan, dk/dv the k-major plan — no
  atomics exist on TPU and none are needed.
- Slices are diagonal bands (d_lo <= j - i <= d_hi): the mask is two compares.
- Online-softmax merge math matches functional/utils.py (lse in natural log,
  -inf on fully-masked rows).

Mosaic-compatibility notes (mirrors the bundled TPU kernels
jax/experimental/pallas/ops/tpu/{flash_attention,splash_attention}):

- No ``-inf`` arithmetic inside kernels: masking uses a large finite
  ``MASK_VALUE`` (splash's DEFAULT_MASK_VALUE); fully-masked rows are detected
  by threshold at finalize and converted to (out=0, lse=-inf) on the host.
- No ``lax.cond`` over tiles: the full-tile fast path ORs the band mask with a
  scalar ``is_full`` flag (splash's ``should_not_mask`` idiom).
- lse is emitted broadcast across ``NUM_LANES`` (out block ``(bq, 128)``,
  like splash's logsumexp) and sliced on the host; the backward kernels read
  lse/delta from a lanes-major layout ``(hq, sublanes, sqp)`` with q in the
  lane dimension (splash's backward logsumexp layout).
- m/l scratch are ``(bq, NUM_LANES)`` fp32; softmax rescale uses
  ``jnp.tile`` over 128-lane groups (both bundled kernels' idiom) which
  requires ``block_k % 128 == 0`` — guaranteed by :func:`default_blocks`.

max_logits: the fwd kernel additionally emits the per-(head, q-tile) running
max of the (scaled, softcapped) logits — the TPU equivalent of the CUDA
softmax max tracking (ref csrc/flexible_flash_attention/softmax.h, surfaced
via common/forward_meta.py:21) — reduced to per-head [hq] on the host.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..env import general as env_general
from ..env import kernel as env_kernel
from ..resilience.inject import maybe_inject
from ..utils.mem_budget import VMEM_ALLOWED_BYTES, ffa_kernel_residency
from . import _named
from .ffa_plan import (  # noqa: F401
    EK0,
    EK1,
    EQ0,
    EQ1,
    IS_FULL,
    DHI,
    DLO,
    IS_FIRST,
    IS_LAST,
    KE,
    KS,
    QE,
    QS,
    QVF,
    QVL,
    FFAPlan,
    get_ffa_plan,
)
from .mask_utils import types_to_bands

NEG_INF = float("-inf")


def _registry_mod():
    """Lazy handle on the backend registry (kernels/registry.py) — every
    kernel-choice read in this file flows through it, not raw env flags."""
    from . import registry as _registry

    return _registry
NUM_LANES = 128
NUM_SUBLANES = 8
# exp2-domain softmax (softcap-free path): folding log2(e) into the q
# pre-scale turns every exp(x) into a bare exp2, deleting the per-element
# multiply Mosaic otherwise emits inside exp (flash_attention's idiom)
LOG2E = float(np.log2(np.e))
LN2 = float(np.log(2.0))
# splash's DEFAULT_MASK_VALUE: large but finite so no inf arithmetic reaches
# Mosaic; exp(MASK_VALUE - anything_sane) underflows to exactly 0.
MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
# anything at or below this is "never attended" (real logits are O(1e2))
EMPTY_THRESH = 0.5 * MASK_VALUE


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclass(frozen=True, eq=False)
class FFAParams:
    """Static kernel parameters (hashable by identity for custom_vjp)."""

    num_work: int
    num_work_t: int
    num_q_tiles: int
    num_k_tiles: int
    block_q: int
    block_k: int
    softmax_scale: float
    softcap: float
    group: int  # hq // hk
    interpret: bool
    # emit the per-head max-logits output (ref forward_meta.py:21). Costs an
    # extra (hq, sqp, 128) fp32 HBM write, so it is opt-in; when off, the
    # returned max_logits is a constant -inf placeholder.
    emit_max_logits: bool = False
    # Backward-specific tile overrides (TPU analogue of the reference's FFA
    # BWD tuning flags, docs/source/user_guide/env_variables.md:111): the dq
    # and dkv kernels have different VMEM/compute profiles than fwd (dkv
    # holds (bk, d)+(bk, dv) fp32 scratch and loops the GQA group innermost),
    # so they may want their own block sizes. None = inherit fwd blocks.
    # When set, the plan tuple carries 12 arrays (fwd6 + dq3 + dkv3) and
    # num_work_dq / num_work_dkv are the respective work counts.
    block_q_dq: int | None = None
    block_k_dq: int | None = None
    block_q_dkv: int | None = None
    block_k_dkv: int | None = None
    num_work_dq: int | None = None
    num_work_dkv: int | None = None
    # ffa_plan.min_revisit_distance of the k-major list the one-pass
    # backward would walk (the dkv override's own list when there is one;
    # the least over the ranks' lists of a stacked plan). A host integer
    # beside num_work_t: the plan's arrays may be traced, the mode may read
    # only statics. 0 = not known, which keeps the backward split.
    min_revisit_distance: int = 0
    # the runtime key's label (DistAttnRuntimeKey.label), or None: it rides
    # after the body's name in every Pallas call's scope and keys the
    # registry's record of this call's tiles and backward mode
    label: str | None = None

    def dq_blocks(self) -> tuple[int, int]:
        return (self.block_q_dq or self.block_q,
                self.block_k_dq or self.block_k)

    def dkv_blocks(self) -> tuple[int, int]:
        return (self.block_q_dkv or self.block_q,
                self.block_k_dkv or self.block_k)


def plan_arrays(plan: FFAPlan) -> tuple[jax.Array, ...]:
    """The 6 device arrays of a plan (q-major triple + k-major triple)."""
    return (
        jnp.asarray(plan.work_qt),
        jnp.asarray(plan.work_kt),
        jnp.asarray(plan.meta),
        jnp.asarray(plan.work_qt_t),
        jnp.asarray(plan.work_kt_t),
        jnp.asarray(plan.meta_t),
    )


def _item_mask(
    meta_ref, w, q_base, k_base, bq: int, bk: int, transposed: bool = False,
    repeat: int = 1,
):
    """Boolean mask of work item w on the tile at (q_base, k_base).

    Shape (bq, bk) with q rows, or (bk, bq) when ``transposed`` (k rows) —
    built directly with swapped iota since Mosaic cannot transpose i1 vectors.
    The scalar is_full flag is OR-ed in (splash's should_not_mask idiom), so
    interior tiles need no separate code path.

    ``repeat`` > 1 emits the same q tile stacked for ``repeat`` packed
    heads — ``(repeat*bq, bk)`` (q rows) or ``(bk, repeat*bq)``
    (transposed; packed heads along lanes) — via iota-mod rather than an
    i1 tile (which Mosaic cannot relayout).
    """
    qs, qe = meta_ref[w, QS], meta_ref[w, QE]
    ks, ke = meta_ref[w, KS], meta_ref[w, KE]
    lo, hi = meta_ref[w, DLO], meta_ref[w, DHI]
    full = meta_ref[w, IS_FULL] == 1
    if transposed:
        shape = (bk, repeat * bq)
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        if repeat > 1:
            rows = jax.lax.rem(rows, jnp.int32(bq))
        rows = q_base + rows
        cols = k_base + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    else:
        shape = (repeat * bq, bk)
        rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        if repeat > 1:
            rows = jax.lax.rem(rows, jnp.int32(bq))
        rows = q_base + rows
        cols = k_base + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    in_rect = (rows >= qs) & (rows < qe) & (cols >= ks) & (cols < ke)
    d = cols - rows
    band = in_rect & (d >= lo) & (d <= hi)
    return band | jnp.broadcast_to(full, band.shape)


def _lane_tile(col, width: int):
    """(r, NUM_LANES) fp32 -> (r, width) by lane-group tiling (flash_attention
    idiom; width % NUM_LANES == 0) or slicing (width < NUM_LANES)."""
    if width <= NUM_LANES:
        return col[:, :width]
    assert width % NUM_LANES == 0, f"{width=} not a multiple of {NUM_LANES}"
    return jnp.tile(col, (1, width // NUM_LANES))


# extent-clamp chunking: at most this many lane-dim chunks per tile — more
# chunks skip finer-grained dead work but each live chunk re-pays the MXU
# ramp and mask arithmetic, and past ~8 the chunk dots drop under the MXU's
# efficient minimum anyway
_MAX_CLAMP_CHUNKS = 8


def _clamp_chunks(width: int) -> int:
    """Number of lane-dimension chunks the extent-clamped kernel bodies
    split a ``width``-wide tile into; 0 = clamping off (the legacy
    single-dot bodies lower unchanged). Chunk width must stay a lane-quantum
    multiple (``_lane_tile``/Mosaic layout rule), so the count is the
    largest divisor of ``width // NUM_LANES`` within the chunk cap."""
    from . import registry as _registry

    if not _registry.extent_clamp_enabled() or width % NUM_LANES:
        return 0
    m = width // NUM_LANES
    return max(c for c in range(1, min(_MAX_CLAMP_CHUNKS, m) + 1) if m % c == 0)


def _item_extents(meta_ref, w):
    """(eq0, eq1, ek0, ek1, live) scalars of work item w: the tile-local
    live sub-rectangle the plan builder derived from the band geometry
    (ffa_plan._extend_meta_extents). ``live`` is False exactly for dummy /
    pad_plan filler items (all-zero extent)."""
    eq0, eq1 = meta_ref[w, EQ0], meta_ref[w, EQ1]
    ek0, ek1 = meta_ref[w, EK0], meta_ref[w, EK1]
    return eq0, eq1, ek0, ek1, (eq1 > eq0) & (ek1 > ek0)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    work_qt_ref,
    work_kt_ref,
    meta_ref,
    q_ref,
    k_ref,
    v_ref,
    *rest,
    softcap: float,
    bq: int,
    bk: int,
    emit_ml: bool,
    nc: int,
):
    if emit_ml:
        out_ref, lse_ref, ml_ref, m_scr, l_scr, acc_scr = rest
    else:
        out_ref, lse_ref, m_scr, l_scr, acc_scr = rest
        ml_ref = None
    w = pl.program_id(1)
    is_first = meta_ref[w, IS_FIRST]
    is_last = meta_ref[w, IS_LAST]
    is_full = meta_ref[w, IS_FULL]
    # softcap-free path runs the online softmax in the log2 domain (q was
    # pre-scaled by softmax_scale * log2(e) on the host)
    use_exp2 = softcap == 0.0
    exp_fn = jnp.exp2 if use_exp2 else jnp.exp

    @pl.when(is_first == 1)
    def _():
        m_scr[:] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0]  # pre-scaled by softmax_scale (* log2e when softcap-free)
    k = k_ref[0]

    def update(s, v_blk, width: int):
        m_prev = m_scr[...]  # (bq, NUM_LANES)
        m_blk = jnp.max(s, axis=1)[:, None]  # (bq, 1)
        m_new = jnp.maximum(m_prev, m_blk)  # (bq, NUM_LANES)
        p = exp_fn(s - _lane_tile(m_new, width))
        alpha = exp_fn(m_prev - m_new)  # (bq, NUM_LANES); ==1 while empty

        l_new = l_scr[...] * alpha + jnp.sum(p, axis=1)[:, None]
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype),
            v_blk,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * _lane_tile(alpha, acc_scr.shape[-1]) + pv
        m_scr[:] = m_new
        l_scr[:] = l_new

    def score(k_blk):
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        return s

    if nc == 0:
        s_raw = score(k)

        # interior tiles skip the band-mask arithmetic entirely (VPU is the
        # bottleneck with bf16 MXUs; splash's should-not-mask split)
        @pl.when(is_full == 1)
        def _():
            update(s_raw, v_ref[0], bk)

        @pl.when(is_full == 0)
        def _():
            q_base = work_qt_ref[w] * bq
            k_base = work_kt_ref[w] * bk
            update(
                jnp.where(
                    _item_mask(meta_ref, w, q_base, k_base, bq, bk),
                    s_raw,
                    MASK_VALUE,
                ),
                v_ref[0],
                bk,
            )
    else:
        # extent-clamped body: partial tiles run only the k chunks the live
        # extent touches — skipped chunks lie fully outside the band, so
        # their legacy contribution was exactly 0 (masked p underflows to
        # 0.0; never-live rows are discarded by finalize's empty threshold)
        ck = bk // nc
        _, _, ek0, ek1, live = _item_extents(meta_ref, w)

        @pl.when(is_full == 1)
        def _():
            update(score(k), v_ref[0], bk)

        for c in range(nc):
            c0 = c * ck

            @pl.when((is_full == 0) & live & (ek0 < c0 + ck) & (ek1 > c0))
            def _(c0=c0):
                q_base = work_qt_ref[w] * bq
                k_base = work_kt_ref[w] * bk
                update(
                    jnp.where(
                        _item_mask(
                            meta_ref, w, q_base, k_base + c0, bq, ck
                        ),
                        score(k[c0 : c0 + ck]),
                        MASK_VALUE,
                    ),
                    v_ref[0][c0 : c0 + ck],
                    ck,
                )

    @pl.when(is_last == 1)
    def _():
        m = m_scr[...]
        l = l_scr[...]
        # rows never covered by any slice: m stayed at MASK_VALUE (l holds
        # exp(0)-garbage from masked-only tiles) -> out 0, lse MASK-flagged
        # (converted to -inf on the host)
        empty = m <= EMPTY_THRESH
        l_safe = jnp.where(empty | (l == 0.0), 1.0, l)
        o = acc_scr[:] / _lane_tile(l_safe, acc_scr.shape[-1])
        o = jnp.where(_lane_tile(empty, o.shape[-1]), 0.0, o)
        out_ref[0] = o.astype(out_ref.dtype)
        if use_exp2:
            # convert back to the natural-log contract
            lse_nat = (m + jnp.log2(l_safe)) * LN2
            m_nat = m * LN2
        else:
            lse_nat = m + jnp.log(l_safe)
            m_nat = m
        lse_ref[...] = jnp.where(empty, MASK_VALUE, lse_nat).astype(
            jnp.float32
        )
        if ml_ref is not None:
            # per-row running max of scaled/softcapped logits (lanes equal);
            # host reduces rows -> per-head. Empty rows forced to MASK_VALUE
            # (m * ln2 would otherwise shift the sentinel).
            ml_ref[...] = jnp.where(empty, MASK_VALUE, m_nat).astype(
                jnp.float32
            )


def _ffa_fwd_pallas(params: FFAParams, work_qt, work_kt, meta, q_t, k_t, v_t):
    """q_t/k_t/v_t are head-major padded: [hq,sqp,d], [hk,skp,d], [hk,skp,dv].

    Returns (out_t [hq,sqp,dv], lse_t [hq,sqp] fp32 with -inf on uncovered
    rows, ml [hq] fp32 per-head max logit with -inf for never-covered heads).
    """
    bq, bk = params.block_q, params.block_k
    hq, sqp, d = q_t.shape
    hk, skp, dv = v_t.shape
    g = params.group
    W = params.num_work
    emit_ml = params.emit_max_logits

    # fold softmax_scale into q (saves a (bq,bk) VPU multiply per grid
    # step); the softcap-free path also folds log2(e) to run the softmax in
    # the exp2 domain
    q_scale = params.softmax_scale * (LOG2E if params.softcap == 0.0 else 1.0)
    q_t = (q_t.astype(jnp.float32) * q_scale).astype(q_t.dtype)

    lse_spec = pl.BlockSpec(
        (None, bq, NUM_LANES), lambda h, w, qt, kt, mt: (h, qt[w], 0),
        memory_space=pltpu.VMEM,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(hq, W),
        in_specs=[
            pl.BlockSpec(
                (1, bq, d), lambda h, w, qt, kt, mt: (h, qt[w], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, bk, d), lambda h, w, qt, kt, mt: (h // g, kt[w], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, bk, dv), lambda h, w, qt, kt, mt: (h // g, kt[w], 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, bq, dv), lambda h, w, qt, kt, mt: (h, qt[w], 0),
                memory_space=pltpu.VMEM,
            ),
            lse_spec,
        ] + ([lse_spec] if emit_ml else []),
        scratch_shapes=[
            pltpu.VMEM((bq, NUM_LANES), jnp.float32),
            pltpu.VMEM((bq, NUM_LANES), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
    )

    kernel = partial(
        _fwd_kernel,
        softcap=params.softcap,
        bq=bq,
        bk=bk,
        emit_ml=emit_ml,
        nc=_clamp_chunks(bk),
    )
    lse_shape = jax.ShapeDtypeStruct((hq, sqp, NUM_LANES), jnp.float32)
    outs = _named.pallas_call(
        kernel,
        label=params.label,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hq, sqp, dv), q_t.dtype),
            lse_shape,
        ] + ([lse_shape] if emit_ml else []),
        interpret=params.interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * W * bq * bk * d * hq,
            bytes_accessed=(q_t.size + k_t.size + v_t.size) * q_t.dtype.itemsize,
            transcendentals=W * bq * bk * hq,
        ),
    )(work_qt, work_kt, meta, q_t, k_t, v_t)
    out_t, lse_b = outs[0], outs[1]
    lse_raw = lse_b[..., 0]  # (hq, sqp)
    lse_t = jnp.where(lse_raw <= EMPTY_THRESH, NEG_INF, lse_raw)
    if emit_ml:
        ml_raw = jnp.max(outs[2], axis=(1, 2))  # (hq,)
        ml = jnp.where(ml_raw <= EMPTY_THRESH, NEG_INF, ml_raw)
    else:
        ml = jnp.full((hq,), NEG_INF, dtype=jnp.float32)
    return out_t, lse_t, ml


def _fwd_kernel_gqa(
    work_qt_ref,
    work_kt_ref,
    meta_ref,
    q_ref,
    k_ref,
    v_ref,
    out_ref,
    lse_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    softcap: float,
    bq: int,
    bk: int,
    g: int,
    nc: int,
):
    """GQA-packed forward: the whole query group of one kv head per grid
    step. vs :func:`_fwd_kernel`: grid (hk, W) instead of (hq, W), so each
    k/v tile is fetched ONCE per work item instead of ``g`` times (k/v HBM
    traffic /g) and per-step bookkeeping amortizes over a g x taller MXU
    op. Same online-softmax math on ``g*bq`` packed rows; rows of different
    heads never interact (the mask repeats per head; softmax is row-wise).
    """
    w = pl.program_id(1)
    is_first = meta_ref[w, IS_FIRST]
    is_last = meta_ref[w, IS_LAST]
    is_full = meta_ref[w, IS_FULL]
    use_exp2 = softcap == 0.0
    exp_fn = jnp.exp2 if use_exp2 else jnp.exp

    @pl.when(is_first == 1)
    def _():
        m_scr[:] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    d = q_ref.shape[-1]
    dv = v_ref.shape[-1]
    # (g, bq, d) block -> (g*bq, d) packed rows: contiguous sublane merge
    q = q_ref[0].reshape(g * bq, d)
    k = k_ref[0]

    def update(s, v_blk, width: int):
        m_prev = m_scr[...]  # (g*bq, NUM_LANES)
        m_blk = jnp.max(s, axis=1)[:, None]
        m_new = jnp.maximum(m_prev, m_blk)
        p = exp_fn(s - _lane_tile(m_new, width))
        alpha = exp_fn(m_prev - m_new)
        l_new = l_scr[...] * alpha + jnp.sum(p, axis=1)[:, None]
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype),
            v_blk,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * _lane_tile(alpha, dv) + pv
        m_scr[:] = m_new
        l_scr[:] = l_new

    def score(k_blk):
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        return s

    if nc == 0:
        s_raw = score(k)

        @pl.when(is_full == 1)
        def _():
            update(s_raw, v_ref[0], bk)

        @pl.when(is_full == 0)
        def _():
            q_base = work_qt_ref[w] * bq
            k_base = work_kt_ref[w] * bk
            update(
                jnp.where(
                    _item_mask(
                        meta_ref, w, q_base, k_base, bq, bk, repeat=g
                    ),
                    s_raw,
                    MASK_VALUE,
                ),
                v_ref[0],
                bk,
            )
    else:
        # extent-clamped body (see _fwd_kernel): the live k extent is
        # head-independent — the packed heads share the work item's band —
        # so chunk skipping is uniform across the packed rows
        ck = bk // nc
        _, _, ek0, ek1, live = _item_extents(meta_ref, w)

        @pl.when(is_full == 1)
        def _():
            update(score(k), v_ref[0], bk)

        for c in range(nc):
            c0 = c * ck

            @pl.when((is_full == 0) & live & (ek0 < c0 + ck) & (ek1 > c0))
            def _(c0=c0):
                q_base = work_qt_ref[w] * bq
                k_base = work_kt_ref[w] * bk
                update(
                    jnp.where(
                        _item_mask(
                            meta_ref, w, q_base, k_base + c0, bq, ck,
                            repeat=g,
                        ),
                        score(k[c0 : c0 + ck]),
                        MASK_VALUE,
                    ),
                    v_ref[0][c0 : c0 + ck],
                    ck,
                )

    @pl.when(is_last == 1)
    def _():
        m = m_scr[...]
        l = l_scr[...]
        empty = m <= EMPTY_THRESH
        l_safe = jnp.where(empty | (l == 0.0), 1.0, l)
        o = acc_scr[:] / _lane_tile(l_safe, dv)
        o = jnp.where(_lane_tile(empty, dv), 0.0, o)
        out_ref[0] = o.reshape(g, bq, dv).astype(out_ref.dtype)
        if use_exp2:
            lse_nat = (m + jnp.log2(l_safe)) * LN2
        else:
            lse_nat = m + jnp.log(l_safe)
        lse_ref[0] = (
            jnp.where(empty, MASK_VALUE, lse_nat)
            .reshape(g, bq, NUM_LANES)
            .astype(jnp.float32)
        )


def _ffa_fwd_pallas_gqa(
    params: FFAParams, work_qt, work_kt, meta, q_t, k_t, v_t
):
    """GQA-packed forward pallas call (see :func:`_fwd_kernel_gqa`).

    Preconditions (enforced by the caller's dispatch): group > 1,
    max_logits not requested. Heads of one group are adjacent in q_t
    (head h uses kv head h // g), so the (hq, sqp, d) -> (hk, g, sqp, d)
    reshape is free.
    """
    bq, bk = params.block_q, params.block_k
    hq, sqp, d = q_t.shape
    hk, skp, dv = v_t.shape
    g = params.group
    W = params.num_work

    q_scale = params.softmax_scale * (LOG2E if params.softcap == 0.0 else 1.0)
    q_t = (q_t.astype(jnp.float32) * q_scale).astype(q_t.dtype)
    q_g = q_t.reshape(hk, g, sqp, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(hk, W),
        in_specs=[
            pl.BlockSpec(
                (1, g, bq, d), lambda h, w, qt, kt, mt: (h, 0, qt[w], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, bk, d), lambda h, w, qt, kt, mt: (h, kt[w], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, bk, dv), lambda h, w, qt, kt, mt: (h, kt[w], 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, g, bq, dv), lambda h, w, qt, kt, mt: (h, 0, qt[w], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, g, bq, NUM_LANES),
                lambda h, w, qt, kt, mt: (h, 0, qt[w], 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((g * bq, NUM_LANES), jnp.float32),
            pltpu.VMEM((g * bq, NUM_LANES), jnp.float32),
            pltpu.VMEM((g * bq, dv), jnp.float32),
        ],
    )
    kernel = partial(
        _fwd_kernel_gqa, softcap=params.softcap, bq=bq, bk=bk, g=g,
        nc=_clamp_chunks(bk),
    )
    outs = _named.pallas_call(
        kernel,
        label=params.label,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hk, g, sqp, dv), q_t.dtype),
            jax.ShapeDtypeStruct((hk, g, sqp, NUM_LANES), jnp.float32),
        ],
        interpret=params.interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * W * bq * bk * d * hq,
            bytes_accessed=(q_t.size + k_t.size + v_t.size)
            * q_t.dtype.itemsize,
            transcendentals=W * bq * bk * hq,
        ),
    )(work_qt, work_kt, meta, q_g, k_t, v_t)
    out_t = outs[0].reshape(hq, sqp, dv)
    lse_raw = outs[1].reshape(hq, sqp, NUM_LANES)[..., 0]
    lse_t = jnp.where(lse_raw <= EMPTY_THRESH, NEG_INF, lse_raw)
    ml = jnp.full((hq,), NEG_INF, dtype=jnp.float32)
    return out_t, lse_t, ml


# What the v5e compiler takes of the packed q-major bodies (fwd, dq): a
# sweep of g x head dims x dtype at 256 x 512, compiled for a described
# chip (PERF.md, PR 25), passed every shape with at most 1024 packed rows
# and a modeled residency under 9 MiB, and refused shapes the plain VMEM
# budget admits — 2048 rows (g = 8) wanted 17.1-18.8 MiB of the core's 16
# for a model of 9.8-13.5, dq at d = 256, g = 4, bf16 16.85 for 10.0: the
# model counts two score-sized temporaries and Mosaic keeps more.
Q_MAJOR_PACK_MAX_ROWS = 1024
Q_MAJOR_PACK_MAX_BYTES = 9 * 1024 * 1024
# What the v5e compiler takes of a plan's table: ``s32[W, 15]`` is a
# scalar-prefetch operand whose rows pad to 512 bytes of the core's 1 MiB of
# SMEM. Bisected between W = 1342 and 2664 by compiling for a described chip
# (PERF.md, PR 36): the packed forward, the one-pass and the split backward
# at g = 8 (128 x 512), g = 16 (64 x 512), g = 4 and g = 1 (256 x 512) and
# g = 2 (128 x 128), d = 128 bf16, q-major and k-major lists of one length
# — every one compiled at W = 2008 and was refused at 2009 ("Exceeded smem
# capacity by 1.1K"). 24 rows of margin for what else a body may keep there.
PLAN_TABLE_MAX_WORK = 1984


def gqa_pack_fits(
    kind: str, group: int, bq: int, bk: int, d: int, dv: int | None,
    itemsize: int = 2,
) -> bool:
    """Can the ``kind`` ("fwd" | "dq" | "dkv" | "fused") body run
    GQA-packed at this tile, flags apart? Real grouping and a VMEM guard:
    the EXACT packed-step residency (blocks + scratch + score-tile
    intermediates, utils/mem_budget.ffa_kernel_residency — the same model
    the static kernel checker proves K1 with) must fit the per-core budget
    with headroom, and for the q-major bodies the tighter bounds above."""
    if group <= 1:
        return False
    budget = VMEM_ALLOWED_BYTES
    if kind in ("fwd", "dq"):
        if group * bq > Q_MAJOR_PACK_MAX_ROWS:
            return False
        budget = Q_MAJOR_PACK_MAX_BYTES
    return ffa_kernel_residency(
        kind, bq, bk, d, head_dim_v=dv, dtype_bytes=itemsize, group=group,
        packed=True,
    ) <= budget


def gqa_pack_admitted(
    kind: str, group: int, bq: int, bk: int, d: int, dv: int | None,
    itemsize: int = 2,
) -> bool:
    """:func:`gqa_pack_fits` and the pass's env flag (on by default; the
    registry's pin). ONE predicate for the four trace-time dispatch guards
    below: this is the rule that buys a pass its q rows a grid step — the
    packed body wherever there is a group and the chip's compiler takes
    it, else the plain one, at the same tiles."""
    flag = "dkv" if kind == "fused" else kind  # the packing trade-off is dkv's
    return (
        gqa_pack_fits(kind, group, bq, bk, d, dv, itemsize)
        and _registry_mod().gqa_pack_variant(flag) == "gqa_packed"
    )


def _use_gqa_pack(
    params: FFAParams, d: int, dv: int, itemsize: int = 2
) -> bool:
    """Trace-time dispatch to the packed fwd kernel: admitted
    (:func:`gqa_pack_admitted`) and no max-logits, which the packed kernel
    doesn't emit."""
    return not params.emit_max_logits and gqa_pack_admitted(
        "fwd", params.group, params.block_q, params.block_k, d, dv, itemsize)


# ---------------------------------------------------------------------------
# backward: dq (q-major plan)
# ---------------------------------------------------------------------------


def _lanes_layout(x: jax.Array, sublanes: int) -> jax.Array:
    """(hq, sqp) fp32 -> (hq, sublanes, sqp): q in the lane dim, broadcast
    over sublanes (splash's backward logsumexp/di layout)."""
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], sublanes, x.shape[1]))


def _bwd_dq_kernel(
    work_qt_ref,
    work_kt_ref,
    meta_ref,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dq_ref,
    dq_scr,
    *,
    softcap: float,
    scale: float,
    bq: int,
    bk: int,
    nc: int,
):
    w = pl.program_id(1)
    is_first = meta_ref[w, IS_FIRST]
    is_last = meta_ref[w, IS_LAST]
    is_full = meta_ref[w, IS_FULL]
    use_exp2 = softcap == 0.0
    exp_fn = jnp.exp2 if use_exp2 else jnp.exp

    @pl.when(is_first == 1)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q = q_ref[0]  # pre-scaled by softmax_scale (* log2e when softcap-free)
    k = k_ref[0]

    # lse/delta live q-in-lanes: ref block (1, bq); column views via
    # expand_dims (splash dq idiom). lse arrives in natural log; the exp2
    # path converts the (bq,1) column, never the (bq,bk) tile.
    lse = jnp.expand_dims(lse_ref[0], -1)  # (bq, 1)
    delta = jnp.expand_dims(delta_ref[0], -1)  # (bq, 1)

    def score(k_blk):
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if softcap > 0.0:
            sc = softcap * jnp.tanh(s / softcap)
            return sc, 1.0 - (sc / softcap) ** 2
        return s, None

    def accum(sm, dcap, dp, k_blk, masked: bool):
        if masked:
            neg = lse <= EMPTY_THRESH  # uncovered rows (host clamps -inf)
            lse_safe = jnp.where(neg, 0.0, lse)
            if use_exp2:
                lse_safe = lse_safe * LOG2E
            p = exp_fn(sm - lse_safe)  # exp(MASK_VALUE - O(1)) == 0
            p = jnp.where(neg, 0.0, p)
        else:
            # a full tile's rows are covered by definition -> lse finite
            p = exp_fn(sm - (lse * LOG2E if use_exp2 else lse))
        ds = p * (dp - delta)
        if dcap is not None:
            ds = ds * dcap
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def dp_of(v_blk):
        return jax.lax.dot_general(
            do_ref[0], v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if nc == 0:
        sc, dcap = score(k)
        dp = dp_of(v_ref[0])

        @pl.when(is_full == 1)
        def _():
            accum(sc, dcap, dp, k, masked=False)

        @pl.when(is_full == 0)
        def _():
            q_base = work_qt_ref[w] * bq
            k_base = work_kt_ref[w] * bk
            accum(
                jnp.where(
                    _item_mask(meta_ref, w, q_base, k_base, bq, bk),
                    sc, MASK_VALUE,
                ),
                dcap, dp, k,
                masked=True,
            )
    else:
        # extent-clamped body: skipped k chunks are fully masked, and the
        # masked path's p is exactly 0 there (exp underflow / neg-row
        # forcing), so dropping them does not change dq
        ck = bk // nc
        _, _, ek0, ek1, live = _item_extents(meta_ref, w)

        @pl.when(is_full == 1)
        def _():
            sc, dcap = score(k)
            accum(sc, dcap, dp_of(v_ref[0]), k, masked=False)

        for c in range(nc):
            c0 = c * ck

            @pl.when((is_full == 0) & live & (ek0 < c0 + ck) & (ek1 > c0))
            def _(c0=c0):
                q_base = work_qt_ref[w] * bq
                k_base = work_kt_ref[w] * bk
                k_c = k[c0 : c0 + ck]
                sc, dcap = score(k_c)
                accum(
                    jnp.where(
                        _item_mask(
                            meta_ref, w, q_base, k_base + c0, bq, ck
                        ),
                        sc, MASK_VALUE,
                    ),
                    dcap, dp_of(v_ref[0][c0 : c0 + ck]), k_c,
                    masked=True,
                )

    @pl.when(is_last == 1)
    def _():
        # softmax_scale folds into the flush (ds carries no scale): one VPU
        # multiply on the resident tile instead of an XLA full-array pass
        dq_ref[0] = dq_scr[:] * scale


def _clamp_lse(lse_t: jax.Array) -> jax.Array:
    """Replace -inf (uncovered-row lse) with MASK_VALUE so no inf enters the
    kernels; threshold compares recover the flag."""
    return jnp.maximum(lse_t, MASK_VALUE)


def _ffa_bwd_dq_pallas(
    params: FFAParams, work_qt, work_kt, meta, q_t, k_t, v_t, do_t, lse_t, delta_t
):
    bq, bk = params.dq_blocks()
    hq, sqp, d = q_t.shape
    _, _, dv = v_t.shape
    g = params.group
    W = params.num_work_dq if params.num_work_dq is not None else params.num_work

    # pre-scale q (exp2 domain when softcap-free); the missing scale factor
    # on ds is applied to dq on return
    q_scale = params.softmax_scale * (LOG2E if params.softcap == 0.0 else 1.0)
    q_t = (q_t.astype(jnp.float32) * q_scale).astype(q_t.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(hq, W),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, w, qt, kt, mt: (h, qt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda h, w, qt, kt, mt: (h // g, kt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dv), lambda h, w, qt, kt, mt: (h // g, kt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, dv), lambda h, w, qt, kt, mt: (h, qt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, 1, bq), lambda h, w, qt, kt, mt: (h, 0, qt[w]),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, 1, bq), lambda h, w, qt, kt, mt: (h, 0, qt[w]),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda h, w, qt, kt, mt: (h, qt[w], 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
    )
    kernel = partial(
        _bwd_dq_kernel, softcap=params.softcap,
        scale=params.softmax_scale, bq=bq, bk=bk, nc=_clamp_chunks(bk),
    )
    (dq_t,) = _named.pallas_call(
        kernel,
        label=params.label,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((hq, sqp, d), jnp.float32)],
        interpret=params.interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(work_qt, work_kt, meta, q_t, k_t, v_t, do_t,
      _lanes_layout(_clamp_lse(lse_t), 1), _lanes_layout(delta_t, 1))
    return dq_t  # softmax_scale already folded into the kernel flush


def _bwd_dq_kernel_gqa(
    work_qt_ref,
    work_kt_ref,
    meta_ref,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dq_ref,
    dq_scr,
    *,
    softcap: float,
    scale: float,
    bq: int,
    bk: int,
    g: int,
    nc: int,
):
    """GQA-packed dq: grid (hk, W) — the whole query group of one kv head
    per grid step (vs :func:`_bwd_dq_kernel`'s (hq, W)). k/v are fetched
    ONCE per work item instead of ``g`` times and the per-step s/dp matmuls
    run ``g``x taller. lse/delta arrive TILE-PACKED from the host:
    ``(hk, num_q_tiles, g*bq)`` with packed row ``gi*bq + r`` = head
    ``h*g+gi``, row ``qt*bq + r`` — so the kernel's column view is the same
    lanes->sublanes expand the unpacked kernel uses, just ``g``x taller.
    """
    w = pl.program_id(1)
    is_first = meta_ref[w, IS_FIRST]
    is_last = meta_ref[w, IS_LAST]
    is_full = meta_ref[w, IS_FULL]
    use_exp2 = softcap == 0.0
    exp_fn = jnp.exp2 if use_exp2 else jnp.exp

    @pl.when(is_first == 1)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    d = q_ref.shape[-1]
    q = q_ref[0].reshape(g * bq, d)  # pre-scaled on host
    k = k_ref[0]

    lse = jnp.expand_dims(lse_ref[0], -1)  # (g*bq, 1), tile-packed rows
    delta = jnp.expand_dims(delta_ref[0], -1)
    dv = v_ref.shape[-1]
    do = do_ref[0].reshape(g * bq, dv)

    def score(k_blk):
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if softcap > 0.0:
            sc = softcap * jnp.tanh(s / softcap)
            return sc, 1.0 - (sc / softcap) ** 2
        return s, None

    def accum(sm, dcap, dp, k_blk, masked: bool):
        if masked:
            neg = lse <= EMPTY_THRESH
            lse_safe = jnp.where(neg, 0.0, lse)
            if use_exp2:
                lse_safe = lse_safe * LOG2E
            p = exp_fn(sm - lse_safe)
            p = jnp.where(neg, 0.0, p)
        else:
            p = exp_fn(sm - (lse * LOG2E if use_exp2 else lse))
        ds = p * (dp - delta)
        if dcap is not None:
            ds = ds * dcap
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def dp_of(v_blk):
        return jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if nc == 0:
        sc, dcap = score(k)
        dp = dp_of(v_ref[0])

        @pl.when(is_full == 1)
        def _():
            accum(sc, dcap, dp, k, masked=False)

        @pl.when(is_full == 0)
        def _():
            q_base = work_qt_ref[w] * bq
            k_base = work_kt_ref[w] * bk
            accum(
                jnp.where(
                    _item_mask(
                        meta_ref, w, q_base, k_base, bq, bk, repeat=g
                    ),
                    sc, MASK_VALUE,
                ),
                dcap, dp, k,
                masked=True,
            )
    else:
        # extent-clamped body (see _bwd_dq_kernel); the live k extent is
        # shared by the packed heads
        ck = bk // nc
        _, _, ek0, ek1, live = _item_extents(meta_ref, w)

        @pl.when(is_full == 1)
        def _():
            sc, dcap = score(k)
            accum(sc, dcap, dp_of(v_ref[0]), k, masked=False)

        for c in range(nc):
            c0 = c * ck

            @pl.when((is_full == 0) & live & (ek0 < c0 + ck) & (ek1 > c0))
            def _(c0=c0):
                q_base = work_qt_ref[w] * bq
                k_base = work_kt_ref[w] * bk
                k_c = k[c0 : c0 + ck]
                sc, dcap = score(k_c)
                accum(
                    jnp.where(
                        _item_mask(
                            meta_ref, w, q_base, k_base + c0, bq, ck,
                            repeat=g,
                        ),
                        sc, MASK_VALUE,
                    ),
                    dcap, dp_of(v_ref[0][c0 : c0 + ck]), k_c,
                    masked=True,
                )

    @pl.when(is_last == 1)
    def _():
        # softmax_scale folded into the flush (see _bwd_dq_kernel)
        dq_ref[0] = (dq_scr[:] * scale).reshape(g, bq, d)


def _tile_pack_rows(x_t: jax.Array, hk: int, g: int, bq: int) -> jax.Array:
    """(hq, sqp) fp32 -> (hk, num_q_tiles, 1, g*bq) tile-packed rows for
    the packed dq kernel (host-side; one transpose of a small fp32 array).
    The unit sublane axis keeps the BlockSpec's trailing-two dims equal to
    the array dims (the Pallas TPU (8, 128) divisibility rule)."""
    hq, sqp = x_t.shape
    nqt = sqp // bq
    return (
        x_t.reshape(hk, g, nqt, bq).transpose(0, 2, 1, 3).reshape(
            hk, nqt, 1, g * bq
        )
    )


def _ffa_bwd_dq_pallas_gqa(
    params: FFAParams, work_qt, work_kt, meta, q_t, k_t, v_t, do_t, lse_t,
    delta_t,
):
    """GQA-packed dq pallas call (see :func:`_bwd_dq_kernel_gqa`)."""
    bq, bk = params.dq_blocks()
    hq, sqp, d = q_t.shape
    hk, skp, dv = v_t.shape
    g = params.group
    W = params.num_work_dq if params.num_work_dq is not None else params.num_work

    use_exp2 = params.softcap == 0.0
    q_scale = params.softmax_scale * (LOG2E if use_exp2 else 1.0)
    q_t = (q_t.astype(jnp.float32) * q_scale).astype(q_t.dtype)
    q_g = q_t.reshape(hk, g, sqp, d)
    do_g = do_t.reshape(hk, g, sqp, dv)
    lse_p = _tile_pack_rows(_clamp_lse(lse_t), hk, g, bq)
    delta_p = _tile_pack_rows(delta_t, hk, g, bq)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(hk, W),
        in_specs=[
            pl.BlockSpec((1, g, bq, d), lambda h, w, qt, kt, mt: (h, 0, qt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda h, w, qt, kt, mt: (h, kt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dv), lambda h, w, qt, kt, mt: (h, kt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, g, bq, dv),
                         lambda h, w, qt, kt, mt: (h, 0, qt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, None, 1, g * bq),
                         lambda h, w, qt, kt, mt: (h, qt[w], 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, None, 1, g * bq),
                         lambda h, w, qt, kt, mt: (h, qt[w], 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, g, bq, d),
                         lambda h, w, qt, kt, mt: (h, 0, qt[w], 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[pltpu.VMEM((g * bq, d), jnp.float32)],
    )
    kernel = partial(
        _bwd_dq_kernel_gqa, softcap=params.softcap,
        scale=params.softmax_scale, bq=bq, bk=bk, g=g,
        nc=_clamp_chunks(bk),
    )
    (dq_g,) = _named.pallas_call(
        kernel,
        label=params.label,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((hk, g, sqp, d), jnp.float32)],
        interpret=params.interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(work_qt, work_kt, meta, q_g, k_t, v_t, do_g, lse_p, delta_p)
    return dq_g.reshape(hq, sqp, d)  # scale folded into the kernel flush


def _use_gqa_pack_dq(
    params: FFAParams, d: int, dv: int | None = None, itemsize: int = 2
) -> bool:
    """Trace-time dispatch to the packed dq kernel
    (:func:`gqa_pack_admitted` at the dq pass's own tiles)."""
    return gqa_pack_admitted(
        "dq", params.group, *params.dq_blocks(), d, dv, itemsize)


def ffa_bwd_dq_pallas_dispatch(
    params: FFAParams, work_qt, work_kt, meta, q_t, k_t, v_t, do_t, lse_t,
    delta_t,
):
    """dq backward with the GQA-packing dispatch applied — the ONE entry
    every backward path (custom-vjp core, CP multi-stage, sink, dynamic)
    uses so the packed dq kernel is reachable from all of them (mirrors
    :func:`ffa_fwd_pallas_dispatch`)."""
    fn = (
        _ffa_bwd_dq_pallas_gqa
        if _use_gqa_pack_dq(params, q_t.shape[2], v_t.shape[2],
                            q_t.dtype.itemsize)
        else _ffa_bwd_dq_pallas
    )
    return fn(params, work_qt, work_kt, meta, q_t, k_t, v_t, do_t, lse_t,
              delta_t)


# ---------------------------------------------------------------------------
# backward: dk/dv (k-major plan)
# ---------------------------------------------------------------------------


def _bwd_dkv_kernel(
    work_qt_ref,
    work_kt_ref,
    meta_ref,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dk_ref,
    dv_ref,
    dk_scr,
    dv_scr,
    *,
    softcap: float,
    bq: int,
    bk: int,
    group: int,
    nc: int,
):
    # grid (hk, W, gi): the GQA group dim is innermost so dk/dv accumulate
    # over the g q-heads of a kv head in VMEM scratch — the kv-head output
    # is written once (vs per-q-head partials + a host reshape-sum, which
    # costs g x the HBM writes; the CUDA kernel accumulates in-epilogue the
    # same way). k/v blocks stay resident across the g inner steps.
    w = pl.program_id(1)
    gi = pl.program_id(2)
    is_first = meta_ref[w, IS_FIRST]
    is_last = meta_ref[w, IS_LAST]
    is_full = meta_ref[w, IS_FULL]
    use_exp2 = softcap == 0.0
    exp_fn = jnp.exp2 if use_exp2 else jnp.exp

    @pl.when((is_first == 1) & (gi == 0))
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q = q_ref[0]  # pre-scaled by softmax_scale on the host: dk = ds_t @ q'
    # (exp2 path: q' also carries log2e; the host divides dk by log2e)
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]

    def score(q_blk):
        # s_t: (bk, rows(q_blk)) — k rows, q cols
        s_t = jax.lax.dot_general(
            k, q_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if softcap > 0.0:
            sc_t = softcap * jnp.tanh(s_t / softcap)
            return sc_t, 1.0 - (sc_t / softcap) ** 2
        return s_t, None

    def accum(sm_t, dcap_t, lse_c, delta_c, do_blk, q_blk, masked: bool):
        dp_t = jax.lax.dot_general(
            v, do_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if masked:
            neg = lse_c <= EMPTY_THRESH
            lse_safe = jnp.where(neg, 0.0, lse_c)
            if use_exp2:
                lse_safe = lse_safe * LOG2E
            p_t = exp_fn(sm_t - lse_safe)
            p_t = jnp.where(neg, 0.0, p_t)
        else:
            p_t = exp_fn(sm_t - (lse_c * LOG2E if use_exp2 else lse_c))
        dv_scr[:] += jax.lax.dot_general(
            p_t.astype(do.dtype), do_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds_t = p_t * (dp_t - delta_c)
        if dcap_t is not None:
            ds_t = ds_t * dcap_t
        # q is pre-scaled, so ds_t @ q' == (ds_t * scale) @ q == dk exactly
        dk_scr[:] += jax.lax.dot_general(
            ds_t.astype(q.dtype), q_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # lse/delta q-in-lanes rows: ref block (sublanes, bq) -> (1, bq) views
    lse = lse_ref[:1, :]  # (1, bq)
    delta = delta_ref[:1, :]  # (1, bq)

    if nc == 0:
        sc_t, dcap_t = score(q)

        @pl.when(is_full == 1)
        def _():
            accum(sc_t, dcap_t, lse, delta, do, q, masked=False)

        @pl.when(is_full == 0)
        def _():
            q_base = work_qt_ref[w] * bq
            k_base = work_kt_ref[w] * bk
            accum(
                jnp.where(
                    _item_mask(meta_ref, w, q_base, k_base, bq, bk,
                               transposed=True),
                    sc_t, MASK_VALUE,
                ),
                dcap_t, lse, delta, do, q,
                masked=True,
            )
    else:
        # extent-clamped body: q is the LANE dim of s_t here, so partial
        # tiles chunk the q extent (eq0/eq1) instead of the k extent;
        # skipped chunks are fully masked -> p_t exactly 0 in the legacy
        # path, so dropping them does not change dk/dv
        cq = bq // nc
        eq0, eq1, _, _, live = _item_extents(meta_ref, w)

        @pl.when(is_full == 1)
        def _():
            sc_t, dcap_t = score(q)
            accum(sc_t, dcap_t, lse, delta, do, q, masked=False)

        for c in range(nc):
            c0 = c * cq

            @pl.when((is_full == 0) & live & (eq0 < c0 + cq) & (eq1 > c0))
            def _(c0=c0):
                q_base = work_qt_ref[w] * bq
                k_base = work_kt_ref[w] * bk
                q_c = q[c0 : c0 + cq]
                sc_t, dcap_t = score(q_c)
                accum(
                    jnp.where(
                        _item_mask(meta_ref, w, q_base + c0, k_base, cq,
                                   bk, transposed=True),
                        sc_t, MASK_VALUE,
                    ),
                    dcap_t,
                    lse_ref[:1, c0 : c0 + cq],
                    delta_ref[:1, c0 : c0 + cq],
                    do[c0 : c0 + cq],
                    q_c,
                    masked=True,
                )

    @pl.when((is_last == 1) & (gi == group - 1))
    def _():
        dk_ref[0] = dk_scr[:]
        dv_ref[0] = dv_scr[:]


def _ffa_bwd_dkv_pallas(
    params: FFAParams, work_qt_t, work_kt_t, meta_t,
    q_t, k_t, v_t, do_t, lse_t, delta_t,
):
    bq, bk = params.dkv_blocks()
    hq, sqp, d = q_t.shape
    hk, skp, dv = v_t.shape
    g = params.group
    WT = (
        params.num_work_dkv
        if params.num_work_dkv is not None
        else params.num_work_t
    )

    # pre-scale q: dk = ds_t @ q' carries the scale factor exactly; the
    # exp2-path log2e factor is divided back out of dk on return
    use_exp2 = params.softcap == 0.0
    q_scale = params.softmax_scale * (LOG2E if use_exp2 else 1.0)
    q_t = (q_t.astype(jnp.float32) * q_scale).astype(q_t.dtype)

    # grid (hk, WT, g): group innermost so the kv-head dk/dv accumulate in
    # scratch over the g q-heads (outputs and k/v fetches are per kv head —
    # 1/g the HBM traffic of per-q-head partials)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(hk, WT, g),
        in_specs=[
            pl.BlockSpec(
                (1, bq, d),
                lambda h, w, gi, qt, kt, mt: (h * g + gi, qt[w], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, bk, d), lambda h, w, gi, qt, kt, mt: (h, kt[w], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, bk, dv), lambda h, w, gi, qt, kt, mt: (h, kt[w], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, bq, dv),
                lambda h, w, gi, qt, kt, mt: (h * g + gi, qt[w], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (None, NUM_SUBLANES, bq),
                lambda h, w, gi, qt, kt, mt: (h * g + gi, 0, qt[w]),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (None, NUM_SUBLANES, bq),
                lambda h, w, gi, qt, kt, mt: (h * g + gi, 0, qt[w]),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, bk, d), lambda h, w, gi, qt, kt, mt: (h, kt[w], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, bk, dv), lambda h, w, gi, qt, kt, mt: (h, kt[w], 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
    )
    kernel = partial(
        _bwd_dkv_kernel, softcap=params.softcap,
        bq=bq, bk=bk, group=g, nc=_clamp_chunks(bq),
    )
    dk_t, dv_t = _named.pallas_call(
        kernel,
        label=params.label,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hk, skp, d), jnp.float32),
            jax.ShapeDtypeStruct((hk, skp, dv), jnp.float32),
        ],
        interpret=params.interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
    )(work_qt_t, work_kt_t, meta_t, q_t, k_t, v_t, do_t,
      _lanes_layout(_clamp_lse(lse_t), NUM_SUBLANES),
      _lanes_layout(delta_t, NUM_SUBLANES))
    if use_exp2:
        dk_t = dk_t * LN2  # divide the folded log2e back out
    return dk_t, dv_t


def _bwd_dkv_kernel_gqa(
    work_qt_ref,
    work_kt_ref,
    meta_ref,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dk_ref,
    dv_ref,
    dk_scr,
    dv_scr,
    *,
    softcap: float,
    bq: int,
    bk: int,
    g: int,
    clamp: bool,
):
    """GQA-packed dk/dv: grid (hk, WT) — the whole query group of one kv
    head per grid step (vs :func:`_bwd_dkv_kernel`'s (hk, WT, g) with the
    group innermost). q/do arrive as (g, bq, ·) blocks reshaped to packed
    (g*bq, ·) rows, so s_t/dp_t are ONE (bk, g*bq) MXU contraction and the
    dk/dv accumulations contract over all g heads at once — summing the
    packed columns IS the group sum, since each packed column belongs to
    exactly one (head, row) pair. q/do are fetched once per work item
    instead of per group member and the matmuls run ``g``x longer,
    feeding the MXU full tiles (FlashAttention-2's bwd work-partitioning
    lesson). lse/delta arrive TILE-PACKED (:func:`_tile_pack_rows`) and
    broadcast over the bk rows.
    """
    w = pl.program_id(1)
    is_first = meta_ref[w, IS_FIRST]
    is_last = meta_ref[w, IS_LAST]
    is_full = meta_ref[w, IS_FULL]
    use_exp2 = softcap == 0.0
    exp_fn = jnp.exp2 if use_exp2 else jnp.exp

    @pl.when(is_first == 1)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    d = q_ref.shape[-1]
    dv = v_ref.shape[-1]
    q = q_ref[0].reshape(g * bq, d)  # pre-scaled on host
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0].reshape(g * bq, dv)

    lse = lse_ref[...]  # (1, g*bq), tile-packed cols; broadcasts over bk rows
    delta = delta_ref[...]

    def score():
        # s_t: (bk, g*bq) — k rows, packed (head, q-row) cols
        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if softcap > 0.0:
            sc_t = softcap * jnp.tanh(s_t / softcap)
            return sc_t, 1.0 - (sc_t / softcap) ** 2
        return s_t, None

    def accum(sm_t, dcap_t, masked: bool):
        dp_t = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if masked:
            neg = lse <= EMPTY_THRESH
            lse_safe = jnp.where(neg, 0.0, lse)
            if use_exp2:
                lse_safe = lse_safe * LOG2E
            p_t = exp_fn(sm_t - lse_safe)
            p_t = jnp.where(neg, 0.0, p_t)
        else:
            p_t = exp_fn(sm_t - (lse * LOG2E if use_exp2 else lse))
        # contraction over the g*bq packed cols == the per-group sum the
        # unpacked kernel does across its g inner grid steps
        dv_scr[:] += jax.lax.dot_general(
            p_t.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds_t = p_t * (dp_t - delta)
        if dcap_t is not None:
            ds_t = ds_t * dcap_t
        dk_scr[:] += jax.lax.dot_general(
            ds_t.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if not clamp:
        sc_t, dcap_t = score()

        @pl.when(is_full == 1)
        def _():
            accum(sc_t, dcap_t, masked=False)

        @pl.when(is_full == 0)
        def _():
            q_base = work_qt_ref[w] * bq
            k_base = work_kt_ref[w] * bk
            accum(
                jnp.where(
                    _item_mask(meta_ref, w, q_base, k_base, bq, bk,
                               transposed=True, repeat=g),
                    sc_t, MASK_VALUE,
                ),
                dcap_t,
                masked=True,
            )
    else:
        # the packed lane dim interleaves the g heads' q rows, so it cannot
        # be chunked by a single q extent; clamping here is the whole-item
        # guard — dummy/pad items (empty extent) skip both MXU passes
        # (their legacy contribution was exactly 0: masked p_t underflows)
        _, _, _, _, live = _item_extents(meta_ref, w)

        @pl.when((is_full == 1) & live)
        def _():
            sc_t, dcap_t = score()
            accum(sc_t, dcap_t, masked=False)

        @pl.when((is_full == 0) & live)
        def _():
            q_base = work_qt_ref[w] * bq
            k_base = work_kt_ref[w] * bk
            sc_t, dcap_t = score()
            accum(
                jnp.where(
                    _item_mask(meta_ref, w, q_base, k_base, bq, bk,
                               transposed=True, repeat=g),
                    sc_t, MASK_VALUE,
                ),
                dcap_t,
                masked=True,
            )

    @pl.when(is_last == 1)
    def _():
        dk_ref[0] = dk_scr[:]
        dv_ref[0] = dv_scr[:]


def _ffa_bwd_dkv_pallas_gqa(
    params: FFAParams, work_qt_t, work_kt_t, meta_t,
    q_t, k_t, v_t, do_t, lse_t, delta_t,
):
    """GQA-packed dk/dv pallas call (see :func:`_bwd_dkv_kernel_gqa`)."""
    bq, bk = params.dkv_blocks()
    hq, sqp, d = q_t.shape
    hk, skp, dv = v_t.shape
    g = params.group
    WT = (
        params.num_work_dkv
        if params.num_work_dkv is not None
        else params.num_work_t
    )

    use_exp2 = params.softcap == 0.0
    q_scale = params.softmax_scale * (LOG2E if use_exp2 else 1.0)
    q_t = (q_t.astype(jnp.float32) * q_scale).astype(q_t.dtype)
    q_g = q_t.reshape(hk, g, sqp, d)
    do_g = do_t.reshape(hk, g, sqp, dv)
    lse_p = _tile_pack_rows(_clamp_lse(lse_t), hk, g, bq)
    delta_p = _tile_pack_rows(delta_t, hk, g, bq)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(hk, WT),
        in_specs=[
            pl.BlockSpec((1, g, bq, d),
                         lambda h, w, qt, kt, mt: (h, 0, qt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda h, w, qt, kt, mt: (h, kt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dv), lambda h, w, qt, kt, mt: (h, kt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, g, bq, dv),
                         lambda h, w, qt, kt, mt: (h, 0, qt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, None, 1, g * bq),
                         lambda h, w, qt, kt, mt: (h, qt[w], 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, None, 1, g * bq),
                         lambda h, w, qt, kt, mt: (h, qt[w], 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda h, w, qt, kt, mt: (h, kt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dv), lambda h, w, qt, kt, mt: (h, kt[w], 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
    )
    kernel = partial(
        _bwd_dkv_kernel_gqa, softcap=params.softcap, bq=bq, bk=bk, g=g,
        clamp=_registry_mod().extent_clamp_enabled(),
    )
    dk_t, dv_t = _named.pallas_call(
        kernel,
        label=params.label,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hk, skp, d), jnp.float32),
            jax.ShapeDtypeStruct((hk, skp, dv), jnp.float32),
        ],
        interpret=params.interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(work_qt_t, work_kt_t, meta_t, q_g, k_t, v_t, do_g, lse_p, delta_p)
    if use_exp2:
        dk_t = dk_t * LN2  # divide the folded log2e back out
    return dk_t, dv_t


def _use_gqa_pack_dkv(
    params: FFAParams, sqp: int, d: int, dv: int, itemsize: int = 2
) -> bool:
    """Trace-time dispatch to the packed dkv kernel: admitted
    (:func:`gqa_pack_admitted`: blocks + (bk, d+dv) fp32 scratch + the
    (bk, g*bq) fp32 s_t/dp_t tiles must fit) and shapes divide (the dkv q
    tile must tile the padded seqlen for the host-side lse/delta
    tile-pack)."""
    bq, bk = params.dkv_blocks()
    return sqp % bq == 0 and gqa_pack_admitted(
        "dkv", params.group, bq, bk, d, dv, itemsize)


def ffa_bwd_dkv_pallas_dispatch(
    params: FFAParams, work_qt_t, work_kt_t, meta_t, q_t, k_t, v_t, do_t,
    lse_t, delta_t,
):
    """dk/dv backward with the GQA-packing dispatch applied — the ONE
    entry every backward path (custom-vjp core, CP multi-stage, sink,
    dynamic) uses so the packed dkv kernel is reachable from all of them
    (mirrors :func:`ffa_bwd_dq_pallas_dispatch`)."""
    fn = (
        _ffa_bwd_dkv_pallas_gqa
        if _use_gqa_pack_dkv(params, q_t.shape[1], q_t.shape[2],
                             v_t.shape[2], q_t.dtype.itemsize)
        else _ffa_bwd_dkv_pallas
    )
    return fn(params, work_qt_t, work_kt_t, meta_t, q_t, k_t, v_t, do_t,
              lse_t, delta_t)


# ---------------------------------------------------------------------------
# backward: delta preprocessing (rowsum of dO ⊙ O)
# ---------------------------------------------------------------------------


def _delta_kernel(o_ref, do_ref, delta_ref, *, bq: int):
    """delta = rowsum(dO ⊙ O) in fp32 for one (head, q-tile) block.

    Shared preprocessing of every backward pass (split dq, split dkv, and
    the fused one-pass kernel all consume delta); running it as a Pallas
    kernel removes the XLA full-array pass over o and do the old
    ``jnp.sum`` epilogue cost. The result is emitted lanes-broadcast
    ``(bq, NUM_LANES)`` — the proven lse output layout — and sliced to a
    column on the host; no accumulator, every grid step is independent.
    """
    prod = o_ref[0].astype(jnp.float32) * do_ref[0].astype(jnp.float32)
    col = jnp.sum(prod, axis=-1)[:, None]  # (bq, 1)
    delta_ref[0] = jnp.broadcast_to(col, (bq, NUM_LANES))


def _ffa_delta_pallas(out_t, do_t, block_q: int, interpret: bool,
                      label: str | None = None):
    """Tiled delta kernel over head-major padded (hq, sqp, dv) arrays.

    ``block_q`` must divide sqp (always true for the fwd padded geometry:
    sqp = num_q_tiles * block_q). Returns (hq, sqp) fp32.
    """
    hq, sqp, dv = out_t.shape
    bq = min(block_q, sqp)
    nqt = sqp // bq
    (delta_b,) = _named.pallas_call(
        partial(_delta_kernel, bq=bq),
        label=label,
        grid=(hq, nqt),
        in_specs=[
            pl.BlockSpec((1, bq, dv), lambda h, i: (h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, dv), lambda h, i: (h, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, NUM_LANES), lambda h, i: (h, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct((hq, sqp, NUM_LANES), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
    )(out_t, do_t)
    return delta_b[..., 0]


def ffa_delta_pallas_dispatch(params: FFAParams, out_t, do_t):
    """delta preprocessing entry used by every backward path (mirrors the
    fwd/dq/dkv dispatch naming so the static kernel checker drives it the
    same way)."""
    return _ffa_delta_pallas(
        out_t, do_t, params.block_q, params.interpret, params.label)


# ---------------------------------------------------------------------------
# backward: fused one-pass (k-major plan, revisit-accumulated dq)
# ---------------------------------------------------------------------------


# Grid steps the one-pass bodies need between leaving a q tile's dq window
# and fetching it again. The pipeline starts step i's write-back when step
# i ends and waits for it when step i + 1 ends; it issues step j's fetch
# when step j - 1 starts. At j - i = 3 the fetch is issued after the
# write-back it has to see was waited for, so the order holds by the
# pipeline's own wait. At 2 the fetch is queued behind a write-back still
# in flight: the chip read such blocks whole (62 revisits a kv head at 2,
# 61 at 3, g = 4 and g = 1, three runs each, against the split pair; TPU
# v5e, my chip run, PR 30, PERF.md §6), but in a kernel alone on the chip,
# and nothing but the DMA queue's order stands behind it — not relied on.
# ffa_plan._late_revisit_order walks the k-major list so that the cells'
# lists read 4 on one chip and 3 on a chunked rank at cp 4; a list under
# 3, or one whose distance is not known (0), runs the split pair.
FUSED_DQ_REVISIT_DISTANCE = 3


def _dq_window_moved(work_qt_ref, w):
    """True when step ``w``'s dq window is another block than step
    ``w - 1``'s (always at a head's first step): the window was written
    back and re-fetched, so the output buffer holds none of this tile."""
    return (w == 0) | (work_qt_ref[w] != work_qt_ref[jnp.maximum(w - 1, 0)])


def _bwd_fused_kernel(
    work_qt_ref,
    work_kt_ref,
    meta_ref,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dqin_ref,
    dq_ref,
    dk_ref,
    dv_ref,
    dk_scr,
    dv_scr,
    *,
    softcap: float,
    scale: float,
    bq: int,
    bk: int,
    group: int,
    nc: int,
    readback: bool,
):
    """Fused one-pass backward: dk, dv AND dq from ONE score recompute.

    Same grid and dk/dv discipline as :func:`_bwd_dkv_kernel` (k-major
    plan, grid (hk, WT, g), group innermost, VMEM scratch flushed on the
    k tile's last visit). The fused extra: each work item's dq
    contribution ``ds @ k`` is accumulated into the dq output window. The
    k-major traversal visits one q tile many times, non-consecutively, and
    the chip writes an output window back when its block index changes and
    does NOT read it back on a later visit. So the partial sum comes in as
    an operand: ``dqin_ref`` is the dq array itself (aliased to the
    output, same index map), fetched whenever the block changes. A run of
    adjacent visits starts from it (or from zero when the plan's
    first-q-visit flag QVF is set), accumulates in the resident output
    window, and the last visit (QVL) folds softmax_scale in before the
    window is written back. The fetch of a revisit is issued a grid step
    ahead, so the plan keeps non-adjacent visits FUSED_DQ_REVISIT_DISTANCE
    steps apart (:func:`fused_bwd_feasible`). Never-visited q tiles (fully
    masked rows) keep the zero background the wrapper donates. This shares
    the s_t/p_t recompute between dq and dk/dv — 5 tile matmuls per work
    item where the split passes spend 7 — and halves the backward HBM
    reads of q/k/v/do.

    ``readback`` is False only under ``interpret=True``: that interpreter
    carries the aliased operand and the output as two arrays (the operand
    stays zero) but keeps the output's contents between visits, so there
    the window itself is the partial sum. The TPU interpreter
    (``pltpu.InterpretParams``) and the chip take the operand.
    """
    w = pl.program_id(1)
    gi = pl.program_id(2)
    is_first = meta_ref[w, IS_FIRST]
    is_last = meta_ref[w, IS_LAST]
    is_full = meta_ref[w, IS_FULL]
    qvf = meta_ref[w, QVF]
    qvl = meta_ref[w, QVL]
    use_exp2 = softcap == 0.0
    exp_fn = jnp.exp2 if use_exp2 else jnp.exp

    @pl.when((is_first == 1) & (gi == 0))
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    d = q_ref.shape[-1]

    # revisit-accumulation init: this (head, q tile) dq window is seen for
    # the first time in the k-major traversal — start it from zero
    @pl.when(qvf == 1)
    def _():
        dq_ref[0] = jnp.zeros((bq, d), jnp.float32)

    if readback:
        # a later visit: the window's block changed on the way here (with
        # the group innermost every step changes it), so it holds nothing
        # of this tile — take the partial sum the last visit wrote back
        moved = _dq_window_moved(work_qt_ref, w) if group == 1 else True

        @pl.when(moved & (qvf == 0))
        def _():
            dq_ref[0] = dqin_ref[0]

    q = q_ref[0]  # pre-scaled by softmax_scale (* log2e when softcap-free)
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]

    def score(q_blk):
        s_t = jax.lax.dot_general(
            k, q_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if softcap > 0.0:
            sc_t = softcap * jnp.tanh(s_t / softcap)
            return sc_t, 1.0 - (sc_t / softcap) ** 2
        return s_t, None

    def accum(sm_t, dcap_t, lse_c, delta_c, do_blk, q_blk, c0: int,
              rows: int, masked: bool):
        dp_t = jax.lax.dot_general(
            v, do_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if masked:
            neg = lse_c <= EMPTY_THRESH
            lse_safe = jnp.where(neg, 0.0, lse_c)
            if use_exp2:
                lse_safe = lse_safe * LOG2E
            p_t = exp_fn(sm_t - lse_safe)
            p_t = jnp.where(neg, 0.0, p_t)
        else:
            p_t = exp_fn(sm_t - (lse_c * LOG2E if use_exp2 else lse_c))
        dv_scr[:] += jax.lax.dot_general(
            p_t.astype(do.dtype), do_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds_t = p_t * (dp_t - delta_c)
        if dcap_t is not None:
            ds_t = ds_t * dcap_t
        # q is pre-scaled, so ds_t @ q' == (ds_t * scale) @ q == dk exactly
        dk_scr[:] += jax.lax.dot_general(
            ds_t.astype(q.dtype), q_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # the fused extra product: ds^T-contraction with k gives this
        # item's (rows, d) dq contribution, read-modify-written into the
        # revisited output window (k carries NO scale; applied at flush)
        dq_ref[0, c0:c0 + rows] += jax.lax.dot_general(
            ds_t.astype(k.dtype), k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # lse/delta q-in-lanes rows: ref block (sublanes, bq) -> (1, bq) views
    lse = lse_ref[:1, :]
    delta = delta_ref[:1, :]

    if nc == 0:
        sc_t, dcap_t = score(q)

        @pl.when(is_full == 1)
        def _():
            accum(sc_t, dcap_t, lse, delta, do, q, 0, bq, masked=False)

        @pl.when(is_full == 0)
        def _():
            q_base = work_qt_ref[w] * bq
            k_base = work_kt_ref[w] * bk
            accum(
                jnp.where(
                    _item_mask(meta_ref, w, q_base, k_base, bq, bk,
                               transposed=True),
                    sc_t, MASK_VALUE,
                ),
                dcap_t, lse, delta, do, q, 0, bq,
                masked=True,
            )
    else:
        # extent-clamped body (see _bwd_dkv_kernel): q is the lane dim of
        # s_t, so partial tiles chunk the q extent; a skipped chunk's p_t
        # was exactly 0 in the unclamped path, so its dq/dk/dv terms all
        # vanish and dropping it changes nothing
        cq = bq // nc
        eq0, eq1, _, _, live = _item_extents(meta_ref, w)

        @pl.when(is_full == 1)
        def _():
            sc_t, dcap_t = score(q)
            accum(sc_t, dcap_t, lse, delta, do, q, 0, bq, masked=False)

        for c in range(nc):
            c0 = c * cq

            @pl.when((is_full == 0) & live & (eq0 < c0 + cq) & (eq1 > c0))
            def _(c0=c0):
                q_base = work_qt_ref[w] * bq
                k_base = work_kt_ref[w] * bk
                q_c = q[c0 : c0 + cq]
                sc_t, dcap_t = score(q_c)
                accum(
                    jnp.where(
                        _item_mask(meta_ref, w, q_base + c0, k_base, cq,
                                   bk, transposed=True),
                        sc_t, MASK_VALUE,
                    ),
                    dcap_t,
                    lse_ref[:1, c0 : c0 + cq],
                    delta_ref[:1, c0 : c0 + cq],
                    do[c0 : c0 + cq],
                    q_c,
                    c0, cq,
                    masked=True,
                )

    @pl.when((is_last == 1) & (gi == group - 1))
    def _():
        dk_ref[0] = dk_scr[:]
        dv_ref[0] = dv_scr[:]

    # revisit-accumulation flush: last visit of this q tile — fold
    # softmax_scale into the resident window (both exp2 and softcap paths
    # accumulate the UNSCALED ds @ k above)
    @pl.when(qvl == 1)
    def _():
        dq_ref[0] = dq_ref[0] * scale


def _ffa_bwd_fused_pallas(
    params: FFAParams, work_qt_t, work_kt_t, meta_t,
    q_t, k_t, v_t, do_t, lse_t, delta_t,
):
    """Fused one-pass backward pallas call (see :func:`_bwd_fused_kernel`).

    Returns (dq_t, dk_t, dv_t), all fp32. The dq output is aliased to a
    zero input (``input_output_aliases``) with the output's own index map:
    a visit fetches what the tile's last visit wrote back, and q tiles the
    k-major work list never visits (fully masked rows) keep the donated
    zero background, so no dummy work items are needed and the plan's work
    counts are untouched.
    """
    bq, bk = params.dkv_blocks()
    hq, sqp, d = q_t.shape
    hk, skp, dv = v_t.shape
    g = params.group
    WT = (
        params.num_work_dkv
        if params.num_work_dkv is not None
        else params.num_work_t
    )

    use_exp2 = params.softcap == 0.0
    q_scale = params.softmax_scale * (LOG2E if use_exp2 else 1.0)
    q_t = (q_t.astype(jnp.float32) * q_scale).astype(q_t.dtype)
    dqz = jnp.zeros((hq, sqp, d), jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(hk, WT, g),
        in_specs=[
            pl.BlockSpec(
                (1, bq, d),
                lambda h, w, gi, qt, kt, mt: (h * g + gi, qt[w], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, bk, d), lambda h, w, gi, qt, kt, mt: (h, kt[w], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, bk, dv), lambda h, w, gi, qt, kt, mt: (h, kt[w], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, bq, dv),
                lambda h, w, gi, qt, kt, mt: (h * g + gi, qt[w], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (None, NUM_SUBLANES, bq),
                lambda h, w, gi, qt, kt, mt: (h * g + gi, 0, qt[w]),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (None, NUM_SUBLANES, bq),
                lambda h, w, gi, qt, kt, mt: (h * g + gi, 0, qt[w]),
                memory_space=pltpu.VMEM,
            ),
            # the dq array itself, aliased to the output and indexed like
            # it: the partial sum of the window's earlier visits
            pl.BlockSpec(
                (1, bq, d),
                lambda h, w, gi, qt, kt, mt: (h * g + gi, qt[w], 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, bq, d),
                lambda h, w, gi, qt, kt, mt: (h * g + gi, qt[w], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, bk, d), lambda h, w, gi, qt, kt, mt: (h, kt[w], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, bk, dv), lambda h, w, gi, qt, kt, mt: (h, kt[w], 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
    )
    kernel = partial(
        _bwd_fused_kernel, softcap=params.softcap,
        scale=params.softmax_scale, bq=bq, bk=bk, group=g,
        nc=_clamp_chunks(bq), readback=params.interpret is not True,
    )
    dq_t, dk_t, dv_t = _named.pallas_call(
        kernel,
        label=params.label,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hq, sqp, d), jnp.float32),
            jax.ShapeDtypeStruct((hk, skp, d), jnp.float32),
            jax.ShapeDtypeStruct((hk, skp, dv), jnp.float32),
        ],
        # operand 9 (dqz, counting the 3 scalar-prefetch args) -> output 0
        input_output_aliases={9: 0},
        interpret=params.interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
    )(work_qt_t, work_kt_t, meta_t, q_t, k_t, v_t, do_t,
      _lanes_layout(_clamp_lse(lse_t), NUM_SUBLANES),
      _lanes_layout(delta_t, NUM_SUBLANES), dqz)
    if use_exp2:
        dk_t = dk_t * LN2  # divide the folded log2e back out
    return dq_t, dk_t, dv_t


def _bwd_fused_kernel_gqa(
    work_qt_ref,
    work_kt_ref,
    meta_ref,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dqin_ref,
    dq_ref,
    dk_ref,
    dv_ref,
    dk_scr,
    dv_scr,
    *,
    softcap: float,
    scale: float,
    bq: int,
    bk: int,
    g: int,
    clamp: bool,
    readback: bool,
):
    """GQA-packed fused one-pass backward: grid (hk, WT), the whole query
    group of one kv head per step (see :func:`_bwd_dkv_kernel_gqa` for the
    packing scheme). The dq window is the full (g, bq, d) group block of
    the work item's q tile, read-modify-written under the same QVF/QVL
    discipline and the same aliased ``dqin_ref`` operand as
    :func:`_bwd_fused_kernel` — one init, one read-back and one flush per
    run of visits covers all g heads at once. Clamping is the whole-item
    live guard (the packed lane dim interleaves the g heads' q rows, so
    it cannot be chunked by a single q extent); init, read-back and flush
    stay OUTSIDE the guard so dead items still honor their visit flags.
    """
    w = pl.program_id(1)
    is_first = meta_ref[w, IS_FIRST]
    is_last = meta_ref[w, IS_LAST]
    is_full = meta_ref[w, IS_FULL]
    qvf = meta_ref[w, QVF]
    qvl = meta_ref[w, QVL]
    use_exp2 = softcap == 0.0
    exp_fn = jnp.exp2 if use_exp2 else jnp.exp

    @pl.when(is_first == 1)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    d = q_ref.shape[-1]
    dv = v_ref.shape[-1]

    @pl.when(qvf == 1)
    def _():
        dq_ref[0] = jnp.zeros((g, bq, d), jnp.float32)

    if readback:
        moved = _dq_window_moved(work_qt_ref, w)

        @pl.when(moved & (qvf == 0))
        def _():
            dq_ref[0] = dqin_ref[0]

    q = q_ref[0].reshape(g * bq, d)  # pre-scaled on host
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0].reshape(g * bq, dv)

    lse = lse_ref[...]  # (1, g*bq), tile-packed cols
    delta = delta_ref[...]

    def score():
        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if softcap > 0.0:
            sc_t = softcap * jnp.tanh(s_t / softcap)
            return sc_t, 1.0 - (sc_t / softcap) ** 2
        return s_t, None

    def accum(sm_t, dcap_t, masked: bool):
        dp_t = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if masked:
            neg = lse <= EMPTY_THRESH
            lse_safe = jnp.where(neg, 0.0, lse)
            if use_exp2:
                lse_safe = lse_safe * LOG2E
            p_t = exp_fn(sm_t - lse_safe)
            p_t = jnp.where(neg, 0.0, p_t)
        else:
            p_t = exp_fn(sm_t - (lse * LOG2E if use_exp2 else lse))
        dv_scr[:] += jax.lax.dot_general(
            p_t.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds_t = p_t * (dp_t - delta)
        if dcap_t is not None:
            ds_t = ds_t * dcap_t
        dk_scr[:] += jax.lax.dot_general(
            ds_t.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # fused dq contribution for ALL g heads at once: (g*bq, d) packed
        # rows unpacked back into the (g, bq, d) revisited window
        dq_ref[0] += jax.lax.dot_general(
            ds_t.astype(k.dtype), k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(g, bq, d)

    if not clamp:
        sc_t, dcap_t = score()

        @pl.when(is_full == 1)
        def _():
            accum(sc_t, dcap_t, masked=False)

        @pl.when(is_full == 0)
        def _():
            q_base = work_qt_ref[w] * bq
            k_base = work_kt_ref[w] * bk
            accum(
                jnp.where(
                    _item_mask(meta_ref, w, q_base, k_base, bq, bk,
                               transposed=True, repeat=g),
                    sc_t, MASK_VALUE,
                ),
                dcap_t,
                masked=True,
            )
    else:
        # whole-item live guard (see _bwd_dkv_kernel_gqa); dead items'
        # contribution was exactly 0, so skipping their MXU passes is free
        _, _, _, _, live = _item_extents(meta_ref, w)

        @pl.when((is_full == 1) & live)
        def _():
            sc_t, dcap_t = score()
            accum(sc_t, dcap_t, masked=False)

        @pl.when((is_full == 0) & live)
        def _():
            q_base = work_qt_ref[w] * bq
            k_base = work_kt_ref[w] * bk
            sc_t, dcap_t = score()
            accum(
                jnp.where(
                    _item_mask(meta_ref, w, q_base, k_base, bq, bk,
                               transposed=True, repeat=g),
                    sc_t, MASK_VALUE,
                ),
                dcap_t,
                masked=True,
            )

    @pl.when(is_last == 1)
    def _():
        dk_ref[0] = dk_scr[:]
        dv_ref[0] = dv_scr[:]

    @pl.when(qvl == 1)
    def _():
        dq_ref[0] = dq_ref[0] * scale


def _ffa_bwd_fused_pallas_gqa(
    params: FFAParams, work_qt_t, work_kt_t, meta_t,
    q_t, k_t, v_t, do_t, lse_t, delta_t,
):
    """GQA-packed fused one-pass backward pallas call (see
    :func:`_bwd_fused_kernel_gqa`)."""
    bq, bk = params.dkv_blocks()
    hq, sqp, d = q_t.shape
    hk, skp, dv = v_t.shape
    g = params.group
    WT = (
        params.num_work_dkv
        if params.num_work_dkv is not None
        else params.num_work_t
    )

    use_exp2 = params.softcap == 0.0
    q_scale = params.softmax_scale * (LOG2E if use_exp2 else 1.0)
    q_t = (q_t.astype(jnp.float32) * q_scale).astype(q_t.dtype)
    q_g = q_t.reshape(hk, g, sqp, d)
    do_g = do_t.reshape(hk, g, sqp, dv)
    lse_p = _tile_pack_rows(_clamp_lse(lse_t), hk, g, bq)
    delta_p = _tile_pack_rows(delta_t, hk, g, bq)
    dqz = jnp.zeros((hk, g, sqp, d), jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(hk, WT),
        in_specs=[
            pl.BlockSpec((1, g, bq, d),
                         lambda h, w, qt, kt, mt: (h, 0, qt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda h, w, qt, kt, mt: (h, kt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dv), lambda h, w, qt, kt, mt: (h, kt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, g, bq, dv),
                         lambda h, w, qt, kt, mt: (h, 0, qt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, None, 1, g * bq),
                         lambda h, w, qt, kt, mt: (h, qt[w], 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, None, 1, g * bq),
                         lambda h, w, qt, kt, mt: (h, qt[w], 0, 0),
                         memory_space=pltpu.VMEM),
            # the dq array itself, aliased to the output, indexed like it
            pl.BlockSpec((1, g, bq, d),
                         lambda h, w, qt, kt, mt: (h, 0, qt[w], 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, g, bq, d),
                         lambda h, w, qt, kt, mt: (h, 0, qt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda h, w, qt, kt, mt: (h, kt[w], 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dv), lambda h, w, qt, kt, mt: (h, kt[w], 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
    )
    kernel = partial(
        _bwd_fused_kernel_gqa, softcap=params.softcap,
        scale=params.softmax_scale, bq=bq, bk=bk, g=g,
        clamp=_registry_mod().extent_clamp_enabled(),
        readback=params.interpret is not True,
    )
    dq_g, dk_t, dv_t = _named.pallas_call(
        kernel,
        label=params.label,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hk, g, sqp, d), jnp.float32),
            jax.ShapeDtypeStruct((hk, skp, d), jnp.float32),
            jax.ShapeDtypeStruct((hk, skp, dv), jnp.float32),
        ],
        # operand 9 (dqz, counting the 3 scalar-prefetch args) -> output 0
        input_output_aliases={9: 0},
        interpret=params.interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(work_qt_t, work_kt_t, meta_t, q_g, k_t, v_t, do_g, lse_p, delta_p,
      dqz)
    if use_exp2:
        dk_t = dk_t * LN2  # divide the folded log2e back out
    return dq_g.reshape(hq, sqp, d), dk_t, dv_t


def _use_gqa_pack_fused(
    params: FFAParams, sqp: int, d: int, dv: int, itemsize: int = 2
) -> bool:
    """Trace-time dispatch to the packed fused kernel: same conditions as
    the packed dkv kernel (shared env flag — the packing trade-off is
    identical) with the LARGER fused residency — dkv's plus the revisited
    dq window and its aliased zero background (utils/mem_budget
    ``ffa_kernel_residency("fused", ...)``, one source of truth with K1)."""
    bq, bk = params.dkv_blocks()
    return sqp % bq == 0 and gqa_pack_admitted(
        "fused", params.group, bq, bk, d, dv, itemsize)


def fused_bwd_feasible(
    params: FFAParams, sqp: int, d: int, dv: int, itemsize: int = 2
) -> bool:
    """True when a fused-kernel variant can run this plan: its per-step
    VMEM residency fits the budget, and the k-major list leaves a q tile's
    dq window FUSED_DQ_REVISIT_DISTANCE grid steps before it comes back
    (the read-modify-write of :func:`_bwd_fused_kernel`). The guard that
    forces split mode even under a 'fused' pin."""
    dist = params.min_revisit_distance
    if _use_gqa_pack_fused(params, sqp, d, dv, itemsize):
        return dist >= FUSED_DQ_REVISIT_DISTANCE
    if params.group > 1:
        # the unpacked body walks the group innermost: a head's window
        # comes back every g steps, inside a run of one q tile too (a
        # group of 2 is under the distance and runs the pair)
        dist = min(dist, params.group)
    bq, bk = params.dkv_blocks()
    return dist >= FUSED_DQ_REVISIT_DISTANCE and (
        ffa_kernel_residency(
            "fused", bq, bk, d, head_dim_v=dv, dtype_bytes=itemsize,
            group=params.group, packed=False,
        )
        <= VMEM_ALLOWED_BYTES
    )


def ffa_bwd_fused_pallas_dispatch(
    params: FFAParams, work_qt_t, work_kt_t, meta_t, q_t, k_t, v_t, do_t,
    lse_t, delta_t,
):
    """Fused one-pass backward with the GQA-packing dispatch applied
    (mirrors :func:`ffa_bwd_dkv_pallas_dispatch`)."""
    fn = (
        _ffa_bwd_fused_pallas_gqa
        if _use_gqa_pack_fused(params, q_t.shape[1], q_t.shape[2],
                               v_t.shape[2], q_t.dtype.itemsize)
        else _ffa_bwd_fused_pallas
    )
    return fn(params, work_qt_t, work_kt_t, meta_t, q_t, k_t, v_t, do_t,
              lse_t, delta_t)


def ffa_bwd_mode(
    params: FFAParams, sqp: int, d: int, dv: int, itemsize: int,
    meta_cols: int,
) -> str:
    """Resolved backward execution mode — "fused" or "split" — decidable
    at trace time (static work counts / blocks / dims only; no plan
    contents, which may be traced arrays under shard_map).

    In order: a 'split' pin (MAGI_ATTENTION_BACKEND_FFA_BWD); the
    feasibility guards (plan meta layout, fused VMEM residency, the plan's
    revisit distance), any of which forces 'split'; a 'fused' pin; else
    tile_policy.choose_bwd_mode over the static shapes, memoized per key
    by the backend registry (kernels/registry.py), which records every
    outcome (``registry.last_choice("ffa_bwd")``).
    """
    from ..env import backend as env_backend
    from . import registry as _registry
    from .tile_policy import choose_bwd_mode

    pin = env_backend.ffa_bwd_pin()
    key = bwd_mode_key(params, d, dv, itemsize)
    # plan meta that predates the QVF/QVL visit-flag columns (hand-built
    # 13-col metas in older tests) cannot drive the fused kernel
    if pin != "split" and (
        meta_cols <= QVL
        or not fused_bwd_feasible(params, sqp, d, dv, itemsize)
    ):
        return _registry.note_choice(
            "ffa_bwd", key, "split", "guard", label=params.label).name
    return _registry.resolve(
        "ffa_bwd",
        key,
        lambda: choose_bwd_mode(
            *key[:7], dv, itemsize=itemsize, group=params.group
        ),
        pin=pin,
        label=params.label,
    ).name


def bwd_mode_key(
    params: FFAParams, d: int, dv: int, itemsize: int
) -> tuple[int, ...]:
    """The registry key of one backward-mode decision: the exact static
    quantities choose_bwd_mode consumes — (w_dq, bq_dq, bk_dq, wt, bq_dkv,
    bk_dkv, d, dv, itemsize, group)."""
    bq_dq, bk_dq = params.dq_blocks()
    bq_dkv, bk_dkv = params.dkv_blocks()
    w_dq = (
        params.num_work_dq
        if params.num_work_dq is not None
        else params.num_work
    )
    wt = (
        params.num_work_dkv
        if params.num_work_dkv is not None
        else params.num_work_t
    )
    return (
        w_dq, bq_dq, bk_dq, wt, bq_dkv, bk_dkv, d, dv, itemsize,
        params.group,
    )


def resolved_bwd_mode(
    params: FFAParams, sqp: int, d: int, dv: int, itemsize: int = 2
) -> str:
    """The mode :func:`ffa_bwd_pallas_dispatch` will pick for a
    current-layout (META_DIM-column) plan — the telemetry layer stamps
    ``attn_step`` records' ``bwd_mode`` with this."""
    from .ffa_plan import META_DIM

    return ffa_bwd_mode(params, sqp, d, dv, itemsize, META_DIM)


def ffa_bwd_pallas_dispatch(
    params: FFAParams, dq_arrays, dkv_arrays, q_t, k_t, v_t, do_t, lse_t,
    delta_t,
):
    """ONE backward entry for every path (custom-vjp core, mixed branches,
    CP multi-stage, sink, dynamic): returns (dq_t, dk_t, dv_t).

    Picks the fused one-pass kernel (:func:`ffa_bwd_mode`) when the env
    flag / cost model / VMEM guard allow it, else the split dq + dkv
    passes. A fused-kernel failure is one resilience rung ABOVE the split
    path: with MAGI_ATTENTION_FALLBACK=1 it degrades to split (recorded as
    a resilience event) before the calc_attn tile ladder ever engages.
    """
    hq, sqp, d = q_t.shape
    dv = v_t.shape[2]
    meta_t = dkv_arrays[2]
    meta_cols = meta_t.shape[1] if meta_t.ndim == 2 else 0
    mode = ffa_bwd_mode(params, sqp, d, dv, q_t.dtype.itemsize, meta_cols)
    if mode == "fused":
        from ..resilience import fallback as _fallback

        try:
            maybe_inject("kernel_lowering")
            return ffa_bwd_fused_pallas_dispatch(
                params, *dkv_arrays, q_t, k_t, v_t, do_t, lse_t, delta_t
            )
        except _fallback.kernel_failure_types() as e:
            from ..env import resilience as env_resilience

            if not env_resilience.is_fallback_enable():
                raise
            _fallback.record_resilience_event(
                "fallback", "kernel_lowering",
                action_detail="fused_bwd_to_split",
                error=type(e).__name__,
            )
    dq_t = ffa_bwd_dq_pallas_dispatch(
        params, *dq_arrays, q_t, k_t, v_t, do_t, lse_t, delta_t
    )
    dk_t, dv_t = ffa_bwd_dkv_pallas_dispatch(
        params, *dkv_arrays, q_t, k_t, v_t, do_t, lse_t, delta_t
    )
    return dq_t, dk_t, dv_t


# ---------------------------------------------------------------------------
# static kernel contracts (consumed by analysis/kernel_check.py)
# ---------------------------------------------------------------------------

# One entry per Pallas kernel body in this file; the static checker's K2
# (accumulator discipline) and K4 (precision) passes read these as ground
# truth and verify the kernel SOURCE against them, so a drive-by edit that
# drops an init or moves a flush out of its guard fails `make kernel-audit`.
# Names refer to ref parameters / unpacked locals inside the kernel body.
# ``group_inner`` marks kernels whose grid revisits the same output tile
# across an inner grid dimension: init/flush must then additionally be
# qualified on that dimension's first/last position — the dkv-GQA-pack bug
# class K2 exists for. ``out_dtypes`` pairs positionally with the
# pallas_call out_shape ("input" = operand dtype passthrough, "f32" =
# must be float32); trailing optional outputs may be absent at capture.
PALLAS_CONTRACTS: dict[str, dict] = {
    "_fwd_kernel": dict(
        wrapper="_ffa_fwd_pallas",
        scratch=("m_scr", "l_scr", "acc_scr"),
        outputs=("out_ref", "lse_ref", "ml_ref"),
        out_dtypes=("input", "f32", "f32"),
        init_guard="is_first",
        flush_guard="is_last",
        group_inner=None,
    ),
    "_fwd_kernel_gqa": dict(
        wrapper="_ffa_fwd_pallas_gqa",
        scratch=("m_scr", "l_scr", "acc_scr"),
        outputs=("out_ref", "lse_ref"),
        out_dtypes=("input", "f32"),
        init_guard="is_first",
        flush_guard="is_last",
        group_inner=None,
    ),
    "_bwd_dq_kernel": dict(
        wrapper="_ffa_bwd_dq_pallas",
        scratch=("dq_scr",),
        outputs=("dq_ref",),
        out_dtypes=("f32",),
        init_guard="is_first",
        flush_guard="is_last",
        group_inner=None,
    ),
    "_bwd_dq_kernel_gqa": dict(
        wrapper="_ffa_bwd_dq_pallas_gqa",
        scratch=("dq_scr",),
        outputs=("dq_ref",),
        out_dtypes=("f32",),
        init_guard="is_first",
        flush_guard="is_last",
        group_inner=None,
    ),
    "_bwd_dkv_kernel": dict(
        wrapper="_ffa_bwd_dkv_pallas",
        scratch=("dk_scr", "dv_scr"),
        outputs=("dk_ref", "dv_ref"),
        out_dtypes=("f32", "f32"),
        init_guard="is_first",
        flush_guard="is_last",
        group_inner=dict(var="gi", count="group"),
    ),
    "_bwd_dkv_kernel_gqa": dict(
        wrapper="_ffa_bwd_dkv_pallas_gqa",
        scratch=("dk_scr", "dv_scr"),
        outputs=("dk_ref", "dv_ref"),
        out_dtypes=("f32", "f32"),
        init_guard="is_first",
        flush_guard="is_last",
        group_inner=None,
    ),
    # Fused one-pass backward kernels: dk/dv follow the standard scratch
    # discipline; dq is a REVISIT-accumulated output — no scratch run
    # exists, the output window itself is zero-initialized under the
    # first-q-visit guard and scale-flushed under the last-q-visit guard
    # (K2's revisit rule). ``revisit`` names that output, its guards, and
    # the aliased operand a later visit reads its partial sum back from.
    "_bwd_fused_kernel": dict(
        wrapper="_ffa_bwd_fused_pallas",
        scratch=("dk_scr", "dv_scr"),
        outputs=("dq_ref", "dk_ref", "dv_ref"),
        out_dtypes=("f32", "f32", "f32"),
        init_guard="is_first",
        flush_guard="is_last",
        group_inner=dict(var="gi", count="group"),
        revisit=dict(out="dq_ref", init_guard="qvf", flush_guard="qvl",
                     readback="dqin_ref"),
    ),
    "_bwd_fused_kernel_gqa": dict(
        wrapper="_ffa_bwd_fused_pallas_gqa",
        scratch=("dk_scr", "dv_scr"),
        outputs=("dq_ref", "dk_ref", "dv_ref"),
        out_dtypes=("f32", "f32", "f32"),
        init_guard="is_first",
        flush_guard="is_last",
        group_inner=None,
        revisit=dict(out="dq_ref", init_guard="qvf", flush_guard="qvl",
                     readback="dqin_ref"),
    ),
    # Delta preprocessing: stateless map kernel — every grid step writes
    # its own block once, so there is no accumulator discipline to prove.
    "_delta_kernel": dict(
        wrapper="_ffa_delta_pallas",
        scratch=(),
        outputs=("delta_ref",),
        out_dtypes=("f32",),
        init_guard=None,
        flush_guard=None,
        group_inner=None,
    ),
}


# ---------------------------------------------------------------------------
# public entry (custom VJP)
# ---------------------------------------------------------------------------


def _bwd_plan_slices(arrays: tuple):
    """(dq_triple, dkv_triple) of a 6- or 12-array plan tuple.

    6 arrays: dq shares the fwd q-major triple, dkv the k-major triple.
    12 arrays: fwd6 + dq-specific q-major triple + dkv-specific k-major
    triple (built with the bwd block overrides, see FFAParams).
    """
    if len(arrays) == 12:
        return arrays[6:9], arrays[9:12]
    return arrays[0:3], arrays[3:6]


def ffa_fwd_pallas_dispatch(params: FFAParams, work_qt, work_kt, meta,
                            q_t, k_t, v_t):
    """Forward pallas call with the GQA-packing dispatch applied — the ONE
    entry every forward path (custom-vjp core, CP multi-stage, sink) uses
    so the packed kernel is reachable from all of them."""
    maybe_inject("kernel_lowering")
    fwd = (
        _ffa_fwd_pallas_gqa
        if _use_gqa_pack(params, q_t.shape[2], v_t.shape[2],
                         q_t.dtype.itemsize)
        else _ffa_fwd_pallas
    )
    return fwd(params, work_qt, work_kt, meta, q_t, k_t, v_t)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ffa_core(q_t, k_t, v_t, arrays, params: FFAParams):
    # dtype-polymorphic: compute always runs in q's dtype; k/v may arrive
    # fp32 (the high-precision wire-reduce path upcasts receive buffers so
    # their COTANGENTS legally stay fp32 through the group-reduce — ref
    # _reduce_partial_dkv, dist_attn.py:2123) and are cast down here.
    kc, vc = k_t.astype(q_t.dtype), v_t.astype(q_t.dtype)
    return ffa_fwd_pallas_dispatch(params, *arrays[0:3], q_t, kc, vc)


def _ffa_core_fwd(q_t, k_t, v_t, arrays, params: FFAParams):
    out_t, lse_t, ml = ffa_fwd_pallas_dispatch(
        params, *arrays[0:3], q_t,
        k_t.astype(q_t.dtype), v_t.astype(q_t.dtype),
    )
    # residuals keep the PRIMAL-dtype k/v: under HP reduce that is fp32
    # (2x residual HBM — the documented cost of the flag); the cotangents
    # below then legally leave in fp32 for the wire reduce
    res = (q_t, k_t, v_t, out_t, lse_t, arrays)
    return (out_t, lse_t, ml), res


def _ffa_core_bwd(params: FFAParams, res, cts):
    # lse/max_logits are auxiliary outputs: their cotangents are ignored (the
    # CP runtime differentiates the lse-merge manually, matching the
    # reference).
    do_t, _, _ = cts
    q_t, k_t, v_t, out_t, lse_t, arrays = res
    kc, vc = k_t.astype(q_t.dtype), v_t.astype(q_t.dtype)
    dq_arrays, dkv_arrays = _bwd_plan_slices(arrays)
    # delta = rowsum(dO ⊙ O) via the shared Pallas delta kernel — no XLA
    # full-array pass over o/do
    delta_t = ffa_delta_pallas_dispatch(params, out_t, do_t)  # (hq, sqp)
    dq_t, dk_t, dv_t = ffa_bwd_pallas_dispatch(
        params, dq_arrays, dkv_arrays, q_t, kc, vc, do_t, lse_t, delta_t,
    )
    # dk/dv already come back per kv head: the dkv kernel accumulates the
    # GQA group in-kernel (no host reshape-sum). The kernels emit fp32; the
    # casts below are identity when the primal k/v were fp32 (HP reduce).
    return (
        dq_t.astype(q_t.dtype),
        dk_t.astype(k_t.dtype),
        dv_t.astype(v_t.dtype),
        tuple(None for _ in arrays),
    )


_ffa_core.defvjp(_ffa_core_fwd, _ffa_core_bwd)


def _should_interpret() -> bool:
    """Pallas interpret mode is the CPU route and nothing else: on when
    the default backend is ``cpu`` (tests, host-side rehearsal), an error
    when ``MAGI_ATTENTION_PALLAS_INTERPRET=1`` meets any other backend —
    an accelerator run that interpreted its kernels would pass every
    check while measuring nothing."""
    backend = jax.default_backend()
    if env_general.is_interpret_mode_enable() and backend != "cpu":
        raise RuntimeError(
            "MAGI_ATTENTION_PALLAS_INTERPRET=1 with jax.default_backend()="
            f"{backend!r}: interpret mode is the CPU test route; unset the "
            "variable to compile the kernels for this device"
        )
    return backend == "cpu"


def ffa_attn_with_plan(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    arrays: tuple[jax.Array, ...],
    params: FFAParams,
    return_max_logits: bool = False,
):
    """FFA over an explicit plan — the CP-runtime entry point.

    Args:
        q/k/v: ``[sq,hq,d] / [sk,hk,d] / [sk,hk,dv]``, seq-major.
        arrays: the 6 plan arrays (:func:`plan_arrays`) — or 12 when
            bwd-specific block overrides are active (fwd6 + dq3 + dkv3, see
            FFAParams) — possibly traced (per-rank metadata under
            shard_map), padded to params.num_work / params.num_work_t.
        params: static dims + scalars; sq/sk must fit the tile counts.

    Returns:
        (out ``[sq,hq,dv]``, lse ``[sq,hq]`` fp32), plus per-head max_logits
        ``[hq]`` fp32 when ``return_max_logits``.
    """
    sq, hq, d = q.shape
    sk, hk, dv = v.shape
    sqp = params.num_q_tiles * params.block_q
    skp = params.num_k_tiles * params.block_k
    q_t = jnp.pad(q, ((0, sqp - sq), (0, 0), (0, 0))).transpose(1, 0, 2)
    k_t = jnp.pad(k, ((0, skp - sk), (0, 0), (0, 0))).transpose(1, 0, 2)
    v_t = jnp.pad(v, ((0, skp - sk), (0, 0), (0, 0))).transpose(1, 0, 2)
    out_t, lse_t, ml = _ffa_core(q_t, k_t, v_t, tuple(arrays), params)
    out = out_t.transpose(1, 0, 2)[:sq]
    lse = lse_t.T[:sq]
    if return_max_logits:
        return out, lse, ml
    return out, lse


def resolve_bwd_overrides(
    bq: int, bk: int, sqp: int, skp: int,
    policy_dq: tuple[int, int] | None = None,
    policy_dkv: tuple[int, int] | None = None,
) -> tuple[tuple[int, int] | None, tuple[int, int] | None]:
    """Bwd-tile overrides resolved against a padded geometry.

    Returns ``(dq_blocks, dkv_blocks)``; an entry is None when unset or
    incompatible (the bwd kernels index the same padded q/k/v and lse
    buffers as fwd, so the override must divide the fwd-padded geometry and
    satisfy TPU alignment — incompatible values silently inherit fwd's).
    ``policy_dq``/``policy_dkv`` are the auto-tile policy's per-pass picks
    (:func:`tile_policy.choose_blocks_per_pass`); explicit env settings
    always take precedence over them, component-wise.
    """

    def gate(env_bq: int, env_bk: int,
             policy: tuple[int, int] | None) -> tuple[int, int] | None:
        pol_bq, pol_bk = policy or (0, 0)
        obq = env_bq or pol_bq or bq
        obk = env_bk or pol_bk or bk
        obq, obk = min(obq, sqp), min(obk, skp)
        if (
            (obq, obk) == (bq, bk)
            or sqp % obq or skp % obk
            or obq % 8 or obk % 128
        ):
            return None
        return obq, obk

    return (
        gate(env_kernel.ffa_block_q_dq(), env_kernel.ffa_block_k_dq(),
             policy_dq),
        gate(env_kernel.ffa_block_q_dkv(), env_kernel.ffa_block_k_dkv(),
             policy_dkv),
    )


def assemble_bwd_overrides(
    arrays: tuple, bq: int, bk: int, num_q_tiles: int, num_k_tiles: int,
    build_triple, min_revisit_distance: int,
    policy_dq: tuple[int, int] | None = None,
    policy_dkv: tuple[int, int] | None = None,
) -> tuple[tuple, dict]:
    """Shared override assembly for single-device and stacked (CP) plans —
    ONE place defines the 12-array layout and FFAParams override fields.

    Args:
        arrays: the 6 fwd plan arrays (possibly rank-stacked).
        build_triple: ``(blocks, kind)`` — kind "dq" returns a q-major
            triple + its num_work cap; "dkv" a k-major triple + its
            num_work_t cap + its list's min_revisit_distance.
        min_revisit_distance: of ``arrays``' own k-major list (the least
            over the ranks of a stack); a dkv override's list replaces it.

    Returns ``(arrays, FFAParams fields)`` — arrays extended to 12 when an
    override is active; the fields always carry ``min_revisit_distance``
    of the list the one-pass backward would walk.
    """
    dq_blocks, dkv_blocks = resolve_bwd_overrides(
        bq, bk, num_q_tiles * bq, num_k_tiles * bk,
        policy_dq=policy_dq, policy_dkv=policy_dkv,
    )
    overrides: dict = {"min_revisit_distance": min_revisit_distance}
    if not (dq_blocks or dkv_blocks):
        return tuple(arrays), overrides
    dq_triple = tuple(arrays[0:3])
    dkv_triple = tuple(arrays[3:6])
    if dq_blocks:
        dq_triple, w_dq = build_triple(dq_blocks, "dq")
        overrides.update(
            block_q_dq=dq_blocks[0], block_k_dq=dq_blocks[1],
            num_work_dq=w_dq,
        )
    if dkv_blocks:
        dkv_triple, wt_dkv, dist = build_triple(dkv_blocks, "dkv")
        overrides.update(
            block_q_dkv=dkv_blocks[0], block_k_dkv=dkv_blocks[1],
            num_work_dkv=wt_dkv, min_revisit_distance=dist,
        )
    return tuple(arrays) + tuple(dq_triple) + tuple(dkv_triple), overrides


def apply_bwd_overrides(
    arrays: tuple, qr, kr, d_lo, d_hi, sq: int, sk: int, bq: int, bk: int,
    num_q_tiles: int, num_k_tiles: int,
    policy_dq: tuple[int, int] | None = None,
    policy_dkv: tuple[int, int] | None = None,
) -> tuple[tuple, dict]:
    """Single-plan wrapper of :func:`assemble_bwd_overrides`."""

    def build_triple(blocks, kind):
        p = get_ffa_plan(qr, kr, d_lo, d_hi, sq, sk, *blocks)
        if kind == "dq":
            return plan_arrays(p)[0:3], p.num_work
        return plan_arrays(p)[3:6], p.num_work_t, p.min_revisit_distance

    return assemble_bwd_overrides(
        arrays, bq, bk, num_q_tiles, num_k_tiles, build_triple,
        # the plan ``arrays`` came from: a cache hit
        get_ffa_plan(
            qr, kr, d_lo, d_hi, sq, sk, bq, bk).min_revisit_distance,
        policy_dq=policy_dq, policy_dkv=policy_dkv,
    )


def default_blocks(sq: int, sk: int, block_q=None, block_k=None) -> tuple[int, int]:
    bq = block_q or env_kernel.ffa_block_q()
    bk = block_k or env_kernel.ffa_block_k()
    return min(bq, _round_up(sq, 16)), min(bk, _round_up(sk, 128))


# a call's tiles name by everything it is computed from: an eager call per
# step pays one lookup, not three registry resolves and residency sums
_TILES_NAMES: dict[tuple, str] = {}


def note_tiles(
    params: FFAParams, d: int, dv: int, itemsize: int, source: str
) -> str:
    """Record the per-pass tiles of a call under the registry's
    ``ffa_tiles`` decision, e.g. ``fwd256x512g4 dq256x512g4 dkv256x512g4``:
    a ``g`` suffix marks a GQA-packed body, whose rows a grid step are g
    times the tile's. ``source`` is who chose the tiles
    (``registry.tiles_source``)."""
    sqp = params.num_q_tiles * params.block_q
    passes = (
        ("fwd", (params.block_q, params.block_k)),
        ("dq", params.dq_blocks()),
        ("dkv", params.dkv_blocks()),
    )
    key = (passes, sqp, params.group, params.emit_max_logits, d, dv,
           itemsize, _registry_mod().gqa_pack_flags())
    name = _TILES_NAMES.get(key)
    if name is None:
        packed = (
            _use_gqa_pack(params, d, dv, itemsize),
            _use_gqa_pack_dq(params, d, dv, itemsize),
            _use_gqa_pack_dkv(params, sqp, d, dv, itemsize),
        )
        name = _TILES_NAMES[key] = " ".join(
            f"{tag}{bq}x{bk}" + (f"g{params.group}" if on else "")
            for (tag, (bq, bk)), on in zip(passes, packed)
        )
    _registry_mod().note_choice(
        "ffa_tiles", (sqp, d, dv, itemsize, params.group), name, source,
        label=params.label)
    return name


# ---------------------------------------------------------------------------
# mixed-granularity dispatch: coarse-block pass over dense slices + fine-
# block pass over fragmented slices, merged through the LSE-merge math
# (tile_policy.choose_mixed_dispatch decides when the split is profitable)
# ---------------------------------------------------------------------------


def _merge_out_lse(o1, l1, o2, l2):
    """Exact two-way online-softmax merge of (out, lse) pairs, seq-major.

    Same math as functional/utils.py's lse merge (reimplemented locally:
    functional imports this module, so importing it here would cycle). lse
    is natural-log with -inf on uncovered rows. Because the two passes
    partition the slice set, merged out == sum_i exp(lse_i - lse) * out_i
    and merged lse == log(sum_i exp(lse_i)) — the single-pass results up
    to fp roundoff."""
    m = jnp.maximum(l1, l2)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    w1 = jnp.where(jnp.isneginf(l1), 0.0, jnp.exp(l1 - m_safe))
    w2 = jnp.where(jnp.isneginf(l2), 0.0, jnp.exp(l2 - m_safe))
    s = w1 + w2
    covered = s > 0.0
    lse = jnp.where(
        covered, m_safe + jnp.log(jnp.where(covered, s, 1.0)), NEG_INF
    )
    s_safe = jnp.where(covered, s, 1.0)[..., None]
    out = (
        o1.astype(jnp.float32) * w1[..., None]
        + o2.astype(jnp.float32) * w2[..., None]
    ) / s_safe
    return out.astype(o1.dtype), lse


def _mixed_branch_fwd(q, k, v, arrays, params: FFAParams):
    """One forward pass of the mixed dispatch: pad/transpose to the branch's
    padded geometry, run the fwd kernel, slice back to seq-major."""
    sq = q.shape[0]
    sk = k.shape[0]
    sqp = params.num_q_tiles * params.block_q
    skp = params.num_k_tiles * params.block_k
    q_t = jnp.pad(q, ((0, sqp - sq), (0, 0), (0, 0))).transpose(1, 0, 2)
    k_t = jnp.pad(k, ((0, skp - sk), (0, 0), (0, 0))).transpose(1, 0, 2)
    v_t = jnp.pad(v, ((0, skp - sk), (0, 0), (0, 0))).transpose(1, 0, 2)
    out_t, lse_t, _ = ffa_fwd_pallas_dispatch(
        params, *arrays[0:3], q_t,
        k_t.astype(q_t.dtype), v_t.astype(q_t.dtype),
    )
    return out_t.transpose(1, 0, 2)[:sq], lse_t.T[:sq]


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ffa_mixed(q, k, v, arrays_a, arrays_b, params_a: FFAParams,
               params_b: FFAParams):
    # A dedicated custom_vjp at the merged level is mandatory: the branch
    # cores ignore their lse cotangents (see _ffa_core_bwd), so naive
    # autodiff THROUGH the lse merge would drop the coupling between the
    # branches' softmax normalizers and return wrong branch gradients.
    o1, l1 = _mixed_branch_fwd(q, k, v, arrays_a, params_a)
    o2, l2 = _mixed_branch_fwd(q, k, v, arrays_b, params_b)
    return _merge_out_lse(o1, l1, o2, l2)


def _ffa_mixed_fwd(q, k, v, arrays_a, arrays_b, params_a, params_b):
    out, lse = _ffa_mixed(q, k, v, arrays_a, arrays_b, params_a, params_b)
    return (out, lse), (q, k, v, out, lse, arrays_a, arrays_b)


def _ffa_mixed_bwd(params_a: FFAParams, params_b: FFAParams, res, cts):
    # Each branch kernel receives the MERGED lse/delta: p = exp(s - lse)
    # then is the GLOBAL softmax probability of every entry the branch's
    # slices cover, and since the branches partition the mask the summed
    # branch gradients equal the single-pass gradients exactly. The lse
    # cotangent is ignored (same contract as _ffa_core_bwd).
    do, _ = cts
    q, k, v, out, lse, arrays_a, arrays_b = res
    sq, sk = q.shape[0], k.shape[0]
    do = do.astype(q.dtype)
    # delta via the shared Pallas delta kernel, computed ONCE on branch
    # a's padded geometry and sliced back to seq-major — both branches
    # consume the same merged delta, and padded do rows are zero so their
    # delta is exactly 0 (matching the old zero padding per branch)
    sqp_a = params_a.num_q_tiles * params_a.block_q
    out_h = jnp.pad(out, ((0, sqp_a - sq), (0, 0), (0, 0))).transpose(1, 0, 2)
    do_h = jnp.pad(do, ((0, sqp_a - sq), (0, 0), (0, 0))).transpose(1, 0, 2)
    delta = ffa_delta_pallas_dispatch(params_a, out_h, do_h).T[:sq]  # (sq, hq)

    def branch(arrays, params: FFAParams):
        sqp = params.num_q_tiles * params.block_q
        skp = params.num_k_tiles * params.block_k
        q_t = jnp.pad(q, ((0, sqp - sq), (0, 0), (0, 0))).transpose(1, 0, 2)
        k_t = jnp.pad(k, ((0, skp - sk), (0, 0), (0, 0))).transpose(1, 0, 2)
        v_t = jnp.pad(v, ((0, skp - sk), (0, 0), (0, 0))).transpose(1, 0, 2)
        kc, vc = k_t.astype(q_t.dtype), v_t.astype(q_t.dtype)
        do_t = jnp.pad(do, ((0, sqp - sq), (0, 0), (0, 0))).transpose(1, 0, 2)
        # padded q rows are uncovered: pad the merged lse with -inf (the
        # dispatch clamps it to MASK_VALUE, making p exactly 0 there) —
        # padding with 0 would fabricate probabilities exp(s - 0)
        lse_t = jnp.pad(
            lse, ((0, sqp - sq), (0, 0)), constant_values=NEG_INF
        ).T
        delta_t = jnp.pad(delta, ((0, sqp - sq), (0, 0))).T
        dq_arrays, dkv_arrays = _bwd_plan_slices(arrays)
        dq_t, dk_t, dv_t = ffa_bwd_pallas_dispatch(
            params, dq_arrays, dkv_arrays, q_t, kc, vc, do_t, lse_t,
            delta_t,
        )
        return (
            dq_t.transpose(1, 0, 2)[:sq],
            dk_t.transpose(1, 0, 2)[:sk],
            dv_t.transpose(1, 0, 2)[:sk],
        )

    dq1, dk1, dv1 = branch(arrays_a, params_a)
    dq2, dk2, dv2 = branch(arrays_b, params_b)
    return (
        (dq1 + dq2).astype(q.dtype),
        (dk1 + dk2).astype(k.dtype),
        (dv1 + dv2).astype(v.dtype),
        tuple(None for _ in arrays_a),
        tuple(None for _ in arrays_b),
    )


_ffa_mixed.defvjp(_ffa_mixed_fwd, _ffa_mixed_bwd)


def _mixed_params(
    plan: FFAPlan, softmax_scale: float, softcap: float, group: int
) -> FFAParams:
    """Branch params for the mixed dispatch: plain 6-array plans, no bwd
    overrides, no max-logits (the dispatch gate excludes that path)."""
    return FFAParams(
        num_work=plan.num_work,
        num_work_t=plan.num_work_t,
        num_q_tiles=plan.num_q_tiles,
        num_k_tiles=plan.num_k_tiles,
        block_q=plan.block_q,
        block_k=plan.block_k,
        softmax_scale=softmax_scale,
        softcap=softcap,
        group=group,
        interpret=_should_interpret(),
        min_revisit_distance=plan.min_revisit_distance,
    )


def ffa_attn(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_ranges,
    k_ranges,
    attn_type_map=None,
    softmax_scale: float | None = None,
    softcap: float = 0.0,
    block_q: int | None = None,
    block_k: int | None = None,
    d_lo=None,
    d_hi=None,
    return_max_logits: bool = False,
):
    """Pallas FFA over slice metadata. Same contract as sdpa_attn.

    Slices may be given as mask types (``attn_type_map``) or directly as
    diagonal bands (``d_lo``/``d_hi``). The metadata must be *concrete*
    (host) values — it parameterizes the kernel grid. Inside jit-traced code,
    close over it (the runtime manager caches traced plans per mask,
    mirroring the reference's runtime LRU), or use :func:`ffa_attn_with_plan`.
    """
    try:
        qr = np.asarray(q_ranges, dtype=np.int32)
        kr = np.asarray(k_ranges, dtype=np.int32)
        if d_lo is None or d_hi is None:
            tm = (
                np.zeros(len(qr), dtype=np.int32)
                if attn_type_map is None
                else np.asarray(attn_type_map, dtype=np.int32)
            )
            d_lo, d_hi = types_to_bands(qr, kr, tm)
        else:
            d_lo = np.asarray(d_lo, dtype=np.int32)
            d_hi = np.asarray(d_hi, dtype=np.int32)
    except Exception as e:  # pragma: no cover
        raise ValueError(
            "ffa_attn requires concrete (host) slice metadata; inside jit, "
            "close over the metadata or use ffa_attn_with_plan"
        ) from e

    sq, hq, d = q.shape
    sk, hk, dv = v.shape
    if softmax_scale is None:
        softmax_scale = float(d) ** -0.5
    if (
        not return_max_logits
        and block_q is None
        and block_k is None
        and not _registry_mod().tiles_pinned()
    ):
        # mixed-granularity dispatch: when the cost model (or a 'mixed' pin,
        # MAGI_ATTENTION_BACKEND_MIXED_BLOCKS) says a coarse/fine split wins,
        # run two plans and merge — only reachable when blocks are not
        # pinned (explicit settings always win) and max-logits is off (the
        # merge does not combine per-head maxima)
        from .tile_policy import choose_mixed_dispatch

        mix = choose_mixed_dispatch(
            qr, kr, d_lo, d_hi, sq, sk, d, dv,
            itemsize=q.dtype.itemsize,
            coarse_blocks=default_blocks(sq, sk),
        )
        if mix is not None:
            di, fi = mix.dense_idx, mix.frag_idx
            plan_a = get_ffa_plan(
                qr[di], kr[di], d_lo[di], d_hi[di], sq, sk,
                *mix.coarse_blocks,
            )
            plan_b = get_ffa_plan(
                qr[fi], kr[fi], d_lo[fi], d_hi[fi], sq, sk,
                *mix.fine_blocks,
            )
            return _ffa_mixed(
                q, k, v, plan_arrays(plan_a), plan_arrays(plan_b),
                _mixed_params(
                    plan_a, float(softmax_scale), float(softcap), hq // hk
                ),
                _mixed_params(
                    plan_b, float(softmax_scale), float(softcap), hq // hk
                ),
            )
    policy_dq = policy_dkv = None
    explicit = block_q is not None or block_k is not None
    auto_tile = False
    if not explicit and not _registry_mod().tiles_pinned():
        from .tile_policy import auto_tile_enabled, choose_blocks_per_pass

        auto_tile = auto_tile_enabled()
        if auto_tile:
            # plan-geometry-driven, per-PASS tile choice (ref tile tables
            # analogue): fwd/dq score the q-major plan, dkv the k-major one,
            # and thin bands get their own block_k candidates; explicit
            # env/arg settings always take precedence
            (block_q, block_k), policy_dq, policy_dkv = (
                choose_blocks_per_pass(
                    qr, kr, d_lo, d_hi, sq, sk, d, dv,
                    itemsize=q.dtype.itemsize,
                )
            )
    source = _registry_mod().tiles_source(explicit, auto_tile)
    bq, bk = default_blocks(sq, sk, block_q, block_k)
    if source == "default":
        # nothing chose the tile: block_q follows the group, so that a
        # packed grid step is as many rows at g = 8 or 16 as at g = 4
        from .tile_policy import group_block_q, max_ffa_work

        bq, source = group_block_q(
            hq // hk, d, dv, q.dtype.itemsize, bq, bk,
            partial(max_ffa_work, qr, kr, d_lo, d_hi, sq, sk),
            emit_max_logits=return_max_logits)

    plan = get_ffa_plan(qr, kr, d_lo, d_hi, sq, sk, bq, bk)
    arrays = plan_arrays(plan)
    arrays, overrides = apply_bwd_overrides(
        arrays, qr, kr, d_lo, d_hi, sq, sk, bq, bk,
        plan.num_q_tiles, plan.num_k_tiles,
        policy_dq=policy_dq, policy_dkv=policy_dkv,
    )

    params = FFAParams(
        num_work=plan.num_work,
        num_work_t=plan.num_work_t,
        num_q_tiles=plan.num_q_tiles,
        num_k_tiles=plan.num_k_tiles,
        block_q=bq,
        block_k=bk,
        softmax_scale=float(softmax_scale),
        softcap=float(softcap),
        group=hq // hk,
        interpret=_should_interpret(),
        emit_max_logits=return_max_logits,
        **overrides,
    )
    note_tiles(params, d, dv, q.dtype.itemsize, source)
    return ffa_attn_with_plan(
        q, k, v, arrays, params, return_max_logits=return_max_logits
    )
