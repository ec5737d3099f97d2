"""Memory budget calculators (ref: magi_attention/utils/mem_budget.py:126-215).

The reference budgets FFA workspace HBM; on TPU the scarce resource is VMEM
(~16 MB/core): the fwd kernel keeps one q tile, one k tile, one v tile, the
out tile, and the fp32 accumulators resident. These helpers size tiles and
bound the maximum merged-buffer seqlen for a given budget.
"""

from __future__ import annotations

# Per-core VMEM on current TPU generations (v4/v5e/v5p: 16 MiB), and the
# margin left for Mosaic's own spills/semaphores/metadata. Every layer that
# bounds kernel residency — the tile policy's candidate filter, the packed-
# kernel dispatch guards in kernels/ffa.py, verifier rule R5 and the static
# kernel checker's K1 — derives its limit from THESE constants, so the
# budget model cannot diverge between plan-time and kernel-time checks.
VMEM_LIMIT_BYTES = 16 * 1024 * 1024
VMEM_HEADROOM_BYTES = 2 * 1024 * 1024
VMEM_ALLOWED_BYTES = VMEM_LIMIT_BYTES - VMEM_HEADROOM_BYTES


def ffa_vmem_budget(
    block_q: int,
    block_k: int,
    head_dim: int,
    head_dim_v: int | None = None,
    dtype_bytes: int = 2,
) -> int:
    """Approximate fwd-kernel VMEM residency in bytes (per grid step, double
    buffered by the pipeline)."""
    dv = head_dim_v or head_dim
    q = block_q * head_dim * dtype_bytes
    k = block_k * head_dim * dtype_bytes
    v = block_k * dv * dtype_bytes
    out = block_q * dv * dtype_bytes
    acc = block_q * dv * 4
    ml = 2 * block_q * 128 * 4
    s = block_q * block_k * 4  # logits tile (fp32)
    return 2 * (q + k + v + out) + acc + ml + s


def ffa_bwd_vmem_budget(
    kind: str,
    block_q: int,
    block_k: int,
    head_dim: int,
    head_dim_v: int | None = None,
    dtype_bytes: int = 2,
) -> int:
    """Approximate bwd-kernel VMEM residency in bytes for one grid step:
    the fwd residency plus the pass's fp32 accumulator scratch and the
    recomputed score tile ((bq, bk) for dq, transposed — same size — for
    dkv). ``kind`` is "dq" or "dkv"."""
    if kind not in ("dq", "dkv"):
        raise ValueError(f"kind must be 'dq' or 'dkv', got {kind!r}")
    dv = head_dim_v or head_dim
    scratch = block_q * head_dim if kind == "dq" else block_k * (head_dim + dv)
    return (
        ffa_vmem_budget(block_q, block_k, head_dim, dv, dtype_bytes)
        + 4 * (scratch + block_q * block_k)
    )


def ffa_kernel_residency(
    kind: str,
    block_q: int,
    block_k: int,
    head_dim: int,
    head_dim_v: int | None = None,
    dtype_bytes: int = 2,
    group: int = 1,
    packed: bool = False,
    emit_ml: bool = False,
    include_intermediates: bool = True,
) -> int:
    """EXACT declared VMEM residency of one FFA kernel grid step, in bytes.

    Mirrors the BlockSpec/scratch shapes in ``kernels/ffa.py`` closed-form:
    every in/out block is double-buffered by the Pallas pipeline, scratch is
    single-buffered, and (when ``include_intermediates``) the fp32 score-
    sized value tiles Mosaic must materialize are added (one (rows, bk) tile
    for fwd — p reuses s's storage — and two for the bwd passes: s + dp).
    The static kernel checker (analysis/kernel_check, rule K1) asserts this
    function matches the captured pallas_call contracts bit-for-bit, so the
    dispatch guards below it cannot drift from the real kernels.

    ``packed`` selects the GQA-packed variant (query rows x ``group``);
    unpacked kernels are per-q-head, so ``group`` is ignored for them
    except dkv's lse/delta sublane layout which is group-independent.
    """
    if kind not in (
        "fwd", "dq", "dkv", "fused", "delta", "decode", "decode_spec",
        "decode_int8", "bsp_fwd", "bsp_bwd",
    ):
        raise ValueError(
            f"kind must be 'fwd'|'dq'|'dkv'|'fused'|'delta'|'decode'|"
            f"'decode_spec'|'decode_int8'|'bsp_fwd'|'bsp_bwd', got {kind!r}"
        )
    dv = head_dim_v or head_dim
    g = group if packed else 1
    bq, bk, d = block_q, block_k, head_dim
    f32 = 4

    k_in = bk * d * dtype_bytes
    v_in = bk * dv * dtype_bytes
    q_in = g * bq * d * dtype_bytes
    if kind == "fwd":
        blocks = q_in + k_in + v_in
        blocks += g * bq * dv * dtype_bytes  # out
        blocks += g * bq * 128 * f32  # lse (lanes-broadcast)
        if emit_ml and not packed:
            blocks += bq * 128 * f32  # max-logits (fwd unpacked only)
        scratch = (2 * g * bq * 128 + g * bq * dv) * f32  # m, l, acc
        inter = g * bq * bk * f32  # s (p reuses its storage)
    elif kind == "dq":
        blocks = q_in + k_in + v_in
        blocks += g * bq * dv * dtype_bytes  # do
        blocks += 2 * (g if packed else 1) * bq * f32  # lse + delta rows
        blocks += g * bq * d * f32  # dq out (fp32)
        scratch = g * bq * d * f32
        inter = 2 * g * bq * bk * f32  # s + dp
    elif kind == "dkv":
        blocks = q_in + k_in + v_in
        blocks += g * bq * dv * dtype_bytes  # do
        # lse/delta: packed rides (1, g*bq) rows; unpacked an (8, bq) slab
        blocks += 2 * (g * bq if packed else 8 * bq) * f32
        blocks += (bk * d + bk * dv) * f32  # dk + dv outs (fp32)
        scratch = (bk * d + bk * dv) * f32
        inter = 2 * g * bq * bk * f32  # s_t + dp_t
    elif kind == "fused":
        # one-pass backward: the dkv residency PLUS the dq output window
        # and the aliased dq operand a later visit reads its partial sum
        # back from (same block, same index map; both fp32, both declared
        # BlockSpecs so both pipeline-double-buffered)
        blocks = q_in + k_in + v_in
        blocks += g * bq * dv * dtype_bytes  # do
        blocks += 2 * (g * bq if packed else 8 * bq) * f32  # lse + delta
        blocks += (bk * d + bk * dv) * f32  # dk + dv outs (fp32)
        blocks += 2 * g * bq * d * f32  # dq out + aliased dq in (fp32)
        scratch = (bk * d + bk * dv) * f32
        inter = 2 * g * bq * bk * f32  # s_t + dp_t
    elif kind == "delta":
        # stateless rowsum(dO ⊙ O) map kernel: o + do blocks in, one
        # lanes-broadcast fp32 block out, no scratch; group-independent
        blocks = 2 * bq * dv * dtype_bytes  # o + do
        blocks += bq * 128 * f32  # delta (lanes-broadcast)
        scratch = 0
        inter = bq * dv * f32  # fp32 elementwise product
    elif kind in ("decode", "decode_spec", "bsp_fwd"):
        # decode (kernels/paged_decode.py): bq = GQA group rows of one kv
        # head, bk = page_size. decode_spec (the speculative-verify
        # variant): identical shape with bq = spec_k * group rows — the
        # draft window rides the q tile. bsp_fwd (kernels/block_sparse.py):
        # bq = block_size_q * group rows of one q block, bk = d_stride
        # chunk rows. Identical residency shape: q tile, one streamed k/v
        # chunk, out + lanes-broadcast lse, m/l/acc scratch
        # (group/packed/emit_ml are ignored).
        blocks = bq * d * dtype_bytes  # q group tile
        blocks += bk * d * dtype_bytes + bk * dv * dtype_bytes  # one k/v page
        blocks += bq * dv * dtype_bytes  # out
        blocks += bq * 128 * f32  # lse (lanes-broadcast)
        scratch = (2 * bq * 128 + bq * dv) * f32  # m, l, acc
        inter = bq * bk * f32  # s (p reuses its storage)
    elif kind == "decode_int8":
        # int8-KV decode (kernels/paged_decode.py): k/v pages are int8
        # codes (1 byte/elem regardless of the compute dtype), each riding
        # a (1, 1) f32 per-(page, head) scale block on the same page-table
        # prefetch; q/out stay at the compute dtype. Dequant is in-kernel,
        # so scratch/intermediates match the base decode shape.
        blocks = bq * d * dtype_bytes  # q group tile
        blocks += bk * d + bk * dv  # one int8 k/v page (1 byte/elem)
        blocks += 2 * f32  # k + v per-page scale blocks
        blocks += bq * dv * dtype_bytes  # out
        blocks += bq * 128 * f32  # lse (lanes-broadcast)
        scratch = (2 * bq * 128 + bq * dv) * f32  # m, l, acc
        inter = bq * bk * f32  # s (p reuses its storage)
    else:  # bsp_bwd (kernels/block_sparse.py fused backward): q/do tiles,
        # one streamed k/v chunk, lanes-broadcast lse + delta, fp32 dq out
        # plus revisit-accumulated dk/dv output windows with their aliased
        # zero-background input blocks, dq fp32 scratch
        blocks = bq * d * dtype_bytes  # q tile
        blocks += bk * d * dtype_bytes + bk * dv * dtype_bytes  # k/v chunk
        blocks += bq * dv * dtype_bytes  # do
        blocks += 2 * bq * 128 * f32  # lse + delta (lanes-broadcast)
        blocks += bq * d * f32  # dq out (fp32)
        blocks += 2 * (bk * d + bk * dv) * f32  # dk/dv outs + dkz/dvz ins
        scratch = bq * d * f32  # dq accumulator
        inter = 2 * bq * bk * f32  # s + dp
    total = 2 * blocks + scratch
    if include_intermediates:
        total += inter
    return total


def ffa_max_total_seqlen(
    vmem_bytes: int,
    block_q: int,
    block_k: int,
    head_dim: int,
    dtype_bytes: int = 2,
) -> int:
    """Upper bound on the merged kv length whose *index metadata* fits the
    scalar-prefetch budget (the payload streams from HBM, so the real bound
    is plan size, not seqlen)."""
    per_item = 15 * 4 + 2 * 4  # meta row (9 band + 4 extent + 2 q-visit cols) + two work indices
    max_items = max(1, vmem_bytes // (8 * per_item))
    return max_items * block_k
