"""Pallas FFA kernel tuning flags (ref: magi_attention/env/ffa.py)."""

from __future__ import annotations

from .general import _get_int, _get_str


def ffa_block_q() -> int:
    """Q tile rows per grid step (multiple of 8 for fp32 / 16 for bf16).

    One tile for every pass unless a pass has its own (FFA_BLOCK_*_DQ /
    _DKV below). 256 stays the default since the v5e A/B of PR 25
    (PERF.md): the GQA-packed bodies carry g x 256 rows a step at it and
    won every cell at g = 4, d = 128 — forward 287 ms a step of the dense
    cell at 256 plain, 233 at 512 plain, 201 at 256 packed — and 512 rows
    under the packed dkv is refused by the chip's compiler (16.68 of 16
    MiB of VMEM). The key still matters where nothing packs (g = 1,
    max-logits, a pack flag at 0): 512 there was faster at g = 4 and is
    not measured at g = 1."""
    return _get_int("MAGI_ATTENTION_FFA_BLOCK_Q", 256)


def ffa_block_k() -> int:
    """K tile rows per grid step (multiple of 128)."""
    return _get_int("MAGI_ATTENTION_FFA_BLOCK_K", 512)


def ffa_block_q_dq() -> int:
    """Q tile rows for the dq backward kernel; 0 = inherit FFA_BLOCK_Q.
    (TPU analogue of the reference's FFA BWD tuning flags,
    docs/source/user_guide/env_variables.md:111.) Must divide the fwd-padded
    seqlen; incompatible values silently inherit."""
    return _get_int("MAGI_ATTENTION_FFA_BLOCK_Q_DQ", 0)


def ffa_block_k_dq() -> int:
    """K tile rows for the dq backward kernel; 0 = inherit FFA_BLOCK_K."""
    return _get_int("MAGI_ATTENTION_FFA_BLOCK_K_DQ", 0)


def ffa_block_q_dkv() -> int:
    """Q tile rows for the dk/dv backward kernel; 0 = inherit FFA_BLOCK_Q."""
    return _get_int("MAGI_ATTENTION_FFA_BLOCK_Q_DKV", 0)


def ffa_block_k_dkv() -> int:
    """K tile rows for the dk/dv backward kernel; 0 = inherit FFA_BLOCK_K.
    The dkv kernel holds (bk, d)+(bk, dv) fp32 scratch, so smaller bk eases
    VMEM pressure at large head_dim."""
    return _get_int("MAGI_ATTENTION_FFA_BLOCK_K_DKV", 0)


def ffa_blocks_pinned() -> bool:
    """True when the operator pinned the fwd tile sizes via env — explicit
    settings always beat MAGI_ATTENTION_FFA_AUTO_TILE (key ownership lives
    HERE; callers must not hardcode these names)."""
    import os

    return (
        "MAGI_ATTENTION_FFA_BLOCK_Q" in os.environ
        or "MAGI_ATTENTION_FFA_BLOCK_K" in os.environ
    )


def ffa_pass_blocks_pinned() -> bool:
    """True when a backward pass's own tile is set via env
    (FFA_BLOCK_{Q,K}_{DQ,DKV}); 0 inherits, so it pins nothing."""
    return any((ffa_block_q_dq(), ffa_block_k_dq(),
                ffa_block_q_dkv(), ffa_block_k_dkv()))


def ffa_native_plan() -> str:
    """Native (C) FFA work-list builder: 'auto' (use when the native lib
    builds; silently fall back), '1' (require), '0' (pure Python). Unlike
    MAGI_ATTENTION_CPP_BACKEND (off by default — the range-object FFI churn
    loses there), the plan builder is pure array marshalling and wins
    outright, so auto is the default."""
    return _get_str("MAGI_ATTENTION_NATIVE_FFA_PLAN", "auto").lower()


def ffa_extent_clamp() -> bool:
    """Clamp the FFA kernels' dot_general / accumulator updates to each
    work item's live extent (the EQ0..EK1 meta columns the plan builder
    derives from the band geometry): partially-filled tiles split their
    lane dimension into chunks and skip the chunks the band never touches,
    so a 10%-live tile costs ~10% instead of 100%. ON by default; the
    legacy single-dot bodies are bit-preserved under 0."""
    return _get_int("MAGI_ATTENTION_FFA_EXTENT_CLAMP", 1) == 1


def ffa_gqa_pack_dq() -> bool:
    """GQA-pack the dq backward kernel (grid (hk, W)): k/v fetched once
    per work item instead of per q-head, s/dp matmuls g x taller,
    lse/delta tile-packed on the host. ON by default since the v5e A/B of
    PR 25 (PERF.md): at g = 4 it beat the plain body at 256 rows (198 ->
    158 ms a step of the dense cell) and at 512 rows (168) in every cell.
    VMEM-guarded like the fwd pack (kernels/ffa.gqa_pack_fits). 0 brings
    the plain body back, at the same tiles."""
    return _get_int("MAGI_ATTENTION_FFA_GQA_PACK_DQ", 1) == 1


def ffa_gqa_pack_dkv() -> bool:
    """GQA-pack the dk/dv backward kernel (grid (hk, WT) instead of
    (hk, WT, g)): the g query heads of a kv head are packed into the
    sublane dimension of ONE MXU contraction per work item, so q/do are
    fetched once per work item instead of per group member and the
    s_t/dp_t/dk/dv matmuls run g x longer. ON by default — the unpacked
    path loops the group innermost and starves the MXU (77 vs 138 TF/s on
    r5 silicon); VMEM-guarded, falls back automatically when the packed
    tiles would not fit or shapes do not divide."""
    return _get_int("MAGI_ATTENTION_FFA_GQA_PACK_DKV", 1) == 1


def ffa_gqa_pack() -> bool:
    """Pack the whole GQA query group of one kv head into each fwd grid
    step (grid (hk, W) instead of (hq, W)): k/v HBM traffic drops by the
    group factor and per-step bookkeeping amortizes over a taller MXU op.
    ON by default since the v5e A/B of PR 25 (PERF.md): at g = 4 it beat
    the plain body at 256 rows (287 -> 201 ms a step of the dense cell)
    and at 512 rows (233) in every cell. Ignored when max-logits output is
    requested or the chip's compiler would refuse the packed step
    (kernels/ffa.gqa_pack_fits: over 1024 packed rows, or VMEM); 0 brings
    the plain body back, at the same tiles."""
    return _get_int("MAGI_ATTENTION_FFA_GQA_PACK", 1) == 1
