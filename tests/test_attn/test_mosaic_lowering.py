"""Chip-independent Mosaic lowering regression gate (VERDICT r2 item 3).

Every test here lowers the Pallas FFA kernels *for the TPU platform* from
the CPU-only test environment via JAX cross-platform lowering
(``.trace(...).lower(lowering_platforms=('tpu',))``). That runs the full
Pallas->Mosaic path — BlockSpec validation, index-map evaluation, Mosaic
MLIR generation + verification — without executing, so BlockSpec/layout
bugs (like the max-logits lse-layout bug once found only on the chip) are
caught always-on in CI.

Limit (documented per the verdict): the Mosaic->LLO *compile* inside XLA
needs libtpu, so errors raised only by the Mosaic backend compiler (e.g.
some unsupported-relayout cases) still require silicon; everything up to
serialized-Mosaic-module emission is gated here.

Ref coverage intent: tests/test_attn/test_flex_flash_attn.py's kernel grid
(dtype x head_dim x GQA x masks), compile-checked instead of executed.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.kernels import ffa


def _lower_tpu(fn, *args):
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert "tpu_custom_call" in text, "Pallas did not lower to Mosaic"
    return text


@pytest.fixture()
def mosaic(monkeypatch):
    """Force the real (non-interpret) kernel path so lowering hits Mosaic."""
    monkeypatch.setattr(ffa, "_should_interpret", lambda: False)


def _mk_inputs(s, hq, hk, d, dv, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((s, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((s, hk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((s, hk, dv)), dtype)
    return q, k, v


def _varlen_meta(s):
    bounds = [0, s // 4, (2 * s) // 3, s]
    qr = np.array(
        [[a, b] for a, b in zip(bounds[:-1], bounds[1:])], np.int32
    )
    tm = np.array([1, 0, 1], np.int32)  # mixed causal/full
    return qr, qr.copy(), tm


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("bq,bk", [(256, 512), (512, 512), (512, 1024)])
def test_fwd_lowers(mosaic, dtype, d, bq, bk):
    s, hq, hk = 2048, 4, 2
    q, k, v = _mk_inputs(s, hq, hk, d, d, dtype)
    qr, kr, tm = _varlen_meta(s)
    _lower_tpu(
        lambda q, k, v: ffa.ffa_attn(
            q, k, v, qr, kr, tm, block_q=bq, block_k=bk
        )[0],
        q, k, v,
    )


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("bq,bk", [(256, 512), (512, 1024)])
def test_bwd_lowers(mosaic, dtype, d, bq, bk):
    """Grad lowering covers both the dq and dkv kernels."""
    s, hq, hk = 2048, 4, 2
    q, k, v = _mk_inputs(s, hq, hk, d, d, dtype)
    qr, kr, tm = _varlen_meta(s)

    def loss(q, k, v):
        o, _ = ffa.ffa_attn(q, k, v, qr, kr, tm, block_q=bq, block_k=bk)
        return jnp.sum(o.astype(jnp.float32))

    text = _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    # three distinct kernels (fwd from the VJP's fwd pass + dq + dkv)
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("emit_ml", [False, True])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_variants_lower(mosaic, emit_ml, softcap):
    """max-logits output (the r2 silicon-only bug) and the softcap path."""
    s, hq, hk, d = 1024, 4, 2, 128
    q, k, v = _mk_inputs(s, hq, hk, d, d, jnp.bfloat16)
    qr, kr, tm = _varlen_meta(s)
    fn = partial(
        ffa.ffa_attn,
        q_ranges=qr, k_ranges=kr, attn_type_map=tm,
        softcap=softcap, return_max_logits=emit_ml,
    )
    _lower_tpu(lambda q, k, v: fn(q, k, v)[0], q, k, v)


def test_dv_neq_dk_lowers(mosaic):
    s, hq, hk, d, dv = 1024, 4, 2, 128, 64
    q, k, v = _mk_inputs(s, hq, hk, d, dv, jnp.bfloat16)
    qr, kr, tm = _varlen_meta(s)

    def loss(q, k, v):
        o, _ = ffa.ffa_attn(q, k, v, qr, kr, tm)
        return jnp.sum(o.astype(jnp.float32))

    _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)


def test_non_multiple_seqlen_lowers(mosaic):
    """seqlen not a multiple of the blocks: padded tiles + dummy items."""
    s = 1000
    q, k, v = _mk_inputs(s, 4, 2, 128, 128, jnp.bfloat16)
    qr = np.array([[0, s]], np.int32)
    tm = np.array([1], np.int32)
    _lower_tpu(
        lambda q, k, v: ffa.ffa_attn(q, k, v, qr, qr.copy(), tm)[0],
        q, k, v,
    )


def test_bwd_block_overrides_lower(mosaic, monkeypatch):
    """dq/dkv-specific block sizes (MAGI_ATTENTION_FFA_BLOCK_*_D{Q,KV})."""
    monkeypatch.setenv("MAGI_ATTENTION_FFA_BLOCK_Q_DQ", "128")
    monkeypatch.setenv("MAGI_ATTENTION_FFA_BLOCK_K_DQ", "256")
    monkeypatch.setenv("MAGI_ATTENTION_FFA_BLOCK_Q_DKV", "256")
    monkeypatch.setenv("MAGI_ATTENTION_FFA_BLOCK_K_DKV", "128")
    s = 2048
    q, k, v = _mk_inputs(s, 4, 2, 128, 128, jnp.bfloat16)
    qr, kr, tm = _varlen_meta(s)

    def loss(q, k, v):
        o, _ = ffa.ffa_attn(q, k, v, qr, kr, tm, block_q=256, block_k=512)
        return jnp.sum(o.astype(jnp.float32))

    text = _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert text.count("tpu_custom_call") >= 3


def test_sink_path_lowers(mosaic):
    """flex_flash_attn_func with attention sink lowers end to end."""
    from magiattention_tpu.functional.flex_flash_attn import (
        flex_flash_attn_func,
    )

    s, hq, hk, d = 1024, 4, 2, 128
    q, k, v = _mk_inputs(s, hq, hk, d, d, jnp.bfloat16)
    qr, kr, tm = _varlen_meta(s)
    sink = jnp.zeros((2, hq), jnp.float32)

    def loss(q, k, v, sink):
        o, _ = flex_flash_attn_func(
            q, k, v, qr, kr, attn_type_map=tm, sink=sink
        )
        return jnp.sum(o.astype(jnp.float32))

    _lower_tpu(jax.grad(loss, argnums=(0, 1, 2, 3)), q, k, v, sink)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("g", [2, 4])
def test_gqa_packed_fwd_lowers(mosaic, monkeypatch, dtype, g):
    """MAGI_ATTENTION_FFA_GQA_PACK=1: the packed (hk, W)-grid fwd kernel
    (rank-4 q/out blocks, iota-mod repeated mask) must lower to Mosaic."""
    monkeypatch.setenv("MAGI_ATTENTION_FFA_GQA_PACK", "1")
    s, hk, d = 2048, 2, 128
    q, k, v = _mk_inputs(s, hk * g, hk, d, d, dtype)
    qr, kr, tm = _varlen_meta(s)
    _lower_tpu(
        lambda q, k, v: ffa.ffa_attn(
            q, k, v, qr, kr, tm, block_q=512, block_k=512
        )[0],
        q, k, v,
    )


def test_gqa_packed_bwd_lowers(mosaic, monkeypatch):
    """Packed fwd composes with the (unpacked) bwd kernels under grad."""
    monkeypatch.setenv("MAGI_ATTENTION_FFA_GQA_PACK", "1")
    s, hq, hk, d = 2048, 4, 2, 128
    q, k, v = _mk_inputs(s, hq, hk, d, d, jnp.bfloat16)
    qr, kr, tm = _varlen_meta(s)

    def loss(q, k, v):
        o, _ = ffa.ffa_attn(q, k, v, qr, kr, tm, block_q=512, block_k=512)
        return jnp.sum(o.astype(jnp.float32))

    text = _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_gqa_packed_dq_lowers(mosaic, monkeypatch, dtype):
    """MAGI_ATTENTION_FFA_GQA_PACK_DQ=1: the packed (hk, W)-grid dq kernel
    (rank-4 q/do blocks, tile-packed lse/delta rows) must lower to Mosaic
    — with and without dq-specific tile overrides."""
    monkeypatch.setenv("MAGI_ATTENTION_FFA_GQA_PACK_DQ", "1")
    s, hq, hk, d = 2048, 4, 2, 128
    q, k, v = _mk_inputs(s, hq, hk, d, d, dtype)
    qr, kr, tm = _varlen_meta(s)

    def loss(q, k, v):
        o, _ = ffa.ffa_attn(q, k, v, qr, kr, tm, block_q=512, block_k=512)
        return jnp.sum(o.astype(jnp.float32))

    text = _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert text.count("tpu_custom_call") >= 3

    monkeypatch.setenv("MAGI_ATTENTION_FFA_BLOCK_Q_DQ", "256")
    monkeypatch.setenv("MAGI_ATTENTION_FFA_BLOCK_K_DQ", "1024")
    _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
