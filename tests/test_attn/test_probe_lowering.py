"""Mosaic lowering gate for the EXACT silicon-queue probe bodies.

A chip window is minutes long; a probe body that fails to compile wastes
it entirely. These tests cross-platform-lower (CPU host -> TPU target)
the same (shape, tiling, flag) combinations the queue scripts run —
the seq-8192 headline FFA fwd and fwd+bwd bodies, the GQA-pack variants,
the vmapped-MQA splash body, and the paged-decode body — so a probe that
would die in the window dies here first. Same mechanism and limits as
test_mosaic_lowering.py (everything up to serialized Mosaic emission;
the Mosaic->LLO compile still needs libtpu).
"""

from __future__ import annotations

import pytest

pytestmark = pytest.mark.slow  # seq-8192 traces: heavy host work

import jax
import jax.numpy as jnp
import numpy as np

from magiattention_tpu.kernels import ffa


def _lower_tpu(fn, *args):
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    return lowered.as_text()


@pytest.fixture()
def mosaic(monkeypatch):
    monkeypatch.setattr(ffa, "_should_interpret", lambda: False)


S, HQ, HK, D = 8192, 16, 8, 128  # the tpu_true_rate.py / bench.py shape


def _headline_inputs():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((S, HQ, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((S, HK, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((S, HK, D)), jnp.bfloat16)
    qr = np.array([[0, S]], np.int32)
    kr = np.array([[0, S]], np.int32)
    tm = np.array([1], np.int32)
    return q, k, v, qr, kr, tm


@pytest.mark.parametrize("bq,bk", [(512, 512), (256, 512), (512, 1024),
                                   (1024, 1024)])
def test_headline_fwd_lowers(mosaic, bq, bk):
    q, k, v, qr, kr, tm = _headline_inputs()

    def body(q):
        return ffa.ffa_attn(
            q, k, v, qr, kr, tm, block_q=bq, block_k=bk
        )[0].astype(jnp.bfloat16)

    assert "tpu_custom_call" in _lower_tpu(body, q)


def test_headline_fwdbwd_lowers(mosaic):
    q, k, v, qr, kr, tm = _headline_inputs()
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((S, HQ, D)), jnp.bfloat16)

    def loss(q, k, v):
        o, _ = ffa.ffa_attn(q, k, v, qr, kr, tm, block_q=512, block_k=512)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))

    text = _lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    # fwd + dq + dkv kernels must all be present
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("value", ["1", "0"], ids=["packed", "plain"])
@pytest.mark.parametrize("flag", ["MAGI_ATTENTION_FFA_GQA_PACK",
                                  "MAGI_ATTENTION_FFA_GQA_PACK_DQ"])
def test_gqa_pack_variants_lower(mosaic, monkeypatch, flag, value):
    """Both bodies of the q-major passes lower: the packed ones (the
    default where there is a group) and the plain ones the flags bring
    back."""
    monkeypatch.setenv(flag, value)
    q, k, v, qr, kr, tm = _headline_inputs()

    if flag.endswith("_DQ"):
        rng = np.random.default_rng(2)
        w = jnp.asarray(rng.standard_normal((S, HQ, D)), jnp.bfloat16)

        def loss(q):
            o, _ = ffa.ffa_attn(
                q, k, v, qr, kr, tm, block_q=512, block_k=512
            )
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))

        assert "tpu_custom_call" in _lower_tpu(jax.grad(loss), q)
    else:
        def body(q):
            return ffa.ffa_attn(
                q, k, v, qr, kr, tm, block_q=512, block_k=512
            )[0].astype(jnp.bfloat16)

        assert "tpu_custom_call" in _lower_tpu(body, q)


def test_splash_gqa_body_lowers():
    """The tpu_true_rate splash-GQA bar: vmapped MQA kernel at the
    headline shape must lower for TPU (jax's kernel, our composition)."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sp

    grp = HQ // HK
    mask = sp.MultiHeadMask([sp.CausalMask((S, S)) for _ in range(grp)])
    kern = jax.vmap(
        sp.splash_attention_kernel.make_splash_mqa_single_device(mask)
    )
    rng = np.random.default_rng(3)
    qg = jnp.asarray(rng.standard_normal((HK, grp, S, D)), jnp.bfloat16)
    kg = jnp.asarray(rng.standard_normal((HK, S, D)), jnp.bfloat16)
    vg = jnp.asarray(rng.standard_normal((HK, S, D)), jnp.bfloat16)

    def body(q):
        return kern(q, kg, vg).astype(jnp.bfloat16)

    assert "tpu_custom_call" in _lower_tpu(body, qg)


def test_decode_probe_body_lowers(mosaic):
    """The tpu_decode_probe paged-attention body at ctx=32768."""
    from magiattention_tpu.kernels.paged_kv import (
        PagedKVCache, append_kv, assign_pages, paged_attn,
    )

    ctx, page = 32768, 128
    n_pages = ctx // page + 2
    cache = PagedKVCache.create(
        num_pages=n_pages, page_size=page, n_kv_heads=HK, head_dim=D,
        max_seqs=1, max_pages_per_seq=n_pages, dtype=jnp.bfloat16,
    )
    cache = assign_pages(cache, 0, np.arange(n_pages, dtype=np.int32))
    rng = np.random.default_rng(4)
    k_ctx = jnp.asarray(rng.standard_normal((ctx, HK, D)), jnp.bfloat16)
    v_ctx = jnp.asarray(rng.standard_normal((ctx, HK, D)), jnp.bfloat16)
    cache = append_kv(cache, 0, k_ctx, v_ctx)
    q1 = jnp.asarray(rng.standard_normal((1, HQ, D)), jnp.bfloat16)

    def body(q):
        o, _ = paged_attn(q, cache, seq_id=0, q_start=ctx - 1,
                          max_pages=n_pages)
        return o.astype(jnp.bfloat16)

    # paged_attn may lower to pure XLA ops (no pallas); the gate is that
    # trace+lower completes for the TPU platform at the probe shape and
    # produces a non-trivial module
    text = _lower_tpu(body, q1)
    assert "func.func public @main" in text or "module" in text
