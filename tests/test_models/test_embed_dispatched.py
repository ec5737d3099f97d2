"""The way into the models: token ids are dispatched, then embedded.

``llama.embed_dispatched`` (shared by ``models/llama.py`` and
``models/moe.py``) looks up the embedding of the *dispatched* ids, so nothing
``dim`` wide exists before the sequence is cut to a chip's share. The order it
replaced, kept here as ``_embed_then_dispatch``, gives the same rows bit for
bit but at cp > 1 builds all ``total_seqlen`` rows on every chip from the
chip's vocabulary shard and all-reduces them every step. Toy widths on the
virtual mesh; the tokens repeat (S > vocabulary), so the table's scatter-add
has rows to add. Tier-1: ``test_llama.py`` and ``test_moe.py`` are slow as a
whole.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from magiattention_tpu.api import dispatch, magi_attn_flex_key
from magiattention_tpu.models import LlamaConfig, MoEConfig, llama, moe

S, DIM, VOCAB = 256, 48, 64
WIDTHS = dict(
    vocab_size=VOCAB, dim=DIM, n_layers=1, n_heads=2, n_kv_heads=1,
    head_dim=16, ffn_hidden=64, dtype="bfloat16", remat=True,
)


@dataclasses.dataclass(frozen=True)
class Family:
    """One model family's entry points under the names the tests use."""

    module: object  # where ``embed_dispatched`` and ``attn_block`` are bound
    cfg: LlamaConfig
    init: object
    shard: object  # (params, mesh) -> ZeRO-sharded params
    logits: object  # (params, cfg, tokens, key) -> dispatched logits
    loss: object  # (params, cfg, tokens, labels, key) -> scalar
    lower_step: object  # the family's jitted train step, lowered


def _llama(cp: int) -> Family:  # the same at every cp
    return Family(
        llama, LlamaConfig(**WIDTHS), llama.init_params, llama.shard_params,
        llama.forward, llama.loss_fn, llama.train_step.lower)


def _moe(cp: int) -> Family:
    # expert-parallel over cp where there is more than one chip: replicated,
    # the FFN's routing sees all S tokens, which is the FFN's own [S, dim]
    ep = "cp" if cp > 1 else None
    return Family(
        moe, MoEConfig(**WIDTHS, n_experts=4, top_k=2), moe.init_moe_params,
        lambda p, mesh: moe.shard_moe_params(p, mesh, ep_axis=ep),
        lambda *a: moe.moe_forward(*a, ep_axis=ep)[0],
        lambda *a: moe.moe_loss_fn(*a, ep_axis=ep),
        lambda *a: moe.moe_train_step.lower(*a, ep))


FAMILIES = {"llama": _llama, "moe": _moe}
# keep every bf16 rounding the program asks for: by default the CPU compiler
# drops f32 -> bf16 -> f32 round trips where its fusions let it, and the
# two orders then differ in which cotangent rows were rounded
EXACT_BF16 = {"xla_allow_excess_precision": False}
EACH = pytest.mark.parametrize("family", sorted(FAMILIES))
EACH_CP = pytest.mark.parametrize("cp", [1, 4])


def _embed_then_dispatch(embed, tokens, attn_key, dtype):
    """The order ``embed_dispatched`` replaced."""
    return dispatch(jnp.take(embed, tokens, axis=0).astype(dtype), attn_key)


def _cell(family: str, cp: int):
    fam = FAMILIES[family](cp)
    mesh = Mesh(np.array(jax.devices("cpu")[:cp]), axis_names=("cp",))
    key = magi_attn_flex_key(
        [[0, S // 2], [S // 2, S]], [[0, S // 2], [S // 2, S]], [1, 1],
        S, S, mesh=mesh, chunk_size=16)
    params = fam.shard(fam.init(fam.cfg, jax.random.key(0)), mesh)
    tokens = np.random.default_rng(cp).integers(0, VOCAB, S).astype(np.int32)
    labels = np.concatenate([tokens[1:], [-1]]).astype(np.int32)
    return fam, key, params, jnp.asarray(tokens), jnp.asarray(labels)


def _old_order(monkeypatch, fam: Family) -> None:
    monkeypatch.setattr(fam.module, "embed_dispatched", _embed_then_dispatch)


@EACH_CP
@EACH
def test_layer0_is_fed_the_dispatched_embedding(monkeypatch, family, cp):
    fam, key, params, tokens, _ = _cell(family, cp)
    fed = []

    class Stop(Exception):
        pass

    def first_layer_only(x, *_):
        fed.append(x)
        raise Stop

    monkeypatch.setattr(fam.module, "attn_block", first_layer_only)
    with pytest.raises(Stop):
        fam.logits(
            params, dataclasses.replace(fam.cfg, remat=False), tokens, key)
    want = _embed_then_dispatch(
        params["embed"], tokens, key, fam.cfg.jdtype)
    assert fed[0].dtype == want.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(fed[0], np.float32), np.asarray(want, np.float32))


@EACH_CP
@EACH
def test_logits_and_table_gradient_equal_the_old_order(
        monkeypatch, family, cp):
    fam, key, params, tokens, labels = _cell(family, cp)

    def run():
        # fresh functions each time: a jitted one would keep the trace it
        # made under the other order
        logits, grad = (
            jax.jit(f).lower(params).compile(compiler_options=EXACT_BF16)
            for f in (
                lambda p: fam.logits(p, fam.cfg, tokens, key),
                jax.grad(lambda p: fam.loss(p, fam.cfg, tokens, labels, key)),
            ))
        return np.asarray(logits(params)), np.asarray(grad(params)["embed"])

    logits, g_embed = run()
    _old_order(monkeypatch, fam)
    logits_old, g_embed_old = run()
    np.testing.assert_array_equal(logits, logits_old)
    assert np.abs(g_embed_old).max() > 0
    # the same float32 sum of the same bf16-born rows, in another order
    np.testing.assert_allclose(g_embed, g_embed_old, rtol=1e-5, atol=1e-7)


def _whole_sequence_lines(hlo: str) -> list[str]:
    """The instructions of a per-chip program whose shape is
    ``[total_seqlen, dim]`` (or the ``[S, 1, dim]`` a gather is born as)."""
    shape = re.compile(rf"\[{S},(1,)?{DIM}\]")
    return [line.strip() for line in hlo.splitlines() if shape.search(line)]


@EACH
def test_cp4_step_has_nothing_total_seqlen_by_dim(monkeypatch, family):
    fam, key, params, tokens, labels = _cell(family, 4)
    hlo = fam.lower_step(
        params, fam.cfg, tokens, labels, key).compile().as_text()
    assert "all-gather" in hlo  # a partitioned program, ZeRO's gathers in it
    assert _whole_sequence_lines(hlo) == []

    # the same assertion fails on the old order: the test sees what it guards
    _old_order(monkeypatch, fam)

    def old_step(params, tokens, labels):  # the step's body, traced afresh
        loss, grads = jax.value_and_grad(fam.loss)(
            params, fam.cfg, tokens, labels, key)
        return jax.tree.map(
            lambda p, g: p - 1e-4 * g.astype(p.dtype), params, grads), loss

    old = _whole_sequence_lines(
        jax.jit(old_step, donate_argnums=0).lower(
            params, tokens, labels).compile().as_text())
    assert old
    assert any(" all-reduce(" in line for line in old), old
