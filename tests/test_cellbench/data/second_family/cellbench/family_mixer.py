"""Step builder of the ``mixer`` family, the second family of the tests: a
pre-norm decoder whose layers are either ``attention`` (multi-head softmax
attention through the program's ``calc_attn``, no rotary embedding) or
``gated`` (a token-local gated unit, no attention call at all), in the
order the configuration's ``layer_types`` gives. Float32 throughout.

It is added to a benchmark as files alone
(``test_rehearse.py::test_a_family_is_added_as_files_alone``): this file,
``reference_mixer.py`` beside it, a configuration, an event class for the
kernel a real ``gated`` layer would bring, and a metric that reads it. What
is the program's and not the block's (the mask compilers, the plan's
counts, the kernels of a traced step) it takes from ``family_llama.py``.
"""

from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from magiattention_tpu.api import calc_attn, dispatch, undispatch

from cellbench import family_llama as program
from cellbench import flops, manifest

_reference = manifest.load_module(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "reference_mixer")
reference, CHECKS = _reference.reference, _reference.CHECKS

make_key, timed_plan = program.make_key, program.timed_plan
plan_facts, pallas_kernels = program.plan_facts, program.pallas_kernels
what_ran = program.what_ran

TOY = {
    "hidden_size": 128, "num_attention_heads": 2, "head_dim": 128,
    "intermediate_size": 256, "vocab_size": 512,
    "layer_types": ["gated", "attention", "gated"],
}


class Mixer(NamedTuple):
    dim: int
    heads: int
    head_dim: int
    ffn: int
    vocab: int
    layer_types: tuple[str, ...]
    eps: float


def model_config(cfg: dict) -> Mixer:
    return Mixer(
        cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
        cfg["intermediate_size"], cfg["vocab_size"],
        tuple(cfg["layer_types"]), cfg["rms_norm_eps"])


def _shapes(m: Mixer) -> dict:
    inner = m.heads * m.head_dim
    blocks = [
        {"norm": (m.dim,), "wq": (m.dim, inner), "wk": (m.dim, inner),
         "wv": (m.dim, inner), "wo": (inner, m.dim)}
        if kind == "attention" else
        {"norm": (m.dim,), "w_in": (m.dim, m.ffn), "w_gate": (m.dim, m.ffn),
         "w_out": (m.ffn, m.dim)}
        for kind in m.layer_types
    ]
    return {"embed": (m.vocab, m.dim), "head": (m.dim, m.vocab),
            "blocks": blocks}


def init_params(m: Mixer, mesh, seed: int) -> dict:
    """float32 leaves from ``seed`` in one jitted call, replicated."""
    shapes = _shapes(m)
    leaves, tree = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(tree, [
            jnp.ones(shape, jnp.float32) if len(shape) == 1 else
            jax.random.normal(k, shape, jnp.float32) * shape[0] ** -0.5
            for k, shape in zip(keys, leaves)])

    return jax.jit(make, out_shardings=NamedSharding(mesh, P()))(
        jax.random.PRNGKey(seed))


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def forward(params: dict, m: Mixer, tokens, key):
    """Logits in the dispatched order."""
    x = dispatch(params["embed"][tokens], key)
    for kind, blk in zip(m.layer_types, params["blocks"]):
        h = _norm(x, blk["norm"], m.eps)
        if kind == "attention":
            q, k, v = (
                (h @ blk[w]).reshape(-1, m.heads, m.head_dim)
                for w in ("wq", "wk", "wv"))
            out, _ = calc_attn(q, k, v, key)
            x = x + out.reshape(-1, m.heads * m.head_dim) @ blk["wo"]
        else:
            x = x + (jax.nn.silu(h @ blk["w_gate"]) * (h @ blk["w_in"])) @ (
                blk["w_out"])
    return x @ params["head"]


def _loss(params, m, tokens, labels, key):
    logits = forward(params, m, tokens, key)
    labels = dispatch(labels, key)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    valid = labels >= 0
    loss = jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(valid.sum(), 1)
    return loss, logits


@partial(jax.jit, static_argnums=(1, 4), donate_argnums=(0,))
def train_step(params, m: Mixer, tokens, labels, key, lr: float = 1e-3):
    (loss, _), grads = jax.value_and_grad(_loss, has_aux=True)(
        params, m, tokens, labels, key)
    return jax.tree.map(lambda p, g: p - lr * g, params, grads), loss


def check_program(m: Mixer, key):
    """jitted ``(params, tokens, labels) -> {name: value}`` for the names
    of ``CHECKS``: the gradients are of block 0's gate and of the first
    attention block's value projection."""
    attn = m.layer_types.index("attention")

    @jax.jit
    def run(params, tokens, labels):
        (loss, logits), grads = jax.value_and_grad(_loss, has_aux=True)(
            params, m, tokens, labels, key)
        return {"loss": loss, "logits": undispatch(logits, key),
                "grad_gate0": grads["blocks"][0]["w_gate"],
                "grad_value": grads["blocks"][attn]["wv"]}

    return run


def required_flops_per_step(cfg: dict, spec) -> int:
    """Required convention: the weights every layer multiplies by, and
    attention over the band in the ``attention`` layers alone."""
    dim, inner = cfg["hidden_size"], (
        cfg["num_attention_heads"] * cfg["head_dim"])
    kinds = cfg["layer_types"]
    attending = kinds.count("attention")
    weights = (
        attending * 4 * dim * inner
        + (len(kinds) - attending) * 3 * dim * cfg["intermediate_size"]
        + dim * cfg["vocab_size"])
    attn = attending * (1 + flops.ATTN_BWD_OVER_FWD) * flops.attn_fwd_flops(
        flops.band_area(spec), cfg["num_attention_heads"],
        cfg["head_dim"], cfg["head_dim"])
    return int(flops.matmul_flops(weights, spec.tokens) + attn)


def ffa_calls(cfg: dict) -> list[dict]:
    """Only the ``attention`` layers call FFA, and with no remat each
    makes two calls a step."""
    return [{
        "layers": cfg["layer_types"].count("attention"),
        "passes": ("fwd", "bwd"),
        "hq": cfg["num_attention_heads"], "hk": cfg["num_attention_heads"],
        "d_qk": cfg["head_dim"], "d_v": cfg["head_dim"],
    }]
