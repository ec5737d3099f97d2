"""The plain reference of the ``mixer`` family's block: ``jax.numpy`` in
float32, nothing of the program. It builds its mask from the documents'
boundaries (``spec.cu_seqlens``) and the window, not from an array handed
to it."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CHECKS = {
    "loss": {"kind": "abs_per_sqrt_targets", "tol": 1e-3,
             "why": "float32 on both sides"},
    "logits": {"kind": "rel_frobenius", "tol": 1e-3,
               "why": "float32 on both sides"},
    "grad_gate0": {"kind": "rel_frobenius", "tol": 1e-3,
                   "why": "through every block above, the attention too"},
    "grad_value": {"kind": "rel_frobenius", "tol": 1e-3,
                   "why": "through the softmax's values"},
}


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mask(spec):
    row = np.arange(spec.tokens)
    doc = np.searchsorted(np.asarray(spec.cu_seqlens[1:]), row, side="right")
    back = row[:, None] - row[None, :]
    mask = (doc[:, None] == doc[None, :]) & (back >= 0)
    return mask if spec.window is None else mask & (back < spec.window)


def _loss(params, cfg, tokens, labels, mask):
    heads, dh, eps = (
        cfg["num_attention_heads"], cfg["head_dim"], cfg["rms_norm_eps"])
    x = params["embed"][tokens]
    for kind, blk in zip(cfg["layer_types"], params["blocks"]):
        h = _norm(x, blk["norm"], eps)
        if kind == "attention":
            q, k, v = (
                (h @ blk[w]).reshape(-1, heads, dh).transpose(1, 0, 2)
                for w in ("wq", "wk", "wv"))
            s = jnp.where(
                mask, jnp.einsum("hqd,hkd->hqk", q, k) * dh ** -0.5, -jnp.inf)
            out = jnp.einsum("hqk,hkd->qhd", jax.nn.softmax(s, axis=-1), v)
            x = x + out.reshape(-1, heads * dh) @ blk["wo"]
        else:
            x = x + (jax.nn.silu(h @ blk["w_gate"]) * (h @ blk["w_in"])) @ (
                blk["w_out"])
    logits = x @ params["head"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    valid = labels >= 0
    return jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(
        valid.sum(), 1), logits


def reference(params: dict, cfg: dict, tokens, labels, spec) -> dict:
    attn = cfg["layer_types"].index("attention")
    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.value_and_grad(_loss, has_aux=True)(
            params, cfg, tokens, labels, _mask(spec))
    return {"loss": loss, "logits": logits,
            "grad_gate0": grads["blocks"][0]["w_gate"],
            "grad_value": grads["blocks"][attn]["wv"]}
