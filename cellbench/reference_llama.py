"""The plain reference of the ``llama`` family's block, and what is compared.

Pre-norm decoder as Mistral and Llama publish it: RMSNorm, rotary position
embedding in the half-split (``rotate_half``) convention, grouped-query
softmax attention under an arbitrary boolean mask, SwiGLU, an untied output
head, mean next-token cross entropy over the positions that have a target.
Float32 throughout, ``jax.numpy`` only, under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul is a
bf16 one without it). It imports nothing from the program: the parameter
tree is data, ``{"embed", "final_norm", "lm_head", "layers": [{"attn_norm",
"wq", "wk", "wv", "wo", "mlp_norm", "w_gate", "w_up", "w_down"}]}`` with
``x @ w`` layouts. ``family_llama.py`` hands :func:`reference` and
:data:`CHECKS` to the harness; ``cellbench/reference.py`` compares.

Memory, not speed, shapes two details: each layer is a ``jax.checkpoint``
and attention runs one query head at a time (``lax.map``), so the
``tokens x tokens`` scores of one head are all that is ever held.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from cellbench import flops


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (tokens, heads, head_dim); position = row index."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, mask):
    """q: (T, hq, d); k, v: (T, hk, d); mask: (T, T) bool, row = query."""
    group = q.shape[1] // k.shape[1]
    scale = q.shape[-1] ** -0.5

    def one_head(args):
        qh, kh, vh = args  # (T, d) each
        s = jnp.where(mask, (qh @ kh.T) * scale, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    out = jax.lax.map(
        jax.checkpoint(one_head),
        (
            q.transpose(1, 0, 2),
            jnp.repeat(k, group, axis=1).transpose(1, 0, 2),
            jnp.repeat(v, group, axis=1).transpose(1, 0, 2),
        ),
    )
    return out.transpose(1, 0, 2)


def forward(params: dict, cfg: dict, tokens, mask):
    """Logits ``(tokens, vocab)`` in float32, natural order."""
    hq, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    x = params["embed"][tokens]

    @jax.checkpoint
    def layer(x, lyr):
        h = _rms_norm(x, lyr["attn_norm"], eps)
        q = _rope((h @ lyr["wq"]).reshape(-1, hq, dh), theta)
        k = _rope((h @ lyr["wk"]).reshape(-1, hk, dh), theta)
        v = (h @ lyr["wv"]).reshape(-1, hk, dh)
        x = x + _attention(q, k, v, mask).reshape(-1, hq * dh) @ lyr["wo"]
        h = _rms_norm(x, lyr["mlp_norm"], eps)
        return x + (jax.nn.silu(h @ lyr["w_gate"]) * (h @ lyr["w_up"])) @ (
            lyr["w_down"])

    for lyr in params["layers"]:
        x = layer(x, lyr)
    return _rms_norm(x, params["final_norm"], eps) @ params["lm_head"]


def loss_and_logits(params: dict, cfg: dict, tokens, labels, mask):
    logits = forward(params, cfg, tokens, mask)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    valid = labels >= 0
    loss = jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(valid.sum(), 1)
    return loss, logits


def loss_logits_grads(params: dict, cfg: dict, tokens, labels, mask):
    """Loss, logits, and the gradients of layer 0's ``wq`` and ``wk`` (the
    two leaves whose gradient passes through every layer above and through
    both sides of the softmax)."""

    def f(wq, wk):
        lyr0 = {**params["layers"][0], "wq": wq, "wk": wk}
        p = {**params, "layers": [lyr0, *params["layers"][1:]]}
        return loss_and_logits(p, cfg, tokens, labels, mask)

    with jax.default_matmul_precision("highest"):
        (loss, logits), (gq, gk) = jax.value_and_grad(
            f, argnums=(0, 1), has_aux=True
        )(params["layers"][0]["wq"], params["layers"][0]["wk"])
    return loss, logits, gq, gk


def reference(params: dict, cfg: dict, tokens, labels, spec) -> dict:
    """The values :data:`CHECKS` names, from the reference on ``spec`` (a
    :class:`~cellbench.traffic_gen.MaskSpec`), on the device that holds
    ``tokens``. This block wants the boolean array of the mask."""
    mask = jax.device_put(flops.mask_array(spec), tokens.sharding)
    loss, logits, gq, gk = jax.jit(partial(loss_logits_grads, cfg=cfg))(
        params, tokens=tokens, labels=labels, mask=mask)
    return {"loss": loss, "logits": logits, "grad_wq0": gq, "grad_wk0": gk}


# The names compared, each with its kind (``cellbench/reference.py``) and
# its tolerance. The system computes in bf16 with fp32 accumulation from
# fp32 master weights; the reference is fp32. bf16 has 8 significand bits:
# one rounding is 2^-9 = 2.0e-3 at the worst and 1.1e-3 rms, relative.
# Measured on the chip beside each bound (PR 22, all four cells, several
# seeds).
CHECKS = {
    # Loss: a token's loss moves by its logit error, about 1.5e-2 of logits
    # of order 1, with either sign, so the mean over n target tokens is off
    # by 1.5e-2 / sqrt(n) (2.3e-4 at 4096 tokens, measured 1e-5 to 4e-4
    # absolute). The bound is ten of those. bf16 accumulation (7e-2 a
    # logit) biases the log-sum-exp by half its variance, 2.5e-3 absolute,
    # beyond the bound at the sizes the chip runs.
    "loss": {
        "kind": "abs_per_sqrt_targets", "tol": 10 * 1.5e-2,
        "why": "ten times a mean of per-token errors of 1.5e-2",
    },
    # Logits: the residual stream, every projection's input, its bf16 copy
    # of the weights and its output are each rounded once, about 14
    # roundings a layer and 4 around the head, independent and of either
    # sign: a relative Frobenius error of 1.1e-3 * sqrt(14 * layers + 4) =
    # 0.9e-2 at 4 or 5 layers, and the kernel's own roundings inside
    # attention (q * scale, p, the bf16 output) on top. Measured 1.4e-2 to
    # 1.5e-2. Accumulating one projection in bf16 instead of fp32 adds a
    # rounding per partial sum of a contraction at least 4096 long, 1.1e-3
    # * sqrt(4096) = 7e-2 in that matmul alone. The bound sits between. (A
    # wrong position id, permutation, label shift or mask is an error of
    # order 1.)
    "logits": {
        "kind": "rel_frobenius", "tol": 3e-2,
        "why": "bf16 roundings give 1.5e-2, bf16 accumulation 7e-2",
    },
    # Gradients of layer 0's wq and wk (the two leaves whose gradient
    # passes through every layer above and through both sides of the
    # softmax): the backward pass rounds as the forward does and the
    # forward runs twice (remat), about three times the roundings, and the
    # forward's logit error enters through the softmax: 1.1e-3 * sqrt(3 *
    # (14 * layers + 4)) = 1.6e-2 at 5 layers plus the forward's 1e-2.
    # Measured 2.0e-2 to 2.3e-2. Their last contraction runs over every
    # token of the check (>= 4096): in bf16 that alone would be 7e-2.
    "grad_wq0": {
        "kind": "rel_frobenius", "tol": 5e-2,
        "why": "three times the forward's roundings give 2.3e-2",
    },
    "grad_wk0": {
        "kind": "rel_frobenius", "tol": 5e-2,
        "why": "as grad_wq0, through the key side of the softmax",
    },
}
