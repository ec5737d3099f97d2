"""The plain reference of the ``afmoe`` family's layer (Arcee Trinity), and
what is compared.

A decoder layer as the family's ``config.json`` publishes its sizes and, for
what is no config key, as its public modelling code is remembered (each such
line is under ``assumed`` in the configuration file). ``x`` is ``(T,
hidden)``; every ``RMSNorm`` has its own weight, eps ``rms_norm_eps``::

    x0 = E[tokens] * sqrt(hidden)                             # mup_enabled
    a  = RMSNorm_in(x)
    q  = RMSNorm_q(reshape(a Wq, (T, heads, d)))              # weight (d,), shared by the heads
    k  = RMSNorm_k(reshape(a Wk, (T, kv_heads, d)));  v = reshape(a Wv, (T, kv_heads, d))
    sliding layer:  q, k = RoPE(q, k; rope_theta)             # full layer: no positional embedding
    o  = softmax(q k^T / sqrt(d) + mask) v                    # causal inside the token's document;
                                                              # sliding: key j seen by query i iff 0 <= i - j < sliding_window
    y  = (o * sigmoid(a Wg)) Wo                               # Wg hidden -> heads * d, one gate a head channel
    x  = x + RMSNorm_post_attn(y)
    m  = RMSNorm_pre_mlp(x)
    f  = Wd (silu(Wg' m) * Wu m)                 width intermediate_size        # layer < num_dense_layers
       = shared(m) + sum_{e in chosen(m)} w_e expert_e(m)     # else; shared and every expert SwiGLU, moe_intermediate_size wide
    x  = x + RMSNorm_post_mlp(f)
    router: s = sigmoid(m Wr) in float32 (router_experts wide); chosen = top num_experts_per_tok of s + b;
            w = s[chosen] / sum(s[chosen]) * route_scale      # route_norm
            b (expert_bias) is a buffer: it picks, weighs nothing, has no gradient, no step updates it
    logits = RMSNorm_final(x) W_head                          # untied; loss = next-token cross entropy, no auxiliary term

Float32 throughout, ``jax.numpy`` only, under
``jax.default_matmul_precision("highest")``. It imports nothing from the
program (``take_leaves`` / ``with_leaves``, which pick and replace named
leaves of a tree, are ``reference_nemotron_h``'s): the parameter tree is
data. ``params["layers"]`` holds two blocks a model layer, the attention's leaves (``attn_norm, wq, wk, wv, wo, q_norm,
k_norm, w_attn_gate, attn_post_norm``) and then the MLP's (dense: ``norm,
w_gate, w_up, w_down, post_norm``; experts: ``norm, router, e_bias, w_up
(held, hidden, 2 width)`` with gate and up side by side, ``w_down, ws_up
(hidden, 2 width), ws_down, post_norm``), ``x @ w`` layouts.

Each layer's mask is built here from ``layer_types`` and ``sliding_window``
and the documents of ``spec`` (``spec.window`` is not read: the traffic has
none). Memory shapes three details: every sub-block is a ``jax.checkpoint``,
attention runs one query head at a time, and the held experts are a
``lax.scan`` of one dense SwiGLU each.

**Departures, each noted.** (1) RoPE takes a token's position inside its
document, the program its row in the packed sequence: the scores depend on
differences of positions only, so the two agree but for float32 roundings of
the angles. (2) Of ``num_experts`` experts the ``n`` HELD are computed
(``expert_offset`` onward); what the others would add is left out, as in the
program. (3) **The routes are teacher-forced**, as in
``reference_nemotron_h.py`` (its docstring, "The routes", says why a
comparison that lets each side choose cannot be tight): ``routes``, when
given, are the expert ids the program chose, per expert layer; the reference
weighs THOSE experts with its own scores, ``route_choice`` holds the
program's set to the reference's own top-k outside near-ties (``ROUTE_TIE``)
and ``route_scores`` compares the values the top-k was taken of.

``dtype`` (the control, not the reference): the same equations with every
parameter, activation and matmul result in that type (``bfloat16``: the
nearest precision below the configuration's bf16 inputs with float32
accumulation and float32 masters). The comparison has to fail it.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import flops
from cellbench.reference_nemotron_h import take_leaves, with_leaves  # noqa: F401

# Half the width of a near-tie of score + bias, ``reference_nemotron_h``'s
# and between the same two readings here (my chip runs, PR 33, published
# widths, 8192 tokens): no sound run chose outside it (``route_choice`` 0 in
# 16 of 16), every planted fault did.
ROUTE_TIE = 4e-2
SLIDING, FULL = "sliding_attention", "full_attention"


def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def positions_in_documents(spec) -> np.ndarray:
    cu = np.asarray(spec.cu_seqlens)
    return (np.arange(spec.tokens) - np.repeat(cu[:-1], np.diff(cu))).astype(
        np.int32)


def layer_masks(cfg: dict, spec) -> dict[str, np.ndarray]:
    """``{layer type: (T, T) bool mask, row = query}`` for the types the
    configuration's layers have."""
    kinds = set(cfg["layer_types"])
    if kinds - {SLIDING, FULL}:
        raise ValueError(f"layer_types {sorted(kinds)}")
    out = {}
    if FULL in kinds:
        out[FULL] = flops.mask_array(dataclasses.replace(spec, window=None))
    if SLIDING in kinds:
        out[SLIDING] = flops.mask_array(
            dataclasses.replace(spec, window=cfg["sliding_window"]))
    return out


def _rope(x, pos, theta):
    """Half-split (``rotate_half``) convention; x (T, heads, d)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None]
    cos, sin = (f(ang)[:, None, :].astype(x.dtype) for f in (jnp.cos, jnp.sin))
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(a, lyr, cfg, mask, pos, rope: bool):
    hq, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = _rms_norm((a @ lyr["wq"]).reshape(-1, hq, dh), lyr["q_norm"], eps)
    k = _rms_norm((a @ lyr["wk"]).reshape(-1, hk, dh), lyr["k_norm"], eps)
    v = (a @ lyr["wv"]).reshape(-1, hk, dh)
    if rope:
        q, k = (_rope(t, pos, cfg["rope_theta"]) for t in (q, k))

    def one_head(args):
        qh, kh, vh = args
        s = jnp.where(mask, (qh @ kh.T) * dh ** -0.5, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    out = jax.lax.map(jax.checkpoint(one_head), (
        q.transpose(1, 0, 2),
        jnp.repeat(k, hq // hk, axis=1).transpose(1, 0, 2),
        jnp.repeat(v, hq // hk, axis=1).transpose(1, 0, 2)))
    o = out.transpose(1, 0, 2).reshape(-1, hq * dh)
    return (o * jax.nn.sigmoid(a @ lyr["w_attn_gate"])) @ lyr["wo"]


def _swiglu(m, w_gate_up, w_down):
    """``w_gate_up`` (hidden, 2 width): gate, then up."""
    gate, up = jnp.split(m @ w_gate_up, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_down


def _experts(m, lyr, route, cfg):
    """``(the held experts' part + the shared expert, the reference's own
    top-k of score + bias, sorted (T, k), the set chosen as a 0/1 array (T,
    router's width): the reference's own, but ``route``'s word for an expert
    within ``ROUTE_TIE`` of the k-th)``."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(m.astype(jnp.float32) @ lyr["router"].astype(
        jnp.float32)).astype(m.dtype)
    biased = s + lyr["e_bias"]
    own_biased, own = jax.lax.top_k(biased, k)  # sorted
    chosen = own if route is None else route
    width = s.shape[-1]
    choice = jnp.sum(jax.nn.one_hot(own, width), axis=1)
    if route is not None:
        choice = jnp.where(
            jnp.abs(biased - own_biased[:, -1:]) <= ROUTE_TIE,
            jnp.sum(jax.nn.one_hot(route, width), axis=1), choice)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * (
        cfg["route_scale"])

    @jax.checkpoint
    def expert_part(e, w_up, w_down):
        gate = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        return gate[:, None].astype(m.dtype) * _swiglu(m, w_up, w_down)

    held = lyr["w_up"].shape[0]
    routed, _ = jax.lax.scan(
        lambda total, expert: (total + expert_part(*expert), None),
        jnp.zeros_like(m),
        (cfg["expert_offset"] + jnp.arange(held), lyr["w_up"], lyr["w_down"]))
    return (routed + _swiglu(m, lyr["ws_up"], lyr["ws_down"]), own_biased,
            choice)


def forward(params, cfg, tokens, masks, pos, routes=None):
    """``(logits (tokens, vocab) float32 in natural order, (the reference's
    own sorted top-k of score + bias (expert layers, tokens, k), the sets
    chosen (expert layers, tokens, router's width)))``."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens] * jnp.asarray(
        cfg["hidden_size"] ** 0.5, params["embed"].dtype)
    routes = iter(routes) if routes is not None else None
    scores, choices = [], []
    for i, kind in enumerate(cfg["layer_types"]):
        attn, mlp = params["layers"][2 * i], params["layers"][2 * i + 1]
        y = jax.checkpoint(partial(
            _attention, cfg=cfg, mask=masks[kind], pos=pos,
            rope=kind == SLIDING))(_rms_norm(x, attn["attn_norm"], eps), attn)
        x = x + _rms_norm(y, attn["attn_post_norm"], eps)
        m = _rms_norm(x, mlp["norm"], eps)
        if i < cfg["num_dense_layers"]:
            f = jax.checkpoint(_swiglu)(m, jnp.concatenate(
                [mlp["w_gate"], mlp["w_up"]], axis=-1), mlp["w_down"])
        else:
            route = next(routes) if routes is not None else None
            f, own, choice = jax.checkpoint(partial(_experts, cfg=cfg))(
                m, mlp, route)
            scores.append(own)
            choices.append(choice)
        x = x + _rms_norm(f, mlp["post_norm"], eps)
    logits = (_rms_norm(x, params["final_norm"], eps) @ params["lm_head"])
    return logits.astype(jnp.float32), (
        jnp.stack(scores).astype(jnp.float32), jnp.stack(choices))


def loss_and_logits(params, cfg, tokens, labels, masks, pos, routes=None):
    logits, routing = forward(params, cfg, tokens, masks, pos, routes)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    valid = labels >= 0
    loss = jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(valid.sum(), 1)
    return loss, (logits, *routing)


def grad_leaves(cfg: dict) -> dict:
    """``{check name: (block index, leaf, index into the leaf or None)}``:
    the first sliding layer's ``wq`` and output gate, the first full layer's
    ``wq``, the first expert layer's router and its first held expert's
    gate-and-up weight."""
    kinds = cfg["layer_types"]
    sliding, full = kinds.index(SLIDING), kinds.index(FULL)
    expert = 2 * cfg["num_dense_layers"] + 1
    return {
        "grad_wq_sliding": (2 * sliding, "wq", None),
        "grad_wq_full": (2 * full, "wq", None),
        "grad_attn_gate": (2 * sliding, "w_attn_gate", None),
        "grad_router": (expert, "router", None),
        "grad_expert_w_up": (expert, "w_up", 0),
    }


def loss_logits_grads(params, cfg, tokens, labels, masks, pos, routes,
                      dtype=jnp.float32):
    where = grad_leaves(cfg)
    params = jax.tree.map(lambda p: p.astype(dtype), params)

    def f(leaves):
        return loss_and_logits(
            with_leaves(params, where, leaves), cfg, tokens, labels, masks,
            pos, routes)

    with jax.default_matmul_precision("highest"):
        (loss, (logits, scores, choice)), grads = jax.value_and_grad(
            f, has_aux=True)(take_leaves(params, where))
    return {"loss": loss, "logits": logits, "route_scores": scores,
            "route_choice": choice, **grads}


def reference(params, cfg, tokens, labels, spec, routes=None,
              dtype=jnp.float32) -> dict:
    """The values :data:`CHECKS` names, on the device that holds ``tokens``;
    ``routes`` and ``dtype`` as the module's docstring says."""
    put = partial(jax.device_put, device=tokens.sharding)
    return jax.jit(partial(loss_logits_grads, cfg=cfg, dtype=dtype))(
        params, tokens=tokens, labels=labels,
        masks={kind: put(m) for kind, m in layer_masks(cfg, spec).items()},
        pos=put(positions_in_documents(spec)), routes=routes)


# The names compared, each with its kind (``cellbench/reference.py``) and
# tolerance. The system computes in bf16 with fp32 accumulation from fp32
# masters (the router's scores, the gates' products and the norms' sums in
# fp32); the reference is fp32. One bf16 rounding is 1.1e-3 rms, relative.
# Beside each limit its two readings at the published widths on the chip, 8192
# tokens (my chip runs, PR 33; ``PERF.md`` section 6): the largest of 16 sound
# runs over 16 seeds, and the lowest reading that has to fail — of the planted
# faults (``K``: the window key in the full layer; ``F``: the full key in the
# sliding layers; ``R``: a rotation in the full layer; ``G``: the output gate
# left out; one seed) and of ``B``, this reference in bf16 against itself in
# float32, which fails ``route_scores`` ALONE (a bf16 stream with bf16 sums is
# otherwise what the program computes: its other readings lie 5 to 15% above
# the program's).
CHECKS = {
    # sound 2.6e-4 | G 3.2e-3 (limit 1.66e-3 at 8188 targets; B 5e-5)
    "loss": {
        "kind": "abs_per_sqrt_targets", "tol": 10 * 1.5e-2,
        "why": "ten times a mean of per-token errors of 1.5e-2",
    },
    # sound 1.31e-2 to 1.33e-2 | K 3.7e-2, R 5.1e-2, F 0.23, G 0.56 (B 1.45e-2)
    "logits": {
        "kind": "rel_frobenius", "tol": 3e-2,
        "why": "bf16 roundings of ten blocks and the head; a layer under "
               "the other kind's mask, a rotation in the full layer or a "
               "missing gate is an error of 4e-2 and more",
    },
    # sound 0 in every run | R 3.9e-3, K 5.9e-3, F 0.24, G 0.61 (B 0)
    "route_choice": {
        "kind": "rel_frobenius", "tol": 1e-3,
        "why": "no token may be sent to an expert that is further than a "
               "near-tie below the reference's eighth: the count is held at 0",
    },
    # sound 1.32e-3 to 1.36e-3 (16 runs) | B 2.15e-3, 2.17e-3, 2.42e-3 (three
    # seeds; at toy widths on the CPU, three seeds: sound 1.19e-3 to 1.21e-3,
    # B 2.30e-3 to 2.44e-3): the router's scores rounded to bf16. The limit is
    # the geometric mean of the nearest two readings.
    "route_scores": {
        "kind": "rel_frobenius", "tol": 1.7e-3,
        "why": "score + bias of the experts chosen, scored in float32 from "
               "the bf16 stream: 1.36e-3; a router scored in bf16, the "
               "precision below the stated one, reads 2.15e-3 and more",
    },
    # sound 1.98e-2 | R 8.3e-2, F 0.32 (B 2.17e-2)
    "grad_wq_sliding": {
        "kind": "rel_frobenius", "tol": 5e-2,
        "why": "the first layer's wq through the band, the q/k norms, the "
               "rotation and every block above: three times the forward's "
               "roundings",
    },
    # sound 2.54e-2 | K 0.30, F 0.31, R 1.13 (B 2.79e-2)
    "grad_wq_full": {
        "kind": "rel_frobenius", "tol": 5e-2,
        "why": "the full layer's wq: the dense triangle without a rotation; "
               "the window key or a rotation there is an error of 0.3 and more",
    },
    # sound 1.95e-2 | G 1.0 exactly, R 8.8e-2 (B 2.12e-2)
    "grad_attn_gate": {
        "kind": "rel_frobenius", "tol": 5e-2,
        "why": "the output gate of the first layer: zero where the gate is "
               "left out",
    },
    # sound 2.42e-2 | R 8.7e-2, F 0.41 (B 2.56e-2)
    "grad_router": {
        "kind": "rel_frobenius", "tol": 5e-2,
        "why": "through the routing weights of the experts held",
    },
    # sound 2.01e-2 | R 7.7e-2, F 0.34; one routed row dropped a token block
    # reads 0.22 at toy widths on the CPU (B 2.13e-2)
    "grad_expert_w_up": {
        "kind": "rel_frobenius", "tol": 5e-2,
        "why": "one held expert's gate and up halves: a row dropped from the "
               "grouped product, or silu taken of the wrong half, is an "
               "error of order 1",
    },
}
