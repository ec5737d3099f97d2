"""The plain reference of the ``solar_open2`` family's blocks, and what is
compared.

Solar Open 2's decoder as its config publishes it, with the conventions the
configuration file's ``assumed`` states. Every block is ``x +
mixer(RMSNorm(x))``; a layer is its mixer block and then its expert block;
layer ``i`` mixes by GQA where ``i`` is in ``gqa_layers`` and by KDA
otherwise:

* KDA, the gated delta rule with a per-channel decay: ``q, k, v = silu(conv4(h
  W))`` each, depthwise and causal without bias, taps never reaching across a
  document's first token; q and k L2-normalised per head; ``g = -exp(A_log)
  softplus(h W_fa W_fb + dt_bias)`` per key channel, ``beta = 2 sigmoid(h
  W_b)``; per head ``S_t = (I - beta_t k_t k_t^T) diag(exp g_t) S_{t-1} +
  beta_t k_t v_t^T`` with ``S = 0`` before a document's first token, ``o_t =
  128 ** -0.5 S_t^T q_t``; ``(RMSNorm_head(o) * sigmoid(h W_ga W_gb)) W_o``.
  The recurrence is run AS the recurrence, one token at a time
  (:func:`recurrence`: ``lax.scan``, every head at once, the states
  rematerialised in blocks of ``SCAN_BLOCK`` tokens): no chunk, no WY form,
  no running sum of decays;
* GQA: grouped-query softmax attention, causal inside a document, no rotary
  embedding and no q/k norm, the output times ``sigmoid(h W_gate)`` before
  ``W_o``;
* experts: ``reference_afmoe._experts`` (sigmoid scores over all
  ``router_experts``, the top ``num_experts_per_tok`` of score + bias, the
  chosen scores renormalised, times the published scale 1, SwiGLU experts,
  a plain loop over the experts HELD, one shared SwiGLU expert), teacher-forced
  on the routes as that family's comparison is (``reference_nemotron_h.py``,
  "The routes", says why).

Float32 throughout, ``jax.numpy`` only, under
``jax.default_matmul_precision("highest")``. It imports nothing from the
program: the parameter tree is data (``x @ w`` layouts, leaves named as the
program names them).

**The timed recurrence.** Beside the model at the check's tokens,
``correct`` runs one KDA layer's kernel at the TIMED tokens on the timed
mask's document starts, every head, forward and its ``jax.vjp``, on seeded
inputs drawn as the layer's are (:func:`draw_timed`): :func:`timed` is the
same call through :func:`recurrence`. ``TIMED_CHECKS`` are its names.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import flops, reference_afmoe, reference_nemotron_h

SCAN_BLOCK = 64
L2_EPS = 1e-6
HIGHEST = jax.lax.Precision.HIGHEST


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def pattern(cfg: dict) -> str:
    """The program's blocks: per layer ``*`` (GQA) or ``K`` (KDA), then
    ``E``."""
    assert cfg["first_k_dense_replace"] == 0, cfg["name"]
    gqa = set(cfg["gqa_layers"])
    return "".join(("*" if i in gqa else "K") + "E"
                   for i in range(cfg["num_hidden_layers"]))


def recurrence(q, k, v, g, beta, first):
    """``o`` (T, H, D) of the recurrence token by token; q, k, v, g (T, H,
    D), beta (T, H), ``first`` (T,) a document's first token. Float32."""
    t, h, d = q.shape
    pad = -t % SCAN_BLOCK
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            for x in (q, k, v, g, beta))
        first = jnp.pad(first, (0, pad))

    def token(s, inp):
        q_t, k_t, v_t, g_t, b_t, first_t = inp
        s = jnp.where(first_t, 0.0, s) * jnp.exp(g_t)[:, :, None]
        err = v_t - jnp.einsum("hk,hkv->hv", k_t, s, precision=HIGHEST)
        s = s + (b_t[:, None] * k_t)[:, :, None] * err[:, None, :]
        return s, d ** -0.5 * jnp.einsum(
            "hk,hkv->hv", q_t, s, precision=HIGHEST)

    @jax.checkpoint
    def block(s, inps):
        return jax.lax.scan(token, s, inps)

    blocks = jax.tree.map(
        lambda x: x.reshape(-1, SCAN_BLOCK, *x.shape[1:]),
        (q, k, v, g, beta, first))
    _, o = jax.lax.scan(block, jnp.zeros((h, d, d), jnp.float32), blocks)
    return o.reshape(-1, h, d)[:t]


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _kda(u, lyr, cfg, pos):
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    t = u.shape[0]
    q, k, v = (reference_nemotron_h._conv_silu(u @ lyr[name], w, 0.0, pos)
               .reshape(t, heads, d)
               for name, w in zip(("w_q", "w_k", "w_v"), lyr["conv_w"]))
    g = -jnp.exp(lyr["A_log"])[:, None] * jax.nn.softplus(
        u @ lyr["w_fa"] @ lyr["w_fb"] + lyr["dt_bias"]).reshape(t, heads, d)
    beta = jax.nn.sigmoid(u @ lyr["w_b"]) * (
        2.0 if cfg["kda_allow_neg_eigval"] else 1.0)
    o = recurrence(_l2(q), _l2(k), v, g, beta, pos == 0)
    o = _rms_norm(o, lyr["o_norm"], cfg["rms_norm_eps"]).reshape(t, -1)
    return (o * jax.nn.sigmoid(u @ lyr["w_ga"] @ lyr["w_gb"])) @ lyr["w_o"]


def _attention(u, lyr, cfg, mask):
    hq, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = (u @ lyr["wq"]).reshape(-1, hq, dh)
    k = (u @ lyr["wk"]).reshape(-1, hk, dh)
    v = (u @ lyr["wv"]).reshape(-1, hk, dh)

    def one_head(args):
        qh, kh, vh = args
        s = jnp.where(mask, (qh @ kh.T) * dh ** -0.5, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    out = jax.lax.map(jax.checkpoint(one_head), (
        q.transpose(1, 0, 2),
        jnp.repeat(k, hq // hk, axis=1).transpose(1, 0, 2),
        jnp.repeat(v, hq // hk, axis=1).transpose(1, 0, 2)))
    o = out.transpose(1, 0, 2).reshape(-1, hq * dh)
    return (o * jax.nn.sigmoid(u @ lyr["w_attn_gate"])) @ lyr["wo"]


def _expert_cfg(cfg: dict) -> dict:
    """What ``reference_afmoe._experts`` reads, under its names."""
    return {"num_experts_per_tok": cfg["num_experts_per_tok"],
            "route_scale": 1.0,
            "expert_offset": cfg["expert_offset"]}


def forward(params, cfg, tokens, mask, pos, routes=None):
    """``(logits (tokens, vocab) float32 in natural order, (the reference's
    own sorted top-k of score + bias (expert blocks, tokens, k), the sets
    chosen (expert blocks, tokens, router's width)))``."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens]
    routes = iter(routes) if routes is not None else None
    scores, choices = [], []
    for kind, lyr in zip(pattern(cfg), params["layers"]):
        if kind == "K":
            x = x + jax.checkpoint(partial(_kda, cfg=cfg, pos=pos))(
                _rms_norm(x, lyr["norm"], eps), lyr)
        elif kind == "*":
            x = x + jax.checkpoint(partial(_attention, cfg=cfg, mask=mask))(
                _rms_norm(x, lyr["attn_norm"], eps), lyr)
        else:
            route = next(routes) if routes is not None else None
            y, own, choice = jax.checkpoint(partial(
                reference_afmoe._experts, cfg=_expert_cfg(cfg)))(
                _rms_norm(x, lyr["norm"], eps), lyr, route)
            x = x + y
            scores.append(own)
            choices.append(choice)
    logits = _rms_norm(x, params["final_norm"], eps) @ params["lm_head"]
    return logits, (jnp.stack(scores), jnp.stack(choices))


def loss_and_logits(params, cfg, tokens, labels, mask, pos, routes=None):
    logits, routing = forward(params, cfg, tokens, mask, pos, routes)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    valid = labels >= 0
    loss = jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(valid.sum(), 1)
    return loss, (logits, *routing)


def grad_leaves(pattern_: str) -> dict:
    """``{check name: (block index, leaf, index into the leaf or None)}``:
    the first ``K`` block's ``w_k``, ``A_log``, ``dt_bias`` and ``w_b``, the
    first ``*`` block's ``wq``, the first ``E`` block's router and its first
    held expert's gate-and-up weight."""
    kda, attn, expert = (pattern_.index(kind) for kind in "K*E")
    return {
        "grad_w_k": (kda, "w_k", None),
        "grad_A_log": (kda, "A_log", None),
        "grad_dt_bias": (kda, "dt_bias", None),
        "grad_w_b": (kda, "w_b", None),
        "grad_wq": (attn, "wq", None),
        "grad_router": (expert, "router", None),
        "grad_expert_w_up": (expert, "w_up", 0),
    }


with_leaves = reference_nemotron_h.with_leaves
take_leaves = reference_nemotron_h.take_leaves


def loss_logits_grads(params, cfg, tokens, labels, mask, pos, routes):
    where = grad_leaves(pattern(cfg))

    def f(leaves):
        return loss_and_logits(
            with_leaves(params, where, leaves), cfg, tokens, labels, mask,
            pos, routes)

    with jax.default_matmul_precision("highest"):
        (loss, (logits, scores, choice)), grads = jax.value_and_grad(
            f, has_aux=True)(take_leaves(params, where))
    return {"loss": loss, "logits": logits, "route_scores": scores,
            "route_choice": choice, **grads}


def kda_blocks(params, cfg, stream, pos):
    """On the program's own residual stream (``stream``: the input of every
    block, then of the final norm), what each ``K`` block adds to ITS input,
    stacked: it then differs from the program's by that block's roundings
    alone, where the whole stream carries every block's below it (a KDA
    layer's output is normed per head, which lifts the relative error of a
    small ``S^T q`` into the stream)."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _kda(_rms_norm(stream[i].astype(jnp.float32), lyr["norm"], eps),
                 lyr, cfg, pos)
            for i, (kind, lyr) in enumerate(zip(pattern(cfg),
                                                params["layers"]))
            if kind == "K"])


def reference(params, cfg, tokens, labels, spec, routes=None,
              stream=None) -> dict:
    """The values :data:`CHECKS` names, on the device that holds ``tokens``;
    ``routes`` as the module's docstring says, ``stream`` the program's
    residual stream for :func:`kda_blocks`."""
    put = partial(jax.device_put, device=tokens.sharding)
    mask = put(flops.mask_array(spec))
    pos = put(reference_afmoe.positions_in_documents(spec))
    out = jax.jit(partial(loss_logits_grads, cfg=cfg))(
        params, tokens=tokens, labels=labels, mask=mask, pos=pos,
        routes=routes)
    if stream is not None:
        out["kda_blocks"] = jax.jit(partial(kda_blocks, cfg=cfg))(
            params, stream=stream, pos=pos)
    return out


# -- the timed recurrence -----------------------------------------------------

def draw_timed(cfg: dict, tokens: int, seed: int):
    """``(q, k, v, g, beta, do)`` of one KDA layer at ``tokens``, every head,
    drawn from ``seed`` as the layer's are at the seeded weights: q and k
    L2-normalised normal draws, v a SiLU of one, the decay ``-exp(A_log)
    softplus(z + dt_bias)`` with ``A_log = log U[1, 16]``, ``z`` normal
    (what ``h W_fa W_fb`` is for a normed ``h``) and ``dt_bias`` the
    initialisation's, ``beta = 2 sigmoid(z)``; q, k, v and the cotangent
    ``do`` bf16, the configuration's precision; g and beta float32."""
    lin = cfg["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    shape = (tokens, h, d)
    normal = partial(jax.random.normal, shape=shape, dtype=jnp.float32)
    dt = jnp.exp(jax.random.uniform(ks[0], (h * d,)) * (
        np.log(cfg["time_step_max"]) - np.log(cfg["time_step_min"]))
        + np.log(cfg["time_step_min"]))
    dt = jnp.maximum(dt, cfg["time_step_floor"])
    dt_bias = (dt + jnp.log(-jnp.expm1(-dt))).reshape(h, d)
    a_log = jnp.log(jax.random.uniform(ks[1], (h,), minval=1.0, maxval=16.0))
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(normal(ks[2]) + dt_bias)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (tokens, h)))
    bf16 = jnp.bfloat16
    q, k = (_l2(normal(key)).astype(bf16) for key in ks[4:6])
    v = jax.nn.silu(normal(ks[6])).astype(bf16)
    do = normal(ks[7]).astype(bf16)
    return q, k, v, g, beta, do


def timed(q, k, v, g, beta, do, first) -> dict:
    """The recurrence and its ``jax.vjp`` at ``do``, float32 at
    ``highest``: ``{"out", "dq", "dk", "dv", "dg", "dbeta"}``."""
    f32 = [x.astype(jnp.float32) for x in (q, k, v, g, beta)]
    with jax.default_matmul_precision("highest"):
        out, pull = jax.vjp(lambda *a: recurrence(*a, first), *f32)
        grads = pull(do.astype(jnp.float32))
    return dict(zip(("out", "dq", "dk", "dv", "dg", "dbeta"), (out, *grads)))


# The names compared of the model at the check's tokens, each with its kind
# (``cellbench/reference.py``) and tolerance. The system computes in bf16 with
# fp32 accumulation from fp32 masters (the recurrence inside its kernel, the
# router's scores and the norms' sums in fp32); the reference is fp32. Each
# limit lies between the largest sound reading and the reading of q, k and v
# in fp8 in every KDA layer (F, below the configuration's bf16), near their
# geometric mean (``python -m cellbench.kda_timed_probe --model``; the other
# controls: C the state carried across a document start, B beta not doubled,
# S the carried state in bf16, which moves no model name and fails the timed
# ``_dg`` and ``_dbeta``). Beside each: sound | controls, on a TPU v5e at the
# cell's widths and 4096 tokens, three seeds (PERF.md section 6).
CHECKS = {
    # sound 4.7e-4 to 7.4e-4 | F 1.47e-3, C 1.45e-3, B 6.0e-4 (limit 2.35e-3
    # at 4090 targets): the accepted cells' limit, three times the sound
    "loss": {
        "kind": "abs_per_sqrt_targets", "tol": 10 * 1.5e-2,
        "why": "ten times a mean of per-token errors of 1.5e-2",
    },
    # sound 3.08e-2 to 3.13e-2 | C 9.75e-2, F 0.117, B 0.401
    "logits": {
        "kind": "rel_frobenius", "tol": 5.5e-2,
        "why": "bf16 roundings of eight blocks and the head, carried down "
               "the stream: three KDA blocks norm each head's output, which "
               "lifts the relative error of a small S^T q; a state carried "
               "across a document, beta not doubled or fp8 inputs read 0.1 "
               "and more",
    },
    # sound 0 | F 1.5e-2, C 5.8e-2, B 0.26
    "route_choice": {
        "kind": "rel_frobenius", "tol": 1e-3,
        "why": "no token may be sent to an expert that is further than a "
               "near-tie below the reference's eighth: the count is held at 0",
    },
    # sound 1.71e-3 to 1.79e-3 | C 2.98e-3, F 5.05e-3, B 1.16e-2
    "route_scores": {
        "kind": "rel_frobenius", "tol": 2.7e-3,
        "why": "score + bias of the experts chosen, scored in float32 from "
               "the bf16 stream",
    },
    # sound 5.22e-2 to 5.31e-2 | C 8.37e-2, F 9.16e-2, B 0.293
    "kda_blocks": {
        "kind": "rel_frobenius", "tol": 7.0e-2,
        "why": "what the three KDA blocks add to the program's own stream: "
               "the projections', the convolutions', q's and k's roundings "
               "once, lifted where the per-head norm divides a small output; "
               "fp8 inputs read 9.2e-2, a state across a document 8.4e-2",
    },
    # sound 3.9e-2 to 5.4e-2 over the seven names and three seeds | F 0.59
    # to 1.0, B 0.59 to 0.64, C 0.05 to 0.18
    **{name: {
        "kind": "rel_frobenius", "tol": 0.13,
        "why": "through the recurrence's backward and every block above, "
               "twice the stream's reading; fp8 inputs or beta not doubled "
               "read 0.59 and more",
    } for name in grad_leaves("*EKE")},
}

# The names compared of the timed recurrence (:func:`timed` against the
# program's kernel). q, k, v, the cotangent and what the kernel writes for
# o, dq, dk, dv are bf16, so those read one bf16 rounding of the output;
# g's and beta's gradients are float32 and read what the kernel's own
# arithmetic loses. Each limit lies near the geometric mean of the largest
# sound reading and the reading of the control below the stated precision
# that moves that name (``python -m cellbench.kda_timed_probe``: F q, k, v
# and the cotangent in fp8 for the bf16 arrays; S the carried state in bf16
# for the float32 ones; also C the state carried across one document start
# inside a chunk, B beta not doubled). Beside each: sound | controls, on a
# TPU v5e at the cell's 16384 tokens and 64 heads, four seeds (PERF.md
# section 6).
TIMED_CHECKS = {
    # sound 1.659e-3 | S 1.89e-3 (passes) | C 3.1e-2, F 7.2e-2, B 0.50
    "kda_timed_out": {
        "kind": "rel_frobenius", "tol": 1.1e-2,
        "why": "o rounded once to bf16; fp8 inputs, beta not doubled or a "
               "state carried across a document read 3e-2 and more",
    },
    # sound 1.659e-3 | C 3.1e-2, F 6.2e-2, B 0.50
    "kda_timed_dq": {
        "kind": "rel_frobenius", "tol": 1.0e-2,
        "why": "dq rounded once to bf16",
    },
    # sound 1.660e-3 | C 3.6e-2, F 6.9e-2, B 0.55
    "kda_timed_dk": {
        "kind": "rel_frobenius", "tol": 1.1e-2,
        "why": "dk rounded once to bf16",
    },
    # sound 1.660e-3 | C 3.1e-2, F 7.2e-2, B 0.50
    "kda_timed_dv": {
        "kind": "rel_frobenius", "tol": 1.1e-2,
        "why": "dv rounded once to bf16",
    },
    # sound 9.7e-5 to 1.05e-4 | S 1.62e-3, C 6.1e-2, F 8.1e-2, B 0.48
    "kda_timed_dg": {
        "kind": "rel_frobenius", "tol": 4.1e-4,
        "why": "float32 out of a float32 kernel: a state kept in bf16 "
               "between chunks reads 1.6e-3",
    },
    # sound 4.7e-5 to 5.3e-5 | S 9.9e-4, C 3.3e-2, F 8.0e-2, B 0.24
    "kda_timed_dbeta": {
        "kind": "rel_frobenius", "tol": 2.3e-4,
        "why": "float32 out of a float32 kernel: a state kept in bf16 "
               "between chunks reads 9.9e-4",
    },
}
