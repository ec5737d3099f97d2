"""The plain reference of the ``mistral4`` family's layer (Mistral Small 4:
latent attention, softmax-routed SwiGLU experts), and what is compared.

A decoder layer as the family's ``config.json`` publishes its sizes; what is
no config key follows the convention named beside it (each such line is
under ``assumed`` in the configuration file). ``x`` is ``(T, hidden)``;
every ``RMSNorm`` has its own weight, eps ``rms_norm_eps``; no projection
has a bias; ``heads`` = ``num_attention_heads``, ``nope`` / ``rope`` / ``dv``
= ``qk_nope_head_dim`` / ``qk_rope_head_dim`` / ``v_head_dim``::

    h    = RMSNorm_in(x)
    c_q  = RMSNorm_qa(h W_qa)                                 # q_lora_rank wide
    q    = reshape(c_q W_qb, (T, heads, nope + rope))         # [q_nope | q_rope] a head
    [c_kv | k_r] = h W_kva                                    # kv_lora_rank, then ONE rotary key of rope a token
    [k_nope | v] = reshape(RMSNorm_kva(c_kv) W_kvb, (T, heads, nope + dv))
    q_rope, k_r  = RoPE(q_rope, k_r; p)                       # p: the token's position in its document;
                                                              # rope_interleave: channels (0, 1), (2, 3), .. pair
    freqs: YaRN (rope_parameters): theta ** (-2 i / rope), a frequency turning fewer than beta_slow times over
           original_max_position_embeddings divided by factor, one turning more than beta_fast times kept, a linear
           ramp over the index between (ends rounded outward); cos and sin times mscale / mscale_all_dim = 1
    q    = q * (1 + llama_4_scaling_beta ln(1 + floor(p / original_max_position_embeddings)))
    k    = [k_nope | k_r for every head]
    o    = softmax(q k^T (nope + rope) ** -0.5 m ** 2 + mask) v   # m = 0.1 mscale_all_dim ln(factor) + 1;
                                                              # causal inside the token's document
    x    = x + reshape(o, (T, heads dv)) W_o
    m_   = RMSNorm_mlp(x)
    x    = x + shared(m_) + sum_{e in chosen(m_)} w_e expert_e(m_)    # every one W_down (silu(W_gate m_) * W_up m_),
                                                              # moe_intermediate_size wide; first_k_dense_replace 0
    router: s = softmax(m_ W_r) in float32 (router_experts wide); chosen = top num_experts_per_tok of s + b;
            w = s[chosen] / sum(s[chosen]) * routed_scaling_factor    # norm_topk_prob: the softmax over the chosen logits
            b is a buffer: it picks, weighs nothing, has no gradient, no step updates it
    logits = RMSNorm_final(x) W_head                          # untied; loss = next-token cross entropy, no auxiliary term

Float32 throughout, ``jax.numpy`` only, under
``jax.default_matmul_precision("highest")``. It imports nothing from the
program (``take_leaves`` / ``with_leaves``, which pick and replace named
leaves of a tree, are ``reference_nemotron_h``'s): the parameter tree is
data. ``params["layers"]`` holds two blocks a model layer: the attention's
leaves (``attn_norm, w_q_a, q_a_norm, w_q_b, w_kv_a, kv_a_norm, w_kv_b,
wo``) and the experts' (``norm, router, e_bias, w_up (held, hidden, 2
width)`` with gate and up side by side, ``w_down, ws_up (hidden, 2 width),
ws_down``), ``x @ w`` layouts. Memory shapes three details: every sub-block
is a ``jax.checkpoint``, attention runs one query head at a time over the
whole dense mask, and the held experts are a ``lax.scan`` of one dense
SwiGLU each.

**Departures, each noted.** (1) The program folds ``m ** 2`` and the
position scale into q before q's rounding and leaves ``calc_attn``'s softmax
scale at ``(nope + rope) ** -0.5``; here they are where the description has
them. (2) The program writes a rotated vector as every pair's first channel
and then every pair's second; here the pairs stay where they were. q and the
rotary key are permuted alike, and a score is a sum over the channels.
(3) Of ``router_experts`` experts the ``n_routed_experts`` HELD are computed
(``expert_offset`` onward); what the others would add is left out, as in the
program. (4) **The routes are teacher-forced**, as in
``reference_nemotron_h.py`` (its docstring, "The routes", says why a
comparison that lets each side choose cannot be tight): ``routes``, when
given, are the expert ids the program chose, per expert layer; the reference
weighs THOSE experts with its own scores, ``route_choice`` holds the
program's set to the reference's own top-k outside near-ties (``ROUTE_TIE``)
and ``route_scores`` compares the values the top-k was taken of. (5) The
vision tower is no part of a text step and is not here. (6) **Block by
block, the stream is forced too**: ``stream``, when given, is the program's
own residual stream (``hybrid.forward(with_stream=True)``: every block's
input and the head's), and beside its own forward the reference runs each
of its blocks once more on the program's input of that block. What a block
adds then differs by that block's roundings alone, where the whole-stream
names compound a flipped attention row over the eight blocks (``CHECKS``).

``dtype`` (the control, not the reference): the same equations in the
nearest precision below the configuration's (bf16 inputs, float32
accumulation, float32 masters), which is ``bfloat16`` THROUGHOUT: every
parameter and activation in that type and every matmul's running sum too
(:func:`_mm`: a product is summed ``ACC_CHUNK`` of the contracted channels
at a time, the MXU's pass, and the running sum is rounded after every
pass; attention likewise over blocks of ``ACC_CHUNK_KEYS`` keys, the FFA
tile's). Rounding only a matmul's RESULT would be no lower a precision: the
program's activations are bf16 already, and such a control reads within a
quarter of the program itself (my chip run, PR 37). The comparison has to
fail it on the stream: ``CHECKS`` gives the readings. (The backward is
rounded as the forward's transposes are: a product's results, and the
running sums of the chains' products; a weight's gradient sums over the
tokens in one product.)
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import flops
from cellbench.reference_nemotron_h import take_leaves, with_leaves  # noqa: F401

# Half the width of a near-tie of score + bias. A softmax score over 128
# experts is a few hundredths where a sigmoid score is a half, and so is the
# band. Between its two readings on the chip (published widths, 8192 tokens,
# four layers, my chip run, PR 37): the program's deepest sound choice lay
# 5.4e-3 under the reference's fourth (177 of 32768 token-layers beyond 2e-3,
# one beyond 5e-3, none beyond 1e-2; its score + bias of a chosen expert
# differs from the reference's by 1.2e-3 rms, 1.6e-2 at most), and a token's
# lowest-scored expert, the planted fault, lies 2.0e-2 to 3.5e-2 under it. (A
# token's fourth and fifth best lie 2.4e-3 apart in the median: the band is
# several experts wide, and a choice inside it is not judged.)
ROUTE_TIE = 1e-2


ACC_CHUNK = 128  # contracted channels a pass of the MXU
ACC_CHUNK_KEYS = 512  # keys a grid step of the FFA tile


def _mm(a, b):
    """``a @ b``. For the control (``a`` not float32) the running sum is
    kept in ``a``'s type: rounded after every ``ACC_CHUNK`` contracted
    channels."""
    if a.dtype == jnp.float32 or a.shape[-1] <= ACC_CHUNK:
        return a @ b
    passes = -(-a.shape[-1] // ACC_CHUNK)
    pad = passes * ACC_CHUNK - a.shape[-1]
    a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    b = jnp.pad(b, [(0, pad)] + [(0, 0)] * (b.ndim - 1))
    a = jnp.moveaxis(a.reshape(*a.shape[:-1], passes, ACC_CHUNK), -2, 0)
    b = b.reshape(passes, ACC_CHUNK, *b.shape[1:])
    total, _ = jax.lax.scan(
        lambda total, ab: (total + ab[0] @ ab[1], None),
        jnp.zeros((*a.shape[1:-1], b.shape[-1]), a.dtype), (a, b))
    return total


def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def positions_in_documents(spec) -> np.ndarray:
    cu = np.asarray(spec.cu_seqlens)
    return (np.arange(spec.tokens) - np.repeat(cu[:-1], np.diff(cu))).astype(
        np.int32)


def yarn_frequencies(cfg: dict) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` rotary frequencies, float32."""
    rope, p = cfg["qk_rope_head_dim"], cfg["rope_parameters"]
    plain = p["rope_theta"] ** (-np.arange(0, rope, 2) / rope)

    def index_turning(turns):  # the (real) index whose frequency turns so
        return rope * np.log(p["original_max_position_embeddings"] / (
            turns * 2 * np.pi)) / (2 * np.log(p["rope_theta"]))

    low = max(np.floor(index_turning(p["beta_fast"])), 0)
    high = min(np.ceil(index_turning(p["beta_slow"])), rope - 1)
    if low == high:
        high += 1e-3
    divided = np.clip((np.arange(rope // 2) - low) / (high - low), 0, 1)
    return (plain / p["factor"] * divided + plain * (1 - divided)).astype(
        np.float32)


def softmax_scale(cfg: dict) -> float:
    p = cfg["rope_parameters"]
    m = 0.1 * p["mscale_all_dim"] * np.log(p["factor"]) + 1.0
    return float((cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
                 * m * m)


def _rope(x, pos, cfg):
    """``x`` (T, heads, rope): channels (2 i, 2 i + 1) turn by ``pos`` times
    the i-th frequency (``rope_interleave``), cos and sin times ``mscale /
    mscale_all_dim``."""
    p = cfg["rope_parameters"]
    assert cfg["rope_interleave"]
    ang = pos.astype(jnp.float32)[:, None] * yarn_frequencies(cfg)[None]
    factor = p["mscale"] / p["mscale_all_dim"]
    cos, sin = ((f(ang) * factor)[:, None, :].astype(x.dtype)
                for f in (jnp.cos, jnp.sin))
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


def _attention(h, lyr, cfg, mask, pos):
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    p = cfg["rope_parameters"]
    c_q = _rms_norm(_mm(h, lyr["w_q_a"]), lyr["q_a_norm"], eps)
    q = _mm(c_q, lyr["w_q_b"]).reshape(-1, heads, nope + rope)
    kv_a = _mm(h, lyr["w_kv_a"])
    c_kv = _rms_norm(kv_a[:, :cfg["kv_lora_rank"]], lyr["kv_a_norm"], eps)
    kv = _mm(c_kv, lyr["w_kv_b"]).reshape(-1, heads, nope + dv)
    k_r = _rope(kv_a[:, None, cfg["kv_lora_rank"]:], pos, cfg)[:, 0]
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], pos, cfg)], axis=-1)
    q = q * (1.0 + p["llama_4_scaling_beta"] * jnp.log1p(jnp.floor(
        pos.astype(jnp.float32) / p["original_max_position_embeddings"]))
    )[:, None, None].astype(q.dtype)
    scale = softmax_scale(cfg)

    def one_head(args):
        qh, kh, vh = args
        kh = jnp.concatenate([kh, k_r], axis=-1)  # the one rotary key
        s = jnp.where(mask, (qh @ kh.T) * scale, -jnp.inf)
        if s.dtype == jnp.float32:
            return jax.nn.softmax(s, axis=-1) @ vh
        # the control: numerator and denominator summed a block of keys at
        # a time, the running sums in the stream's type
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        num, den = jnp.zeros_like(vh), jnp.zeros_like(vh[:, :1])
        for at in range(0, e.shape[-1], ACC_CHUNK_KEYS):
            block = e[:, at:at + ACC_CHUNK_KEYS]
            num = num + block @ vh[at:at + ACC_CHUNK_KEYS]
            den = den + jnp.sum(block, axis=-1, keepdims=True)
        return num / den

    out = jax.lax.map(jax.checkpoint(one_head), (
        q.transpose(1, 0, 2), kv[..., :nope].transpose(1, 0, 2),
        kv[..., nope:].transpose(1, 0, 2)))
    return _mm(out.transpose(1, 0, 2).reshape(-1, heads * dv), lyr["wo"])


def _swiglu(m, w_gate_up, w_down):
    """``w_gate_up`` (hidden, 2 width): gate, then up."""
    gate, up = jnp.split(_mm(m, w_gate_up), 2, axis=-1)
    return _mm(jax.nn.silu(gate) * up, w_down)


def _experts(m, lyr, route, cfg):
    """``(the held experts' part + the shared expert, the reference's own
    top-k of score + bias, sorted (T, k), the set chosen as a 0/1 array (T,
    router's width): the reference's own, but ``route``'s word for an expert
    within ``ROUTE_TIE`` of the k-th)``."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.softmax(_mm(m, lyr["router"]).astype(jnp.float32), axis=-1)
    s = s.astype(m.dtype)  # held in the stream's type
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
        cfg["routed_scaling_factor"])

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


def forward(params, cfg, tokens, mask, pos, routes=None, stream=None):
    """``(logits (tokens, vocab) float32 in natural order, (the reference's
    own sorted top-k of score + bias (layers, tokens, k), the sets chosen
    (layers, tokens, router's width)), block by block)``. ``stream``
    (blocks + 1, tokens, hidden), when given, is the PROGRAM's residual
    stream, the input of every block and of the head: block by block is
    then ``{"attn_blocks", "expert_blocks"}``, what each block of this
    reference adds to the program's own input of it (layers, tokens,
    hidden), and ``"head_logits"``, the head on the program's last; nothing
    of them flows into the logits or the gradients."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens]
    routes = iter(routes) if routes is not None else None
    scores, choices, forced = [], [], {"attn_blocks": [], "expert_blocks": []}
    for i in range(cfg["num_hidden_layers"]):
        attn, mlp = params["layers"][2 * i], params["layers"][2 * i + 1]
        attention = jax.checkpoint(lambda x, lyr: _attention(
            _rms_norm(x, lyr["attn_norm"], eps), lyr, cfg, mask, pos))
        experts = jax.checkpoint(lambda x, lyr, route: _experts(
            _rms_norm(x, lyr["norm"], eps), lyr, route, cfg))
        route = next(routes) if routes is not None else None
        if stream is not None:
            at = [stream[2 * i + j].astype(x.dtype) for j in (0, 1)]
            forced["attn_blocks"].append(attention(at[0], attn))
            forced["expert_blocks"].append(experts(at[1], mlp, route)[0])
        x = x + attention(x, attn)
        f, own, choice = experts(x, mlp, route)
        scores.append(own)
        choices.append(choice)
        x = x + f

    def head(x):
        return _mm(_rms_norm(x, params["final_norm"], eps),
                   params["lm_head"]).astype(jnp.float32)

    forced = {} if stream is None else jax.lax.stop_gradient({
        **{k: jnp.stack(v).astype(jnp.float32) for k, v in forced.items()},
        "head_logits": head(stream[-1].astype(x.dtype))})
    return head(x), (jnp.stack(scores).astype(jnp.float32),
                     jnp.stack(choices)), forced


def loss_and_logits(params, cfg, tokens, labels, mask, pos, routes=None,
                    stream=None):
    logits, *routing = forward(params, cfg, tokens, mask, pos, routes, stream)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    valid = labels >= 0
    loss = jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(valid.sum(), 1)
    return loss, (logits, *routing[0], routing[1])


# ``{check name: (block index, leaf, index into the leaf or None)}``: the
# first layer's four latent matrices, its router and its first held expert's
# gate-and-up weight.
GRAD_LEAVES = {
    "grad_w_q_a": (0, "w_q_a", None),
    "grad_w_q_b": (0, "w_q_b", None),
    "grad_w_kv_a": (0, "w_kv_a", None),
    "grad_w_kv_b": (0, "w_kv_b", None),
    "grad_router": (1, "router", None),
    "grad_expert_w_up": (1, "w_up", 0),
}


def loss_logits_grads(params, cfg, tokens, labels, mask, pos, routes,
                      stream=None, dtype=jnp.float32):
    params = jax.tree.map(lambda p: p.astype(dtype), params)

    def f(leaves):
        return loss_and_logits(
            with_leaves(params, GRAD_LEAVES, leaves), cfg, tokens, labels,
            mask, pos, routes, stream)

    with jax.default_matmul_precision("highest"):
        (loss, (logits, scores, choice, forced)), grads = jax.value_and_grad(
            f, has_aux=True)(take_leaves(params, GRAD_LEAVES))
    return {"loss": loss, "logits": logits, "route_scores": scores,
            "route_choice": choice, **forced, **grads}


def reference(params, cfg, tokens, labels, spec, routes=None, stream=None,
              dtype=jnp.float32) -> dict:
    """The values :data:`CHECKS` names (the three block by block only where
    ``stream`` is given), on the device that holds ``tokens``; ``routes``,
    ``stream`` and ``dtype`` as the module's docstring says."""
    put = partial(jax.device_put, device=tokens.sharding)
    return jax.jit(partial(loss_logits_grads, cfg=cfg, dtype=dtype))(
        params, tokens=tokens, labels=labels,
        mask=put(flops.mask_array(spec)),
        pos=put(positions_in_documents(spec)), routes=routes, stream=stream)


# The names compared, each with its kind (``cellbench/reference.py``) and
# tolerance. The system computes in bf16 with fp32 accumulation from fp32
# masters (the router's scores, the norms' sums, the rotation and q's scales
# in fp32); the reference is fp32. One bf16 rounding is 1.1e-3 rms, relative.
#
# **Whole stream, and block by block.** The softmax scale is 2.2 / sqrt(128)
# and q and k are drawn from a seed, unit variance a channel: a query's scores
# spread 2.2 where the other families' spread 1, its largest over some
# thousand keys lie near 8, and a relative error d of the stream moves those
# by 8 d sqrt(2), an order more than d. A token whose attention flips between
# two keys leaves the program's and the reference's streams apart for good:
# ``logits``, the gradients and ``route_scores`` are sums over such tokens,
# three times the other families' readings, the same to a part in a hundred
# from seed to seed. ``attn_blocks``, ``expert_blocks`` and ``head_logits``
# are read where nothing compounds: each block of the reference on the
# program's own input of it (``forward``'s ``stream``).
#
# Beside each limit its two readings at the published widths on the chip, 8192
# tokens (my chip runs, PR 37; ``PERF.md`` section 6): the largest sound one
# over the seeds run, and ``B``, this reference in bf16 throughout (running
# sums too) against itself in float32 on the same routes and stream, which
# reads twice the program and more on every name of the stream; then the
# planted faults' (``N``: the kv latent's norm dropped, read with ``B``;
# ``M``: m ** 2 left out of the softmax scale; ``H``: rotary pairs
# half-split; one seed each, before the names block by block were there). At
# toy widths on the CPU (``tests/test_models/test_mistral4.py``): ``P``, the
# position scale left out, which the chip's check cannot see (no document of
# its 8192 tokens reaches position 8192), and ``S``, sigmoid scores for the
# softmax.
CHECKS = {
    # sound 6.8e-4 | N 3.8e-3, H 4.8e-3, M 8.9e-3 (limit 1.66e-3 at 8188
    # targets; B 3.8e-4 to 7.5e-4: the loss is a mean, and does not tell)
    "loss": {
        "kind": "abs_per_sqrt_targets", "tol": 10 * 1.5e-2,
        "why": "ten times a mean of per-token errors of 1.5e-2",
    },
    # sound 3.86e-2 to 3.92e-2 (seventeen runs, seventeen seeds) | B 8.19e-2
    # | N 0.45, M 1.15, H 1.31 (toy: sound 2.0e-2 to 2.4e-2, P 6.0e-2)
    "logits": {
        "kind": "rel_frobenius", "tol": 5.6e-2,
        "why": "bf16 roundings of eight blocks and the head under a softmax "
               "scale that spreads a query's scores 2.2 wide; running sums "
               "kept in bf16 read twice that, a latent without its norm, a "
               "scale without m ** 2 or another rotation 0.4 and more",
    },
    # sound 0 at ROUTE_TIE 1e-2 | B 2.8e-3 to 4.8e-3 | one token sent to its
    # lowest-scored expert 3.9e-3 (toy 3.1e-2), S 7e-2 at toy
    "route_choice": {
        "kind": "rel_frobenius", "tol": 1e-3,
        "why": "no token may be sent to an expert that is further than a "
               "near-tie below the reference's fourth: the count is held at 0",
    },
    # sound 2.29e-2 to 2.34e-2 | B 4.84e-2 to 4.86e-2 | N 0.21, M 0.42, H
    # 0.45; S 16.5 at toy (toy: sound 1.6e-3 to 1.4e-2)
    "route_scores": {
        "kind": "rel_frobenius", "tol": 3.4e-2,
        "why": "score + bias of the experts chosen, scored in float32 from "
               "the bf16 stream: a relative error d of a logit is d of its "
               "softmax score, and the stream's is 2e-2 here; a stream of "
               "bf16 running sums reads twice that, another score function "
               "or a fault below the router 0.2 and more",
    },
    # sound 1.02e-2 | B 1.97e-2 | N 0.125 (toy: sound 9.5e-3, P 2e-2 and more)
    "attn_blocks": {
        "kind": "rel_frobenius", "tol": 1.4e-2,
        "why": "what the four attention blocks add to the program's own "
               "stream: the chains' and FFA's roundings under the sharp "
               "softmax, once, and the stream's own rounding of the sum; "
               "running sums in bf16 (the chains', the keys') read twice "
               "that",
    },
    # sound 5.05e-3 to 5.06e-3 | B 1.22e-2 (N 5.06e-3: a fault in the
    # attention is not this name's)
    "expert_blocks": {
        "kind": "rel_frobenius", "tol": 7.8e-3,
        "why": "what the four expert blocks add to the program's own stream, "
               "the routes forced: the shared and the held experts' two "
               "products each; running sums in bf16 over 4096 and 2048 "
               "channels read 2.4 times that",
    },
    # sound 2.35e-3 | B 7.14e-3
    "head_logits": {
        "kind": "rel_frobenius", "tol": 4.1e-3,
        "why": "the final norm and the head on the program's own last "
               "stream: two roundings; a running sum in bf16 over 4096 "
               "channels reads three times that",
    },
    # sound 7.1e-2 to 7.9e-2 over the six names and seventeen runs | B 0.152
    # to 0.157 | N 0.77, M 1.02, H 1.36 (toy: sound 3.7e-2 to 5.3e-2, P 0.11
    # to 0.13)
    **{name: {
        "kind": "rel_frobenius", "tol": 1e-1,
        "why": "through the sharp softmax twice (forward and backward) and "
               "every block above: twice the forward's reading; running sums "
               "in bf16 read 0.15, a position scale left out of q 0.11 and "
               "more at toy widths, any other planted fault 0.6 and more",
    } for name in GRAD_LEAVES},
}
