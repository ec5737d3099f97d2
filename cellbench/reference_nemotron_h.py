"""The plain reference of the ``nemotron_h`` family's blocks, and what is
compared.

NVIDIA-Nemotron-3-Nano's decoder as its config publishes it: every block is
``x + mixer(RMSNorm(x))`` with one mixer, by ``hybrid_override_pattern``:

* ``M``, Mamba-2: ``[z | xBC | dt] = in_proj(u)``; ``xBC = silu(conv1d(xBC)
  + b)``, causal, depthwise, taps never reaching across a document's first
  token; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t`` with ``h = 0`` before a
  document's first token, ``y_t = h_t C_t + D x_t``; ``y * silu(z)``, RMSNorm
  over each of the ``n_groups`` groups of channels, ``out_proj``. The
  recurrence is run AS the recurrence, one token at a time (``lax.scan``,
  every head at once, states rematerialised in blocks of ``SCAN_BLOCK``
  tokens): no chunk, no quadratic form, no running sum of decays;
* ``E``, experts: ``s = sigmoid(h W_r)`` over all ``router_experts``, the
  top ``num_experts_per_tok`` of ``s + bias`` chosen, weights ``s[chosen] /
  sum * routed_scaling_factor``; an expert is ``W_down relu(W_up h)^2``, a
  plain loop over the experts HELD (``n_routed_experts`` of them, numbers
  ``expert_offset`` onward: what the others would add is left out, as in
  the program); one shared expert of the same form for every token;
* ``*``, attention: grouped-query softmax attention, causal inside a
  document, no rotary embedding (``assumed`` in the configuration file).

Float32 throughout, ``jax.numpy`` only, under
``jax.default_matmul_precision("highest")``. It imports nothing from the
program: the parameter tree is data (``x @ w`` layouts, leaves named as
below).

**The routes.** Choosing the top 6 of 128 scores is discontinuous: two
computations of the same layer that differ by a rounding disagree on the
sixth expert for a few tokens in a hundred (the gap between the sixth and
seventh score is under 1e-2 for one token in eight), and each such token is
then off by a whole expert's output. So the comparison is teacher-forced:
``routes``, when given, are the expert ids the program chose, per ``E``
block ``(tokens, k)``; the reference weighs THOSE experts with its own
float32 scores. That the program's choice is a legitimate one is held by two
checks of their own. ``route_choice``, token by token: the program's chosen
set as a 0/1 array over the router's width against the reference's own
top-k set, in which an expert whose score + bias lies within ``ROUTE_TIE``
of the reference's k-th takes the program's word (either choice is a
legitimate one there); one token with an expert further off is an error of
``sqrt(2 / (tokens x k x blocks))``, above the limit. ``route_scores``: the
reference's own top-k of score + bias, sorted, against the program's score +
bias of the experts it chose (what the top-k is taken of: at a near-tie the
two sides' sixth values agree, whichever expert each took). Without
``routes`` the reference routes by its own scores.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import flops

SCAN_BLOCK = 64
# Half the width of a near-tie of score + bias, between its two readings (my
# chip runs, PR 31, published widths, 4096 tokens): the program's scores
# differ from the reference's by its bf16 stream, by at most 1.26e-2 for any
# chosen expert of any token, and its deepest sound choice lay between 5e-3
# and 1e-2 under the reference's k-th (17 of 98304 pairs beyond 5e-3, none
# beyond 1e-2); the planted fault lies 0.1 under it. (A token's sixth and
# seventh best lie 7.3e-3 apart in the median: the band is several experts
# wide, and a choice inside it is not judged.)
ROUTE_TIE = 4e-2


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _positions_in_documents(spec) -> np.ndarray:
    cu = np.asarray(spec.cu_seqlens)
    return np.arange(spec.tokens) - np.repeat(cu[:-1], np.diff(cu))


def _conv_silu(x, w, b, pos):
    """``w`` (taps, channels), ``w[-1]`` on the token itself."""
    taps = w.shape[0]
    out = x * w[-1] + b
    for lag in range(1, taps):
        back = jnp.concatenate([jnp.zeros_like(x[:lag]), x[:-lag]], axis=0)
        out = out + jnp.where((pos >= lag)[:, None], back, 0.0) * w[
            taps - 1 - lag]
    return jax.nn.silu(out)


def _recurrence(x, dt, a, b, c, first):
    """``y_t = C_t h_t``; x (T, H, P), dt (T, H), a (H,), b and c (T, H, N)
    already spread over the heads, ``first`` (T,) marks a document's first
    token."""
    t, h, p = x.shape
    pad = -t % SCAN_BLOCK
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                       for v in (x, dt, b, c))
        first = jnp.pad(first, (0, pad))

    def token(state, inp):
        x_t, dt_t, b_t, c_t, first_t = inp
        state = jnp.where(first_t, 0.0, state)
        state = jnp.exp(dt_t * a)[:, None, None] * state + (
            b_t[:, :, None] * (dt_t[:, None] * x_t)[:, None, :])
        return state, jnp.einsum("hn,hnp->hp", c_t, state)

    @jax.checkpoint
    def block(state, inps):
        return jax.lax.scan(token, state, inps)

    blocks = jax.tree.map(
        lambda v: v.reshape(-1, SCAN_BLOCK, *v.shape[1:]),
        (x, dt, b, c, first))
    _, y = jax.lax.scan(
        block, jnp.zeros((h, b.shape[-1], p), x.dtype), blocks)
    return y.reshape(-1, h, p)[:t]


def _mamba(u, lyr, cfg, pos):
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    d_in, t = heads * p, u.shape[0]
    zxbcdt = u @ lyr["in_proj"]
    z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:2 * d_in + 2 * groups * n],
                  zxbcdt[:, 2 * d_in + 2 * groups * n:])
    xbc = _conv_silu(xbc, lyr["conv_w"], lyr["conv_b"], pos)
    x = xbc[:, :d_in].reshape(t, heads, p)
    b = xbc[:, d_in:d_in + groups * n].reshape(t, groups, n)
    c = xbc[:, d_in + groups * n:].reshape(t, groups, n)
    rep = heads // groups  # head h uses group h // rep
    dt = jax.nn.softplus(dt + lyr["dt_bias"])
    y = _recurrence(
        x, dt, -jnp.exp(lyr["A_log"]), jnp.repeat(b, rep, axis=1),
        jnp.repeat(c, rep, axis=1), pos == 0)
    y = (y + lyr["D"][:, None] * x).reshape(t, d_in) * jax.nn.silu(z)
    yg = y.reshape(t, groups, d_in // groups)
    yg = yg * jax.lax.rsqrt(
        jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg["norm_eps"])
    return (yg.reshape(t, d_in) * lyr["gate_norm"]) @ lyr["out_proj"]


def _relu2_mlp(h, w_up, w_down):
    return jnp.square(jax.nn.relu(h @ w_up)) @ w_down


def _experts(h, lyr, route, cfg):
    """``(the held experts' part + the shared expert, the reference's own
    top-k of score + bias, sorted (T, k), the set chosen as a 0/1 array (T,
    router's width): the reference's own, but ``route``'s word for an expert
    within ``ROUTE_TIE`` of the k-th)``."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ lyr["router"])
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
        return gate[:, None] * _relu2_mlp(h, w_up, w_down)

    held = lyr["w_up"].shape[0]
    routed, _ = jax.lax.scan(
        lambda total, expert: (total + expert_part(*expert), None),
        jnp.zeros_like(h),
        (cfg["expert_offset"] + jnp.arange(held), lyr["w_up"], lyr["w_down"]))
    return (routed + _relu2_mlp(h, lyr["ws_up"], lyr["ws_down"]), own_biased,
            choice)


def _attention(h, lyr, cfg, mask):
    hq, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = (h @ lyr["wq"]).reshape(-1, hq, dh)
    k = (h @ lyr["wk"]).reshape(-1, hk, dh)
    v = (h @ lyr["wv"]).reshape(-1, hk, dh)

    def one_head(args):
        qh, kh, vh = args
        s = jnp.where(mask, (qh @ kh.T) * dh ** -0.5, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    out = jax.lax.map(jax.checkpoint(one_head), (
        q.transpose(1, 0, 2),
        jnp.repeat(k, hq // hk, axis=1).transpose(1, 0, 2),
        jnp.repeat(v, hq // hk, axis=1).transpose(1, 0, 2)))
    return out.transpose(1, 0, 2).reshape(-1, hq * dh) @ lyr["wo"]


def forward(params, cfg, tokens, mask, pos, routes=None):
    """``(logits (tokens, vocab) float32 in natural order, (the reference's
    own sorted top-k of score + bias (E blocks, tokens, k), the sets chosen
    (E blocks, tokens, router's width), as :func:`_experts` gives them))``."""
    eps = cfg["norm_eps"]
    x = params["embed"][tokens]
    routes = iter(routes) if routes is not None else None
    scores, choices = [], []
    for kind, lyr in zip(cfg["hybrid_override_pattern"], params["layers"]):
        if kind == "M":
            x = x + jax.checkpoint(partial(_mamba, cfg=cfg, pos=pos))(
                _rms_norm(x, lyr["norm"], eps), lyr)
        elif kind == "E":
            route = next(routes) if routes is not None else None
            y, own, choice = jax.checkpoint(partial(_experts, cfg=cfg))(
                _rms_norm(x, lyr["norm"], eps), lyr, route)
            x = x + y
            scores.append(own)
            choices.append(choice)
        else:
            x = x + jax.checkpoint(partial(_attention, cfg=cfg, mask=mask))(
                _rms_norm(x, lyr["attn_norm"], eps), lyr)
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


def grad_leaves(pattern: str) -> dict:
    """``{check name: (layer index, leaf, index into the leaf or None)}``:
    the first ``M`` block's ``in_proj`` and ``A_log``, the first ``E``
    block's router and its first held expert's ``w_up``, the first ``*``
    block's ``wq``."""
    m, e, a = (pattern.index(kind) for kind in "ME*")
    return {
        "grad_in_proj": (m, "in_proj", None),
        "grad_A_log": (m, "A_log", None),
        "grad_router": (e, "router", None),
        "grad_expert_w_up": (e, "w_up", 0),
        "grad_wq": (a, "wq", None),
    }


def with_leaves(params: dict, where: dict, leaves: dict) -> dict:
    """``params`` with each named leaf (or slice of one) replaced."""
    layers = list(params["layers"])
    for name, (i, leaf, index) in where.items():
        value = leaves[name]
        if index is not None:
            value = layers[i][leaf].at[index].set(value)
        layers[i] = {**layers[i], leaf: value}
    return {**params, "layers": layers}


def take_leaves(params: dict, where: dict) -> dict:
    return {
        name: params["layers"][i][leaf] if index is None
        else params["layers"][i][leaf][index]
        for name, (i, leaf, index) in where.items()}


def loss_logits_grads(params, cfg, tokens, labels, mask, pos, routes):
    where = grad_leaves(cfg["hybrid_override_pattern"])

    def f(leaves):
        return loss_and_logits(
            with_leaves(params, where, leaves), cfg, tokens, labels, mask,
            pos, routes)

    with jax.default_matmul_precision("highest"):
        (loss, (logits, scores, choice)), grads = jax.value_and_grad(
            f, has_aux=True)(take_leaves(params, where))
    return {"loss": loss, "logits": logits, "route_scores": scores,
            "route_choice": choice, **grads}


def reference(params, cfg, tokens, labels, spec, routes=None) -> dict:
    """The values :data:`CHECKS` names, on the device that holds ``tokens``;
    ``routes`` as the module's docstring says."""
    put = partial(jax.device_put, device=tokens.sharding)
    return jax.jit(partial(loss_logits_grads, cfg=cfg))(
        params, tokens=tokens, labels=labels,
        mask=put(flops.mask_array(spec)),
        pos=put(_positions_in_documents(spec).astype(np.int32)),
        routes=routes)


# The names compared, each with its kind (``cellbench/reference.py``) and
# tolerance. The system computes in bf16 with fp32 accumulation from fp32
# masters (the scan's state, decays and the router's scores in fp32); the
# reference is fp32. One bf16 rounding is 1.1e-3 rms, relative. The loss,
# logits and wq bounds are the llama family's, whose derivation carries over
# (9 blocks here of one mixer each against 5 of two). Beside each: the
# largest reading on the chip over 33 runs and 29 seeds at the published
# widths (my chip runs, PR 31), and what breaks it. The reference itself in
# bf16 against float32 fails ``loss`` alone there (3.2e-3).
CHECKS = {
    # read 7.8e-5 to 4.4e-4 against 2.3e-3 at 4096 tokens
    "loss": {
        "kind": "abs_per_sqrt_targets", "tol": 10 * 1.5e-2,
        "why": "ten times a mean of per-token errors of 1.5e-2",
    },
    # ~10 roundings a block (the stream, the mixer's input, weights, the
    # inner activations, its output) over 9 blocks and the head: 1.1e-3 *
    # sqrt(94) = 1.1e-2; read 1.24e-2 to 1.27e-2. One routed row's expert
    # output missing is that token's logits off by a fifth.
    "logits": {
        "kind": "rel_frobenius", "tol": 3e-2,
        "why": "bf16 roundings give 1.1e-2 to 1.3e-2; a dropped routed row "
               "or a token routed elsewhere is an expert's whole output",
    },
    # the chosen sets, token by token, equal outside near-ties (ROUTE_TIE):
    # read 0 in every sound run; one token with one expert that is no
    # near-best is sqrt(1 / (4096 tokens x 6 x 4 blocks)) = 3.2e-3 or more
    "route_choice": {
        "kind": "rel_frobenius", "tol": 1e-3,
        "why": "no token may be sent to an expert that is further than a "
               "near-tie below the reference's sixth: the count is held at 0",
    },
    # score + bias of the experts chosen, sorted: read 1.1e-3 to 1.2e-3
    # (the router's input is off by the stream's 1e-2, a sigmoid score moves
    # by a quarter of its logit's error, the bias by nothing). Compared as
    # the score alone it read 6.3e-3 to 7.0e-3 once the bias was not zero:
    # a near-tie of score + bias is no near-tie of the score. One token in a
    # hundred sent to an expert 0.1 below its sixth reads 4.3e-3 (my chip
    # run, PR 31, at the check's size).
    "route_scores": {
        "kind": "rel_frobenius", "tol": 2.5e-3,
        "why": "twice the bf16 stream's reading; a choice that is not "
               "among the reference's near-best is off by the gap between "
               "the experts, 0.1 and more a token",
    },
    # the gradients see the forward twice (remat) and the backward once:
    # three times the roundings, 1.1e-3 * sqrt(3 * 94) = 1.8e-2, plus the
    # forward's error through the loss; read 1.84e-2 to 1.87e-2
    "grad_in_proj": {
        "kind": "rel_frobenius", "tol": 5e-2,
        "why": "through every block above, the scan's backward and the "
               "convolution: three times the forward's roundings",
    },
    # read 1.2e-2 to 3.1e-2 (64 numbers, each a sum with cancellation over
    # every token); with the scan's state, saved states and accumulators in
    # bf16 it fails at toy widths on the CPU (tests/test_models/test_hybrid.py);
    # a v5e's compiler refuses that body, and what can be rounded around it
    # (matmul results, stored decay sums) reads 2.0e-2 to 2.1e-2 there
    "grad_A_log": {
        "kind": "rel_frobenius", "tol": 5e-2,
        "why": "the scan's decay gradient: a state or a decay sum kept in "
               "bf16 shows here first",
    },
    # read 1.94e-2 to 1.99e-2; 0.20 when remat recomputed the routes
    "grad_router": {
        "kind": "rel_frobenius", "tol": 5e-2,
        "why": "through the routing weights of the experts held",
    },
    # read 1.85e-2 to 1.89e-2; 3e5 when the grouped product's rows past its
    # groups were not masked; one row dropped a block reads 7.1e-2 and 8.2e-2
    # on the chip at 4096 tokens, 0.11 to 0.18 at toy widths (CPU test)
    "grad_expert_w_up": {
        "kind": "rel_frobenius", "tol": 5e-2,
        "why": "one held expert's rows only: a row dropped from the grouped "
               "product or sent to the wrong expert is an error of order 1",
    },
    # read 2.57e-2 to 2.61e-2
    "grad_wq": {
        "kind": "rel_frobenius", "tol": 5e-2,
        "why": "as the llama family's grad_wq0, at g = 16",
    },
}
