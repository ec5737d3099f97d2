"""Step builder of the ``afmoe`` family (Arcee Trinity): decoders whose
layers alternate between a sliding window and the full mask, with gated
q/k-normed attention, sandwich norms and SwiGLU shared-plus-routed experts,
run through ``magiattention_tpu.models.hybrid`` exactly as a user would.

With ``reference_afmoe.py`` the only file of the benchmark that knows this
layer's equations. A model layer is two blocks of the program's pattern
(``W`` or ``*``, then ``D`` or ``E``). **A step takes two runtime keys**:
the full-causal key, which owns the dispatch, and a window key made of it
after dispatch (``api.make_varlen_key_for_new_mask_after_dispatch``), both
static arguments of the one ``hybrid.train_step`` program. ``make_key`` and
``timed_plan`` hand the harness both as one :class:`Keys`, which it only
hands back; the keys are labelled ``full`` and ``window``, so a device trace
names a window layer's kernels ``magi_fwd_kernel_window`` and the registry
keeps each key's tiles and backward mode (``what_ran``).

The traffic's ``window`` is null (the mask of the FULL layers is the
cell's): the window is the configuration's ``sliding_window``, which
``model_config`` notes for ``make_key`` (the harness builds the model's
configuration before it plans; a ``make_key`` before any ``model_config``
raises).

What is the program's and not the layer's (``pallas_kernels``, the base of
``plan_facts`` and of ``what_ran``, the mask's slices) is
``cellbench.family_llama``'s; the comparison is teacher-forced on the routes
as ``family_nemotron_h``'s.

**The routers' biases are the benchmark's set-up, not a trained state**, and
the balancing rule is ``cellbench.family_nemotron_h.balancing_bias`` as
there, fitted before the first step, layer by layer. **On which batches
differs, and why.** There a bias fitted on a batch of the family's own evens
every other batch (a token's route follows its embedding). Here it does not:
with weights drawn from a seed an attention layer averages its window, the
norm after it scales that average up to the residual stream's size, and the
experts a batch's tokens prefer then follow the BATCH, not the expert — a
bias fitted on one batch evens that batch (fullest expert 1.00 of the mean)
and leaves every other as uneven as no bias (3.6 to 5.2, the rows this
chip's 32 experts get swinging 5.5% with the seed and ``tokens_per_s`` with
them; my chip runs, PR 33). So the bias is fitted on the ring's own batches
together (:func:`ring_batches`), as the rule would have been run in the
training the cell times: the ring as a whole is even, its rows are the
expected ``tokens x top_k x held / width`` to a part in a thousand, and each
batch keeps the skew of its own (about 2), which
``moe_expert_load_max_over_mean`` reads. Beyond ``manifest.FAMILY_INTERFACE``: ``routing_counters`` (the
expert blocks' rows on the timed batches) and ``grouped_calls`` (the grouped
products a step makes, for ``metrics/moe_grouped_roofline.py``).
"""

from __future__ import annotations

import collections
import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from magiattention_tpu.api import (
    dispatch,
    magi_attn_flex_key,
    make_varlen_key_for_new_mask_after_dispatch,
    undispatch,
)
from magiattention_tpu.kernels import registry
from magiattention_tpu.models import hybrid

from cellbench import family_llama, family_nemotron_h, flops, reference_afmoe
from cellbench.traffic_gen import MaskSpec, token_batches

pallas_kernels = family_llama.pallas_kernels
CHECKS = reference_afmoe.CHECKS
SLIDING, FULL = reference_afmoe.SLIDING, reference_afmoe.FULL

# Rehearsal widths (``--rehearse-cpu``). The attention group of 8 and
# head_dim 128 are kept, so the same kernel bodies and the same packing
# mix run; the window is cut so that it still cuts documents of the toy
# traffic (the traffic's own window is null: the harness scales none). The
# router's width and the experts held stay the configuration's (top 8 of
# 128, 32 held): what ``route_scores`` reads depends on how far up the
# sigmoid the chosen scores lie (top 8 of 32 read 2.2e-3 where the cell reads
# 1.3e-3), and the limit is the cell's.
TOY = {
    "hidden_size": 256, "num_hidden_layers": 3, "num_dense_layers": 1,
    "layer_types": [SLIDING, SLIDING, FULL], "vocab_size": 512,
    "num_attention_heads": 8, "num_key_value_heads": 1, "head_dim": 128,
    "intermediate_size": 512, "moe_intermediate_size": 128,
    "sliding_window": 64, "moe_token_block": 256,
}

FIT_BATCHES = 4  # the ring's batches the biases are fitted on
_RUN: dict = {}  # one run's state, begun anew by ``init_params``
_WINDOW: dict = {}  # the sliding window of the last ``model_config``


# The step's two runtime keys: ``full`` owns the dispatch, ``window`` is made
# of it and shares its layout. (A namedtuple: this file is loaded by path
# and is in no ``sys.modules``, which a dataclass would look itself up in.)
Keys = collections.namedtuple("Keys", ("full", "window"))


def pattern(cfg: dict) -> str:
    """Two blocks a model layer: its attention (``W`` under the window,
    ``*`` under the full mask), then its MLP (``D`` dense, ``E`` experts)."""
    kinds = cfg["layer_types"]
    assert len(kinds) == cfg["num_hidden_layers"], cfg["name"]
    return "".join(
        {SLIDING: "W", FULL: "*"}[kind]
        + ("D" if i < cfg["num_dense_layers"] else "E")
        for i, kind in enumerate(kinds))


def model_config(cfg: dict) -> hybrid.HybridConfig:
    _WINDOW["sliding_window"] = cfg["sliding_window"]
    return hybrid.HybridConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        pattern=pattern(cfg), norm_eps=cfg["rms_norm_eps"], post_norm=True,
        embed_scale=cfg["hidden_size"] ** 0.5,  # mup_enabled
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), rope_in="W",
        qk_norm=True, attn_gate=True, dense_ffn=cfg["intermediate_size"],
        n_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        experts_held=cfg["num_experts"], expert_offset=cfg["expert_offset"],
        expert_ffn=cfg["moe_intermediate_size"],
        shared_ffn=cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        routed_scale=cfg["route_scale"], expert_act="swiglu",
        moe_token_block=cfg["moe_token_block"],
        dtype="bfloat16", remat=True,
    )


def make_key(spec: MaskSpec, mesh: Mesh) -> Keys:
    """Both keys of ``spec``'s documents through the program's public mask
    compilers: the full-causal one plans the dispatch; the window one (a
    query sees its ``sliding_window`` most recent keys counting itself,
    ``window_size=(window - 1, 0)``) is made of it after dispatch."""
    if "sliding_window" not in _WINDOW:
        raise RuntimeError(
            "family_afmoe.make_key before model_config: the window is the "
            "configuration's sliding_window, not the traffic's")
    if spec.window is not None:
        raise ValueError(
            f"traffic window {spec.window}: this family's window is its "
            "layers', the traffic has none")
    qr, kr, types = family_llama.mask_slices(spec)
    full = magi_attn_flex_key(
        qr, kr, types, spec.tokens, spec.tokens, mesh=mesh, cp_axis="cp",
        label="full")
    cu = list(spec.cu_seqlens)
    window = make_varlen_key_for_new_mask_after_dispatch(
        cu, cu, full, causal=False, window_size=(_WINDOW["sliding_window"] - 1, 0),
        label="window")
    return Keys(full=full, window=window)


def timed_plan(spec: MaskSpec, mesh: Mesh):
    """Both keys, and the host milliseconds both plans took."""
    t0 = time.perf_counter()
    keys = make_key(spec, mesh)
    return keys, (time.perf_counter() - t0) * 1e3


def plan_facts(keys: Keys, spec_rows_area: np.ndarray) -> dict:
    """The full key's facts (it owns the dispatch; the window key's ranks
    hold the same rows), ``slices`` the two keys' sum, each key's under
    ``slices_by_key``."""
    facts = family_llama.plan_facts(keys.full, spec_rows_area)
    by_key = {"full": len(keys.full.q_ranges),
              "window": len(keys.window.q_ranges)}
    return {**facts, "slices": sum(by_key.values()), "slices_by_key": by_key}


def init_params(mcfg: hybrid.HybridConfig, mesh: Mesh, seed: int) -> dict:
    """fp32 masters from ``seed``, made on the device by one jitted call;
    the cells of this family run at cp 1, where every leaf is whole."""
    _RUN.clear()
    _RUN.update(seed=seed, batches=[], last=None, counters=None,
                check_routes=None)
    make = jax.jit(
        partial(hybrid.init_params, mcfg),
        out_shardings=NamedSharding(mesh, P()))
    return make(jax.random.PRNGKey(seed))


def _forward(params, mcfg, tokens, keys: Keys):
    return hybrid.forward(
        params, mcfg, tokens, keys.full, with_routes=True,
        window_key=keys.window)


@partial(jax.jit, static_argnums=(1, 3))
def _fitted_bias(params, mcfg, batches, keys, block):
    """The balancing bias of the ``block``-th ``E`` block over the
    program's forwards on ``batches`` ``(n, tokens)``, one after the other;
    the bias alone comes back (set-up holds no array the steps do not)."""
    def scores(tokens):
        _, routes = _forward(params, mcfg, tokens, keys)
        return jnp.stack([r["scores"] for r in routes])[block]

    return family_nemotron_h.balancing_bias(
        jax.lax.map(scores, batches).reshape(-1, mcfg.n_experts), mcfg.top_k)


def balance_routers(params, mcfg, batches, keys: Keys) -> dict:
    """``params`` with every ``E`` block's ``e_bias`` fitted on ``batches``
    ``(n, tokens)`` together, the lowest block first (a block's input
    depends on the biases below it and not on its own)."""
    layers = list(params["layers"])
    experts = [i for i, kind in enumerate(mcfg.pattern) if kind == "E"]
    for n, i in enumerate(experts):
        layers[i] = {**layers[i], "e_bias": _fitted_bias(
            {**params, "layers": layers}, mcfg, batches, keys, n)}
    return {**params, "layers": layers}


def ring_batches(mcfg, tokens: int, seed: int):
    """The token ids of the ring's first ``FIT_BATCHES`` timed batches,
    ``(FIT_BATCHES, tokens)``: ``traffic_gen.token_batches`` draws the ids
    from the seed, the vocabulary and the length alone (the documents only
    place the labels), one batch after the other."""
    spec = MaskSpec(tokens, (0, tokens))
    return jnp.stack([jnp.asarray(toks) for toks, _ in token_batches(
        spec, mcfg.vocab_size, seed, FIT_BATCHES)])


def train_step(params, mcfg, tokens, labels, keys: Keys):
    """The program's own jitted SGD step under both keys; parameters are
    donated. Before the first call on real arrays (the warm-up's) the
    routers' biases are fitted on the ring's batches (:func:`ring_batches`;
    the module's docstring says why not on a batch of the family's own);
    the distinct batches that come through are kept by reference for
    :func:`routing_counters`."""
    live = "seed" in _RUN and isinstance(tokens, jax.Array) and not (
        isinstance(tokens, jax.core.Tracer))
    if live:
        if not _RUN["batches"]:
            params = balance_routers(params, mcfg, ring_batches(
                mcfg, tokens.shape[0], _RUN["seed"]), keys)
        if (len(_RUN["batches"]) < family_nemotron_h.TIMED_BATCHES_KEPT
                and not any(tokens is seen for seen in _RUN["batches"])):
            _RUN["batches"].append(tokens)
    params, loss = hybrid.train_step(
        params, mcfg, tokens, labels, keys.full, window_key=keys.window)
    if live:
        _RUN["last"] = (params, mcfg, keys)  # a reference, donated next step
    return params, loss


def routing_counters() -> dict | None:
    """As ``family_nemotron_h.routing_counters``: from the program's own
    routing on every timed batch at the parameters the last step left, the
    rows the grouped products took (summed over the expert blocks, a
    batch's mean), the fullest expert's rows over its block's mean, the
    rows routed to the experts held that no grouped product took. ``None``
    before a step ran."""
    if not _RUN.get("batches"):
        return None
    if _RUN["counters"] is None:
        params, mcfg, keys = _RUN["last"]
        counted = [jax.device_get(hybrid.routing_counters(
            params, mcfg, tokens, keys.full, window_key=keys.window))
            for tokens in _RUN["batches"]]
        rows = np.stack(
            [c["rows_per_expert"] for c in counted]).astype(np.float64)
        routed = np.stack([c["rows_routed"] for c in counted])
        _RUN["counters"] = {
            "batches": len(counted),
            "routed_rows": float(rows.sum(axis=(1, 2)).mean()),
            "load_max_over_mean": float(
                (rows.max(axis=-1) / rows.mean(axis=-1)).max()),
            "rows_dropped": int(routed.sum() - rows.sum()),
        }
    return _RUN["counters"]


def _leaves(mcfg: hybrid.HybridConfig) -> dict:
    """``reference_afmoe.grad_leaves`` of the program's pattern."""
    return reference_afmoe.grad_leaves({
        "layer_types": [
            SLIDING if kind == "W" else FULL for kind in mcfg.pattern[0::2]],
        "num_dense_layers": mcfg.pattern.count("D")})


def check_program(mcfg: hybrid.HybridConfig, keys: Keys):
    """``(params, tokens, labels) -> {name: value}`` for the names of
    ``CHECKS`` through ``hybrid.forward`` under both keys and ``masked_ce``:
    loss, logits (natural order), per ``E`` block the experts chosen as a
    0/1 array and the score + bias of each, sorted, the gradients of
    ``reference_afmoe.grad_leaves``. The chosen ids are kept for
    :func:`reference`."""
    where = _leaves(mcfg)
    experts = [i for i, kind in enumerate(mcfg.pattern) if kind == "E"]
    key = keys.full

    def f(leaves, params, tokens, labels):
        p = reference_afmoe.with_leaves(params, where, leaves)
        logits, routes = _forward(p, mcfg, tokens, keys)
        loss = hybrid.masked_ce(logits, dispatch(labels, key))
        topi = [undispatch(r["topi"], key) for r in routes]
        biased = [undispatch(r["scores"], key) + p["layers"][i]["e_bias"]
                  for i, r in zip(experts, routes)]
        chosen = jnp.stack([
            -jnp.sort(-jnp.take_along_axis(b, t, axis=-1), axis=-1)
            for b, t in zip(biased, topi)])
        return loss, (undispatch(logits, key), chosen, topi)

    @jax.jit
    def run(params, tokens, labels):
        (loss, (logits, chosen, topi)), grads = jax.value_and_grad(
            f, has_aux=True)(
            reference_afmoe.take_leaves(params, where), params, tokens,
            labels)
        choice = jnp.stack([
            jnp.sum(jax.nn.one_hot(t, mcfg.n_experts, dtype=jnp.float32),
                    axis=1) for t in topi])
        return loss, logits, chosen, choice, topi, grads

    def named(params, tokens, labels) -> dict:
        loss, logits, chosen, choice, topi, grads = run(params, tokens, labels)
        _RUN["check_routes"] = topi
        return {"loss": loss, "logits": logits, "route_scores": chosen,
                "route_choice": choice, **grads}

    return named


def reference(params, cfg, tokens, labels, spec, dtype=jnp.float32) -> dict:
    """The plain reference on the experts the last check program chose
    (``dtype``: ``reference_afmoe``'s lower-precision control)."""
    if _RUN.get("check_routes") is None:
        raise RuntimeError(
            "family_afmoe.reference before check_program's program ran: the "
            "comparison is teacher-forced on the program's routes, and "
            "without them it would be another, looser comparison")
    routes = [jax.device_put(r, tokens.sharding)
              for r in _RUN.pop("check_routes")]
    return reference_afmoe.reference(
        params, cfg, tokens, labels, spec, routes=routes, dtype=dtype)


def _layer_counts(cfg: dict) -> dict:
    kinds = cfg["layer_types"]
    return {"window": kinds.count(SLIDING), "full": kinds.count(FULL),
            "dense": cfg["num_dense_layers"],
            "experts": len(kinds) - cfg["num_dense_layers"]}


def _window_of(cfg: dict) -> dict:
    return {"window": cfg["sliding_window"], "full": None}


def required_flops_per_step(cfg: dict, spec: MaskSpec) -> int:
    """Required convention (``cellbench/flops.py``): matmuls at ``6 p`` a
    token (an expert layer's held experts at the EXPECTED rows: a token's
    choices fall on them with probability held / router's width), each
    attention layer over ITS mask's band area (the window layers' band, the
    full layers' triangle); recomputation not counted."""
    dim, dh = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n = _layer_counts(cfg)
    attention = 2 * dim * hq * dh + 2 * dim * hk * dh + hq * dh * dim
    expert = 3 * dim * cfg["moe_intermediate_size"]
    per_token = cfg["num_experts_per_tok"] * (
        cfg["num_experts"] / cfg["router_experts"])
    weights = (
        (n["window"] + n["full"]) * attention
        + n["dense"] * 3 * dim * cfg["intermediate_size"]
        + n["experts"] * (dim * cfg["router_experts"]
                          + (cfg["num_shared_experts"] + per_token) * expert)
        + dim * cfg["vocab_size"])  # untied head; the embedding gathers
    attn = sum(
        n[kind] * (1 + flops.ATTN_BWD_OVER_FWD) * flops.attn_fwd_flops(
            flops.band_area(dataclasses.replace(spec, window=window)),
            hq, dh, dh)
        for kind, window in _window_of(cfg).items())
    return int(flops.matmul_flops(weights, spec.tokens) + attn)


def ffa_calls(cfg: dict) -> list[dict]:
    """One group a kind of attention layer, each layer three calls a step
    under remat; ``kind`` is the key's label in the kernels' names and
    ``window`` the mask a reader has to price the group on (the cell's
    ``spec`` carries none)."""
    n = _layer_counts(cfg)
    return [{
        "kind": kind, "window": window, "layers": n[kind],
        "passes": ("fwd", "fwd", "bwd"),
        "hq": cfg["num_attention_heads"], "hk": cfg["num_key_value_heads"],
        "d_qk": cfg["head_dim"], "d_v": cfg["head_dim"],
    } for kind, window in _window_of(cfg).items() if n[kind]]


# Of each of an expert layer's two products, the calls a token block makes a
# step (counted in the step compiled for a v5e, PR 33): the forward; the
# block's re-forward under remat (the post-norm's backward reads the layer's
# output, so the whole layer is made again); the token block's own re-forward
# (``dropless_moe_ffn`` rematerialises each block, and the routing weights'
# gradient reads the experts' outputs); the transposed product (``d rows``,
# ``d act``); ``dW``.
GROUPED_CALLS_A_PRODUCT = 5


def grouped_calls(cfg: dict) -> list[dict]:
    """The grouped products an expert layer makes a step, as the program
    makes them (``models/moe.py``): the gate-and-up product ``[rows, dim] x
    [dim, 2 f]`` and the down product ``[rows, f] x [f, dim]``, each
    ``GROUPED_CALLS_A_PRODUCT`` times a token block. ``k`` and ``n`` a
    product's inner and outer width, ``held`` the weights it reads,
    ``token_block`` the tokens a call takes."""
    dim, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return [{
        "layers": _layer_counts(cfg)["experts"], "held": cfg["num_experts"],
        "token_block": cfg["moe_token_block"],
        "products": [
            {"k": dim, "n": 2 * f, "calls": GROUPED_CALLS_A_PRODUCT},
            {"k": f, "n": dim, "calls": GROUPED_CALLS_A_PRODUCT}],
    }]


def what_ran() -> dict:
    """``family_llama.what_ran()`` and, per key (``full``, ``window``), the
    tiles with their packing and the backward mode the registry recorded;
    the grouped product's backend and the routing counters."""
    return {
        **family_llama.what_ran(),
        "ffa_tiles": registry.labelled_choices("ffa_tiles"),
        "ffa_bwd_mode_by_key": registry.labelled_choices("ffa_bwd"),
        "moe_grouped": registry.last_choice("moe_grouped"),
        "moe_grouped_tiles": registry.last_choice("moe_grouped_tiles"),
        "routing": routing_counters(),
    }
