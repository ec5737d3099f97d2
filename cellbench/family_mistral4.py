"""Step builder of the ``mistral4`` family (Mistral Small 4): decoders of
latent attention (low-rank q and kv chains, one rotary key a token, YaRN
frequencies, a position scale on q) and softmax-routed SwiGLU experts with
a shared one, run through ``magiattention_tpu.models.hybrid`` exactly as a
user would.

With ``reference_mistral4.py`` the only file of the benchmark that knows
this layer's equations. A model layer is two blocks of the program's pattern
(``*`` then ``E``); the attention runs EXPANDED — every head's keys and
values materialised, 32 query heads over 32 key-value heads through
``calc_attn``, g = 1 — which is the training form: nothing is absorbed and
no kernel attends in the latent space. The softmax scale beyond ``head_dim
** -0.5`` and the position scale are folded into q by the block
(``models/llama.py:attn_block``); the runtime key is made as every other
family's and carries no scale.

What is the program's and not the layer's (``make_key``, ``timed_plan``,
``plan_facts``, ``pallas_kernels``, the base of ``what_ran``) is
``cellbench.family_llama``'s. As in ``cellbench.family_afmoe`` (its
docstring says why each) the routers' biases are fitted on the ring's own
batches, with its ``ring_batches`` and ``balance_routers`` and then centred
(:func:`balance_routers`), the comparison is teacher-forced on the routes,
and the routing counters are read after the steps: ``routing_counters`` IS
``family_afmoe``'s, on the one run's state the two modules share (a process
runs one cell). Beyond ``manifest.FAMILY_INTERFACE``: ``routing_counters``,
``tight_tier`` and ``grouped_calls`` (for
``metrics/moe_grouped_roofline.py``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from magiattention_tpu.api import dispatch, undispatch
from magiattention_tpu.kernels import registry
from magiattention_tpu.models import hybrid, llama

from cellbench import (
    family_afmoe,
    family_llama,
    family_nemotron_h,
    flops,
    reference_mistral4,
)
from cellbench.traffic_gen import MaskSpec

make_key = family_llama.make_key
timed_plan = family_llama.timed_plan
plan_facts = family_llama.plan_facts
pallas_kernels = family_llama.pallas_kernels
CHECKS = reference_mistral4.CHECKS
ring_batches = family_afmoe.ring_batches
routing_counters = family_afmoe.routing_counters  # reads the shared _RUN

# Rehearsal widths (``--rehearse-cpu``). head_dim 128 = 64 + 64 and g = 1
# are kept, so the same kernel bodies run; the router's width, the experts
# held and the top 4 stay the configuration's (what ``route_scores`` reads
# depends on how far up the softmax the chosen scores lie, and the limit is
# the cell's). ``original_max_position_embeddings`` 64 puts the YaRN ramp
# and the position scale inside the toy's documents.
TOY = {
    "hidden_size": 256, "num_hidden_layers": 2, "vocab_size": 512,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "q_lora_rank": 128, "kv_lora_rank": 64, "moe_intermediate_size": 128,
    "moe_token_block": 256,
    "rope_parameters": {
        "beta_fast": 32, "beta_slow": 1, "factor": 128,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 64, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"},
}

# one run's state, begun anew by ``init_params``: ``family_afmoe``'s own
# dict with the same keys (``last`` holds its ``Keys``, no window key), so
# that its ``routing_counters`` is this family's and no third copy
_RUN = family_afmoe._RUN


def latent(cfg: dict) -> llama.LatentAttention:
    rope = cfg["rope_parameters"]
    assert rope["rope_type"] == "yarn" and rope["mscale"] == (
        rope["mscale_all_dim"]), cfg["name"]  # cos and sin times 1
    assert cfg["rope_interleave"], cfg["name"]  # the block's one pairing
    assert cfg["v_head_dim"] == cfg["qk_head_dim"] == cfg["head_dim"] == (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]), cfg["name"]
    return llama.LatentAttention(
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        rope_dim=cfg["qk_rope_head_dim"],
        yarn_factor=float(rope["factor"]),
        yarn_original_len=rope["original_max_position_embeddings"],
        yarn_beta_fast=float(rope["beta_fast"]),
        yarn_beta_slow=float(rope["beta_slow"]),
        mscale_all_dim=float(rope["mscale_all_dim"]),
        pos_scale_beta=float(rope["llama_4_scaling_beta"]))


def model_config(cfg: dict) -> hybrid.HybridConfig:
    assert cfg["first_k_dense_replace"] == 0 and cfg["norm_topk_prob"]
    return hybrid.HybridConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        pattern="*E" * cfg["num_hidden_layers"],
        norm_eps=cfg["rms_norm_eps"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        latent=latent(cfg),
        n_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"],
        expert_ffn=cfg["moe_intermediate_size"],
        shared_ffn=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        expert_act="swiglu", route="softmax_topk",
        moe_token_block=cfg["moe_token_block"],
        dtype="bfloat16", remat=True,
    )


def init_params(mcfg: hybrid.HybridConfig, mesh: Mesh, seed: int) -> dict:
    """fp32 masters from ``seed``, made on the device by one jitted call;
    the cell of this family runs at cp 1, where every leaf is whole."""
    _RUN.clear()
    _RUN.update(seed=seed, mcfg=mcfg, batches=[], last=None, counters=None,
                check_routes=None)
    make = jax.jit(
        partial(hybrid.init_params, mcfg),
        out_shardings=NamedSharding(mesh, P()))
    return make(jax.random.PRNGKey(seed))


def balance_routers(params, mcfg, batches, key) -> dict:
    """``family_afmoe.balance_routers`` under this family's one key, each
    fitted bias then centred on 0. A choice reads the biases' differences
    alone; the rule's first steps are larger than any softmax score, every
    token then chooses the same few experts and the others' biases rise
    together by 0.8, which would drown the scores ``route_scores``
    compares (a score here is a few hundredths)."""
    fitted = family_afmoe.balance_routers(
        params, mcfg, batches, family_afmoe.Keys(full=key, window=None))
    return {**fitted, "layers": [
        {**lyr, "e_bias": lyr["e_bias"] - jnp.mean(lyr["e_bias"])}
        if "e_bias" in lyr else lyr for lyr in fitted["layers"]]}


def train_step(params, mcfg, tokens, labels, key):
    """The program's own jitted SGD step; parameters are donated. Before
    the first call on real arrays (the warm-up's) the routers' biases are
    fitted on the ring's batches; the distinct batches that come through are
    kept by reference for :func:`routing_counters`."""
    live = "seed" in _RUN and isinstance(tokens, jax.Array) and not (
        isinstance(tokens, jax.core.Tracer))
    if live:
        if not _RUN["batches"]:
            params = balance_routers(params, mcfg, ring_batches(
                mcfg, tokens.shape[0], _RUN["seed"]), key)
        if (len(_RUN["batches"]) < family_nemotron_h.TIMED_BATCHES_KEPT
                and not any(tokens is seen for seen in _RUN["batches"])):
            _RUN["batches"].append(tokens)
    params, loss = hybrid.train_step(params, mcfg, tokens, labels, key)
    if live:  # a reference, donated next step
        _RUN["last"] = (params, mcfg, family_afmoe.Keys(full=key, window=None))
    return params, loss


def tight_tier() -> dict | None:
    """Of the ``blocks`` of tokens all the expert layers ran on the timed
    batches (the program's own routing at the parameters the last step
    left), how many fitted the row buffer sized by what a block expects
    (``blocks_fitted``) and the most rows one had for the experts held.
    ``None`` before a step ran."""
    if not _RUN.get("batches"):
        return None
    params, mcfg, keys = _RUN["last"]
    counted = [jax.device_get(hybrid.routing_counters(
        params, mcfg, tokens, keys.full)) for tokens in _RUN["batches"]]
    block_rows = np.stack([c["block_rows"] for c in counted])
    return {
        "blocks_fitted": int(sum(c["blocks_fitted"].sum() for c in counted)),
        "blocks": int(block_rows.size),
        "block_rows_max": int(block_rows.max()),
    }


def check_program(mcfg: hybrid.HybridConfig, key):
    """``(params, tokens, labels) -> {name: value}`` for the names of
    ``CHECKS`` through ``hybrid.forward`` and ``masked_ce``: loss, logits
    (natural order), per ``E`` block the experts chosen as a 0/1 array and
    the score + bias of each, sorted, the gradients of
    ``reference_mistral4.GRAD_LEAVES``; block by block, what every attention
    and every expert block added to its input, and the head's logits (the
    program's own, again: the reference computes them from the program's
    last stream). The chosen ids and the residual stream are kept for
    :func:`reference`."""
    where = reference_mistral4.GRAD_LEAVES
    experts = [i for i, kind in enumerate(mcfg.pattern) if kind == "E"]

    def f(leaves, params, tokens, labels):
        p = reference_mistral4.with_leaves(params, where, leaves)
        logits, routes, stream = hybrid.forward(
            p, mcfg, tokens, key, with_routes=True, with_stream=True)
        loss = hybrid.masked_ce(logits, dispatch(labels, key))
        stream = jnp.stack([undispatch(x, key) for x in stream])
        topi = [undispatch(r["topi"], key) for r in routes]
        biased = [undispatch(r["scores"], key) + p["layers"][i]["e_bias"]
                  for i, r in zip(experts, routes)]
        chosen = jnp.stack([
            -jnp.sort(-jnp.take_along_axis(b, t, axis=-1), axis=-1)
            for b, t in zip(biased, topi)])
        return loss, (undispatch(logits, key), chosen, topi, stream)

    @jax.jit
    def run(params, tokens, labels):
        (loss, (logits, chosen, topi, stream)), grads = jax.value_and_grad(
            f, has_aux=True)(
            reference_mistral4.take_leaves(params, where), params, tokens,
            labels)
        choice = jnp.stack([
            jnp.sum(jax.nn.one_hot(t, mcfg.n_experts, dtype=jnp.float32),
                    axis=1) for t in topi])
        # what each block added to its own input: exact in float32
        added = jnp.diff(stream.astype(jnp.float32), axis=0)
        return (loss, logits, chosen, choice, added[0::2], added[1::2], topi,
                stream, grads)

    def named(params, tokens, labels) -> dict:
        (loss, logits, chosen, choice, attn_blocks, expert_blocks, topi,
         stream, grads) = run(params, tokens, labels)
        _RUN["check_routes"], _RUN["check_stream"] = topi, stream
        return {"loss": loss, "logits": logits, "route_scores": chosen,
                "route_choice": choice, "attn_blocks": attn_blocks,
                "expert_blocks": expert_blocks, "head_logits": logits,
                **grads}

    return named


def reference(params, cfg, tokens, labels, spec, dtype=jnp.float32) -> dict:
    """The plain reference on the experts the last check program chose,
    and block by block on that program's residual stream (``dtype``:
    ``reference_mistral4``'s lower-precision control)."""
    if _RUN.get("check_routes") is None:
        raise RuntimeError(
            "family_mistral4.reference before check_program's program ran: "
            "the comparison is teacher-forced on the program's routes, and "
            "without them it would be another, looser comparison")
    routes, stream = jax.device_put(
        (list(_RUN.pop("check_routes")), _RUN.pop("check_stream")),
        tokens.sharding)
    return reference_mistral4.reference(
        params, cfg, tokens, labels, spec, routes=routes, stream=stream,
        dtype=dtype)


def layer_matmul_params(cfg: dict) -> dict:
    """Weights a token is multiplied by in one layer: the latent chains and
    the output projection; the router, the shared expert and the held
    experts at the EXPECTED rows (a token's choices fall on them with
    probability held / router's width)."""
    dim, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    expert = 3 * dim * cfg["moe_intermediate_size"]
    per_token = cfg["num_experts_per_tok"] * (
        cfg["n_routed_experts"] / cfg["router_experts"])
    return {
        "attention": dim * cfg["q_lora_rank"]
        + cfg["q_lora_rank"] * heads * (nope + rope)
        + dim * (cfg["kv_lora_rank"] + rope)
        + cfg["kv_lora_rank"] * heads * (nope + dv) + heads * dv * dim,
        "experts": dim * cfg["router_experts"]
        + (cfg["n_shared_experts"] + per_token) * expert,
    }


def required_flops_per_step(cfg: dict, spec: MaskSpec) -> int:
    """Required convention (``cellbench/flops.py``): matmuls at ``6 p`` a
    token (the held experts at their expected rows), every layer's
    attention over the mask's band area as the 32 full heads it runs;
    recomputation not counted."""
    layers = cfg["num_hidden_layers"]
    weights = layers * sum(layer_matmul_params(cfg).values()) + (
        cfg["hidden_size"] * cfg["vocab_size"])  # untied head; embed gathers
    attn = layers * (1 + flops.ATTN_BWD_OVER_FWD) * flops.attn_fwd_flops(
        flops.band_area(spec), cfg["num_attention_heads"],
        cfg["qk_head_dim"], cfg["v_head_dim"])
    return int(flops.matmul_flops(weights, spec.tokens) + attn)


def ffa_calls(cfg: dict) -> list[dict]:
    """One group: every layer attends expanded, as many key-value heads as
    query heads, three calls a step under remat."""
    return [{
        "layers": cfg["num_hidden_layers"], "passes": ("fwd", "fwd", "bwd"),
        "hq": cfg["num_attention_heads"], "hk": cfg["num_attention_heads"],
        "d_qk": cfg["qk_head_dim"], "d_v": cfg["v_head_dim"],
    }]


def grouped_calls(cfg: dict) -> list[dict]:
    """The grouped products an expert layer makes a step, as
    ``family_afmoe.grouped_calls`` (the same layer code, no norm after it:
    the count a product is checked against the compiled step by
    ``tests/test_cellbench/test_mistral4_cell.py``)."""
    dim, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return [{
        "layers": cfg["num_hidden_layers"], "held": cfg["n_routed_experts"],
        "token_block": cfg["moe_token_block"],
        "products": [
            {"k": dim, "n": 2 * f, "calls": GROUPED_CALLS_A_PRODUCT},
            {"k": f, "n": dim, "calls": GROUPED_CALLS_A_PRODUCT}],
    }]


# Of each of an expert layer's two products, the calls a token block makes a
# step: the forward; the block's re-forward under remat; the token block's
# own re-forward (``dropless_moe_ffn`` rematerialises each block); the
# transposed product (``d rows``, ``d act``); ``dW``.
GROUPED_CALLS_A_PRODUCT = 5


def what_ran() -> dict:
    """``family_llama.what_ran()`` and this family's own: the attention's
    form with the key-value channels a token it materialises against the
    latent's, the tiles and the backward mode at g = 1, the route, the
    grouped product's backend, the routing counters and the tight tier's
    share."""
    form = None
    if "mcfg" in _RUN:  # the run's model: attn_block's latent path
        mcfg = _RUN["mcfg"]
        form = (f"expanded: {mcfg.n_heads * 2 * mcfg.head_dim} key-value "
                f"channels a token of a latent "
                f"{mcfg.latent.kv_rank + mcfg.latent.rope_dim}")
    return {
        **family_llama.what_ran(),
        "attention_form": form,
        "ffa_tiles": registry.last_choice("ffa_tiles"),
        "ffa_tiles_source": registry.last_source("ffa_tiles"),
        "moe_route": registry.last_choice("moe_route"),
        "moe_grouped": registry.last_choice("moe_grouped"),
        "moe_grouped_tiles": registry.last_choice("moe_grouped_tiles"),
        "moe_row_buffer": registry.last_choice("moe_row_buffer"),
        "routing": routing_counters(),
        "tight_tier": tight_tier(),
    }
