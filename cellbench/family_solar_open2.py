"""Step builder of the ``solar_open2`` family (Solar Open 2): decoders whose
layers mix by the gated delta rule with a per-channel decay (KDA) three times
in four and by gated NoPE GQA once, each layer followed by sigmoid-routed
SwiGLU experts with a shared one, run through
``magiattention_tpu.models.hybrid`` exactly as a user would.

With ``reference_solar_open2.py`` the only file of the benchmark that knows
this layer's equations. A model layer is two blocks of the program's pattern
(``*`` or ``K``, then ``E``). What is the program's and not the layer's
(``make_key``, ``plan_facts``, ``pallas_kernels``, the base of ``what_ran``)
is ``cellbench.family_llama``'s; as in ``cellbench.family_mistral4`` the
routers' biases are fitted on the ring's own batches and centred, the
comparison is teacher-forced on the routes, and the routing counters are
read after the steps on the one run's state ``family_afmoe`` keeps.

**The timed recurrence.** ``correct`` also runs one KDA layer's kernel
(``kernels/kda.py:kda_scan``) at the TIMED tokens on the timed mask's
document starts (``timed_plan`` keeps the mask), every head, forward and its
``jax.vjp``, on inputs drawn from the seed as the layer's are
(``reference_solar_open2.draw_timed``), against the recurrence token by token.

**The experts held** are a chip's share of a layer's router under the
deployment's expert parallelism (``expert_parallel`` in the configuration
file); the routed scale is the program's default, the published 1
(``tests/test_cellbench/test_solar_open2_cell.py`` holds both to the file).

Beyond ``manifest.FAMILY_INTERFACE``: ``kda_calls`` (the recurrence kernel's
FLOPs and bytes a token, for ``metrics/kda_roofline.py``),
``routing_counters``, ``tight_tier`` and ``grouped_calls``.
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from magiattention_tpu.api import dispatch, undispatch
from magiattention_tpu.kernels import kda, registry, ssd
from magiattention_tpu.models import hybrid

from cellbench import (
    family_afmoe,
    family_llama,
    family_mistral4,
    flops,
    reference_solar_open2,
)
from cellbench.traffic_gen import MaskSpec

make_key = family_llama.make_key
plan_facts = family_llama.plan_facts
pallas_kernels = family_llama.pallas_kernels
attention_choices = family_llama.attention_choices
CHECKS = {**reference_solar_open2.CHECKS, **reference_solar_open2.TIMED_CHECKS}
ring_batches = family_afmoe.ring_batches
balance_routers = family_mistral4.balance_routers
routing_counters = family_afmoe.routing_counters  # reads the shared _RUN
tight_tier = family_mistral4.tight_tier
grouped_calls = family_mistral4.grouped_calls  # the same layer code

# Rehearsal widths (``--rehearse-cpu``). head_dim 128 (both kinds), the
# attention group of 8, the convolution's 4 taps, the router's 320 and its
# top 8 and the 8 experts held are kept, so the same kernel bodies run and
# ``route_scores`` reads what the cell's does.
TOY = {
    "hidden_size": 256, "num_hidden_layers": 4, "vocab_size": 512,
    "num_attention_heads": 8, "num_key_value_heads": 1, "head_dim": 128,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 2, "num_kv_heads": None},
    "kda_gate_rank": 32, "moe_intermediate_size": 128,
    "moe_token_block": 256,
}

_RUN = family_afmoe._RUN  # one run's state, shared as family_mistral4's
TIMED = ("out", "dq", "dk", "dv", "dg", "dbeta")


def experts_held(cfg: dict) -> int:
    """A chip's experts of a layer: the router's width over the deployment's
    expert parallelism."""
    return cfg["router_experts"] // cfg["expert_parallel"]


def model_config(cfg: dict) -> hybrid.HybridConfig:
    lin = cfg["linear_attn_config"]
    assert lin["num_kv_heads"] in (None, lin["num_heads"]), cfg["name"]
    assert not cfg["use_rope"] and not cfg["kda_use_full_proj"], cfg["name"]
    assert cfg["norm_topk_prob"], cfg["name"]
    return hybrid.HybridConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        pattern=reference_solar_open2.pattern(cfg),
        norm_eps=cfg["rms_norm_eps"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=None, attn_gate=cfg["use_gqa_gate"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_gate_rank=cfg["kda_gate_rank"],
        kda_neg_eigval=cfg["kda_allow_neg_eigval"],
        conv_kernel=lin["short_conv_kernel_size"],
        time_step_min=cfg["time_step_min"],
        time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"],
        n_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        experts_held=experts_held(cfg),
        expert_offset=cfg["expert_offset"],
        expert_ffn=cfg["moe_intermediate_size"],
        shared_ffn=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        expert_act="swiglu", route="sigmoid_topk",
        moe_token_block=cfg["moe_token_block"],
        dtype="bfloat16", remat=True,
    )


def init_params(mcfg: hybrid.HybridConfig, mesh: Mesh, seed: int) -> dict:
    """fp32 masters from ``seed``, made on the device by one jitted call;
    the cell of this family runs at cp 1 (``hybrid.forward`` refuses a ``K``
    block at another), where every leaf is whole."""
    _RUN.clear()
    _RUN.update(seed=seed, mcfg=mcfg, batches=[], last=None, counters=None,
                check_routes=None)
    make = jax.jit(
        partial(hybrid.init_params, mcfg),
        out_shardings=NamedSharding(mesh, P()))
    return make(jax.random.PRNGKey(seed))


def timed_plan(spec: MaskSpec, mesh: Mesh):
    """``family_llama.timed_plan``; the timed mask is kept for the timed
    recurrence's check."""
    t0 = time.perf_counter()
    key = make_key(spec, mesh)
    _RUN["timed_spec"] = spec
    return key, (time.perf_counter() - t0) * 1e3


def train_step(params, mcfg, tokens, labels, key):
    """``family_mistral4.train_step``: the program's own jitted SGD step,
    the routers' biases fitted on the ring's batches before the first call
    on real arrays."""
    return family_mistral4.train_step(params, mcfg, tokens, labels, key)


def timed_draw_config(mcfg: hybrid.HybridConfig) -> dict:
    """What ``reference_solar_open2.draw_timed`` reads of a configuration,
    from the program's: the same draws on both sides."""
    return {"linear_attn_config": {"num_heads": mcfg.kda_heads,
                                   "head_dim": mcfg.kda_head_dim},
            "time_step_min": mcfg.time_step_min,
            "time_step_max": mcfg.time_step_max,
            "time_step_floor": mcfg.time_step_floor}


def _timed_inputs(cfg: dict, sharding):
    """The timed recurrence's seeded inputs on ``sharding``, the segment
    rows and the first tokens of its document starts."""
    spec = _RUN["timed_spec"]
    cu = np.asarray(spec.cu_seqlens)
    starts = np.repeat(cu[:-1], np.diff(cu)).astype(np.int32)
    arrays = jax.jit(partial(
        reference_solar_open2.draw_timed, cfg, spec.tokens, _RUN["seed"]),
        out_shardings=sharding)()
    rows, first = jax.device_put((
        ssd.segment_rows(jnp.asarray(starts), chunk=kda.CHUNK),
        jnp.asarray(starts == np.arange(spec.tokens))), sharding)
    return arrays, rows, first


@jax.jit
def _timed_program(q, k, v, g, beta, do, rows):
    out, pull = jax.vjp(
        lambda *a: kda.kda_scan(*a, rows), q, k, v, g, beta)
    return (out, *pull(do.astype(out.dtype)))


def timed_program(mcfg: hybrid.HybridConfig, sharding) -> dict:
    """The program's kernel on the timed recurrence's inputs, forward and
    ``jax.vjp``, brought to the host: ``{"kda_timed_<name>"}``."""
    (q, k, v, g, beta, do), rows, _ = _timed_inputs(
        timed_draw_config(mcfg), sharding)
    got = jax.device_get(_timed_program(q, k, v, g, beta, do, rows))
    return {f"kda_timed_{name}": x for name, x in zip(TIMED, got)}


def timed_reference(cfg: dict, sharding) -> dict:
    """The recurrence on the same inputs, brought to the host."""
    (q, k, v, g, beta, do), _, first = _timed_inputs(cfg, sharding)
    ref = jax.jit(reference_solar_open2.timed)(q, k, v, g, beta, do, first)
    return {f"kda_timed_{name}": x for name, x in jax.device_get(ref).items()}


def check_program(mcfg: hybrid.HybridConfig, key):
    """``(params, tokens, labels) -> {name: value}`` for the names of
    ``CHECKS``: the timed recurrence (when ``timed_plan`` ran), then through
    ``hybrid.forward`` and ``masked_ce`` the loss, logits (natural order),
    per ``E`` block the experts chosen as a 0/1 array and the score + bias of
    each, sorted, the gradients of ``reference_solar_open2.grad_leaves``;
    what every ``K`` block added to its input (the reference computes it from
    the program's stream). The chosen ids and the residual stream are kept for
    :func:`reference`."""
    where = reference_solar_open2.grad_leaves(mcfg.pattern)
    experts = [i for i, kind in enumerate(mcfg.pattern) if kind == "E"]
    delta_rules = [i for i, kind in enumerate(mcfg.pattern) if kind == "K"]

    def f(leaves, params, tokens, labels):
        p = reference_solar_open2.with_leaves(params, where, leaves)
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
            reference_solar_open2.take_leaves(params, where), params, tokens,
            labels)
        choice = jnp.stack([
            jnp.sum(jax.nn.one_hot(t, mcfg.n_experts, dtype=jnp.float32),
                    axis=1) for t in topi])
        # what each K block added to its own input: exact in float32
        added = jnp.diff(stream.astype(jnp.float32), axis=0)[
            np.asarray(delta_rules)]
        return loss, logits, chosen, choice, topi, stream, added, grads

    def named(params, tokens, labels) -> dict:
        timed = {}
        if "timed_spec" in _RUN:
            timed = timed_program(mcfg, tokens.sharding)
        (loss, logits, chosen, choice, topi, stream, added,
         grads) = run(params, tokens, labels)
        _RUN["check_routes"], _RUN["check_stream"] = topi, stream
        return {"loss": loss, "logits": logits, "route_scores": chosen,
                "route_choice": choice, "kda_blocks": added, **grads,
                **timed}

    return named


def reference(params, cfg, tokens, labels, spec) -> dict:
    """The plain reference on the experts the last check program chose, and
    the timed recurrence."""
    if _RUN.get("check_routes") is None:
        raise RuntimeError(
            "family_solar_open2.reference before check_program's program "
            "ran: the comparison is teacher-forced on the program's routes, "
            "and without them it would be another, looser comparison")
    routes, stream = jax.device_put(
        (list(_RUN.pop("check_routes")), _RUN.pop("check_stream")),
        tokens.sharding)
    out = jax.device_get(reference_solar_open2.reference(
        params, cfg, tokens, labels, spec, routes=routes, stream=stream))
    if "timed_spec" in _RUN:
        out.update(timed_reference(cfg, tokens.sharding))
    return out


def block_matmul_params(cfg: dict) -> dict:
    """Weights a token is multiplied by in one block of each kind (the
    convolutions' taps counted as weights); an ``E`` block's held experts at
    the EXPECTED rows (a token's choices fall on them with probability held
    / router's width)."""
    dim, dh = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    lin = cfg["linear_attn_config"]
    width, rank = lin["num_heads"] * lin["head_dim"], cfg["kda_gate_rank"]
    expert = 3 * dim * cfg["moe_intermediate_size"]
    per_token = cfg["num_experts_per_tok"] * (
        experts_held(cfg) / cfg["router_experts"])
    return {
        "K": 4 * dim * width + 2 * (dim * rank + rank * width)
        + dim * lin["num_heads"] + 3 * lin["short_conv_kernel_size"] * width,
        "*": 2 * dim * hq * dh + 2 * dim * hk * dh + hq * dh * dim,
        "E": dim * cfg["router_experts"]
        + (cfg["n_shared_experts"] + per_token) * expert,
    }


def kda_flops_per_token(cfg: dict) -> float:
    """Forward FLOPs a token of one KDA layer in the chunked form at
    ``kda.CHUNK`` = C, per head of D channels: the two pair matrices over
    the causal half of C x C (2 C D), ``T`` (C^2 / 3), ``W`` and ``U`` over
    the lower half (2 C D), ``P v'`` (C D), and the three products with the
    D x D state (``W S0``, ``q S0``, ``k^T v'``: 6 D^2)."""
    lin = cfg["linear_attn_config"]
    c, d = kda.CHUNK, lin["head_dim"]
    return lin["num_heads"] * (5 * c * d + c * c / 3 + 6 * d * d)


def required_flops_per_step(cfg: dict, spec: MaskSpec) -> int:
    """Required convention (``cellbench/flops.py``): matmuls at ``6 p`` a
    token, the GQA blocks over the mask's band area, the recurrence forward
    and twice that backward; recomputation not counted."""
    pattern = reference_solar_open2.pattern(cfg)
    per_block = block_matmul_params(cfg)
    weights = sum(per_block[kind] for kind in pattern) + (
        cfg["hidden_size"] * cfg["vocab_size"])  # untied head; embed gathers
    attn = pattern.count("*") * (
        1 + flops.ATTN_BWD_OVER_FWD) * flops.attn_fwd_flops(
        flops.band_area(spec), cfg["num_attention_heads"], cfg["head_dim"],
        cfg["head_dim"])
    kda_ = pattern.count("K") * 3 * kda_flops_per_token(cfg) * spec.tokens
    return int(flops.matmul_flops(weights, spec.tokens) + attn + kda_)


def ffa_calls(cfg: dict) -> list[dict]:
    """One group: the ``*`` blocks, each three calls a step under remat."""
    return [{
        "layers": reference_solar_open2.pattern(cfg).count("*"),
        "passes": ("fwd", "fwd", "bwd"),
        "hq": cfg["num_attention_heads"], "hk": cfg["num_key_value_heads"],
        "d_qk": cfg["head_dim"], "d_v": cfg["head_dim"],
    }]


def timed_attention(cfg: dict, key) -> list[dict]:
    """As ``family_llama.timed_attention``, of this family's one group."""
    return family_llama.timed_attention_groups(ffa_calls(cfg), key)


def kda_calls(cfg: dict) -> list[dict]:
    """The step's recurrence calls for ``metrics/kda_roofline.py``: the
    ``K`` blocks, each three calls a step under remat (the forward, the
    re-forward that also writes every chunk's incoming state, the
    backward), with each pass's FLOPs and bytes A TOKEN: the backward twice
    the forward's FLOPs; bytes every tensor once (bf16 q, k, v, o and their
    gradients, the cotangent; float32 g, beta and their gradients; a float32
    D x D state a chunk, written by the re-forward and read by the
    backward)."""
    lin = cfg["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    fwd = kda_flops_per_token(cfg)
    state = h * 4 * d * d / kda.CHUNK
    read = h * (2 * 3 * d + 4 * d + 4)  # q, k, v, g, beta
    return [{
        "layers": reference_solar_open2.pattern(cfg).count("K"),
        "passes": ("fwd", "fwd_save", "bwd"),
        "flops_per_token": {"fwd": fwd, "fwd_save": fwd, "bwd": 2 * fwd},
        "bytes_per_token": {
            "fwd": read + h * 2 * d,
            "fwd_save": read + h * 2 * d + state,
            "bwd": 2 * read + h * 2 * d + state},
    }]


def what_ran() -> dict:
    """``family_llama.what_ran()`` and this family's own: the recurrence's
    and the grouped product's backends, the route, the tiles at g = 8, the
    routing counters and the tight tier's share."""
    return {
        **family_llama.what_ran(),
        "kda": registry.last_choice("kda"),
        "ffa_tiles": registry.last_choice("ffa_tiles"),
        "ffa_tiles_source": registry.last_source("ffa_tiles"),
        "moe_route": registry.last_choice("moe_route"),
        "moe_grouped": registry.last_choice("moe_grouped"),
        "moe_grouped_tiles": registry.last_choice("moe_grouped_tiles"),
        "moe_row_buffer": registry.last_choice("moe_row_buffer"),
        "routing": routing_counters(),
        "tight_tier": tight_tier(),
    }
