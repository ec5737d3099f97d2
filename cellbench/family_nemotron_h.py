"""Step builder of the ``nemotron_h`` family: hybrid decoders (Mamba-2,
expert and attention blocks by a layer pattern) run through
``magiattention_tpu.models.hybrid`` exactly as a user would.

With ``reference_nemotron_h.py`` the only file of the benchmark that knows
these blocks' equations. What is the program's and not the block's
(``make_key``, ``timed_plan``, ``plan_facts``, ``pallas_kernels``, the base
of ``what_ran``) is ``cellbench.family_llama``'s. Beyond
``manifest.FAMILY_INTERFACE`` it has what its own metrics read:
``ssd_calls`` (the scan kernel's FLOPs and bytes a token, for
``metrics/ssd_roofline.py``) and ``routing_counters`` (the expert blocks'
rows on the timed batch, from the program's own routing).

**The comparison is teacher-forced on the routes.** ``reference`` hands
the expert ids the check program chose to the plain reference, which weighs
those experts with its own float32 scores; ``route_choice`` holds the
program's chosen set, token by token, to the reference's own outside
near-ties, and ``route_scores`` the values the top-k was taken of
(``reference_nemotron_h.py``, "The routes", says why a comparison that lets
each side choose cannot be tight). The harness calls the check program, then
the reference, and hands nothing from one to the other: the ids pass through
this module (``_RUN["check_routes"]``), and a reference called first raises.

**The routers' biases are the benchmark's set-up, not a trained state.**
``e_score_correction_bias`` is a buffer no step updates. Before the first
step the family fits it, block by block, by the rule it exists for
(aux-loss-free balancing) on a batch of its own drawn from the seed, which
is none of the timed ones (the configuration file's ``assumed`` says why);
``routing_counters`` then reads what is left of the imbalance on every
timed batch.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from magiattention_tpu.api import dispatch, undispatch
from magiattention_tpu.kernels import registry
from magiattention_tpu.models import hybrid

from cellbench import family_llama, flops, reference_nemotron_h
from cellbench.traffic_gen import MaskSpec

make_key = family_llama.make_key
timed_plan = family_llama.timed_plan
plan_facts = family_llama.plan_facts
pallas_kernels = family_llama.pallas_kernels
CHECKS = reference_nemotron_h.CHECKS

# Rehearsal widths (``--rehearse-cpu``). The attention group of 16, head_dim
# 128, the Mamba head of 64 channels and the chunk are kept, so the same
# kernel bodies run; nothing measured at them is a result.
TOY = {
    "hidden_size": 256, "num_hidden_layers": 4,
    "hybrid_override_pattern": "ME*M", "vocab_size": 512,
    "num_attention_heads": 16, "num_key_value_heads": 1, "head_dim": 128,
    "mamba_num_heads": 8, "mamba_head_dim": 64, "n_groups": 2,
    "ssm_state_size": 32, "n_routed_experts": 8, "router_experts": 32,
    "moe_intermediate_size": 128, "moe_shared_expert_intermediate_size": 256,
    "moe_token_block": 256,
}

# the balancing rule's schedule: the step shrinks from FIRST_STEP by DECAY an
# iteration, to 2e-5 after STEPS, under the gap between neighbouring scores
BALANCE_STEPS, BALANCE_FIRST_STEP, BALANCE_DECAY = 512, 0.05, 0.985
TIMED_BATCHES_KEPT = 8  # distinct batches counted; the traffic files ask 4

_RUN: dict = {}  # one run's state, begun anew by ``init_params``


def model_config(cfg: dict) -> hybrid.HybridConfig:
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == cfg["num_hidden_layers"], (pattern, cfg["name"])
    return hybrid.HybridConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], pattern=pattern,
        norm_eps=cfg["norm_eps"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=None,  # ``assumed`` in the configuration file
        mamba_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"], ssm_groups=cfg["n_groups"],
        ssm_state=cfg["ssm_state_size"], conv_kernel=cfg["conv_kernel"],
        chunk_size=cfg["chunk_size"], time_step_min=cfg["time_step_min"],
        time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"],
        n_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"],
        expert_ffn=cfg["moe_intermediate_size"],
        shared_ffn=cfg["moe_shared_expert_intermediate_size"],
        routed_scale=cfg["routed_scaling_factor"],
        moe_token_block=cfg["moe_token_block"],
        dtype="bfloat16", remat=True,
    )


def init_params(mcfg: hybrid.HybridConfig, mesh: Mesh, seed: int) -> dict:
    """fp32 masters from ``seed``, made on the device by one jitted call.
    The family runs at cp 1 (``hybrid.forward`` refuses another key), where
    every leaf is whole on its chip."""
    _RUN.clear()
    _RUN.update(seed=seed, batches=[], last=None, counters=None,
                check_routes=None)
    make = jax.jit(
        partial(hybrid.init_params, mcfg),
        out_shardings=NamedSharding(mesh, P()))
    return make(jax.random.PRNGKey(seed))


def balancing_bias(scores, top_k: int):
    """The bias that evens the experts' load on ``scores`` ``(S, E)``
    (aux-loss-free balancing, DeepSeek-V3 2.1.2): an expert chosen by fewer
    tokens than the mean has its bias raised by a step, one chosen by more
    has it lowered; the step shrinks, so the bias settles."""
    n = scores.shape[-1]
    target = scores.shape[0] * top_k / n

    def nudge(i, bias):
        _, topi = jax.lax.top_k(scores + bias, top_k)
        load = jnp.sum(
            jax.nn.one_hot(topi, n, dtype=jnp.float32), axis=(0, 1))
        return bias + BALANCE_FIRST_STEP * BALANCE_DECAY ** i * jnp.sign(
            target - load)

    return jax.lax.fori_loop(
        0, BALANCE_STEPS, nudge, jnp.zeros((n,), jnp.float32))


@partial(jax.jit, static_argnums=(1, 3))
def _fitted_bias(params, mcfg, tokens, key, block):
    """The balancing bias of the ``block``-th ``E`` block on one of the
    program's forwards. The bias alone comes back: the scores stay the
    program's scratch, so set-up holds no array the steps do not."""
    _, routes = hybrid.forward(params, mcfg, tokens, key, with_routes=True)
    return balancing_bias(
        jnp.stack([r["scores"] for r in routes])[block], mcfg.top_k)


def balance_routers(params, mcfg, tokens, key) -> dict:
    """``params`` with every ``E`` block's ``e_bias`` fitted on ``tokens``,
    the lowest block first: a block's input depends on the biases below it
    and not on its own, so each block costs one forward."""
    layers = list(params["layers"])
    experts = [i for i, kind in enumerate(mcfg.pattern) if kind == "E"]
    for n, i in enumerate(experts):
        layers[i] = {**layers[i], "e_bias": _fitted_bias(
            {**params, "layers": layers}, mcfg, tokens, key, n)}
    return {**params, "layers": layers}


def train_step(params, mcfg, tokens, labels, key):
    """The program's own jitted SGD step; parameters are donated. Before
    the first call on real arrays (the warm-up's, outside the measured
    window) the routers' biases are fitted on a batch drawn from the seed
    that is none of the timed ones. The distinct batches that come through
    are kept by reference for :func:`routing_counters`."""
    # real arrays of a run that init_params began: not a trace, not a lowering
    live = "seed" in _RUN and isinstance(tokens, jax.Array) and not (
        isinstance(tokens, jax.core.Tracer))
    if live:
        if not _RUN["batches"]:
            own = jax.random.randint(
                jax.random.fold_in(jax.random.PRNGKey(_RUN["seed"]), 1),
                tokens.shape, 0, mcfg.vocab_size, tokens.dtype)
            params = balance_routers(params, mcfg, own, key)
        if (len(_RUN["batches"]) < TIMED_BATCHES_KEPT
                and not any(tokens is seen for seen in _RUN["batches"])):
            _RUN["batches"].append(tokens)
    params, loss = hybrid.train_step(params, mcfg, tokens, labels, key)
    if live:
        _RUN["last"] = (params, mcfg, key)  # a reference, donated next step
    return params, loss


def routing_counters() -> dict | None:
    """From the program's own routing (``hybrid.routing_counters``) on every
    timed batch, at the parameters the last step left: rows the grouped
    products took for the experts held, summed over the expert blocks, a
    batch's mean (a step's rows, forward); the fullest expert's rows over
    its block's mean, the worst block of the worst batch; the rows routed
    to the experts held that no grouped product took, all batches. ``None``
    before a step ran. Counted once, on the first call after the steps."""
    if not _RUN.get("batches"):
        return None
    if _RUN["counters"] is None:
        params, mcfg, key = _RUN["last"]
        counted = [jax.device_get(hybrid.routing_counters(
            params, mcfg, tokens, key)) for tokens in _RUN["batches"]]
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


def check_program(mcfg: hybrid.HybridConfig, key):
    """``(params, tokens, labels) -> {name: value}`` for the names of
    ``CHECKS`` through ``hybrid.forward`` and ``masked_ce``: loss, logits
    (natural order), per ``E`` block the experts chosen as a 0/1 array
    (tokens, router's width) and the score + bias of each, sorted (what the
    top-k was taken of), the gradients of
    ``reference_nemotron_h.grad_leaves``. The chosen ids are kept for
    :func:`reference`."""
    where = reference_nemotron_h.grad_leaves(mcfg.pattern)
    experts = [i for i, kind in enumerate(mcfg.pattern) if kind == "E"]

    def f(leaves, params, tokens, labels):
        p = reference_nemotron_h.with_leaves(params, where, leaves)
        logits, routes = hybrid.forward(
            p, mcfg, tokens, key, with_routes=True)
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
            reference_nemotron_h.take_leaves(params, where), params, tokens,
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


def reference(params, cfg, tokens, labels, spec) -> dict:
    """The plain reference on the experts the last check program chose."""
    if _RUN.get("check_routes") is None:
        raise RuntimeError(
            "family_nemotron_h.reference before check_program's program "
            "ran: the comparison is teacher-forced on the program's routes, "
            "and without them it would be another, looser comparison")
    routes = [jax.device_put(r, tokens.sharding)
              for r in _RUN.pop("check_routes")]
    return reference_nemotron_h.reference(
        params, cfg, tokens, labels, spec, routes=routes)


def block_matmul_params(cfg: dict) -> dict:
    """Weights a token is multiplied by in one block of each kind; an ``E``
    block's held experts at the EXPECTED rows (a token's ``top_k`` choices
    fall on the experts held with probability held / router's width)."""
    dim, dh = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d_in = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv_dim = d_in + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    per_token = cfg["num_experts_per_tok"] * (
        cfg["n_routed_experts"] / cfg["router_experts"])
    return {
        "M": dim * (d_in + conv_dim + cfg["mamba_num_heads"]) + d_in * dim
        + cfg["conv_kernel"] * conv_dim,
        "E": dim * cfg["router_experts"]
        + 2 * dim * cfg["moe_shared_expert_intermediate_size"]
        + per_token * 2 * dim * cfg["moe_intermediate_size"],
        "*": dim * hq * dh + 2 * dim * hk * dh + hq * dh * dim,
    }


def scan_flops_per_token(cfg: dict) -> float:
    """Forward FLOPs a token of one scan layer in the chunked form at the
    published chunk ``Q``: per chunk ``C B^T`` a group and ``(L o C B^T) X``
    a head over the causal half of ``Q x Q``, the chunk's state ``B^T X``
    and the carried state's ``C h`` a head."""
    q, n = cfg["chunk_size"], cfg["ssm_state_size"]
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    half = q * (q + 1) // 2
    per_chunk = cfg["n_groups"] * 2 * n * half + heads * (
        2 * p * half + 2 * 2 * q * n * p)
    return per_chunk / q


def required_flops_per_step(cfg: dict, spec: MaskSpec) -> int:
    """Required convention (``cellbench/flops.py``): matmuls at ``6 p`` a
    token, the attention block over the mask's band area, the scan forward
    and twice that backward; recomputation not counted."""
    pattern = cfg["hybrid_override_pattern"]
    per_block = block_matmul_params(cfg)
    weights = sum(per_block[kind] for kind in pattern) + (
        cfg["hidden_size"] * cfg["vocab_size"])  # untied head; embed gathers
    attn = pattern.count("*") * (
        1 + flops.ATTN_BWD_OVER_FWD) * flops.attn_fwd_flops(
        flops.band_area(spec), cfg["num_attention_heads"], cfg["head_dim"],
        cfg["head_dim"])
    scan = pattern.count("M") * 3 * scan_flops_per_token(cfg) * spec.tokens
    return int(flops.matmul_flops(weights, spec.tokens) + attn + scan)


def ffa_calls(cfg: dict) -> list[dict]:
    """One group: the ``*`` blocks, each three calls a step under remat."""
    return [{
        "layers": cfg["hybrid_override_pattern"].count("*"),
        "passes": ("fwd", "fwd", "bwd"),
        "hq": cfg["num_attention_heads"], "hk": cfg["num_key_value_heads"],
        "d_qk": cfg["head_dim"], "d_v": cfg["head_dim"],
    }]


def ssd_calls(cfg: dict) -> list[dict]:
    """The step's scan calls for ``metrics/ssd_roofline.py``: the ``M``
    blocks, each three calls a step under remat (forward, re-forward,
    backward), with each pass's FLOPs and bytes A TOKEN: the backward twice
    the forward's FLOPs; bytes every tensor once (bf16 x, y, B, C and their
    gradients, fp32 dt and its gradient)."""
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    bc = 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    fwd = scan_flops_per_token(cfg)
    return [{
        "layers": cfg["hybrid_override_pattern"].count("M"),
        "passes": ("fwd", "fwd", "bwd"),
        "flops_per_token": {"fwd": fwd, "bwd": 2 * fwd},
        "bytes_per_token": {
            "fwd": 2 * (2 * heads * p + bc) + 4 * heads,
            "bwd": 2 * (3 * heads * p + 2 * bc) + 2 * 4 * heads},
    }]


def what_ran() -> dict:
    """``family_llama.what_ran()`` and this family's own choices: the scan
    and grouped-product backends the registry recorded, the routing
    counters of the timed batches."""
    return {
        **family_llama.what_ran(),
        "ssd": registry.last_choice("ssd"),
        "moe_grouped": registry.last_choice("moe_grouped"),
        "routing": routing_counters(),
    }
