"""Step builder of the ``llama`` family: Mistral-style dense decoders run
through ``magiattention_tpu.models.llama`` exactly as a user would.

This is the only file of the benchmark that knows the program's entry
points, and with ``reference_llama.py`` the only one that knows this
block's equations. It maps a configuration file (published key names) to
``LlamaConfig``, a :class:`~cellbench.traffic_gen.MaskSpec` to a runtime key
through the public mask compilers, makes the parameters on the devices
already sharded, and exposes the train step, the loss-and-gradient program
of the reference check with the reference and the names compared, the
block's FLOP counts, and the counts read from the plan objects. The
harness takes the members ``cellbench/manifest.py:FAMILY_INTERFACE`` lists
(README.md says what each is).
"""

from __future__ import annotations

import time
from functools import partial

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from magiattention_tpu.api import dispatch, magi_attn_flex_key, undispatch
from magiattention_tpu.api.functools import (
    infer_attn_mask_from_cu_seqlens,
    infer_attn_mask_from_sliding_window,
)
from magiattention_tpu.api.magi_attn_interface import _mgr
from magiattention_tpu.common.enum import AttnMaskType
from magiattention_tpu.common.ranges import AttnRanges
from magiattention_tpu.kernels import registry
from magiattention_tpu.kernels.ffa import _should_interpret
from magiattention_tpu.models import llama
from magiattention_tpu.resilience.fallback import resilience_event_counts

from cellbench import flops, reference_llama
from cellbench.traffic_gen import MaskSpec

# The plain reference of this block and the names compared with it: the
# harness asks the family, and this family's live beside it.
reference = reference_llama.reference
CHECKS = reference_llama.CHECKS

# Rehearsal widths (``--rehearse-cpu``): sizes the Pallas interpreter
# finishes in seconds. The group size 4 and head_dim 128 are kept so the
# same kernel variants are selected; nothing measured at them is a result.
TOY = {
    "hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 1,
    "head_dim": 128, "intermediate_size": 512, "vocab_size": 512,
    "num_hidden_layers": 2,
}


def model_config(cfg: dict) -> llama.LlamaConfig:
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ffn_hidden=cfg["intermediate_size"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], dtype="bfloat16", remat=True,
    )


def mask_slices(spec: MaskSpec):
    """``(q_ranges, k_ranges, types)`` of ``spec`` from the program's public
    mask compilers: varlen block-causal, and a causal window of ``window``
    keys counting the query itself as ``window_size=(window - 1, 0)``."""
    cu = list(spec.cu_seqlens)
    if spec.window is None:
        return infer_attn_mask_from_cu_seqlens(cu, cu, causal=True)
    ranges = AttnRanges.from_cu_seqlens(cu)
    return infer_attn_mask_from_sliding_window(
        ranges, AttnRanges.from_cu_seqlens(cu),
        [AttnMaskType.CAUSAL] * len(ranges), (spec.window - 1, 0),
    )


def make_key(spec: MaskSpec, mesh: Mesh):
    qr, kr, types = mask_slices(spec)
    return magi_attn_flex_key(
        qr, kr, types, spec.tokens, spec.tokens, mesh=mesh, cp_axis="cp"
    )


def param_shardings(mcfg: llama.LlamaConfig, mesh: Mesh):
    """What ``llama.shard_params`` gives each leaf (ZeRO-3 over ``cp``: the
    first dimension of every matrix it divides, the rest replicated)."""
    shapes = jax.eval_shape(
        partial(llama.init_params, mcfg), jax.random.PRNGKey(0))
    cp = mesh.shape["cp"]

    def one(x):
        split = x.ndim >= 2 and x.shape[0] % cp == 0
        return NamedSharding(
            mesh, P("cp", *([None] * (x.ndim - 1))) if split else P())

    return jax.tree.map(one, shapes)


def init_params(mcfg: llama.LlamaConfig, mesh: Mesh, seed: int) -> dict:
    """fp32 masters from ``seed``, generated on the devices already in
    their shards by one jitted call (``shard_params`` of a tree born on
    device 0 holds the whole model there first)."""
    make = jax.jit(
        partial(llama.init_params, mcfg),
        out_shardings=param_shardings(mcfg, mesh),
    )
    return make(jax.random.PRNGKey(seed))


def train_step(params, mcfg, tokens, labels, key):
    """The program's own jitted SGD step; parameters are donated."""
    return llama.train_step(params, mcfg, tokens, labels, key)


def check_program(mcfg: llama.LlamaConfig, key):
    """``(params, tokens, labels) -> {name: value}`` for the names of
    ``CHECKS``: loss, logits (natural order), d loss / d layers[0].wq,
    d loss / d layers[0].wk, through ``llama.loss_fn``'s own pieces. The
    jitted program returns them as a tuple in that order (a dict would
    come out sorted: another program, and at cp 4 other roundings)."""

    def f(wq, wk, params, tokens, labels):
        lyr0 = {**params["layers"][0], "wq": wq, "wk": wk}
        p = {**params, "layers": [lyr0, *params["layers"][1:]]}
        logits = llama.forward(p, mcfg, tokens, key)
        loss = llama.masked_ce(logits, dispatch(labels, key))
        return loss, undispatch(logits, key)

    @jax.jit
    def run(params, tokens, labels):
        (loss, logits), (gq, gk) = jax.value_and_grad(
            f, argnums=(0, 1), has_aux=True
        )(params["layers"][0]["wq"], params["layers"][0]["wk"], params,
          tokens, labels)
        return loss, logits, gq, gk

    def named(params, tokens, labels) -> dict:
        return dict(zip(CHECKS, run(params, tokens, labels)))

    return named


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one block's seven projections."""
    dim, dh = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (
        dim * hq * dh + 2 * dim * hk * dh + hq * dh * dim
        + 3 * dim * cfg["intermediate_size"]
    )


def required_flops_per_step(cfg: dict, spec: MaskSpec) -> int:
    """Required convention (``cellbench/flops.py``): forward + backward of
    the whole step; every layer attends over the mask's band area."""
    layers = cfg["num_hidden_layers"]
    matmul = flops.matmul_flops(
        layers * layer_matmul_params(cfg)
        + cfg["hidden_size"] * cfg["vocab_size"],  # untied head; embed is a gather
        spec.tokens)
    attn = layers * (1 + flops.ATTN_BWD_OVER_FWD) * flops.attn_fwd_flops(
        flops.band_area(spec), cfg["num_attention_heads"],
        cfg["head_dim"], cfg["head_dim"])
    return int(matmul + attn)


def ffa_calls(cfg: dict) -> list[dict]:
    """The step's FFA calls for ``flops.ffa_least_seconds``: every layer
    attends, and under ``remat`` (``model_config``) makes three calls a
    step, forward, re-forward and backward."""
    return [{
        "layers": cfg["num_hidden_layers"], "passes": ("fwd", "fwd", "bwd"),
        "hq": cfg["num_attention_heads"], "hk": cfg["num_key_value_heads"],
        "d_qk": cfg["head_dim"], "d_v": cfg["head_dim"],
    }]


def plan_facts(key, spec_rows_area: np.ndarray) -> dict:
    """Counts read from the plan objects (valid on any backend)."""
    mgr = _mgr(key)
    meta = mgr.dispatch_meta_q
    cs = meta.chunk_size
    rank_rows = [
        np.concatenate([np.arange(c * cs, (c + 1) * cs) for c in chunks])
        for chunks in meta.partitions
    ]
    stages = mgr._stage_telemetry_dicts()
    return {
        "rank_rows": rank_rows,
        "rank_areas": [int(spec_rows_area[r].sum()) for r in rank_rows],
        "chunk_size": cs,
        "overlap_degree": mgr.comm_meta.overlap_degree,
        "stage_lowering": [d["lowering_executed"] for d in stages],
        "payload_rows": sum(d["payload_rows"] for d in stages),
        "wire_rows": sum(d["wire_rows"] for d in stages),
        "slices": len(key.q_ranges),
    }


def pallas_kernels(closed_jaxpr) -> dict[str, bool]:
    """``{kernel body name: interpreted}`` of every ``pallas_call`` in a
    traced program: what the compiled step really contains (the technique
    of ``chip_smoke.py``)."""
    found: dict[str, bool] = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["jaxpr"].debug_info.func_name
                found[name] = bool(eqn.params["interpret"]) or found.get(
                    name, False)
            for p in eqn.params.values():
                for sub in p if isinstance(p, (tuple, list)) else (p,):
                    inner = getattr(sub, "jaxpr", sub)
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(closed_jaxpr.jaxpr)
    return found


def what_ran() -> dict:
    """The program's own account of the choices it made in this process."""
    return {
        "calc_attn_backend": registry.last_choice("calc_attn"),
        "ffa_bwd_mode": registry.last_choice("ffa_bwd"),
        "gqa_pack_variant": {
            kind: registry.gqa_pack_variant(kind)
            for kind in ("fwd", "dq", "dkv")
        },
        "should_interpret": _should_interpret(),
        "resilience_events": resilience_event_counts(),
    }


def timed_plan(spec: MaskSpec, mesh: Mesh):
    """The key, and the host milliseconds its plan took: the span around
    ``magi_attn_flex_key``, which validates the mask, solves the dispatch
    and builds the runtime manager with its attention plan."""
    t0 = time.perf_counter()
    key = make_key(spec, mesh)
    return key, (time.perf_counter() - t0) * 1e3
