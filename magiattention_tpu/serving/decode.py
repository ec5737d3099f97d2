"""Batched decode attention with the serving fallback ladder.

Six registry rungs, descending rank order (docs/serving.md,
docs/serving_scale.md):

1. **paged_decode_sharded** — the Pallas kernel under a ``shard_map`` over
   the kv-head axis: one launch per mesh shard. Feasible only when the
   engine asks for >1 shard, the head count splits evenly, enough devices
   exist, and the cache is unquantized. Bitwise-equal to the single-device
   kernel (per-(head, seq) accumulation is untouched by the split).
2. **paged_decode_spec** — multi-token speculative verify
   (:func:`verify_attn_step` only; never feasible for the 1-row step).
3. **paged_decode_int8** — the dequant-in-kernel variant; feasible only on
   quantized caches.
4. **paged_decode** — the PR 8 kernel (unquantized caches).
5. **gather_ffa** — per-slot gather+FFA (:func:`~..kernels.paged_kv.paged_attn`);
   host-static lengths. ``gather_kv`` dequantizes on the way out, so this
   rung (and dense below) serves every cache dtype — it is the recovery
   floor beneath all three new kernels.
6. **dense** — masked jnp softmax over the gathered pages, no Pallas.

Each Pallas rung arms the ``serve_decode`` injection site (NOT the FFA
``kernel_lowering`` site, which prefill's FFA calls also arm — faulting
that would crash prefill, whose calls have no ladder around them).

Descent follows the resilience contract of ``ffa.ffa_bwd_pallas_dispatch``:
recoverable failure types from :func:`kernel_failure_types`, descent only
under ``MAGI_ATTENTION_FALLBACK=1`` (otherwise failures propagate), one
``resilience`` telemetry record per hop. Infeasible rungs are filtered out
BEFORE descent — a pin on an infeasible rung starts from the first
feasible rung at or below it, the same "pin subject to feasibility guards"
rule as the ffa_bwd decision.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..env import backend as env_backend
from ..env import resilience as env_resilience
from ..kernels import registry as _registry
from ..kernels.paged_decode import (
    paged_decode_attn,
    paged_decode_attn_int8,
    paged_decode_attn_sharded,
    paged_decode_attn_spec,
)
from ..kernels.paged_kv import PagedKVCache, gather_kv, paged_attn
from ..resilience import fallback as _fallback
from ..resilience.inject import maybe_inject

NEG_INF = float("-inf")


def _feasibility(
    cache: PagedKVCache, hk: int, shards: int, multi_row: bool
) -> Callable[[str], bool]:
    quantized = cache.quantized

    def feasible(rung: str) -> bool:
        if rung == "paged_decode_sharded":
            return (
                not multi_row
                and not quantized
                and shards > 1
                and hk % shards == 0
            )
        if rung == "paged_decode_spec":
            # quantized verify descends to gather_ffa's dequantized path
            return multi_row and not quantized
        if rung == "paged_decode_int8":
            return not multi_row and quantized
        if rung == "paged_decode":
            return not multi_row and not quantized
        return True  # gather_ffa / dense serve every shape and dtype

    return feasible


def _rungs(
    cache: PagedKVCache,
    key: tuple,
    default: str,
    hk: int,
    shards: int,
    multi_row: bool,
) -> list[str]:
    start = _registry.resolve(
        "serve_decode", key, lambda: default,
        pin=env_backend.serve_decode_pin(),
    ).name
    feasible = _feasibility(cache, hk, shards, multi_row)
    rungs = [r for r in _registry.ladder("serve_decode", start) if feasible(r)]
    if not rungs:  # pinned below every feasible rung: full feasible ladder
        rungs = [r for r in _registry.ladder("serve_decode") if feasible(r)]
    return rungs


def decode_attn_step(
    q_batch: jax.Array,
    cache: PagedKVCache,
    host_lengths: tuple[int, ...],
    softmax_scale: float | None = None,
    shards: int = 1,
) -> tuple[jax.Array, jax.Array]:
    """One decode step for every active slot.

    Args:
        q_batch: ``(max_seqs, hq, d)`` — one query row per slot (zeros on
            inactive slots).
        cache: the paged cache AFTER this step's k/v rows were appended.
        host_lengths: per-slot token counts as host ints (0 = inactive);
            must match ``cache.lengths`` — the gather/dense rungs need them
            static, the kernel rungs ignore them.
        shards: kv-head mesh width the engine wants; 1 disables the
            sharded rung.

    Returns (out ``(max_seqs, hq, dv)``, lse ``(max_seqs, hq)``).
    """
    S, hq, d = q_batch.shape
    hk = cache.k_pages.shape[2]
    dv = cache.v_pages.shape[-1]
    quantized = cache.quantized
    if shards > len(jax.devices()):
        # a kv mesh wider than the host is a configuration error: serving
        # unsharded instead would hide that the devices are not there
        raise ValueError(
            f"decode_shards={shards} but only {len(jax.devices())} "
            f"device(s) are visible ({jax.devices()[0].platform})"
        )
    key = (S, hq, hk, d, dv, str(q_batch.dtype), quantized, shards)
    if quantized:
        default = "paged_decode_int8"
    elif shards > 1:
        default = "paged_decode_sharded"
    else:
        default = "paged_decode"
    rungs = _rungs(cache, key, default, hk, shards, multi_row=False)
    failures = _fallback.kernel_failure_types()
    for i, rung in enumerate(rungs):
        try:
            if rung == "paged_decode_sharded":
                maybe_inject("serve_decode")
                return paged_decode_attn_sharded(
                    q_batch, cache, shards, softmax_scale=softmax_scale
                )
            if rung == "paged_decode_int8":
                maybe_inject("serve_decode")
                return paged_decode_attn_int8(
                    q_batch, cache, softmax_scale=softmax_scale
                )
            if rung == "paged_decode":
                maybe_inject("serve_decode")
                return paged_decode_attn(
                    q_batch, cache, softmax_scale=softmax_scale
                )
            if rung == "gather_ffa":
                return _gather_ffa_decode(
                    q_batch, cache, host_lengths, softmax_scale
                )
            return _dense_decode(q_batch, cache, host_lengths, softmax_scale)
        except failures as e:
            if i + 1 >= len(rungs) or not env_resilience.is_fallback_enable():
                raise
            _fallback.record_resilience_event(
                "fallback", "serve_decode",
                action_detail=f"{rung}_to_{rungs[i + 1]}",
                error=type(e).__name__,
            )
    raise AssertionError("serve_decode ladder is empty")  # pragma: no cover


def verify_attn_step(
    q_spec: jax.Array,
    cache: PagedKVCache,
    host_lengths: tuple[int, ...],
    softmax_scale: float | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Speculative verify: ``spec_k`` draft-token query rows per slot in
    one launch, each row attending its own causal prefix.

    Args:
        q_spec: ``(max_seqs, spec_k, hq, d)`` — draft token ``t`` of a slot
            sits at absolute position ``lengths - spec_k + t`` (the draft
            rows are already appended, so lengths include them).
        host_lengths: per-slot counts AFTER the append (0 = inactive).

    Returns (out ``(max_seqs, spec_k, hq, dv)``,
    lse ``(max_seqs, spec_k, hq)``).
    """
    S, spec_k, hq, d = q_spec.shape
    hk = cache.k_pages.shape[2]
    dv = cache.v_pages.shape[-1]
    key = (
        S, hq, hk, d, dv, str(q_spec.dtype), cache.quantized,
        "spec", spec_k,
    )
    rungs = _rungs(
        cache, key, "paged_decode_spec", hk, shards=1, multi_row=True
    )
    failures = _fallback.kernel_failure_types()
    for i, rung in enumerate(rungs):
        try:
            if rung == "paged_decode_spec":
                maybe_inject("serve_decode")
                return paged_decode_attn_spec(
                    q_spec, cache, softmax_scale=softmax_scale
                )
            if rung == "gather_ffa":
                return _gather_ffa_verify(
                    q_spec, cache, host_lengths, softmax_scale
                )
            return _dense_verify(q_spec, cache, host_lengths, softmax_scale)
        except failures as e:
            if i + 1 >= len(rungs) or not env_resilience.is_fallback_enable():
                raise
            _fallback.record_resilience_event(
                "fallback", "serve_decode",
                action_detail=f"{rung}_to_{rungs[i + 1]}",
                error=type(e).__name__,
            )
    raise AssertionError("serve_decode ladder is empty")  # pragma: no cover


def _gather_ffa_decode(q_batch, cache, host_lengths, softmax_scale):
    """Per-slot gather+FFA decode: the reference rung. The new token sits
    at position ``length - 1`` (appended before attending), so the causal
    band covers exactly the stored rows."""
    S, hq, d = q_batch.shape
    dv = cache.v_pages.shape[-1]
    max_pages = cache.page_table.shape[1]
    outs, lses = [], []
    for s, length in enumerate(host_lengths):
        if length <= 0:
            outs.append(jnp.zeros((hq, dv), q_batch.dtype))
            lses.append(jnp.full((hq,), NEG_INF, jnp.float32))
            continue
        out, lse = paged_attn(
            q_batch[s : s + 1], cache, s,
            q_start=int(length) - 1,
            max_pages=max_pages,
            softmax_scale=softmax_scale,
        )
        outs.append(out[0])
        lses.append(lse[0])
    return jnp.stack(outs), jnp.stack(lses)


def _gather_ffa_verify(q_spec, cache, host_lengths, softmax_scale):
    """Per-slot gather+FFA over the ``spec_k`` draft rows at once: row 0
    sits at ``length - spec_k``, the causal band puts row ``t`` at
    ``length - spec_k + t`` — identical geometry to the spec kernel, and
    (per-row FFA online-softmax invariance, reference.py) bitwise-equal to
    issuing the rows as sequential single-token calls."""
    S, spec_k, hq, d = q_spec.shape
    dv = cache.v_pages.shape[-1]
    max_pages = cache.page_table.shape[1]
    outs, lses = [], []
    for s, length in enumerate(host_lengths):
        if length <= 0:
            outs.append(jnp.zeros((spec_k, hq, dv), q_spec.dtype))
            lses.append(jnp.full((spec_k, hq), NEG_INF, jnp.float32))
            continue
        out, lse = paged_attn(
            q_spec[s], cache, s,
            q_start=int(length) - spec_k,
            max_pages=max_pages,
            softmax_scale=softmax_scale,
        )
        outs.append(out)
        lses.append(lse)
    return jnp.stack(outs), jnp.stack(lses)


def _dense_decode(q_batch, cache, host_lengths, softmax_scale):
    """Masked dense softmax over the gathered pages — no Pallas anywhere."""
    S, hq, d = q_batch.shape
    dv = cache.v_pages.shape[-1]
    hk = cache.k_pages.shape[2]
    g = hq // hk
    if softmax_scale is None:
        softmax_scale = float(d) ** -0.5
    outs, lses = [], []
    for s, length in enumerate(host_lengths):
        if length <= 0:
            outs.append(jnp.zeros((hq, dv), q_batch.dtype))
            lses.append(jnp.full((hq,), NEG_INF, jnp.float32))
            continue
        k, v = gather_kv(cache, s)  # (cap, hk, d)
        k = k[:length].astype(jnp.float32)
        v = v[:length].astype(jnp.float32)
        q = q_batch[s].astype(jnp.float32)  # (hq, d)
        kh = jnp.repeat(k, g, axis=1)  # (length, hq, d)
        scores = jnp.einsum("hd,lhd->hl", q, kh) * softmax_scale
        m = jnp.max(scores, axis=1, keepdims=True)
        p = jnp.exp(scores - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        vh = jnp.repeat(v, g, axis=1)
        out = jnp.einsum("hl,lhd->hd", p / l, vh)
        outs.append(out.astype(q_batch.dtype))
        lses.append((m[:, 0] + jnp.log(l[:, 0])).astype(jnp.float32))
    return jnp.stack(outs), jnp.stack(lses)


def _dense_verify(q_spec, cache, host_lengths, softmax_scale):
    """Dense softmax over the draft rows, one per-row causal horizon."""
    S, spec_k, hq, d = q_spec.shape
    dv = cache.v_pages.shape[-1]
    outs, lses = [], []
    for t in range(spec_k):
        # row t of every slot is a plain decode step over the prefix that
        # ends at its own position
        t_lengths = tuple(
            max(0, length - (spec_k - 1 - t)) if length > 0 else 0
            for length in host_lengths
        )
        out, lse = _dense_decode(
            q_spec[:, t], cache, t_lengths, softmax_scale
        )
        outs.append(out)
        lses.append(lse)
    return jnp.stack(outs, axis=1), jnp.stack(lses, axis=1)
