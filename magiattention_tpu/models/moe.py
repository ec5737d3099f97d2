"""Mixture-of-Experts transformer with expert parallelism (EP) over the mesh.

The reference delegates MoE/expert parallelism to Megatron-LM (ref
examples/megatron/README.md — SURVEY §2.8 lists TP/PP/EP as "delegated to
Megatron; not implemented in-repo"); the TPU build provides it natively so
the CP attention engine composes with an in-framework MoE model family.

TPU-first design (GShard/Switch capacity routing, the canonical XLA MoE):

- **Static shapes everywhere.** Top-k routing lowers to one-hot matmuls and
  a cumsum-based position-in-expert assignment; each expert processes a
  fixed ``capacity`` of token slots per shard. Overflowing tokens are
  dropped (their combine weight is 0, the residual stream carries them
  unchanged) — no dynamic shapes reach XLA, so everything tiles onto the
  MXU.
- **EP = ``lax.all_to_all`` over a mesh axis.** Experts are sharded over the
  ``ep`` axis (which may be the same devices as the cp/dp axis — the
  DeepSpeed-MoE "expert-parallel group == data-parallel group" layout).
  Token slots travel shard -> expert shard and back with two all_to_alls
  riding ICI, exactly the comm pattern the reference's grpcoll a2av tier
  uses for KV (comm/primitives.py) — here it is the *token* payload.
- **Batched expert matmuls.** The per-shard expert FFN is a single
  ``(E_local, tokens, dim) x (E_local, dim, ffn)`` einsum — one batched MXU
  op, not a Python loop over experts.

Gating math follows Mixtral (softmax over selected top-k logits); auxiliary
load-balancing loss follows Switch Transformer (mean fraction x mean prob).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..api import dispatch, get_mesh, get_position_ids
from jax import shard_map
from ..dist_attn_runtime_mgr import DistAttnRuntimeKey
from ..kernels import registry, tile_policy
from ..kernels.grouped_matmul import grouped_matmul, note_tile_stats
from ..utils.profiling import REGION, profile_scope
from .llama import (
    LlamaConfig,
    _rms_norm,
    attn_block,
    embed_dispatched,
    masked_ce,
)


@dataclass(frozen=True)
class MoEConfig(LlamaConfig):
    """Llama backbone with MoE FFN layers (attention path unchanged)."""

    n_experts: int = 8
    top_k: int = 2
    # per-expert token slots per EP shard = ceil(top_k * S_shard / E) * cf
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01


def init_moe_params(cfg: MoEConfig, key: jax.Array) -> dict:
    """Parameter pytree: llama backbone, MoE FFN per layer.

    Expert weights are stacked on a leading ``n_experts`` dim so they shard
    over the ep axis with a plain ``P('ep', ...)`` annotation.
    """
    ks = jax.random.split(key, 2 + cfg.n_layers)
    dim, dh, ffn = cfg.dim, cfg.head_dim, cfg.ffn_hidden
    hq, hk = cfg.n_heads, cfg.n_kv_heads
    E = cfg.n_experts

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, dtype=jnp.float32) * (
            fan_in ** -0.5
        )

    layers = []
    for i in range(cfg.n_layers):
        lk = jax.random.split(ks[2 + i], 9)
        layers.append(
            {
                "attn_norm": jnp.ones((dim,), jnp.float32),
                "wq": dense(lk[0], (dim, hq * dh), dim),
                "wk": dense(lk[1], (dim, hk * dh), dim),
                "wv": dense(lk[2], (dim, hk * dh), dim),
                "wo": dense(lk[3], (hq * dh, dim), hq * dh),
                "mlp_norm": jnp.ones((dim,), jnp.float32),
                "router": dense(lk[4], (dim, E), dim),
                "w_gate": dense(lk[5], (E, dim, ffn), dim),
                "w_up": dense(lk[6], (E, dim, ffn), dim),
                "w_down": dense(lk[7], (E, ffn, dim), ffn),
            }
        )
    return {
        "embed": dense(ks[0], (cfg.vocab_size, dim), dim),
        "final_norm": jnp.ones((dim,), jnp.float32),
        "lm_head": dense(ks[1], (dim, cfg.vocab_size), dim),
        "layers": layers,
    }


def _check_experts_divisible(n_experts: int, ep: int, ep_axis) -> None:
    if ep and n_experts % ep:
        raise ValueError(
            f"n_experts={n_experts} must be divisible by the ep axis size "
            f"{ep} (axis {ep_axis!r})"
        )


def shard_moe_params(
    params: dict, mesh: Mesh, dp_axis: str = "cp", ep_axis: str | None = None
) -> dict:
    """ZeRO-3 first-dim sharding over dp/cp + expert sharding over ep.

    Expert-stacked weights (leading dim ``n_experts``) shard their expert
    dim over ``ep_axis``; everything else follows llama's ZeRO-3 layout.
    ``ep_axis`` may equal ``dp_axis`` (expert-parallel group == data-
    parallel group).
    """
    ep = mesh.shape[ep_axis] if ep_axis else 1

    def s2(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    def s(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in ("w_gate", "w_up", "w_down") and x.ndim == 3:
            if ep_axis:
                _check_experts_divisible(x.shape[0], ep, ep_axis)
                return s2(x, P(ep_axis, None, None))
            # no EP: ZeRO-3 the expert dim over dp like every other
            # first-dim-shardable weight (experts are the dominant params)
            if x.shape[0] % mesh.shape[dp_axis] == 0:
                return s2(x, P(dp_axis, None, None))
            return s2(x, P())
        dp_ok = x.ndim >= 2 and x.shape[0] % mesh.shape[dp_axis] == 0
        if dp_ok:
            return s2(x, P(dp_axis, *([None] * (x.ndim - 1))))
        return s2(x, P())

    return jax.tree_util.tree_map_with_path(s, params)


# ---------------------------------------------------------------------------
# the MoE FFN layer
# ---------------------------------------------------------------------------


def _route(h32, router_w, cfg: MoEConfig):
    """Top-k routing tensors for one shard's tokens.

    Returns (dispatch ``(S, E, C)`` bool-as-dtype one-hot, combine
    ``(S, E, C)`` probs, aux load-balance loss scalar).
    """
    S = h32.shape[0]
    E, K = cfg.n_experts, cfg.top_k
    C = int(np.ceil(K * S / E * cfg.capacity_factor))
    logits = h32 @ router_w  # (S, E) fp32
    probs = jax.nn.softmax(logits, axis=-1)

    # Mixtral gating: softmax over the selected top-k logits
    topv, topi = jax.lax.top_k(logits, K)  # (S, K)
    gates = jax.nn.softmax(topv, axis=-1)  # (S, K)

    # Switch aux loss: E * mean_frac_per_expert . mean_prob_per_expert
    sel1 = jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32)
    aux = E * jnp.mean(sel1, axis=0) @ jnp.mean(probs, axis=0)

    # position-in-expert via cumsum over the flattened (K, S) priority
    # order: k=0 choices of all tokens beat k=1 choices (GShard's policy).
    # Top-k indices are distinct per token, so each (s, e) pair appears at
    # most once across K — sum over K *before* the one-hot over C, keeping
    # the big tensor at (S, E, C) instead of (K, S, E, C).
    onehot = jax.nn.one_hot(topi.T, E, dtype=jnp.float32)  # (K, S, E)
    flat = onehot.reshape(K * S, E)
    pos = jnp.cumsum(flat, axis=0) - flat  # slots before this entry
    pos = pos.reshape(K, S, E)
    keep = flat.reshape(K, S, E) * (pos < C)  # (K, S, E)
    sel = jnp.sum(keep, axis=0)  # (S, E) — 0/1
    pos_se = jnp.sum(pos * keep, axis=0)  # (S, E) — slot when sel
    gate_se = jnp.einsum("sk,kse->se", gates, keep)
    posc = jax.nn.one_hot(
        pos_se.astype(jnp.int32), C, dtype=jnp.float32
    )  # (S, E, C)
    dispatch_t = sel[..., None] * posc  # (S, E, C) — one slot per (s, e)
    combine = gate_se[..., None] * posc
    return dispatch_t, combine, aux


def _moe_ffn_local(
    h, router, w_gate, w_up, w_down, cfg: MoEConfig,
    ep_axis: str | None, ep: int,
):
    """MoE FFN on one shard's tokens ``h: (S_local, dim)``.

    Runs inside shard_map when ``ep_axis`` is set: expert weights arrive
    ep-sharded ``(E/ep, dim, ffn)``; token slots all_to_all to the expert
    shards and back. With ``ep_axis=None`` (single shard) the all_to_alls
    vanish and the full expert stack is local. Expert id convention:
    ``e = ep_rank * (E // ep) + e_local`` (shard p owns the p-th expert
    block).
    """
    dt = h.dtype
    h32 = h.astype(jnp.float32)
    dispatch_t, combine, aux = _route(h32, router, cfg)
    S, E, C = dispatch_t.shape

    # gather token slots: (E, C, dim)
    slots = jnp.einsum("seC,sd->eCd", dispatch_t.astype(dt), h)

    if ep_axis is not None and ep > 1:
        # send each peer its expert block's slots; receive my block's
        # slots from every peer. all_to_all(tiled=False, split 0, concat
        # 0) yields (ep=source_peer, E/ep, C, d); batch experts, stack
        # source peers into the slot axis: (E/ep, ep*C, d).
        recv = jax.lax.all_to_all(
            slots.reshape(ep, E // ep, C, -1), ep_axis,
            split_axis=0, concat_axis=0, tiled=False,
        )
        slots = recv.transpose(1, 0, 2, 3).reshape(E // ep, ep * C, -1)
        aux = jax.lax.pmean(aux, ep_axis)

    # batched expert FFN: one einsum per projection (E_local batched matmul)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", slots, w_gate.astype(dt)))
    u = jnp.einsum("ecd,edf->ecf", slots, w_up.astype(dt))
    out = jnp.einsum("ecf,efd->ecd", g * u, w_down.astype(dt))

    if ep_axis is not None and ep > 1:
        # inverse of the forward exchange: (E/ep, ep*C, d) -> split the
        # slot axis back by token-owner peer -> (ep, E/ep, C, d) -> a2a
        # -> (ep=expert_shard, E/ep, C, d) -> (E, C, d)
        send = out.reshape(E // ep, ep, C, -1).swapaxes(0, 1)
        out = jax.lax.all_to_all(
            send, ep_axis, split_axis=0, concat_axis=0, tiled=False,
        ).reshape(E, C, -1)

    y = jnp.einsum("seC,eCd->sd", combine.astype(dt), out)
    return y, aux


def moe_ffn(h, lyr, cfg: MoEConfig, mesh=None, ep_axis=None):
    """Public MoE FFN entry.

    - ``mesh`` given: wraps itself in a shard_map over ``ep_axis`` (tokens
      sharded over the same axis — the expert-parallel group == data/cp
      group layout).
    - ``mesh=None, ep_axis`` given: already inside a shard_map; uses the
      bound axis name directly.
    - both None: single-shard (no comm).
    """
    args = (lyr["router"], lyr["w_gate"], lyr["w_up"], lyr["w_down"])
    if mesh is None:
        ep = jax.lax.axis_size(ep_axis) if ep_axis is not None else 1
        _check_experts_divisible(cfg.n_experts, ep, ep_axis)
        return _moe_ffn_local(h, *args, cfg, ep_axis, ep)
    ep = mesh.shape[ep_axis]
    _check_experts_divisible(cfg.n_experts, ep, ep_axis)
    fn = shard_map(
        partial(_moe_ffn_local, cfg=cfg, ep_axis=ep_axis, ep=ep),
        mesh=mesh,
        in_specs=(
            P(ep_axis),  # tokens
            P(),  # router (replicated)
            P(ep_axis), P(ep_axis), P(ep_axis),  # expert-stacked weights
        ),
        out_specs=(P(ep_axis), P()),
    )
    return fn(h, *args)


# ---------------------------------------------------------------------------
# a chip's share of an expert layer that drops no token
# ---------------------------------------------------------------------------

ROUTES_NAME = "moe_routes"
ROUTES_SAVED = jax.checkpoint_policies.save_only_these_names(ROUTES_NAME)


def route_sigmoid_topk(h, router, bias, top_k: int, scale: float):
    """DeepSeek-V3-style routing over ALL the experts ``router`` is wide:
    ``s = sigmoid(h W_r)`` in float32, the ``top_k`` of ``s + bias`` chosen
    (``bias`` a buffer: it picks, it neither weighs nor learns), weights
    ``s[chosen] / sum(s[chosen]) * scale``. Returns ``(chosen ids (S, K)
    int32, weights (S, K) float32, s (S, n_experts) float32)``."""
    s = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), router, precision=jax.lax.Precision.HIGHEST))
    return _top_k_of_scores(s, bias, top_k, scale)


def route_softmax_topk(h, router, bias, top_k: int, scale: float):
    """Softmax routing (the Mixtral and DeepSeek-V2 lineage) over ALL the
    experts ``router`` is wide: ``s = softmax(h W_r)`` in float32, and then
    as :func:`route_sigmoid_topk` — the ``top_k`` of ``s + bias`` chosen,
    weights ``s[chosen] / sum(s[chosen]) * scale``, which is the softmax
    over the chosen logits alone. Same returns."""
    s = jax.nn.softmax(jnp.dot(
        h.astype(jnp.float32), router, precision=jax.lax.Precision.HIGHEST),
        axis=-1)
    return _top_k_of_scores(s, bias, top_k, scale)


def _top_k_of_scores(s, bias, top_k: int, scale: float):
    _, topi = jax.lax.top_k(jax.lax.stop_gradient(s + bias), top_k)
    topi = checkpoint_name(topi, ROUTES_NAME)  # saved under remat, see below
    chosen = jnp.take_along_axis(s, topi, axis=-1)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scale
    return topi, weights, s


ROUTES = ("sigmoid_topk", "softmax_topk")


def _local_expert_ids(topi, held: int, offset: int):
    """``(mine, ids)``: whether each chosen expert is one of the ``held``
    from ``offset``, and its number among them (``held`` for one that is
    held elsewhere)."""
    local = topi - offset
    mine = (local >= 0) & (local < held)
    return mine, jnp.where(mine, local, held)


def held_expert_rows(topi, held: int, expert_offset: int = 0):
    """Rows routed to each of the ``held`` experts, ``(held,)`` int32, from
    the chosen ids of :func:`dropless_moe_ffn`."""
    _, ids = _local_expert_ids(topi, held, expert_offset)
    return jnp.zeros((held + 1,), jnp.int32).at[ids.reshape(-1)].add(1)[:held]


EXPERT_ACTS = ("relu2", "swiglu")


def _expert_act(up, act: str):
    """An expert's activation on the float32 result of its up product,
    taken before the rounding: ``relu(up)^2``, or for ``swiglu``, whose up
    product is gate and up side by side ``(.., 2 ffn)``, ``silu(gate) *
    up``."""
    if act == "relu2":
        return jnp.square(jax.nn.relu(up))
    gate, up = jnp.split(up, 2, axis=-1)
    return jax.nn.silu(gate) * up


def _sum_choices(x, slot, gate):
    """``sum_c gate[:, c] x[slot[:, c]]`` for ``slot`` and ``gate`` ``(tokens,
    k)``: a choice at a time, each gathered straight into one float32 sum,
    rounded once to ``x``'s dtype. No (token, choice)-shaped array is made:
    a ``k`` axis second-minor is no tile at k = 4 or 6, and XLA would relay
    out every pair in float32. A slot past ``x``'s rows adds nothing."""
    rows = x.shape[0]
    gate = jnp.where(slot < rows, gate, 0.0)
    slot = jnp.minimum(slot, rows - 1)
    total = jnp.zeros((slot.shape[0], x.shape[-1]), jnp.float32)
    for c in range(slot.shape[1]):
        total = total + gate[:, c, None] * x[slot[:, c]].astype(jnp.float32)
    return total.astype(x.dtype)


@jax.custom_vjp
def _token_rows(h, pairs, slot):
    """``h[pairs // k]``: the tokens' rows of the (token, choice) pairs
    ``pairs`` (the head of the sorted order), without a copy of ``h`` a
    choice. ``slot`` ``(tokens, k)`` is the place of every pair in the
    sorted order, so the backward is a gather too: a token sums ``g[slot]``
    over its ``k`` choices (:func:`_sum_choices`), a slot past ``g``'s rows
    adding nothing."""
    return h[pairs // slot.shape[1]]


def _token_rows_fwd(h, pairs, slot):
    return _token_rows(h, pairs, slot), slot


def _token_rows_bwd(slot, g):
    return _sum_choices(g, slot, jnp.ones(slot.shape, jnp.float32)), None, None


_token_rows.defvjp(_token_rows_fwd, _token_rows_bwd)


@jax.custom_vjp
def _weighed_rows(out, gate, slot, pairs):
    """``y[t] = sum_c gate[t, c] out[slot[t, c]]``, the way back from the
    buffer in expert order to the tokens (:func:`_sum_choices`); a pair
    whose slot is past ``out``'s rows adds nothing. ``pairs`` is the pair
    of each of ``out``'s rows (``slot[pairs[j]] == j``), so the backward
    runs in the buffer's order: row ``j`` of token ``t = pairs[j] // k``
    takes ``d out[j] = gate_j dy[t]`` and gives its pair ``d gate =
    <out[j], dy[t]>``, both in float32; ``capacity`` rows of ``dy`` are
    gathered, not one a pair."""
    return _sum_choices(out, slot, gate)


def _weighed_rows_fwd(out, gate, slot, pairs):
    return _weighed_rows(out, gate, slot, pairs), (out, gate, slot, pairs)


def _weighed_rows_bwd(res, dy):
    out, gate, slot, pairs = res
    rows = out.shape[0]
    dy_rows = dy[pairs // slot.shape[1]].astype(jnp.float32)
    d_out = gate.reshape(-1)[pairs][:, None] * dy_rows
    dots = jnp.sum(out.astype(jnp.float32) * dy_rows, axis=-1)
    d_gate = jnp.where(slot < rows, dots[jnp.minimum(slot, rows - 1)], 0.0)
    return d_out.astype(out.dtype), d_gate.astype(gate.dtype), None, None


_weighed_rows.defvjp(_weighed_rows_fwd, _weighed_rows_bwd)


def _held_experts_block(h, topi, weights, w_up, w_down, sizes, *,
                        offset: int, tile_rows: int, act: str, capacity: int):
    """The held experts' part of the layer for one block of tokens: every
    (token, choice) pair is a row; rows are sorted by local expert id, the
    pairs of experts held elsewhere last, past the groups, where the grouped
    product computes nothing. The buffer in expert order holds the first
    ``capacity`` rows of that order: the caller gives ``tokens x top_k``,
    the worst case, or a smaller size to a block whose ``sizes`` (the group
    sizes the grouped product is given, :func:`held_expert_rows`) sum to no
    more, so no row is ever dropped. The way back gathers each choice's rows
    of the buffer into one float32 sum a token, weighed by the gate (0 for
    an expert held elsewhere, nothing for a pair past the buffer); its
    transpose, and that of the way in, run in the buffer's order
    (:func:`_weighed_rows`, :func:`_token_rows`): no array of the block's
    (token, choice) pairs is made either way."""
    sb, k = topi.shape
    with profile_scope(REGION.moe_route):
        mine, gid = _local_expert_ids(topi, w_up.shape[0], offset)
        order = jnp.argsort(gid.reshape(-1), stable=True)
        slot = jnp.zeros_like(order).at[order].set(
            jnp.arange(sb * k, dtype=order.dtype)).reshape(sb, k)
        head = order[:capacity]
        live = (jnp.arange(capacity) < jnp.sum(sizes))[:, None]
    # past the groups the grouped product writes nothing, forward or
    # backward: what it leaves there is masked on the way in and out
    with profile_scope(REGION.moe_rows):
        rows = jnp.where(live, _token_rows(h, head, slot), 0)
    grouped = partial(grouped_matmul, group_sizes=sizes, tile_rows=tile_rows)
    with profile_scope(REGION.moe_experts):
        up = grouped(rows, w_up)  # float32: the activation before rounding
        inner = jnp.where(live, _expert_act(up, act), 0).astype(h.dtype)
        out = grouped(inner, w_down, out_dtype=h.dtype)
    with profile_scope(REGION.moe_rows):
        out = jnp.where(live, out, 0)
        return _weighed_rows(out, jnp.where(mine, weights, 0.0), slot, head)


def _tiered_experts_block(capacity: int, worst: int, **static):
    """:func:`_held_experts_block` at ``capacity`` rows for a block of
    tokens whose ``sizes`` fit, at ``worst`` (``tokens x top_k``) for one
    whose do not (and for every block, without a branch, where ``capacity``
    is the worst case). Rematerialised: the residuals are the block's
    inputs, and the backward makes the forward again at the size the
    forward ran at. A ``custom_vjp`` and not ``jax.checkpoint`` round a
    ``lax.cond``, whose branches would hand the backward each other's
    residuals, zero-filled and worst-case-sized: here both ``cond``s carry
    block-shaped values only."""

    def at(rows: int):
        return partial(_held_experts_block, capacity=rows, **static)

    def grads_at(rows: int):
        def grads(dy, h, topi, weights, w_up, w_down, sizes):
            return jax.vjp(
                lambda h, weights, w_up, w_down: at(rows)(
                    h, topi, weights, w_up, w_down, sizes),
                h, weights, w_up, w_down)[1](dy)
        return grads

    def tiered(fn_at, *args):
        if capacity >= worst:
            return fn_at(worst)(*args)
        return jax.lax.cond(  # args[-1]: the block's sizes
            jnp.sum(args[-1]) <= capacity, fn_at(capacity), fn_at(worst),
            *args)

    @jax.custom_vjp
    def block(h, topi, weights, w_up, w_down, sizes):
        return tiered(at, h, topi, weights, w_up, w_down, sizes)

    def block_fwd(*args):
        return tiered(at, *args), args

    def block_bwd(args, dy):
        dh, dweights, dw_up, dw_down = tiered(grads_at, dy, *args)
        return dh, None, dweights, dw_up, dw_down, None

    block.defvjp(block_fwd, block_bwd)
    return block


def dropless_moe_ffn(
    h, lyr, *, top_k: int, scale: float, expert_offset: int = 0,
    token_block: int = 8192, act: str = "relu2", route: str = "sigmoid_topk",
):
    """One chip's share of an expert layer that drops no token.

    The layer is told which experts it holds: ``lyr["w_up"]`` ``(held, dim,
    ffn)`` and ``lyr["w_down"]`` ``(held, ffn, dim)`` are experts
    ``expert_offset .. expert_offset + held`` of the ``lyr["router"]``'s
    ``n_experts`` columns. It routes over all of them (``route``, one of
    :data:`ROUTES`: :func:`route_sigmoid_topk`, :func:`route_softmax_topk`;
    ``lyr["e_bias"]`` takes part in the choice either way), computes
    ``w_down relu(w_up h)^2`` of its
    own experts for the rows routed to them, adds the shared expert
    (``ws_up``, ``ws_down``, every token) and leaves out what the experts
    held elsewhere would have added: on one chip the layer runs without its
    exchange, and summing the routed parts of all the shares with the
    shared expert counted once gives the whole layer. Tokens go through in
    blocks of ``token_block`` (each block rematerialised in the backward).

    **The row buffer.** A block sorts its ``token_block x top_k`` (token,
    choice) pairs by expert and runs the experts held on the head of that
    order. The buffer is sized by what a block EXPECTS for the experts held
    (``tile_policy.grouped_row_capacity``: ``held / n_experts`` of the
    pairs, times a margin, in whole row tiles); a block counts the rows it
    got, and one whose rows do not fit runs the same code at the worst
    case, ``token_block x top_k`` rows, so no row is ever dropped and
    nothing is discarded to a capacity. A chip that holds every expert
    expects the worst case: one size, no branch.

    ``act="swiglu"`` is the gated form, three matrices an expert: ``w_up``
    ``(held, dim, 2 ffn)`` holds each expert's gate and up projections side
    by side, so that both are ONE grouped product, and the expert is
    ``w_down (silu(gate) * up)``, the activation taken of the float32
    product before the rounding; ``ws_up`` ``(dim, 2 shared_ffn)`` likewise.

    Under ``jax.checkpoint`` the chosen ids must be SAVED, not recomputed
    (``policy=ROUTES_SAVED``): the recomputed forward is another XLA
    program region, free to keep a bf16 value in float32, and a score that
    moves in its last bits flips the sixth expert of a token in a few
    hundred, whose gradient then belongs to an expert the forward never ran.

    Returns ``(y (S, dim), {"topi": chosen expert ids (S, K), "scores": the
    router's scores (S, n_experts) float32, "group_rows": the rows the
    grouped product took for each held expert (held,), "block_rows": the
    rows each block of tokens had for the experts held (blocks,),
    "blocks_fitted": how many of them fitted the expected buffer})``.
    """
    if act not in EXPERT_ACTS:
        raise ValueError(f"act {act!r}: one of {EXPERT_ACTS}")
    if route not in ROUTES:
        raise ValueError(f"route {route!r}: one of {ROUTES}")
    dt = h.dtype
    s, dim = h.shape
    with profile_scope(REGION.moe_route):
        route_topk = (route_softmax_topk if route == "softmax_topk"
                      else route_sigmoid_topk)
        topi, weights, scores = route_topk(
            h, lyr["router"], lyr["e_bias"], top_k, scale)
    with profile_scope(REGION.moe_experts):
        w_up, w_down = lyr["w_up"].astype(dt), lyr["w_down"].astype(dt)
    sb = token_block if s % token_block == 0 else s
    held, n_experts = w_up.shape[0], lyr["router"].shape[-1]
    # the rows an expert expects of a block are known here, the sizes it
    # gets are not: the row tile and the buffer are the rules' for that
    # expectation
    worst = sb * top_k  # a block's (token, choice) pairs
    tile_rows = tile_policy.grouped_row_tile(worst // n_experts)
    capacity = tile_policy.grouped_row_capacity(
        worst * held / n_experts, worst, tile_rows)
    key = (s, dim, *w_up.shape, top_k)
    registry.note_choice("moe_route", key, route, "config")
    registry.note_choice("moe_grouped", key, "pallas_grouped", "default")
    registry.note_choice(
        "moe_grouped_tiles", key, f"rows{tile_rows}", "shape_rule")
    registry.note_choice(
        "moe_row_buffer", key, f"rows{capacity}of{worst}", "shape_rule")
    block = _tiered_experts_block(
        capacity, worst, offset=expert_offset, tile_rows=tile_rows, act=act)

    def one_block(args):
        with profile_scope(REGION.moe_route):
            sizes = held_expert_rows(args[1], held, expert_offset)
        note_tile_stats(sizes, tile_rows, row_buffer=capacity)
        return block(*args, w_up, w_down, sizes), sizes

    # what the loop over the blocks adds round them (a block's rows cut out
    # and its result put back, the weights' gradients summed over the
    # blocks) carries the loop's scope: row movement
    with profile_scope(REGION.moe_rows):
        routed, sizes = jax.lax.map(one_block, tuple(
            v.reshape(s // sb, sb, -1) for v in (h, topi, weights)))
    with profile_scope(REGION.moe_shared):
        if act == "relu2":
            shared = jnp.square(jax.nn.relu(h @ lyr["ws_up"].astype(dt))) @ (
                lyr["ws_down"].astype(dt))
        else:
            shared = _expert_act(jnp.dot(
                h, lyr["ws_up"].astype(dt),
                preferred_element_type=jnp.float32),
                act).astype(dt) @ lyr["ws_down"].astype(dt)
        y = routed.reshape(s, dim) + shared
    block_rows = jnp.sum(sizes, axis=1)
    return y, {
        "topi": topi, "scores": scores, "group_rows": jnp.sum(sizes, axis=0),
        "block_rows": block_rows,
        "blocks_fitted": jnp.sum(block_rows <= capacity)}


# ---------------------------------------------------------------------------
# full model: llama backbone + MoE FFN
# ---------------------------------------------------------------------------


def moe_forward(
    params: dict,
    cfg: MoEConfig,
    tokens: jax.Array,
    attn_key: DistAttnRuntimeKey,
    ep_axis: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Forward on the dispatched CP layout; MoE FFN with optional EP.

    When ``ep_axis`` is given the caller must run this under pjit on a mesh
    carrying that axis; the MoE layer's shard_map boundary is established
    per layer against the dispatched token shard. Returns
    ``(logits_dispatched, aux_loss)``.
    """
    dt = cfg.jdtype
    x = embed_dispatched(params["embed"], tokens, attn_key, dt)
    pos = get_position_ids(attn_key)
    mesh = get_mesh(attn_key) if ep_axis is not None else None

    aux_total = jnp.zeros((), jnp.float32)

    def layer(x, lyr):
        x = attn_block(x, lyr, cfg, pos, attn_key)
        h = _rms_norm(x, lyr["mlp_norm"], cfg.norm_eps)
        y, aux = moe_ffn(h, lyr, cfg, mesh=mesh, ep_axis=ep_axis)
        return x + y, aux

    if cfg.remat:
        layer = jax.checkpoint(layer)

    for lyr in params["layers"]:
        x, aux = layer(x, lyr)
        aux_total = aux_total + aux

    x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(dt)).astype(jnp.float32)
    return logits, aux_total / max(cfg.n_layers, 1)


def moe_loss_fn(params, cfg, tokens, labels, attn_key, ep_axis=None):
    logits, aux = moe_forward(params, cfg, tokens, attn_key, ep_axis)
    labels_d = dispatch(labels, attn_key)
    return masked_ce(logits, labels_d) + cfg.aux_loss_coef * aux


@partial(jax.jit, static_argnums=(1, 4, 5), donate_argnums=(0,))
def moe_train_step(
    params, cfg: MoEConfig, tokens, labels, attn_key, ep_axis=None,
    lr: float = 1e-4,
):
    loss, grads = jax.value_and_grad(moe_loss_fn)(
        params, cfg, tokens, labels, attn_key, ep_axis
    )
    params = jax.tree.map(
        lambda p, g: p - lr * g.astype(p.dtype), params, grads
    )
    return params, loss


# ---------------------------------------------------------------------------
# dense reference (testing): per-token full expert sum, no capacity drops
# ---------------------------------------------------------------------------


def moe_ffn_reference(h, lyr, cfg: MoEConfig):
    """O(S*E) dense reference of the MoE FFN — every token visits its top-k
    experts directly (no capacity, no drops). Ground truth for the routed
    implementation wherever no slot overflows."""
    h32 = h.astype(jnp.float32)
    logits = h32 @ lyr["router"]
    topv, topi = jax.lax.top_k(logits, cfg.top_k)
    gates = jax.nn.softmax(topv, axis=-1)  # (S, K)
    dt = h.dtype

    def expert(e, x):
        g = jax.nn.silu(x @ lyr["w_gate"][e].astype(dt))
        u = x @ lyr["w_up"][e].astype(dt)
        return (g * u) @ lyr["w_down"][e].astype(dt)

    all_out = jnp.stack(
        [expert(e, h) for e in range(cfg.n_experts)], axis=1
    )  # (S, E, dim)
    sel = jnp.take_along_axis(
        all_out, topi[:, :, None], axis=1
    )  # (S, K, dim)
    return jnp.sum(sel * gates[:, :, None].astype(dt), axis=1)
