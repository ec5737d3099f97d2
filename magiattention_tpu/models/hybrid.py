"""A decoder built from a layer pattern: Mamba-2, expert and attention mixers.

The block builder of the hybrid families (``nemotron_h``): ``pattern`` is a
string with one letter a block, and every block is ``x + mixer(RMSNorm(x))``
with ONE mixer:

* ``M`` — a Mamba-2 mixer: ``in_proj`` to ``[z | x B C | dt]``, a causal
  depthwise convolution and SiLU over ``x B C``, the chunked state-space scan
  (``kernels/ssd.py``), ``D`` skip, a gated group RMSNorm, ``out_proj``. The
  scan's state and the convolution's taps stop at a document's first token;
* ``E`` — this chip's share of a dropless expert layer with a shared expert
  (``models/moe.py:dropless_moe_ffn``);
* ``*`` — attention through ``models/llama.py:attn_block`` (``calc_attn`` on
  the dispatched layout), without a rotary embedding when ``rope_theta`` is
  ``None``.

Everything else is the Llama family's, used and not copied:
``embed_dispatched``, ``_rms_norm``, ``masked_ce``, ``_StepJit`` with
``TPU_STEP_COMPILER_OPTIONS``; fp32 masters, bf16 activations, ``remat`` of
a block, plain SGD. The documents' boundaries come from the runtime key
(``api.get_document_starts``). At cp > 1 the scan's state would have to
cross ``dispatch``'s chunk permutation, which nothing here does yet: the
forward refuses such a key by name.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..api import dispatch, get_document_starts, get_position_ids
from ..dist_attn_runtime_mgr import DistAttnRuntimeKey
from ..kernels import ssd
from .llama import (
    _rms_norm,
    _StepJit,
    attn_block,
    embed_dispatched,
    masked_ce,
)
from .moe import ROUTES_SAVED, dropless_moe_ffn

MIXERS = ("M", "E", "*")


@dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 1024
    dim: int = 256
    pattern: str = "ME*"
    norm_eps: float = 1e-5
    # '*': attention (the names attn_block reads)
    n_heads: int = 4
    n_kv_heads: int = 1
    head_dim: int = 64
    rope_theta: float | None = None
    # 'M': Mamba-2
    mamba_heads: int = 8
    mamba_head_dim: int = 64
    ssm_groups: int = 2
    ssm_state: int = 32
    conv_kernel: int = 4
    chunk_size: int = ssd.CHUNK
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # 'E': experts; the router is n_experts wide, this chip holds
    # experts expert_offset .. expert_offset + experts_held
    n_experts: int = 8
    top_k: int = 2
    experts_held: int = 8
    expert_offset: int = 0
    expert_ffn: int = 128
    shared_ffn: int = 256
    routed_scale: float = 1.0
    moe_token_block: int = 8192
    dtype: str = "bfloat16"
    remat: bool = False

    def __post_init__(self):
        bad = set(self.pattern) - set(MIXERS)
        if bad or not self.pattern:
            raise ValueError(
                f"pattern {self.pattern!r}: one of {MIXERS} a block")
        if self.chunk_size != ssd.CHUNK:
            raise ValueError(
                f"chunk_size {self.chunk_size}: the scan kernel's chunk is "
                f"{ssd.CHUNK}")
        if not 0 <= self.expert_offset <= self.n_experts - self.experts_held:
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} are not "
                f"among the router's {self.n_experts}")

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state


def _dense(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5


def _init_mamba(cfg: HybridConfig, key) -> dict:
    k = jax.random.split(key, 6)
    h, d_in = cfg.mamba_heads, cfg.d_inner
    # dt_bias: the inverse softplus of a log-uniform step in [min, max]
    dt = jnp.exp(jax.random.uniform(k[2], (h,), jnp.float32) * (
        np.log(cfg.time_step_max) - np.log(cfg.time_step_min))
        + np.log(cfg.time_step_min))
    dt = jnp.maximum(dt, cfg.time_step_floor)
    bound = cfg.conv_kernel ** -0.5
    return {
        "norm": jnp.ones((cfg.dim,), jnp.float32),
        "in_proj": _dense(k[0], (cfg.dim, d_in + cfg.conv_dim + h), cfg.dim),
        "conv_w": jax.random.uniform(
            k[1], (cfg.conv_kernel, cfg.conv_dim), jnp.float32, -bound, bound),
        "conv_b": jax.random.uniform(
            k[3], (cfg.conv_dim,), jnp.float32, -bound, bound),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(
            k[4], (h,), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((h,), jnp.float32),
        "gate_norm": jnp.ones((d_in,), jnp.float32),
        "out_proj": _dense(k[5], (d_in, cfg.dim), d_in),
    }


def _init_experts(cfg: HybridConfig, key) -> dict:
    k = jax.random.split(key, 5)
    held, dim, f = cfg.experts_held, cfg.dim, cfg.expert_ffn
    return {
        "norm": jnp.ones((dim,), jnp.float32),
        "router": _dense(k[0], (dim, cfg.n_experts), dim),
        "e_bias": jnp.zeros((cfg.n_experts,), jnp.float32),
        "w_up": _dense(k[1], (held, dim, f), dim),
        "w_down": _dense(k[2], (held, f, dim), f),
        "ws_up": _dense(k[3], (dim, cfg.shared_ffn), dim),
        "ws_down": _dense(k[4], (cfg.shared_ffn, dim), cfg.shared_ffn),
    }


def _init_attention(cfg: HybridConfig, key) -> dict:
    k = jax.random.split(key, 4)
    dim, dh = cfg.dim, cfg.head_dim
    return {
        "attn_norm": jnp.ones((dim,), jnp.float32),
        "wq": _dense(k[0], (dim, cfg.n_heads * dh), dim),
        "wk": _dense(k[1], (dim, cfg.n_kv_heads * dh), dim),
        "wv": _dense(k[2], (dim, cfg.n_kv_heads * dh), dim),
        "wo": _dense(k[3], (cfg.n_heads * dh, dim), cfg.n_heads * dh),
    }


_INIT = {"M": _init_mamba, "E": _init_experts, "*": _init_attention}


def init_params(cfg: HybridConfig, key: jax.Array) -> dict:
    """fp32 masters: matrices as ``llama.init_params``; the scan's ``A_log``
    = log U[1, 16], ``D`` = 1, ``dt_bias`` from the configuration's
    ``time_step_*`` keys, so that the decays are a real model's."""
    ks = jax.random.split(key, 2 + len(cfg.pattern))
    return {
        "embed": _dense(ks[0], (cfg.vocab_size, cfg.dim), cfg.vocab_size),
        "final_norm": jnp.ones((cfg.dim,), jnp.float32),
        "lm_head": _dense(ks[1], (cfg.dim, cfg.vocab_size), cfg.dim),
        "layers": [
            _INIT[kind](cfg, k) for kind, k in zip(cfg.pattern, ks[2:])],
    }


def causal_conv_silu(x, w, b, pos_in_doc):
    """``silu(conv1d(x) + b)``: depthwise, causal, ``w`` ``(taps, channels)``
    with ``w[-1]`` on the token itself; a tap that would reach across its
    document's first token reads zero. The shifted copies stay in ``x``'s
    type (a roll: the rows that wrap are a first document's first, masked
    anyway); products and sum are float32."""
    taps = w.shape[0]
    out = x.astype(jnp.float32) * w[-1] + b
    for lag in range(1, taps):
        back = jnp.where(
            (pos_in_doc >= lag)[:, None], jnp.roll(x, lag, axis=0), 0)
        out += back.astype(jnp.float32) * w[taps - 1 - lag]
    return jax.nn.silu(out).astype(x.dtype)


def gated_group_norm(y, z, w, groups: int, eps: float):
    """``RMSNorm`` over each of ``groups`` runs of channels of ``y *
    silu(z)``, times ``w``; float32 in and out. The groups' mean squares
    and their way back to the channels are two products with a 0/1 matrix
    ``(channels, groups)``: a reshape to ``(T, groups, width)`` costs a
    relayout of the whole array each way."""
    y = y * jax.nn.silu(z)
    channels = y.shape[-1]
    member = (jnp.arange(channels)[:, None] // (channels // groups)
              == jnp.arange(groups)[None, :]).astype(jnp.float32)
    exact = partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    scale = jax.lax.rsqrt(exact(y * y, member) * (groups / channels) + eps)
    return y * exact(scale, member.T) * w


def mamba_mixer(h, lyr, cfg: HybridConfig, pos_in_doc, seg_rows):
    """The Mamba-2 mixer on ``h`` ``(T, dim)``, tokens in natural order."""
    dt_, t = h.dtype, h.shape[0]
    heads, p = cfg.mamba_heads, cfg.mamba_head_dim
    groups, n, d_in = cfg.ssm_groups, cfg.ssm_state, cfg.d_inner
    z, xbc, dt_raw = jnp.split(
        h @ lyr["in_proj"].astype(dt_), [d_in, d_in + cfg.conv_dim], axis=-1)
    xbc = causal_conv_silu(xbc, lyr["conv_w"], lyr["conv_b"], pos_in_doc)
    x, b, c = jnp.split(xbc, [d_in, d_in + groups * n], axis=-1)
    x = x.reshape(t, heads, p)
    step = jax.nn.softplus(dt_raw.astype(jnp.float32) + lyr["dt_bias"])
    y = ssd.ssd_scan(
        x, step, -jnp.exp(lyr["A_log"]), b.reshape(t, groups, n),
        c.reshape(t, groups, n), seg_rows)
    y = y.astype(jnp.float32) + lyr["D"][:, None] * x.astype(jnp.float32)
    y = gated_group_norm(
        y.reshape(t, d_in), z.astype(jnp.float32), lyr["gate_norm"], groups,
        cfg.norm_eps).astype(dt_)
    return y @ lyr["out_proj"].astype(dt_)


def _refuse_cp(attn_key: DistAttnRuntimeKey) -> None:
    if attn_key.cp_size > 1:
        raise NotImplementedError(
            f"hybrid.forward at cp = {attn_key.cp_size}: the scan's state "
            "and the convolution's taps follow a document in natural order, "
            "and dispatch permutes the sequence's chunks over the ranks; "
            "carrying the state across that permutation is not built "
            "(ROADMAP B1). Plan the key for a one-device cp axis.")


def forward(
    params: dict, cfg: HybridConfig, tokens: jax.Array,
    attn_key: DistAttnRuntimeKey, with_routes: bool = False,
):
    """Logits ``(total_seqlen, vocab)`` float32 in dispatched order (at cp 1
    natural order); with ``with_routes`` also each ``E`` block's routing,
    ``[{"topi", "scores", "group_rows"}]``
    (:func:`~.moe.dropless_moe_ffn`)."""
    _refuse_cp(attn_key)
    dt = cfg.jdtype
    x = embed_dispatched(params["embed"], tokens, attn_key, dt)
    pos = get_position_ids(attn_key)
    starts = get_document_starts(attn_key)
    pos_in_doc, seg_rows = pos - starts, ssd.segment_rows(starts)

    def mamba(x, lyr):
        h = _rms_norm(x, lyr["norm"], cfg.norm_eps)
        return x + mamba_mixer(h, lyr, cfg, pos_in_doc, seg_rows), None

    def experts(x, lyr):
        h = _rms_norm(x, lyr["norm"], cfg.norm_eps)
        y, routes = dropless_moe_ffn(
            h, lyr, top_k=cfg.top_k, scale=cfg.routed_scale,
            expert_offset=cfg.expert_offset, token_block=cfg.moe_token_block)
        return x + y, routes

    def attention(x, lyr):
        return attn_block(x, lyr, cfg, pos, attn_key), None

    blocks = {"M": mamba, "E": experts, "*": attention}
    if cfg.remat:  # an E block's chosen experts are saved, never recomputed
        blocks = {kind: jax.checkpoint(fn, policy=ROUTES_SAVED)
                  for kind, fn in blocks.items()}
    routes = []
    for kind, lyr in zip(cfg.pattern, params["layers"]):
        x, routed = blocks[kind](x, lyr)
        if routed is not None:
            routes.append(routed)
    x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(dt)).astype(jnp.float32)
    return (logits, routes) if with_routes else logits


def loss_fn(params, cfg, tokens, labels, attn_key) -> jax.Array:
    """Next-token cross entropy on the dispatched layout; the
    configuration has no auxiliary routing loss."""
    return masked_ce(
        forward(params, cfg, tokens, attn_key), dispatch(labels, attn_key))


@partial(_StepJit, static_argnums=(1, 4), donate_argnums=(0,))
def train_step(
    params: dict, cfg: HybridConfig, tokens: jax.Array, labels: jax.Array,
    attn_key: DistAttnRuntimeKey, lr: float = 1e-4,
) -> tuple[dict, jax.Array]:
    """One SGD step, as ``llama.train_step``."""
    loss, grads = jax.value_and_grad(loss_fn)(
        params, cfg, tokens, labels, attn_key)
    params = jax.tree.map(
        lambda p, g: p - lr * g.astype(p.dtype), params, grads)
    return params, loss


@partial(jax.jit, static_argnums=(1, 3))
def routing_counters(params, cfg: HybridConfig, tokens, attn_key) -> dict:
    """What the expert layers of one forward did with ``tokens``, from the
    program's own routing, per ``E`` block: ``rows_routed`` ``(blocks,)``,
    the (token, choice) pairs whose chosen expert is one of those held;
    ``rows_per_expert`` ``(blocks, held)``, the rows the grouped product
    took for each held expert. A layer that drops no row has their sums
    equal."""
    _, routes = forward(params, cfg, tokens, attn_key, with_routes=True)
    local = jnp.stack([r["topi"] for r in routes]) - cfg.expert_offset
    return {
        "rows_routed": jnp.sum(
            (local >= 0) & (local < cfg.experts_held), axis=(1, 2)),
        "rows_per_expert": jnp.stack([r["group_rows"] for r in routes]),
    }
