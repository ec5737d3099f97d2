"""A decoder built from a layer pattern: Mamba-2, gated delta-rule, expert,
dense and attention mixers.

The block builder of the hybrid families (``nemotron_h``, ``afmoe``,
``mistral4``, ``solar_open2``):
``pattern`` is a string with one letter a block, and every block is ``x +
mixer(RMSNorm(x))`` with ONE mixer (``x + RMSNorm(mixer(RMSNorm(x)))`` where
``post_norm`` is set: a norm of its own on the mixer's output):

* ``M`` — a Mamba-2 mixer: ``in_proj`` to ``[z | x B C | dt]``, a causal
  depthwise convolution and SiLU over ``x B C``, the chunked state-space scan
  (``kernels/ssd.py``), ``D`` skip, a gated group RMSNorm, ``out_proj``. The
  scan's state and the convolution's taps stop at a document's first token;
* ``K`` — a gated delta-rule mixer with a per-channel decay (Kimi Delta
  Attention): q, k, v each projected, a causal depthwise convolution and
  SiLU, q and k L2-normalised per head; a log-decay per key channel ``g =
  -exp(A_log) softplus(h W_fa W_fb + dt_bias)`` (a low-rank projection) and
  ``beta = sigmoid(h W_b)``, doubled where ``kda_neg_eigval`` (the state's
  update ``I - beta k k^T`` may then turn a direction over); the chunked
  recurrence (``kernels/kda.py``); an RMSNorm over each head's channels
  times ``sigmoid(h W_ga W_gb)`` (a low-rank gate), ``W_o``. The state and
  the convolution's taps stop at a document's first token;
* ``E`` — this chip's share of a dropless expert layer with a shared expert
  (``models/moe.py:dropless_moe_ffn``), the experts ``relu2`` (two matrices)
  or ``swiglu`` (three) by ``expert_act``;
* ``D`` — a dense SwiGLU MLP (``models/llama.py:swiglu_mlp``), ``dense_ffn``
  wide;
* ``*`` — attention through ``models/llama.py:attn_block`` (``calc_attn`` on
  the dispatched layout) under the step's key;
* ``W`` — the same block under the step's WINDOW key: a second runtime key,
  made of the first after dispatch
  (``api.make_varlen_key_for_new_mask_after_dispatch``), so that both kinds
  of layer see one layout. A decoder whose layers alternate between a
  sliding window and the full mask is ``W`` and ``*`` blocks in one
  pattern, and its step takes both keys.

An attention block of kind ``k`` rotates q and k where ``rope_theta`` is set
and ``k`` is in ``rope_in`` (a family with a rotary embedding in its window
layers only says ``rope_in="W"``); ``qk_norm`` and ``attn_gate`` give it
the per-head q/k norms and the sigmoid output gate that ``attn_block``
reads from the layer's leaves; ``latent`` (``llama.LatentAttention``) gives
every attention block the latent leaves in place of ``wq``, ``wk``, ``wv``
(low-rank q and kv chains, one rotary key a token, ``n_heads`` key-value
heads through ``calc_attn``), and the positions handed to the blocks are
then each token's place in its document. ``route`` names an ``E`` block's
routing (``moe.ROUTES``). A model layer of attention and then an MLP is two
blocks (``W`` then ``D`` or ``E``).

Everything else is the Llama family's, used and not copied:
``embed_dispatched`` (times ``embed_scale``), ``_rms_norm``, ``masked_ce``,
``_StepJit`` with ``TPU_STEP_COMPILER_OPTIONS``; fp32 masters, bf16
activations, ``remat`` of a block, plain SGD. The documents' boundaries come
from the runtime key (``api.get_document_starts``). At cp > 1 the scan's
state would have to cross ``dispatch``'s chunk permutation, which nothing
here does yet: the forward of a pattern with an ``M`` or a ``K`` refuses such
a key by name.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..api import (
    dispatch,
    get_document_starts,
    get_position_ids,
    same_dispatch,
)
from ..dist_attn_runtime_mgr import DistAttnRuntimeKey
from ..kernels import kda, ssd
from ..utils.profiling import REGION, profile_scope
from .llama import (
    LatentAttention,
    _rms_norm,
    _StepJit,
    attn_block,
    embed_dispatched,
    masked_ce,
    swiglu_mlp,
)
from .moe import EXPERT_ACTS, ROUTES, ROUTES_SAVED, dropless_moe_ffn

MIXERS = ("M", "K", "E", "D", "*", "W")
RECURRENT = {"M": "the Mamba-2 scan", "K": "the gated delta rule"}
ATTENTION = ("*", "W")


@dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 1024
    dim: int = 256
    pattern: str = "ME*"
    norm_eps: float = 1e-5
    post_norm: bool = False  # RMSNorm on every mixer's output as well
    embed_scale: float | None = None  # the embedding times this
    # '*', 'W': attention (the names attn_block reads)
    n_heads: int = 4
    n_kv_heads: int = 1
    head_dim: int = 64
    rope_theta: float | None = None
    rope_in: str = "*W"  # the attention kinds that rotate, given a theta
    qk_norm: bool = False  # RMSNorm over each head's channels of q and k
    attn_gate: bool = False  # the output times sigmoid(h w_attn_gate)
    latent: LatentAttention | None = None  # latent attention, its widths
    # 'D': dense SwiGLU MLP
    dense_ffn: int = 512
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
    # 'K': gated delta rule (conv_kernel and time_step_* shared with 'M')
    kda_heads: int = 4
    kda_head_dim: int = 128
    kda_gate_rank: int = 32  # of the decay's and the output gate's products
    kda_neg_eigval: bool = True  # beta = 2 sigmoid(h W_b), else sigmoid
    # 'E': experts; the router is n_experts wide, this chip holds
    # experts expert_offset .. expert_offset + experts_held
    n_experts: int = 8
    top_k: int = 2
    experts_held: int = 8
    expert_offset: int = 0
    expert_ffn: int = 128
    shared_ffn: int = 256
    routed_scale: float = 1.0
    expert_act: str = "relu2"  # or "swiglu": gate and up side by side
    route: str = "sigmoid_topk"  # or "softmax_topk" (moe.ROUTES)
    moe_token_block: int = 8192
    dtype: str = "bfloat16"
    remat: bool = False

    def __post_init__(self):
        bad = set(self.pattern) - set(MIXERS)
        if bad or not self.pattern:
            raise ValueError(
                f"pattern {self.pattern!r}: one of {MIXERS} a block")
        if set(self.rope_in) - set(ATTENTION):
            raise ValueError(
                f"rope_in {self.rope_in!r}: attention kinds, of {ATTENTION}")
        if self.expert_act not in EXPERT_ACTS:
            raise ValueError(
                f"expert_act {self.expert_act!r}: one of {EXPERT_ACTS}")
        if self.route not in ROUTES:
            raise ValueError(f"route {self.route!r}: one of {ROUTES}")
        if self.latent is not None and (
                self.rope_theta is None
                or not 0 < self.latent.rope_dim < self.head_dim
                or self.latent.rope_dim % 2):
            raise ValueError(
                f"latent attention rotates an even rope_dim "
                f"({self.latent.rope_dim}) of head_dim {self.head_dim} "
                f"channels by rope_theta ({self.rope_theta})")
        if self.latent is not None and (
                self.n_kv_heads != self.n_heads or self.rope_in != "*W"):
            raise ValueError(
                f"latent attention runs expanded, n_heads ({self.n_heads}) "
                f"key-value heads, and rotates in every attention block: "
                f"n_kv_heads {self.n_kv_heads} and rope_in "
                f"{self.rope_in!r} would not be read")
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


def _dt_bias(cfg: HybridConfig, key, width: int):
    """The inverse softplus of a log-uniform step in [time_step_min,
    time_step_max], floored at time_step_floor, ``(width,)``."""
    dt = jnp.exp(jax.random.uniform(key, (width,), jnp.float32) * (
        np.log(cfg.time_step_max) - np.log(cfg.time_step_min))
        + np.log(cfg.time_step_min))
    dt = jnp.maximum(dt, cfg.time_step_floor)
    return dt + jnp.log(-jnp.expm1(-dt))


def _init_mamba(cfg: HybridConfig, key) -> dict:
    k = jax.random.split(key, 6)
    h, d_in = cfg.mamba_heads, cfg.d_inner
    bound = cfg.conv_kernel ** -0.5
    return {
        "norm": jnp.ones((cfg.dim,), jnp.float32),
        "in_proj": _dense(k[0], (cfg.dim, d_in + cfg.conv_dim + h), cfg.dim),
        "conv_w": jax.random.uniform(
            k[1], (cfg.conv_kernel, cfg.conv_dim), jnp.float32, -bound, bound),
        "conv_b": jax.random.uniform(
            k[3], (cfg.conv_dim,), jnp.float32, -bound, bound),
        "dt_bias": _dt_bias(cfg, k[2], h),
        "A_log": jnp.log(jax.random.uniform(
            k[4], (h,), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((h,), jnp.float32),
        "gate_norm": jnp.ones((d_in,), jnp.float32),
        "out_proj": _dense(k[5], (d_in, cfg.dim), d_in),
    }


def _init_kda(cfg: HybridConfig, key) -> dict:
    k = jax.random.split(key, 11)
    dim, rank = cfg.dim, cfg.kda_gate_rank
    width = cfg.kda_heads * cfg.kda_head_dim
    bound = cfg.conv_kernel ** -0.5
    return {
        "norm": jnp.ones((dim,), jnp.float32),
        "w_q": _dense(k[0], (dim, width), dim),
        "w_k": _dense(k[1], (dim, width), dim),
        "w_v": _dense(k[2], (dim, width), dim),
        "conv_w": jax.random.uniform(  # q, k, v; no bias
            k[3], (3, cfg.conv_kernel, width), jnp.float32, -bound, bound),
        "w_fa": _dense(k[4], (dim, rank), dim),
        "w_fb": _dense(k[5], (rank, width), rank),
        "A_log": jnp.log(jax.random.uniform(
            k[6], (cfg.kda_heads,), jnp.float32, 1.0, 16.0)),
        "dt_bias": _dt_bias(cfg, k[7], width),  # one a key channel
        "w_b": _dense(k[8], (dim, cfg.kda_heads), dim),
        "w_ga": _dense(k[9], (dim, rank), dim),
        "w_gb": _dense(jax.random.fold_in(k[9], 1), (rank, width), rank),
        "o_norm": jnp.ones((cfg.kda_head_dim,), jnp.float32),
        "w_o": _dense(k[10], (width, dim), width),
    }


def _init_experts(cfg: HybridConfig, key) -> dict:
    k = jax.random.split(key, 5)
    held, dim, f = cfg.experts_held, cfg.dim, cfg.expert_ffn
    up = 2 if cfg.expert_act == "swiglu" else 1  # gate and up side by side
    return {
        "norm": jnp.ones((dim,), jnp.float32),
        "router": _dense(k[0], (dim, cfg.n_experts), dim),
        "e_bias": jnp.zeros((cfg.n_experts,), jnp.float32),
        "w_up": _dense(k[1], (held, dim, up * f), dim),
        "w_down": _dense(k[2], (held, f, dim), f),
        "ws_up": _dense(k[3], (dim, up * cfg.shared_ffn), dim),
        "ws_down": _dense(k[4], (cfg.shared_ffn, dim), cfg.shared_ffn),
    }


def _init_dense(cfg: HybridConfig, key) -> dict:
    k = jax.random.split(key, 3)
    dim, f = cfg.dim, cfg.dense_ffn
    return {
        "norm": jnp.ones((dim,), jnp.float32),
        "w_gate": _dense(k[0], (dim, f), dim),
        "w_up": _dense(k[1], (dim, f), dim),
        "w_down": _dense(k[2], (f, dim), f),
    }


def _init_attention(cfg: HybridConfig, key) -> dict:
    k = jax.random.split(key, 4)
    dim, dh, lat = cfg.dim, cfg.head_dim, cfg.latent
    lyr = {
        "attn_norm": jnp.ones((dim,), jnp.float32),
        "wo": _dense(k[3], (cfg.n_heads * dh, dim), cfg.n_heads * dh),
    }
    if lat is None:
        lyr["wq"] = _dense(k[0], (dim, cfg.n_heads * dh), dim)
        lyr["wk"] = _dense(k[1], (dim, cfg.n_kv_heads * dh), dim)
        lyr["wv"] = _dense(k[2], (dim, cfg.n_kv_heads * dh), dim)
    else:  # the q chain from k[0], the kv chain from k[1]
        qa, qb = jax.random.split(k[0])
        kva, kvb = jax.random.split(k[1])
        lyr["w_q_a"] = _dense(qa, (dim, lat.q_rank), dim)
        lyr["q_a_norm"] = jnp.ones((lat.q_rank,), jnp.float32)
        lyr["w_q_b"] = _dense(qb, (lat.q_rank, cfg.n_heads * dh), lat.q_rank)
        lyr["w_kv_a"] = _dense(kva, (dim, lat.kv_rank + lat.rope_dim), dim)
        lyr["kv_a_norm"] = jnp.ones((lat.kv_rank,), jnp.float32)
        lyr["w_kv_b"] = _dense(
            kvb, (lat.kv_rank, cfg.n_heads * (2 * dh - lat.rope_dim)),
            lat.kv_rank)
    if cfg.qk_norm:
        lyr["q_norm"] = jnp.ones((dh,), jnp.float32)
        lyr["k_norm"] = jnp.ones((dh,), jnp.float32)
    if cfg.attn_gate:  # a key of its own: the four above stay as they were
        lyr["w_attn_gate"] = _dense(
            jax.random.fold_in(key, 4), (dim, cfg.n_heads * dh), dim)
    return lyr


_INIT = {"M": _init_mamba, "K": _init_kda, "E": _init_experts,
         "D": _init_dense, "*": _init_attention, "W": _init_attention}


def _init_block(cfg: HybridConfig, kind: str, key) -> dict:
    lyr = _INIT[kind](cfg, key)
    if cfg.post_norm:
        lyr["attn_post_norm" if kind in ATTENTION else "post_norm"] = (
            jnp.ones((cfg.dim,), jnp.float32))
    return lyr


def init_params(cfg: HybridConfig, key: jax.Array) -> dict:
    """fp32 masters: matrices as ``llama.init_params``; the scan's and the
    delta rule's ``A_log`` = log U[1, 16], ``D`` = 1, ``dt_bias`` from the
    configuration's ``time_step_*`` keys, so that the decays are a real
    model's."""
    ks = jax.random.split(key, 2 + len(cfg.pattern))
    return {
        "embed": _dense(ks[0], (cfg.vocab_size, cfg.dim), cfg.vocab_size),
        "final_norm": jnp.ones((cfg.dim,), jnp.float32),
        "lm_head": _dense(ks[1], (cfg.dim, cfg.vocab_size), cfg.dim),
        "layers": [
            _init_block(cfg, kind, k) for kind, k in zip(cfg.pattern, ks[2:])],
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


def kda_mixer(h, lyr, cfg: HybridConfig, pos_in_doc, seg_rows):
    """The gated delta-rule mixer on ``h`` ``(T, dim)``, the block's normed
    input, tokens in natural order, up to ``W_o``: the projections in region
    ``attn_qkv``, everything from the convolutions to the gated output norm
    in region ``kda``."""
    dt_, t = h.dtype, h.shape[0]
    heads, d = cfg.kda_heads, cfg.kda_head_dim
    with profile_scope(REGION.attn_qkv):
        q, k, v = (h @ lyr[w].astype(dt_) for w in ("w_q", "w_k", "w_v"))
        f = (h @ lyr["w_fa"].astype(dt_)) @ lyr["w_fb"].astype(dt_)
        b = h @ lyr["w_b"].astype(dt_)
        gate = (h @ lyr["w_ga"].astype(dt_)) @ lyr["w_gb"].astype(dt_)
    with profile_scope(REGION.kda):
        q, k, v = (causal_conv_silu(x, w, 0.0, pos_in_doc).reshape(t, heads, d)
                   for x, w in zip((q, k, v), lyr["conv_w"]))

        def l2(x):  # per head, float32, eps 1e-6 under the root
            x = x.astype(jnp.float32)
            return (x * jax.lax.rsqrt(jnp.sum(
                x * x, axis=-1, keepdims=True) + 1e-6)).astype(dt_)

        g = -jnp.exp(lyr["A_log"])[:, None] * jax.nn.softplus(
            f.astype(jnp.float32) + lyr["dt_bias"]).reshape(t, heads, d)
        beta = jax.nn.sigmoid(b.astype(jnp.float32)) * (
            2.0 if cfg.kda_neg_eigval else 1.0)
        o = kda.kda_scan(l2(q), l2(k), v, g, beta, seg_rows)
        o = _rms_norm(o.astype(jnp.float32), lyr["o_norm"], cfg.norm_eps)
        return (o.reshape(t, heads * d) * jax.nn.sigmoid(
            gate.astype(jnp.float32))).astype(dt_)


def _refuse_cp(cfg: HybridConfig, attn_key: DistAttnRuntimeKey) -> None:
    if attn_key.cp_size > 1:
        mixers = " and ".join(f"{RECURRENT[m]} ('{m}')" for m in RECURRENT
                              if m in cfg.pattern)
        raise NotImplementedError(
            f"hybrid.forward at cp = {attn_key.cp_size}: pattern "
            f"{cfg.pattern!r} has {mixers}, whose state and convolution "
            "taps follow a document in natural order, and dispatch permutes "
            "the sequence's chunks over the ranks; carrying the state "
            "across that permutation is not built (ROADMAP B1). Plan the "
            "key for a one-device cp axis.")


def _check_window_key(cfg, attn_key, window_key) -> None:
    if "W" not in cfg.pattern:
        return
    if window_key is None:
        raise ValueError(
            f"pattern {cfg.pattern!r} has window blocks ('W'): the step "
            "takes their runtime key as window_key, made of attn_key by "
            "api.make_varlen_key_for_new_mask_after_dispatch")
    if not same_dispatch(attn_key, window_key):
        raise ValueError(
            "window_key lays the sequence out otherwise than attn_key: make "
            "it of attn_key with api.make_*_key_for_new_mask_after_dispatch, "
            "which reuses the dispatch")


def forward(
    params: dict, cfg: HybridConfig, tokens: jax.Array,
    attn_key: DistAttnRuntimeKey, with_routes: bool = False,
    window_key: DistAttnRuntimeKey | None = None, with_stream: bool = False,
):
    """Logits ``(total_seqlen, vocab)`` float32 in dispatched order (at cp 1
    natural order); with ``with_routes`` also each ``E`` block's routing,
    ``[{"topi", "scores", "group_rows", "block_rows", "blocks_fitted"}]``
    (:func:`~.moe.dropless_moe_ffn`); with ``with_stream`` then the residual
    stream, the input of every block and last that of the final norm
    ``[(total_seqlen, dim)] * (blocks + 1)`` in dispatched order (what a
    comparison block by block reads). ``attn_key`` owns the dispatch, the
    positions and the documents' starts and is the ``*`` blocks' mask;
    ``window_key`` is the ``W`` blocks'."""
    _check_window_key(cfg, attn_key, window_key)
    dt = cfg.jdtype
    x = embed_dispatched(
        params["embed"], tokens, attn_key, dt, scale=cfg.embed_scale)
    pos = get_position_ids(attn_key)
    if "M" in cfg.pattern or "K" in cfg.pattern:
        _refuse_cp(cfg, attn_key)
        with profile_scope(REGION.ssm if "M" in cfg.pattern else REGION.kda):
            starts = get_document_starts(attn_key)
            pos_in_doc = pos - starts
            if "M" in cfg.pattern:
                seg_rows = ssd.segment_rows(starts)
            if "K" in cfg.pattern:  # the carry flags of the other chunk
                kda_rows = ssd.segment_rows(starts, chunk=kda.CHUNK)
    if cfg.latent is not None:  # attn_block: a token's place in its document
        pos = pos - get_document_starts(attn_key)

    def joined(x, y, lyr):
        if "post_norm" in lyr:
            y = _rms_norm(y, lyr["post_norm"], cfg.norm_eps)
        return x + y

    def mamba(x, lyr):
        with profile_scope(REGION.ssm):
            h = _rms_norm(x, lyr["norm"], cfg.norm_eps)
            return joined(
                x, mamba_mixer(h, lyr, cfg, pos_in_doc, seg_rows), lyr), None

    def delta_rule(x, lyr):
        with profile_scope(REGION.attn_qkv):
            h = _rms_norm(x, lyr["norm"], cfg.norm_eps)
        y = kda_mixer(h, lyr, cfg, pos_in_doc, kda_rows)
        with profile_scope(REGION.attn_out):
            return joined(x, y @ lyr["w_o"].astype(dt), lyr), None

    def experts(x, lyr):
        # the block's norm and its way back to the residual stream are with
        # the shared expert: what every token pays, whatever its route
        with profile_scope(REGION.moe_shared):
            h = _rms_norm(x, lyr["norm"], cfg.norm_eps)
        y, routes = dropless_moe_ffn(
            h, lyr, top_k=cfg.top_k, scale=cfg.routed_scale,
            expert_offset=cfg.expert_offset, token_block=cfg.moe_token_block,
            act=cfg.expert_act, route=cfg.route)
        with profile_scope(REGION.moe_shared):
            return joined(x, y, lyr), routes

    def dense(x, lyr):
        with profile_scope(REGION.mlp):
            h = _rms_norm(x, lyr["norm"], cfg.norm_eps)
            return joined(x, swiglu_mlp(
                h, lyr["w_gate"], lyr["w_up"], lyr["w_down"]), lyr), None

    def attention(kind, key):
        return lambda x, lyr: (attn_block(
            x, lyr, cfg, pos, key, rope=kind in cfg.rope_in), None)

    blocks = {"M": mamba, "K": delta_rule, "E": experts, "D": dense,
              "*": attention("*", attn_key), "W": attention("W", window_key)}
    if cfg.remat:  # an E block's chosen experts are saved, never recomputed
        blocks = {kind: jax.checkpoint(fn, policy=ROUTES_SAVED)
                  for kind, fn in blocks.items()}
    routes, stream = [], [x]
    for kind, lyr in zip(cfg.pattern, params["layers"]):
        x, routed = blocks[kind](x, lyr)
        stream.append(x)
        if routed is not None:
            routes.append(routed)
    with profile_scope(REGION.head_loss):
        x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = (x @ params["lm_head"].astype(dt)).astype(jnp.float32)
    out = (logits, *([routes] if with_routes else []),
           *([stream] if with_stream else []))
    return out if len(out) > 1 else logits


def loss_fn(params, cfg, tokens, labels, attn_key, window_key=None):
    """Next-token cross entropy on the dispatched layout; the
    configurations have no auxiliary routing loss."""
    logits = forward(params, cfg, tokens, attn_key, window_key=window_key)
    with profile_scope(REGION.head_loss):
        return masked_ce(logits, dispatch(labels, attn_key))


@partial(_StepJit, static_argnums=(1, 4), static_argnames=("window_key",),
         donate_argnums=(0,))
def train_step(
    params: dict, cfg: HybridConfig, tokens: jax.Array, labels: jax.Array,
    attn_key: DistAttnRuntimeKey, lr: float = 1e-4, *,
    window_key: DistAttnRuntimeKey | None = None,
) -> tuple[dict, jax.Array]:
    """One SGD step, as ``llama.train_step``. Both keys are static
    arguments of the one program: the ``*`` blocks attend under
    ``attn_key``, the ``W`` blocks under ``window_key``."""
    loss, grads = jax.value_and_grad(loss_fn)(
        params, cfg, tokens, labels, attn_key, window_key)
    with profile_scope(REGION.update):
        params = jax.tree.map(
            lambda p, g: p - lr * g.astype(p.dtype), params, grads)
    return params, loss


@partial(jax.jit, static_argnums=(1, 3), static_argnames=("window_key",))
def routing_counters(
    params, cfg: HybridConfig, tokens, attn_key, *, window_key=None,
) -> dict:
    """What the expert layers of one forward did with ``tokens``, from the
    program's own routing, per ``E`` block: ``rows_routed`` ``(blocks,)``,
    the (token, choice) pairs whose chosen expert is one of those held;
    ``rows_per_expert`` ``(blocks, held)``, the rows the grouped product
    took for each held expert. A layer that drops no row has their sums
    equal. ``block_rows`` ``(blocks, token blocks)``, the rows each block of
    tokens had for the experts held, and ``blocks_fitted`` ``(blocks,)``, how
    many of a layer's token blocks fitted the row buffer sized by what a
    block expects (the others ran at the worst case)."""
    _, routes = forward(
        params, cfg, tokens, attn_key, with_routes=True,
        window_key=window_key)
    local = jnp.stack([r["topi"] for r in routes]) - cfg.expert_offset
    return {
        "rows_routed": jnp.sum(
            (local >= 0) & (local < cfg.experts_held), axis=(1, 2)),
        "rows_per_expert": jnp.stack([r["group_rows"] for r in routes]),
        "block_rows": jnp.stack([r["block_rows"] for r in routes]),
        "blocks_fitted": jnp.stack([r["blocks_fitted"] for r in routes]),
    }
