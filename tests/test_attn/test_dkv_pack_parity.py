"""GQA-packed dkv backward == unpacked dkv backward (tentpole parity pin).

The packed kernel (_bwd_dkv_kernel_gqa) folds the g query heads of a kv
head into one MXU contraction; the group sum it computes is the SAME math
as the unpacked kernel's innermost group loop, differing only in fp32
accumulation order. dq must be bit-identical (the dq pass is untouched by
the flag); dk/dv are pinned at bf16 tolerances, NOT bit-identity.

Coverage mirrors the bench grid's six masks (kernel_bench.build_mask
semantics, hand-rolled here so the module imports stay in the kernels
layer) x GQA ratios g in {1, 2, 4, 8} x head_dim in {64, 128}, on the CPU
interpret backend. Also pins the per-pass auto-tile policy (tiling is
performance-only) and the policy's env-precedence contract.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.kernels.ffa import ffa_attn
# precision module directly: the testing package __init__ pulls in the
# distributed runtime, which this kernels-layer suite must not require
from magiattention_tpu.testing.precision import assert_close

S = 256
HQ = 8


def _mask_case(name: str, s: int):
    """(qr, kr, tm, d_lo, d_hi) for the six bench-grid mask families.

    Band masks (sw_causal) use d_lo/d_hi directly; the rest use type ints
    (0 full, 1 causal). Same coverage intent as kernel_bench.build_mask
    without the common/api imports.
    """
    d_lo = d_hi = None
    if name == "full":
        qr, kr, tm = [[0, s]], [[0, s]], [0]
    elif name == "causal":
        qr, kr, tm = [[0, s]], [[0, s]], [1]
    elif name in ("varlen_full", "varlen_causal"):
        t = 0 if name == "varlen_full" else 1
        bounds = [0, s // 8, s // 3, s // 2, (3 * s) // 4, s]
        qr = [[a, b] for a, b in zip(bounds[:-1], bounds[1:])]
        kr = qr
        tm = [t] * len(qr)
    elif name == "sw_causal":
        # sliding-window causal as an explicit diagonal band
        qr, kr, tm = [[0, s]], [[0, s]], None
        d_lo, d_hi = [-(s // 8)], [0]
    elif name == "video":
        # Magi-1-style block causal: frame f attends frames {f-1, f}
        frames, per = 4, s // 4
        qr = [[f * per, (f + 1) * per] for f in range(frames)]
        kr = [[max(f - 1, 0) * per, (f + 1) * per] for f in range(frames)]
        tm = [0] * frames
    else:
        raise ValueError(name)
    return (
        np.array(qr, np.int32), np.array(kr, np.int32),
        None if tm is None else np.array(tm, np.int32),
        None if d_lo is None else np.array(d_lo, np.int32),
        None if d_hi is None else np.array(d_hi, np.int32),
    )


def _grads(name: str, g: int, d: int, *, seed: int = 0, **ffa_kwargs):
    """(dq, dk, dv) for one mask/GQA-ratio/head-dim combo, bf16 inputs."""
    hk = HQ // g
    qr, kr, tm, d_lo, d_hi = _mask_case(name, S)
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((S, HQ, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((S, hk, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((S, hk, d)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((S, HQ, d)), jnp.float32)

    def loss(q, k, v):
        o, _ = ffa_attn(
            q, k, v, qr, kr, tm, d_lo=d_lo, d_hi=d_hi,
            **({"block_q": 128, "block_k": 128} | ffa_kwargs),
        )
        return jnp.sum(o.astype(jnp.float32) * w)

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _assert_pack_parity(name: str, g: int, d: int, monkeypatch):
    # the dkv body is the subject: keep the pair (unpinned, some of these
    # shapes resolve to the one-pass backward, whose dq the flag does move)
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_FFA_BWD", "split")
    monkeypatch.setenv("MAGI_ATTENTION_FFA_GQA_PACK_DKV", "0")
    ref = _grads(name, g, d)
    monkeypatch.setenv("MAGI_ATTENTION_FFA_GQA_PACK_DKV", "1")
    got = _grads(name, g, d)
    # dq: the flag must not touch the dq pass at all
    np.testing.assert_array_equal(
        np.asarray(got[0]), np.asarray(ref[0]),
        err_msg=f"dq changed by dkv pack flag ({name} g={g} d={d})",
    )
    # dk/dv: same math, different fp32 accumulation order (one long
    # contraction vs g sequential) — bf16-scale tolerances
    for grad, a, b in zip(("dk", "dv"), got[1:], ref[1:]):
        assert_close(
            a, b, atol=1e-2, rtol=1e-2, norm_rtol=1e-3,
            mismatch_thres=1e-3,
            msg=f"{grad} packed vs unpacked ({name} g={g} d={d})",
        )


@pytest.mark.parametrize("g", [1, 2, 4, 8])
@pytest.mark.parametrize(
    "name",
    ["full", "causal", "varlen_full", "varlen_causal", "sw_causal",
     "video"],
)
def test_packed_dkv_matches_unpacked_grid(monkeypatch, name, g):
    """6-mask x GQA-ratio grid at head_dim 64 (g=1 pins the fallback:
    the gate disables packing and both runs take the unpacked kernel)."""
    _assert_pack_parity(name, g, 64, monkeypatch)


@pytest.mark.parametrize("g", [2, 8])
@pytest.mark.parametrize("name", ["causal", "varlen_causal"])
def test_packed_dkv_matches_unpacked_head_dim128(monkeypatch, name, g):
    _assert_pack_parity(name, g, 128, monkeypatch)


def test_pack_gate_defaults_on_for_gqa(monkeypatch):
    """Packed dkv is the DEFAULT when g > 1 and shapes divide (acceptance
    criterion); g == 1 and a non-dividing bq fall back."""
    from magiattention_tpu.kernels.ffa import FFAParams, _use_gqa_pack_dkv

    monkeypatch.delenv("MAGI_ATTENTION_FFA_GQA_PACK_DKV", raising=False)

    def params(group, bq=128, bk=128):
        return FFAParams(
            num_work=4, num_work_t=4, num_q_tiles=2, num_k_tiles=2,
            block_q=bq, block_k=bk, softmax_scale=0.125, softcap=0.0,
            group=group, interpret=True,
        )

    assert _use_gqa_pack_dkv(params(2), 256, 64, 64)
    assert _use_gqa_pack_dkv(params(8), 256, 64, 64)
    assert not _use_gqa_pack_dkv(params(1), 256, 64, 64)  # no group
    assert not _use_gqa_pack_dkv(params(2), 200, 64, 64)  # sqp % bq != 0
    # VMEM guard: a huge packed tile must refuse
    assert not _use_gqa_pack_dkv(params(8, bq=1024, bk=1024), 4096, 128, 128)


@pytest.mark.parametrize("name", ["sw_causal", "varlen_causal"])
def test_per_pass_auto_tile_matches_global(monkeypatch, name):
    """Per-pass/per-band tile policy (MAGI_ATTENTION_FFA_AUTO_TILE=1) is
    performance-only: grads match the fixed global tiling."""
    for var in ("MAGI_ATTENTION_FFA_BLOCK_Q", "MAGI_ATTENTION_FFA_BLOCK_K",
                "MAGI_ATTENTION_FFA_BLOCK_Q_DQ",
                "MAGI_ATTENTION_FFA_BLOCK_K_DQ",
                "MAGI_ATTENTION_FFA_BLOCK_Q_DKV",
                "MAGI_ATTENTION_FFA_BLOCK_K_DKV"):
        monkeypatch.delenv(var, raising=False)
    ref = _grads(name, 2, 64)
    monkeypatch.setenv("MAGI_ATTENTION_FFA_AUTO_TILE", "1")
    # drop the explicit blocks so the policy branch actually runs
    got = _grads(name, 2, 64, block_q=None, block_k=None)
    for grad, a, b in zip(("dq", "dk", "dv"), got, ref):
        assert_close(
            a, b, atol=1e-2, rtol=1e-2, norm_rtol=1e-3,
            mismatch_thres=1e-3,
            msg=f"{grad} auto-tile vs global tiling ({name})",
        )


def test_env_override_beats_policy(monkeypatch):
    """resolve_bwd_overrides: explicit env blocks win over the policy's
    per-pass pick, component-wise."""
    from magiattention_tpu.kernels.ffa import resolve_bwd_overrides

    for var in ("MAGI_ATTENTION_FFA_BLOCK_Q_DQ",
                "MAGI_ATTENTION_FFA_BLOCK_K_DQ",
                "MAGI_ATTENTION_FFA_BLOCK_Q_DKV",
                "MAGI_ATTENTION_FFA_BLOCK_K_DKV"):
        monkeypatch.delenv(var, raising=False)
    # env set: beats the policy component-wise
    monkeypatch.setenv("MAGI_ATTENTION_FFA_BLOCK_Q_DKV", "256")
    dq, dkv = resolve_bwd_overrides(
        512, 512, 1024, 1024, policy_dkv=(128, 256)
    )
    assert dq is None and dkv == (256, 256)
    monkeypatch.delenv("MAGI_ATTENTION_FFA_BLOCK_Q_DKV")
    # policy alone: both passes take the policy pick
    dq, dkv = resolve_bwd_overrides(
        512, 512, 1024, 1024, policy_dq=(256, 512), policy_dkv=(128, 256)
    )
    assert dq == (256, 512) and dkv == (128, 256)
    # policy equal to fwd blocks -> no override
    dq, dkv = resolve_bwd_overrides(
        512, 512, 1024, 1024, policy_dq=(512, 512), policy_dkv=None
    )
    assert dq is None and dkv is None
    # non-dividing policy pick silently inherits fwd blocks
    dq, dkv = resolve_bwd_overrides(
        512, 512, 1024, 1024, policy_dq=(96, 512), policy_dkv=(128, 384)
    )
    assert dq is None and dkv is None
