"""Fused one-pass FFA backward tests (MAGI_ATTENTION_BACKEND_FFA_BWD).

Parity: the fused kernel (shared score recompute for dq/dk/dv, dq
revisit-accumulated across the k-major traversal on the plan's QVF/QVL
columns) must match BOTH the split dq+dkv path and the blockwise-online
jnp reference across the sparse mask families, dtypes, and GQA shapes —
including the extent-clamped fragmented plans.

Units: the Pallas delta kernel (rowsum(dO ⊙ O)), the tile_policy
arithmetic-intensity cost model (the analytic 7 → 5 tile-matmul drop),
mode resolution (`ffa_bwd_mode` pin/meta/VMEM gating), and the
resilience rung: a fused-kernel failure degrades to split under
MAGI_ATTENTION_FALLBACK=1 and raises typed without it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.env.general import scoped_env
from magiattention_tpu.kernels import ffa
from magiattention_tpu.kernels.ffa import (
    FFAParams,
    ffa_attn,
    ffa_delta_pallas_dispatch,
    ffa_bwd_mode,
    resolved_bwd_mode,
)
from magiattention_tpu.kernels.ffa_plan import META_DIM, QVL, _cached_plan
from magiattention_tpu.kernels.sdpa_online import sdpa_online_attn
from magiattention_tpu.kernels.tile_policy import (
    BWD_TILE_MATMULS_FUSED,
    BWD_TILE_MATMULS_SPLIT,
    bwd_hbm_bytes,
    bwd_mxu_elems,
    choose_bwd_mode,
)
from magiattention_tpu.resilience.errors import InjectedFault
from magiattention_tpu.testing import assert_close

from tests.test_attn.test_sparse_dispatch import FAMILIES, TOL, _inputs, _ref

HK, D = 2, 64

GRAD_TOL = {
    jnp.float32: dict(atol=2e-4, rtol=2e-4, norm_rtol=2e-5),
    jnp.bfloat16: dict(atol=3e-2, rtol=3e-2, norm_rtol=2e-2),
}


def _grads(q, k, v, qr, kr, lo, hi, w, env=None, ref=False):
    def loss(q, k, v):
        if ref:
            out, _ = _ref(q, k, v, qr, kr, lo, hi)
        else:
            out, _ = ffa_attn(q, k, v, qr, kr, d_lo=lo, d_hi=hi)
        return jnp.sum(out * w)

    if env is None:
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    with scoped_env(env):
        _cached_plan.cache_clear()
        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    _cached_plan.cache_clear()
    return grads


# -- parity: fused vs the online reference (f32, every family/group) --------


@pytest.mark.parametrize("g", [1, 2, 4])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_fused_grad_parity_vs_sdpa_online(family, g):
    qr, kr, lo, hi = FAMILIES[family]
    q, k, v = _inputs(jnp.float32, hq=HK * g, seed=11)
    w = jnp.asarray(
        np.random.default_rng(12).standard_normal(q.shape), jnp.float32
    )
    grads = _grads(q, k, v, qr, kr, lo, hi, w,
                   env={"MAGI_ATTENTION_BACKEND_FFA_BWD": "fused"})
    grads_ref = _grads(q, k, v, qr, kr, lo, hi, w, ref=True)
    for name, got, want in zip("dq dk dv".split(), grads, grads_ref):
        assert_close(got, want, msg=f"{family} g={g} {name}",
                     **GRAD_TOL[jnp.float32])


# -- parity: fused vs split, both dtypes, packed + unpacked -----------------


@pytest.mark.parametrize("pack", ["0", "1"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "family", ["causal", "sliding_window", "video_sparse"]
)
def test_fused_vs_split_parity(family, dtype, pack):
    """Fused and split backward run the same math in a different order:
    they must agree within the dtype's accumulation-order tolerance, with
    the GQA pack both on and off (g=2 exercises packed fused vs packed
    split when pack=1, unpacked vs unpacked when pack=0)."""
    qr, kr, lo, hi = FAMILIES[family]
    q, k, v = _inputs(dtype, hq=HK * 2, seed=13)
    w = jnp.asarray(
        np.random.default_rng(14).standard_normal(q.shape), jnp.float32
    )
    base_env = {"MAGI_ATTENTION_FFA_GQA_PACK_DKV": pack}
    fused = _grads(q, k, v, qr, kr, lo, hi, w,
                   env={**base_env, "MAGI_ATTENTION_BACKEND_FFA_BWD": "fused"})
    split = _grads(q, k, v, qr, kr, lo, hi, w,
                   env={**base_env, "MAGI_ATTENTION_BACKEND_FFA_BWD": "split"})
    for name, got, want in zip("dq dk dv".split(), fused, split):
        assert_close(got, want, msg=f"{family} pack={pack} {name}",
                     **TOL[dtype])


# -- mode resolution --------------------------------------------------------


def _params(bq=256, bk=512, group=1, **over):
    return FFAParams(
        num_work=8, num_work_t=8, num_q_tiles=4, num_k_tiles=2,
        block_q=bq, block_k=bk, softmax_scale=0.125, softcap=0.0,
        group=group, interpret=True, **over,
    )


class TestBwdModeResolution:
    def test_split_pin_always_split(self):
        with scoped_env({"MAGI_ATTENTION_BACKEND_FFA_BWD": "split"}):
            assert ffa_bwd_mode(_params(), 1024, D, D, 4, META_DIM) == "split"

    def test_legacy_meta_without_visit_cols_is_split(self):
        # 13-col metas (pre-QVF/QVL) cannot drive the fused kernel
        with scoped_env({"MAGI_ATTENTION_BACKEND_FFA_BWD": "fused"}):
            assert ffa_bwd_mode(_params(), 1024, D, D, 4, QVL) == "split"

    def test_fused_pin_fused_when_feasible(self):
        with scoped_env({"MAGI_ATTENTION_BACKEND_FFA_BWD": "fused"}):
            assert ffa_bwd_mode(_params(), 1024, D, D, 4, META_DIM) == "fused"
            assert resolved_bwd_mode(_params(), 1024, D, D, 4) == "fused"

    def test_vmem_infeasible_forces_split_even_under_flag_one(self):
        # (1024, 1024) fp32 tiles at head_dim 256: the fused residency
        # (dkv blocks + double-buffered dq out + aliased zeros input)
        # busts the 14 MiB budget, so a fused pin still resolves to split
        big = _params(bq=1024, bk=1024)
        with scoped_env({"MAGI_ATTENTION_BACKEND_FFA_BWD": "fused"}):
            assert ffa_bwd_mode(big, 2048, 256, 256, 4, META_DIM) == "split"

    def test_forced_fallback_parity(self, monkeypatch):
        """a fused pin with the feasibility gate forced shut: the dispatch
        silently runs split and still matches the reference."""
        qr, kr, lo, hi = FAMILIES["causal"]
        q, k, v = _inputs(jnp.float32, hq=HK, seed=15)
        w = jnp.asarray(
            np.random.default_rng(16).standard_normal(q.shape), jnp.float32
        )
        monkeypatch.setattr(ffa, "fused_bwd_feasible",
                            lambda *a, **kw: False)
        grads = _grads(q, k, v, qr, kr, lo, hi, w,
                       env={"MAGI_ATTENTION_BACKEND_FFA_BWD": "fused"})
        monkeypatch.undo()
        grads_ref = _grads(q, k, v, qr, kr, lo, hi, w, ref=True)
        for name, got, want in zip("dq dk dv".split(), grads, grads_ref):
            assert_close(got, want, msg=f"forced-split {name}",
                         **GRAD_TOL[jnp.float32])


# -- resilience rung: fused failure degrades to split -----------------------


class TestFusedFallbackRung:
    def _boom(self, *a, **kw):
        raise InjectedFault("kernel_lowering", 1)

    def test_degrades_to_split_with_fallback(self, monkeypatch):
        qr, kr, lo, hi = FAMILIES["causal"]
        q, k, v = _inputs(jnp.float32, hq=HK * 2, seed=17)
        w = jnp.asarray(
            np.random.default_rng(18).standard_normal(q.shape), jnp.float32
        )
        monkeypatch.setattr(ffa, "_ffa_bwd_fused_pallas", self._boom)
        monkeypatch.setattr(ffa, "_ffa_bwd_fused_pallas_gqa", self._boom)
        grads = _grads(
            q, k, v, qr, kr, lo, hi, w,
            env={"MAGI_ATTENTION_BACKEND_FFA_BWD": "fused",
                 "MAGI_ATTENTION_FALLBACK": "1"},
        )
        monkeypatch.undo()
        grads_ref = _grads(q, k, v, qr, kr, lo, hi, w, ref=True)
        for name, got, want in zip("dq dk dv".split(), grads, grads_ref):
            assert_close(got, want, msg=f"rung {name}",
                         **GRAD_TOL[jnp.float32])

    def test_raises_typed_without_fallback(self, monkeypatch):
        qr, kr, lo, hi = FAMILIES["causal"]
        q, k, v = _inputs(jnp.float32, hq=HK, seed=19)
        w = jnp.ones_like(q)
        monkeypatch.setattr(ffa, "_ffa_bwd_fused_pallas", self._boom)
        monkeypatch.setattr(ffa, "_ffa_bwd_fused_pallas_gqa", self._boom)
        with pytest.raises(InjectedFault, match="kernel_lowering"):
            _grads(q, k, v, qr, kr, lo, hi, w,
                   env={"MAGI_ATTENTION_BACKEND_FFA_BWD": "fused",
                        "MAGI_ATTENTION_FALLBACK": "0"})


# -- delta kernel -----------------------------------------------------------


def test_delta_kernel_matches_rowsum():
    rng = np.random.default_rng(20)
    hq, sqp, dv = 4, 512, 80
    out_t = jnp.asarray(rng.standard_normal((hq, sqp, dv)), jnp.bfloat16)
    do_t = jnp.asarray(rng.standard_normal((hq, sqp, dv)), jnp.bfloat16)
    delta = ffa_delta_pallas_dispatch(_params(bq=128), out_t, do_t)
    want = jnp.sum(
        out_t.astype(jnp.float32) * do_t.astype(jnp.float32), axis=-1
    )
    assert delta.shape == (hq, sqp) and delta.dtype == jnp.float32
    assert_close(delta, want, atol=1e-5, rtol=1e-5, norm_rtol=1e-6,
                 msg="delta")


# -- cost model -------------------------------------------------------------


class TestBwdCostModel:
    def test_analytic_seven_to_five_drop(self):
        """The tentpole's arithmetic claim: with equal blocks and work
        counts, fused spends exactly 5 tile matmuls where split spends
        7 — the MXU-element ratio is exactly 7/5."""
        assert BWD_TILE_MATMULS_SPLIT == 7
        assert BWD_TILE_MATMULS_FUSED == 5
        args = dict(w_dq=64, bq_dq=256, bk_dq=512,
                    wt=64, bq_dkv=256, bk_dkv=512, d=128)
        split = bwd_mxu_elems("split", **args)
        fused = bwd_mxu_elems("fused", **args)
        assert split * 5 == fused * 7
        assert split == 7 * 64 * 256 * 512 * 128

    def test_fused_halves_qdo_streaming(self):
        # same blocks/counts: split streams q/k/v/do twice (once per
        # pass), fused once plus the dq read-modify-write — strictly less
        args = dict(w_dq=64, bq_dq=256, bk_dq=512,
                    wt=64, bq_dkv=256, bk_dkv=512, d=128, dv=128,
                    itemsize=2, group=1)
        assert bwd_hbm_bytes("fused", **args) < bwd_hbm_bytes("split", **args)

    def test_choose_prefers_fused_on_standard_shapes(self):
        assert choose_bwd_mode(
            64, 256, 512, 64, 256, 512, 128, 128, itemsize=2, group=2
        ) == "fused"
