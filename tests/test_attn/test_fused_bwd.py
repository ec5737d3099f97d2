"""Fused one-pass FFA backward tests (MAGI_ATTENTION_BACKEND_FFA_BWD).

Parity: the fused kernel (shared score recompute for dq/dk/dv, dq
read-modify-written across the k-major traversal on the plan's QVF/QVL
columns, through the aliased dq operand) must match BOTH the split dq+dkv
path and the blockwise-online jnp reference across the sparse mask
families, dtypes, and GQA shapes — including the extent-clamped fragmented
plans — and under the TPU interpreter, which like the chip keeps no output
window between two non-adjacent visits.

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
from magiattention_tpu.kernels import registry
from magiattention_tpu.kernels.ffa_plan import (
    META_DIM,
    NO_REVISIT,
    QVL,
    _cached_plan,
    min_revisit_distance,
)
from magiattention_tpu.kernels.sdpa_online import sdpa_online_attn
from magiattention_tpu.kernels.tile_policy import (
    BWD_TILE_MATMULS_FUSED,
    BWD_TILE_MATMULS_SPLIT,
    bwd_step_macs,
    bwd_step_bytes,
    bwd_step_us,
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


def _grads(q, k, v, qr, kr, lo, hi, w, env=None, ref=False, ran="pin",
           **blocks):
    """Gradients of ``sum(out * w)``; under a backward pin, ``ran`` is the
    mode the program must say it resolved ("pin": the pinned one)."""
    def loss(q, k, v):
        if ref:
            out, _ = _ref(q, k, v, qr, kr, lo, hi)
        else:
            out, _ = ffa_attn(q, k, v, qr, kr, d_lo=lo, d_hi=hi, **blocks)
        return jnp.sum(out * w)

    if env is None:
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    with scoped_env(env):
        _cached_plan.cache_clear()
        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    _cached_plan.cache_clear()
    pin = env.get("MAGI_ATTENTION_BACKEND_FFA_BWD")
    if pin:
        # a pin the guards turned down would make every parity below a
        # comparison of the split pair with itself
        assert registry.last_choice("ffa_bwd") == (
            pin if ran == "pin" else ran)
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
def test_fused_vs_split_parity(family, dtype, pack, monkeypatch):
    """Fused and split backward run the same math in a different order:
    they must agree within the dtype's accumulation-order tolerance, with
    the GQA pack both on and off (g=2 exercises packed fused vs packed
    split when pack=1, unpacked vs unpacked when pack=0)."""
    # the body the chip runs: with the read-back, no window kept
    _tpu_interpreted(monkeypatch)
    qr, kr, lo, hi = FAMILIES[family]
    # g = 4: the unpacked body's window of one head comes back every g
    # steps, which a group of 2 keeps under FUSED_DQ_REVISIT_DISTANCE
    q, k, v = _inputs(dtype, hq=HK * 4, seed=13)
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
    return FFAParams(**{**dict(
        num_work=8, num_work_t=8, num_q_tiles=4, num_k_tiles=2,
        block_q=bq, block_k=bk, softmax_scale=0.125, softcap=0.0,
        group=group, interpret=True, min_revisit_distance=NO_REVISIT,
    ), **over})


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
        grads = _grads(q, k, v, qr, kr, lo, hi, w, ran="split",
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
    @pytest.mark.parametrize("group", [1, 2, 4, 8])
    def test_seven_tile_matmuls_to_five_and_the_group(self, group):
        """The tentpole's arithmetic claim: a work item of the pair spends
        7 tile matmuls (dq 3 + dkv 4) where the one-pass body spends 5, and
        it covers the whole query group — g x bq rows in one packed step —
        so every count is g times the g = 1 count and the ratio stays 7/5
        (the count PR 30 found without g read split 124 G against fused
        89 G at the cells' g = 4)."""
        assert BWD_TILE_MATMULS_SPLIT == 7
        assert BWD_TILE_MATMULS_FUSED == 5
        tile = (256, 512, 128)
        macs = {kind: bwd_step_macs(kind, *tile, group)
                for kind in ("dq", "dkv", "fused")}
        for kind, n in (("dq", 3), ("dkv", 4), ("fused", 5)):
            assert macs[kind] == n * group * 256 * 512 * 128
            assert macs[kind] == group * bwd_step_macs(kind, *tile)
        assert 5 * (macs["dq"] + macs["dkv"]) == 7 * macs["fused"]

    def test_a_fused_step_moves_the_dq_window_in_and_out(self):
        # what changes with every k-major step: q, dO, lse and delta of the
        # packed rows; the one-pass step adds the fp32 dq window twice
        args = dict(bq=256, bk=512, d=128, dv=128, itemsize=2, group=4)
        rows = 4 * 256
        assert bwd_step_bytes("dkv", **args) == rows * 256 * 2 + rows * 8
        assert bwd_step_bytes("fused", **args) - bwd_step_bytes(
            "dkv", **args) == 2 * rows * 128 * 4
        # the q-major dq step streams k and v past its resident rows
        assert bwd_step_bytes("dq", **args) == 512 * 256 * 2

    def test_step_model_reads_what_the_chip_read(self):
        """The three bodies' grid steps at the cells' shape (1024 packed
        rows x 512 keys, d 128, bf16), against the chip's own readings:
        dq 3.63 and dkv 3.60 us (PR 25), the one-pass body 4.49 us (PR
        30): the model is their fit, within 3%."""
        args = (256, 512, 128, 128, 2, 4)
        for kind, read_us in (("dq", 3.63), ("dkv", 3.60), ("fused", 4.49)):
            assert bwd_step_us(kind, *args) == pytest.approx(read_us, rel=0.03)
        # all three are bound by their matmuls there, not by their DMA
        assert bwd_step_bytes("fused", *args) * 1e-6 / 0.819 < 0.5 * (
            bwd_step_us("fused", *args))

    def test_choose_prefers_fused_on_standard_shapes(self):
        assert choose_bwd_mode(
            64, 256, 512, 64, 256, 512, 128, 128, itemsize=2, group=2
        ) == "fused"

    def test_choose_keeps_split_where_the_dq_plan_is_far_cheaper(self):
        # a mask whose k-major tiling fragments ten times worse than its
        # q-major one: rerunning the cheap dq pass beats dragging the dq
        # window through every k-major step
        assert choose_bwd_mode(
            100, 256, 512, 1000, 256, 512, 128, 128, itemsize=2, group=4
        ) == "split"


# -- the dq read-modify-write, as the chip runs it ---------------------------


def _doc_slices(lens):
    cu = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    qr = np.stack([cu[:-1], cu[1:]], axis=1)
    lo = np.full(len(lens), -(1 << 30), np.int32)
    return qr, qr.copy(), lo, np.zeros(len(lens), np.int32)


# (document lengths, block_q, block_k): q tiles of 512 rows under k tiles of
# 128 keys, so the k-major walk leaves and re-enters every q tile at least
# three times; and the cells' own tiles over a packing with documents of
# 128 tokens, two to a q tile, where runs are one or two items long
RMW_CASES = {
    "causal_each_q_tile_left_3_times": ([2048], 512, 128),
    "packing_with_128_token_documents": (
        [128, 128, 640, 128, 384, 128, 128, 128, 256], 256, 512),
}


def _rmw_inputs(case, hk=2, g=4, dtype=jnp.float32):
    lens, bq, bk = RMW_CASES[case]
    seq = sum(lens)
    rng = np.random.default_rng(31)
    q, k, v, w = (
        jnp.asarray(rng.standard_normal((seq, h, D)), dtype)
        for h in (hk * g, hk, hk, hk * g))
    return (q, k, v, *_doc_slices(lens), w), dict(block_q=bq, block_k=bk)


@pytest.mark.parametrize("case", sorted(RMW_CASES))
def test_fused_vs_split_parity_g4_across_revisits(case):
    args, blocks = _rmw_inputs(case)
    lens, bq, bk = RMW_CASES[case]
    plan = ffa.get_ffa_plan(*args[3:7], sum(lens), sum(lens), bq, bk)
    visits = np.bincount(plan.work_qt_t)
    if case.startswith("causal"):
        # every q tile is visited, left and visited again, three times over
        runs = np.flatnonzero(np.diff(plan.work_qt_t, prepend=-1))
        assert np.bincount(plan.work_qt_t[runs]).min() >= 4
        assert plan.min_revisit_distance >= ffa.FUSED_DQ_REVISIT_DISTANCE
    assert visits.min() >= 1
    fused = _grads(*args, env={"MAGI_ATTENTION_BACKEND_FFA_BWD": "fused"},
                   **blocks)
    split = _grads(*args, env={"MAGI_ATTENTION_BACKEND_FFA_BWD": "split"},
                   **blocks)
    want = _grads(*args, ref=True)
    for name, got, pair, ref in zip("dq dk dv".split(), fused, split, want):
        assert_close(got, pair, msg=f"{case} {name} v. split",
                     **TOL[jnp.float32])
        assert_close(got, ref, msg=f"{case} {name} v. reference",
                     **GRAD_TOL[jnp.float32])


def _tpu_interpreted(monkeypatch):
    """The kernels under the TPU interpreter, which like the chip keeps one
    output window, writes it back when its block index changes and does not
    read it back, and fetches an aliased operand from the array the output
    is written to. ``interpret=True``, which tier-1 otherwise runs, keeps
    an output's contents between visits: a body that never reads its
    partial sum back passes there and fails on the chip (PR 30)."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(ffa, "_should_interpret", pltpu.InterpretParams)


@pytest.mark.parametrize("g", [1, 4])
def test_dq_read_modify_write_holds_where_no_window_is_kept(monkeypatch, g):
    _tpu_interpreted(monkeypatch)
    args, blocks = _rmw_inputs("causal_each_q_tile_left_3_times", hk=1, g=g)
    fused = _grads(*args, env={"MAGI_ATTENTION_BACKEND_FFA_BWD": "fused"},
                   **blocks)
    split = _grads(*args, env={"MAGI_ATTENTION_BACKEND_FFA_BWD": "split"},
                   **blocks)
    for name, got, want in zip("dq dk dv".split(), fused, split):
        assert_close(got, want, msg=f"g={g} {name}", **TOL[jnp.float32])


@pytest.mark.parametrize("g", [1, 4])
def test_a_write_only_dq_window_fails_as_it_did_on_the_chip(monkeypatch, g):
    """The body driven as it was before PR 30 — the window never read back
    — under the interpreter that keeps no window: dq is wrong by a tenth
    and more of its largest element (0.25 on the chip at 16384 tokens),
    dk and dv are right. If the read-back were taken out again, this is
    what the parity above would see."""
    _tpu_interpreted(monkeypatch)
    body = "_bwd_fused_kernel_gqa" if g > 1 else "_bwd_fused_kernel"
    real = getattr(ffa, body)

    def write_only(*refs, readback, **static):
        return real(*refs, readback=False, **static)

    write_only.__name__ = real.__name__
    monkeypatch.setattr(ffa, body, write_only)
    args, blocks = _rmw_inputs("causal_each_q_tile_left_3_times", hk=1, g=g)
    fused = _grads(*args, env={"MAGI_ATTENTION_BACKEND_FFA_BWD": "fused"},
                   **blocks)
    split = _grads(*args, env={"MAGI_ATTENTION_BACKEND_FFA_BWD": "split"},
                   **blocks)
    worst = [float(jnp.abs(a - b).max() / jnp.abs(b).max())
             for a, b in zip(fused, split)]
    assert worst[0] > 0.1, worst
    assert max(worst[1:]) < 1e-5, worst


def test_unknown_revisit_distance_keeps_the_backward_split():
    """A plan under the distance the pipeline needs — here one whose list
    nobody measured (hand-built params, distance 0) — resolves to split,
    under a fused pin too; the unpacked body's window comes round every g
    steps, which a group of 3 and more clears."""
    with scoped_env({"MAGI_ATTENTION_BACKEND_FFA_BWD": "fused"}):
        for dist, want in ((0, "split"), (1, "split"), (2, "split"),
                           (ffa.FUSED_DQ_REVISIT_DISTANCE, "fused"),
                           (NO_REVISIT, "fused")):
            got = ffa_bwd_mode(_params(min_revisit_distance=dist),
                               1024, D, D, 4, META_DIM)
            assert got == want, dist
            assert registry.last_choice("ffa_bwd") == want
    # unpinned, the guard is reached before the rule
    assert ffa_bwd_mode(_params(min_revisit_distance=0),
                        1024, D, D, 4, META_DIM) == "split"
    assert min_revisit_distance([0, 1, 2, 0]) == ffa.FUSED_DQ_REVISIT_DISTANCE
