"""Sparse-dispatch parity + unit tests (extent clamping, mixed blocks).

Parity: the extent-clamped FFA kernels (and the mixed-granularity two-pass
dispatch merged through LSE merge) must match the blockwise-online jnp
reference (`kernels/sdpa_online.py`) across the sparse mask families the
bench `--sparse-suite` tracks, in both dtypes and GQA shapes, fwd + vjp.

Units: the live-extent meta columns, `pad_plan` filler accounting, the
`_clamp_chunks` divisor rule, the mixed-dispatch cost model inputs
(`slice_cover_tiles` / `slice_cover_ratios`), `choose_mixed_dispatch`
mode gating, the fragmentation histogram, and a K3 mutation proof that a
corrupted live-extent row is caught by the kernel contract checker.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.analysis.kernel_check import _fragmented_masks
from magiattention_tpu.env.general import scoped_env
from magiattention_tpu.kernels.ffa import _clamp_chunks, ffa_attn
from magiattention_tpu.kernels.ffa_plan import (
    EK0,
    EK1,
    EQ0,
    EQ1,
    IS_FULL,
    LANE_QUANTUM,
    META_DIM,
    QE,
    QS,
    SUBLANE_QUANTUM,
    _cached_plan,
    fragmentation_histogram,
    get_ffa_plan,
    pad_plan,
    plan_extent_stats,
)
from magiattention_tpu.kernels.mask_utils import types_to_bands
from magiattention_tpu.kernels.sdpa_online import sdpa_online_attn
from magiattention_tpu.kernels.tile_policy import (
    FRAG_THRESHOLD,
    choose_mixed_dispatch,
    slice_cover_ratios,
    slice_cover_tiles,
)
from magiattention_tpu.testing import assert_close

S = 512
HK, D = 2, 64

FULL, CAUSAL, INV, BI = 0, 1, 2, 3


def _band_families(seq=S):
    """name -> (q_ranges, k_ranges, d_lo, d_hi); the six families the
    sparse bench suite reports on, at test scale."""
    one = np.asarray([[0, seq]], np.int32)
    full_lo, full_hi = types_to_bands(one, one, np.asarray([FULL], np.int32))
    causal_lo, causal_hi = types_to_bands(
        one, one, np.asarray([CAUSAL], np.int32)
    )
    h = seq // 2
    spq = np.asarray([[0, h], [h, seq], [h, seq]], np.int32)
    spk = np.asarray([[0, h], [0, h // 2], [h, seq]], np.int32)
    sp_lo, sp_hi = types_to_bands(
        spq, spk, np.asarray([CAUSAL, FULL, CAUSAL], np.int32)
    )
    fams = {
        "full": (one, one.copy(), full_lo, full_hi),
        "causal": (one, one.copy(), causal_lo, causal_hi),
        "sliding_window": (
            one, one.copy(),
            np.asarray([-128], np.int32), np.asarray([0], np.int32),
        ),
        "shared_prefix_causal": (spq, spk, sp_lo, sp_hi),
    }
    fams.update(_fragmented_masks(seq))
    return fams


FAMILIES = _band_families()

TOL = {
    jnp.float32: dict(atol=1e-4, rtol=1e-4, norm_rtol=2e-5),
    jnp.bfloat16: dict(atol=3e-2, rtol=3e-2, norm_rtol=2e-2),
}


def _inputs(dtype, hq, seed=0, seq=S):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((seq, hq, D)), dtype=dtype)
    k = jnp.asarray(rng.standard_normal((seq, HK, D)), dtype=dtype)
    v = jnp.asarray(rng.standard_normal((seq, HK, D)), dtype=dtype)
    return q, k, v


def _ref(q, k, v, qr, kr, lo, hi):
    return sdpa_online_attn(
        q, k, v, jnp.asarray(qr), jnp.asarray(kr),
        d_lo=jnp.asarray(lo), d_hi=jnp.asarray(hi),
    )


@pytest.mark.parametrize("g", [1, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_forward_parity_vs_sdpa_online(family, dtype, g):
    """Default path (extent clamp ON, mixed dispatch auto) vs the online
    reference: out and lse, both dtypes, GQA groups 1 and 2."""
    qr, kr, lo, hi = FAMILIES[family]
    q, k, v = _inputs(dtype, hq=HK * g)
    out, lse = ffa_attn(q, k, v, qr, kr, d_lo=lo, d_hi=hi)
    out_ref, lse_ref = _ref(q, k, v, qr, kr, lo, hi)
    tol = TOL[dtype]
    assert_close(out, out_ref, msg=f"{family} out", **tol)
    assert_close(lse, lse_ref, msg=f"{family} lse", **tol)


@pytest.mark.parametrize("g", [1, 2])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_grad_parity_vs_sdpa_online(family, g):
    qr, kr, lo, hi = FAMILIES[family]
    q, k, v = _inputs(jnp.float32, hq=HK * g, seed=1)
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.standard_normal(q.shape), dtype=jnp.float32)

    def loss_ffa(q, k, v):
        out, _ = ffa_attn(q, k, v, qr, kr, d_lo=lo, d_hi=hi)
        return jnp.sum(out * w)

    def loss_ref(q, k, v):
        out, _ = _ref(q, k, v, qr, kr, lo, hi)
        return jnp.sum(out * w)

    grads = jax.grad(loss_ffa, argnums=(0, 1, 2))(q, k, v)
    grads_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, got, want in zip("dq dk dv".split(), grads, grads_ref):
        assert_close(got, want, atol=2e-4, rtol=2e-4, norm_rtol=2e-5,
                     msg=f"{family} {name}")


def _mixed_mask(seq=S):
    """One dense full slice over the first half + a block-diagonal tail:
    the canonical profitable split for the mixed dispatch."""
    h = seq // 2
    blk = 128
    n = (seq - h) // blk
    qr = [[0, h]] + [[h + i * blk, h + (i + 1) * blk] for i in range(n)]
    qr = np.asarray(qr, np.int32)
    kr = qr.copy()
    lo, hi = types_to_bands(qr, kr, np.zeros(len(qr), np.int32))
    return qr, kr, lo, hi


@pytest.mark.parametrize("mode", ["single", "mixed", None])
def test_mixed_dispatch_parity(mode):
    """The two-pass LSE-merged dispatch matches the single-plan path and
    the reference under every MAGI_ATTENTION_BACKEND_MIXED_BLOCKS pin and
    none."""
    qr, kr, lo, hi = _mixed_mask()
    q, k, v = _inputs(jnp.float32, hq=4, seed=3)
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.standard_normal(q.shape), dtype=jnp.float32)
    with scoped_env({"MAGI_ATTENTION_BACKEND_MIXED_BLOCKS": mode}):
        _cached_plan.cache_clear()

        def loss(q, k, v):
            out, _ = ffa_attn(q, k, v, qr, kr, d_lo=lo, d_hi=hi)
            return jnp.sum(out * w)

        out, lse = ffa_attn(q, k, v, qr, kr, d_lo=lo, d_hi=hi)
        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    _cached_plan.cache_clear()
    out_ref, lse_ref = _ref(q, k, v, qr, kr, lo, hi)

    def loss_ref(q, k, v):
        out, _ = _ref(q, k, v, qr, kr, lo, hi)
        return jnp.sum(out * w)

    grads_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    assert_close(out, out_ref, atol=1e-4, rtol=1e-4, norm_rtol=2e-5,
                 msg=f"mode={mode} out")
    assert_close(lse, lse_ref, atol=1e-4, rtol=1e-4, norm_rtol=2e-5,
                 msg=f"mode={mode} lse")
    for name, got, want in zip("dq dk dv".split(), grads, grads_ref):
        assert_close(got, want, atol=2e-4, rtol=2e-4, norm_rtol=2e-5,
                     msg=f"mode={mode} {name}")


def test_clamp_off_matches_clamp_on():
    """The clamped bodies are numerically equivalent to the legacy
    single-dot bodies (chunks only skip fully-dead work)."""
    qr, kr, lo, hi = FAMILIES["video_sparse"]
    q, k, v = _inputs(jnp.float32, hq=4, seed=5)
    outs = {}
    for flag in ("1", "0"):
        with scoped_env({
            "MAGI_ATTENTION_FFA_EXTENT_CLAMP": flag,
            "MAGI_ATTENTION_BACKEND_MIXED_BLOCKS": "single",
        }):
            _cached_plan.cache_clear()
            outs[flag] = ffa_attn(q, k, v, qr, kr, d_lo=lo, d_hi=hi)
    _cached_plan.cache_clear()
    np.testing.assert_allclose(outs["1"][0], outs["0"][0], atol=1e-6)
    np.testing.assert_allclose(outs["1"][1], outs["0"][1], atol=1e-6)


# ---------------------------------------------------------------- units


def test_meta_extent_columns():
    """Full tiles span the whole tile; partial tiles are quantum-aligned
    sub-rectangles; empty/dummy rows are all-zero."""
    qr, kr, lo, hi = FAMILIES["causal"]
    plan = get_ffa_plan(qr, kr, lo, hi, S, S, 256, 512)
    meta = plan.meta
    assert meta.shape[1] == META_DIM
    full = meta[:, IS_FULL] == 1
    bq, bk = plan.block_q, plan.block_k
    assert np.all(meta[full][:, [EQ0, EQ1, EK0, EK1]] == [0, bq, 0, bk])
    real = meta[:, QE] > meta[:, QS]
    ext = meta[real][:, [EQ0, EQ1, EK0, EK1]]
    assert np.all(ext[:, 0] % SUBLANE_QUANTUM == 0)
    assert np.all(ext[:, 1] % SUBLANE_QUANTUM == 0)
    assert np.all(ext[:, 2] % LANE_QUANTUM == 0)
    assert np.all(ext[:, 3] % LANE_QUANTUM == 0)
    assert np.all((ext[:, 0] < ext[:, 1]) & (ext[:, 2] < ext[:, 3]))
    assert np.all(meta[~real][:, [EQ0, EQ1, EK0, EK1]] == 0)


def test_pad_plan_filler_zero_extent():
    qr, kr, lo, hi = FAMILIES["causal"]
    plan = get_ffa_plan(qr, kr, lo, hi, S, S, 256, 512)
    padded = pad_plan(plan, plan.num_work + 4, plan.num_work_t + 4)
    filler = padded.meta[plan.num_work:]
    assert np.all(filler[:, [EQ0, EQ1, EK0, EK1]] == 0)
    assert np.all(filler[:, QS] == filler[:, QE])
    # filler is excluded from the executed/padded accounting entirely
    assert plan_extent_stats(padded) == plan_extent_stats(plan)


def test_extent_stats_fragmented_vs_padded():
    """The clamp's whole point: on fragmented masks the executed elems sit
    well below the padded-tile elems."""
    qr, kr, lo, hi = FAMILIES["block_diag_sparse"]
    plan = get_ffa_plan(qr, kr, lo, hi, S, S, 256, 512)
    stats = plan_extent_stats(plan)
    assert stats["executed_elems"] <= stats["padded_elems"] / 2


def test_clamp_chunks_divisor_rule():
    with scoped_env({"MAGI_ATTENTION_FFA_EXTENT_CLAMP": "1"}):
        assert _clamp_chunks(128) == 1
        assert _clamp_chunks(512) == 4
        assert _clamp_chunks(1024) == 8
        assert _clamp_chunks(1280) == 5  # 10 lanes-multiples -> 5 | cap 8
        assert _clamp_chunks(100) == 0  # not a lane multiple
    with scoped_env({"MAGI_ATTENTION_FFA_EXTENT_CLAMP": "0"}):
        assert _clamp_chunks(512) == 0  # flag off -> legacy bodies


def _brute_force_tiles(qr, kr, lo, hi, bq, bk):
    """Count band-touching (q_tile, k_tile) pairs per slice the slow way:
    a tile is live iff some row i of the slice inside it has a non-empty
    column interval [max(j0, ks, i+lo), min(j1-1, ke-1, i+hi)]."""
    out = []
    for (qs, qe), (ks, ke), dl, dh in zip(qr, kr, lo, hi):
        n = 0
        for t in range(qs // bq, -(-qe // bq)):
            i0, i1 = max(t * bq, qs), min((t + 1) * bq, qe)
            for u in range(ks // bk, -(-ke // bk)):
                j0, j1 = u * bk, (u + 1) * bk
                n += any(
                    max(j0, ks, i + dl) <= min(j1 - 1, ke - 1, i + dh)
                    for i in range(i0, i1)
                )
        out.append(n)
    return np.asarray(out)


def test_slice_cover_tiles_matches_brute_force():
    for family in ("causal", "sliding_window", "video_sparse",
                   "shared_prefix_causal"):
        qr, kr, lo, hi = FAMILIES[family]
        for bq, bk in ((256, 512), (128, 128)):
            got = slice_cover_tiles(qr, kr, lo, hi, bq, bk)
            want = _brute_force_tiles(qr, kr, lo, hi, bq, bk)
            np.testing.assert_array_equal(got, want, err_msg=family)


def test_slice_cover_ratios_orders_fragmentation():
    qr, kr, lo, hi = _mixed_mask(1024)
    ratios = slice_cover_ratios(qr, kr, lo, hi, 256, 512)
    # the dense half-seq full slice covers its tiles tightly; the 128-wide
    # diagonal blocks waste most of a 256x512 tile
    assert ratios[0] < FRAG_THRESHOLD
    assert np.all(ratios[1:] >= FRAG_THRESHOLD)


def test_choose_mixed_dispatch_modes():
    seq = 2048  # dense half fills whole coarse tiles, diag tail wastes them
    qr, kr, lo, hi = _mixed_mask(seq)
    one = np.asarray([[0, seq]], np.int32)
    flo, fhi = types_to_bands(one, one, np.asarray([FULL], np.int32))
    with scoped_env({"MAGI_ATTENTION_BACKEND_MIXED_BLOCKS": "single"}):
        assert choose_mixed_dispatch(qr, kr, lo, hi, seq, seq) is None
    with scoped_env({"MAGI_ATTENTION_BACKEND_MIXED_BLOCKS": "mixed"}):
        mix = choose_mixed_dispatch(qr, kr, lo, hi, seq, seq)
        assert mix is not None
        # the split partitions the slice set, dense/fine tilings distinct
        both = np.sort(np.concatenate([mix.dense_idx, mix.frag_idx]))
        np.testing.assert_array_equal(both, np.arange(len(qr)))
        assert mix.coarse_blocks != mix.fine_blocks
        # a single dense slice has nothing to split
        assert choose_mixed_dispatch(one, one, flo, fhi, seq, seq) is None
    with scoped_env({"MAGI_ATTENTION_BACKEND_MIXED_BLOCKS": None}):
        mix = choose_mixed_dispatch(qr, kr, lo, hi, seq, seq)
        # the dense-1024 + 8x128-diag split is profitable under the model
        assert mix is not None
        assert mix.split_score < mix.single_score
        # a dense-only mask never splits in auto mode
        assert choose_mixed_dispatch(one, one, flo, fhi, seq, seq) is None


def test_fragmentation_histogram_buckets():
    hist = fragmentation_histogram(np.asarray([1.0, 1.5, 3.0, 7.9, 100.0]))
    assert hist == {"lt_1.2": 1, "lt_2": 1, "lt_4": 1, "lt_8": 1, "ge_8": 1}
    assert sum(hist.values()) == 5


def test_corrupted_extent_row_fires_k3():
    """Mutation proof: shrinking one live-extent column by a lane quantum
    (still aligned, still in-bounds) is caught by the K3 extent check."""
    from dataclasses import replace

    from magiattention_tpu.analysis.kernel_check import (
        _mutation_spec,
        capture_ffa_contracts,
        check_k3_extents,
    )
    from magiattention_tpu.analysis.violation import VerifyReport

    base = next(
        c for c in capture_ffa_contracts(_mutation_spec())
        if c.kernel_name == "_fwd_kernel"
    )
    clean = VerifyReport()
    check_k3_extents(clean, base, "clean")
    assert not clean.errors()

    meta = base.prefetch[2].copy()
    w = int(np.nonzero(
        (meta[:, QE] > meta[:, QS]) & (meta[:, EK1] >= LANE_QUANTUM)
    )[0][0])
    meta[w, EK1] -= LANE_QUANTUM
    mutated = replace(
        base, prefetch=(base.prefetch[0], base.prefetch[1], meta)
    )
    report = VerifyReport()
    check_k3_extents(report, mutated, "mutated")
    assert report.fired_rules() == {"K3"}
    assert any("extent" in str(v).lower() for v in report.errors())
