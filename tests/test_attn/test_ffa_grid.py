"""Widened FFA kernel grid (VERDICT r2 item 8).

Targets the coverage intent of the reference's kernel test grid
(tests/test_attn/test_flex_flash_attn.py, 2982 LoC: dtype x head_dim x GQA
x masks x degenerate metadata), not its line count: property-based random
band slices checked fwd+bwd against the independent dense backend, plus
the deterministic degenerate cases. The same shapes are compile-gated for
Mosaic by tests/test_attn/test_mosaic_lowering.py.

Oracle: kernels/sdpa.sdpa_attn — an independent dense implementation of
the identical band-slice contract (disjoint (q, k) cell coverage;
overlapping q ranges with disjoint k ranges are the shared-prefix varlen
case and are in-contract).
"""

from __future__ import annotations

import pytest

# heavy property/e2e suites: the slow tier (make test-all); the fast
# tier keeps this area covered via its smaller sibling files
pytestmark = pytest.mark.slow

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.kernels.ffa import ffa_attn
from magiattention_tpu.kernels.mask_utils import BAND_INF
from magiattention_tpu.kernels.sdpa import sdpa_attn
from magiattention_tpu.testing import assert_close


def _random_band_meta(rng, sq, sk, n):
    """Random in-contract band slices: overlapping q ranges allowed, k
    ranges per q-row disjoint (cells covered at most once) — built by
    splitting the k axis per slice group. Includes degenerate entries
    (empty q range, inverted band) that must be skipped cleanly."""
    qr, kr, lo, hi = [], [], [], []
    k_cuts = np.unique(rng.integers(0, sk + 1, n + 1))
    if k_cuts[0] != 0:
        k_cuts = np.concatenate([[0], k_cuts])
    if k_cuts[-1] != sk:
        k_cuts = np.concatenate([k_cuts, [sk]])
    for i in range(len(k_cuts) - 1):
        k0, k1 = int(k_cuts[i]), int(k_cuts[i + 1])
        if k0 >= k1:
            continue
        q0 = int(rng.integers(0, sq))
        q1 = int(rng.integers(q0, sq + 1))
        qr.append([q0, q1])
        kr.append([k0, k1])
        kind = rng.integers(0, 4)
        if kind == 0:  # full rectangle
            lo.append(-BAND_INF)
            hi.append(BAND_INF)
        elif kind == 1:  # causal-style upper bound
            hi.append(int(rng.integers(-sk // 4, sk // 4)))
            lo.append(-BAND_INF)
        elif kind == 2:  # window
            c = int(rng.integers(-sk // 4, sk // 4))
            w = int(rng.integers(0, sk // 2))
            lo.append(c - w)
            hi.append(c + w)
        else:  # degenerate: empty q range or inverted band
            if rng.integers(0, 2):
                qr[-1] = [q0, q0]
                lo.append(-BAND_INF)
                hi.append(BAND_INF)
            else:
                lo.append(5)
                hi.append(-5)
    return (
        np.asarray(qr, np.int32), np.asarray(kr, np.int32),
        np.asarray(lo, np.int32), np.asarray(hi, np.int32),
    )


@pytest.mark.parametrize("seed", range(12))
def test_random_band_slices_fwd(seed):
    rng = np.random.default_rng(seed)
    sq = int(rng.integers(33, 300))
    sk = int(rng.integers(33, 300))
    hq, hk = [(2, 1), (4, 2), (4, 1), (3, 3)][seed % 4]
    d = [32, 64][seed % 2]
    qr, kr, lo, hi = _random_band_meta(rng, sq, sk, int(rng.integers(2, 8)))
    q = jnp.asarray(rng.standard_normal((sq, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((sk, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((sk, hk, d)), jnp.float32)
    out, lse = ffa_attn(q, k, v, qr, kr, d_lo=lo, d_hi=hi)
    out_ref, lse_ref = sdpa_attn(q, k, v, qr, kr, d_lo=lo, d_hi=hi)
    assert_close(out, out_ref, atol=2e-5, rtol=2e-5, norm_rtol=2e-6,
                 msg=f"seed {seed} out")
    # lse agreement incl. -inf pattern on uncovered rows
    np.testing.assert_array_equal(
        np.isneginf(np.asarray(lse)), np.isneginf(np.asarray(lse_ref)),
        err_msg=f"seed {seed} lse -inf pattern",
    )
    finite = ~np.isneginf(np.asarray(lse_ref))
    np.testing.assert_allclose(
        np.asarray(lse)[finite], np.asarray(lse_ref)[finite],
        atol=2e-5, rtol=2e-5, err_msg=f"seed {seed} lse",
    )


@pytest.mark.parametrize("seed", range(6))
def test_random_band_slices_grads(seed):
    rng = np.random.default_rng(100 + seed)
    sq = int(rng.integers(33, 200))
    sk = int(rng.integers(33, 200))
    hq, hk = [(2, 1), (4, 2), (6, 3)][seed % 3]
    d = 32
    qr, kr, lo, hi = _random_band_meta(rng, sq, sk, int(rng.integers(2, 6)))
    q = jnp.asarray(rng.standard_normal((sq, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((sk, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((sk, hk, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((sq, hq, d)), jnp.float32)

    def loss(fn, q, k, v):
        o, _ = fn(q, k, v, qr, kr, d_lo=lo, d_hi=hi)
        return jnp.sum(o * w)

    g = jax.grad(lambda *a: loss(ffa_attn, *a), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: loss(sdpa_attn, *a), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), g, gr):
        assert_close(a, b, atol=5e-5, rtol=5e-5, norm_rtol=5e-6,
                     msg=f"seed {seed} {name}")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d", [64, 128])
def test_dtype_headdim_grid_fwd_bwd(dtype, d):
    """dtype x head_dim fwd+bwd vs the dense oracle at matching precision."""
    rng = np.random.default_rng(7)
    sq = sk = 192  # non-multiple of every default block size
    hq, hk = 4, 2
    qr = np.array([[0, 64], [64, 192], [64, 192]], np.int32)
    kr = np.array([[0, 192], [0, 64], [64, 192]], np.int32)
    tm = np.array([1, 0, 1], np.int32)
    q = jnp.asarray(rng.standard_normal((sq, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((sk, hk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((sk, hk, d)), dtype)
    w = jnp.asarray(rng.standard_normal((sq, hq, d)), jnp.float32)

    def loss(fn, q, k, v):
        o, _ = fn(q, k, v, qr, kr, tm)
        return jnp.sum(o.astype(jnp.float32) * w)

    out, _ = ffa_attn(q, k, v, qr, kr, tm)
    out_ref, _ = sdpa_attn(
        q, k, v, qr, kr, tm,
        compute_dtype=jnp.float32,
    )
    # bf16 norm bound: the kernel pre-scales q and casts back to bf16 (one
    # extra rounding vs the oracle's fp32 compute), worth ~3e-3 rel-norm
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    ntol = 2e-6 if dtype == jnp.float32 else 5e-3
    assert_close(out.astype(jnp.float32), out_ref.astype(jnp.float32),
                 atol=tol, rtol=tol, norm_rtol=ntol)
    g = jax.grad(lambda *a: loss(ffa_attn, *a), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: loss(sdpa_attn, *a), argnums=(0, 1, 2))(q, k, v)
    gtol = 5e-5 if dtype == jnp.float32 else 5e-2
    gntol = 5e-6 if dtype == jnp.float32 else 1e-2
    for name, a, b in zip("dq dk dv".split(), g, gr):
        assert_close(a.astype(jnp.float32), b.astype(jnp.float32),
                     atol=gtol, rtol=gtol, norm_rtol=gntol, msg=name)


def test_all_degenerate_metadata():
    """Every slice degenerate: kernel must return zeros + -inf lse."""
    rng = np.random.default_rng(0)
    s, h, d = 96, 2, 32
    q = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    qr = np.array([[10, 10], [20, 15]], np.int32)  # empty + inverted
    kr = np.array([[0, 96], [0, 96]], np.int32)
    lo = np.array([-BAND_INF, -BAND_INF], np.int32)
    hi = np.array([BAND_INF, BAND_INF], np.int32)
    out, lse = ffa_attn(q, k, v, qr, kr, d_lo=lo, d_hi=hi)
    assert float(jnp.max(jnp.abs(out))) == 0.0
    assert bool(jnp.all(jnp.isneginf(lse)))


def test_single_row_and_column_slices():
    rng = np.random.default_rng(1)
    s, h, d = 100, 2, 32
    q = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    qr = np.array([[0, 1], [50, 51], [99, 100]], np.int32)
    kr = np.array([[0, 100], [7, 8], [0, 50]], np.int32)
    tm = np.array([0, 0, 0], np.int32)
    out, lse = ffa_attn(q, k, v, qr, kr, tm)
    out_ref, lse_ref = sdpa_attn(q, k, v, qr, kr, tm)
    assert_close(out, out_ref, atol=2e-5, rtol=2e-5, norm_rtol=2e-6)


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("g", [2, 4])
def test_gqa_packed_matches_unpacked(monkeypatch, seed, g):
    """MAGI_ATTENTION_FFA_GQA_PACK parity: the packed fwd kernel must be
    BIT-IDENTICAL to the unpacked one (same math, same accumulation order
    per row — only the grid layout differs), fwd and through jax.grad, on
    random band slices."""
    rng = np.random.default_rng(100 + seed)
    sq = sk = 320  # non-multiple of block sizes
    hk, d = 2, 64
    hq = hk * g
    qr, kr, lo, hi = _random_band_meta(rng, sq, sk, 4)
    q = jnp.asarray(rng.standard_normal((sq, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((sk, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((sk, hk, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((sq, hq, d)), jnp.float32)

    def run():
        out, lse = ffa_attn(q, k, v, qr, kr, d_lo=lo, d_hi=hi,
                            block_q=64, block_k=128)

        def loss(q_, k_, v_):
            o, _ = ffa_attn(q_, k_, v_, qr, kr, d_lo=lo, d_hi=hi,
                            block_q=64, block_k=128)
            return jnp.sum(o * w)

        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return out, lse, grads

    monkeypatch.setenv("MAGI_ATTENTION_FFA_GQA_PACK", "0")
    out_u, lse_u, g_u = run()
    monkeypatch.setenv("MAGI_ATTENTION_FFA_GQA_PACK", "1")
    out_p, lse_p, g_p = run()

    np.testing.assert_array_equal(np.asarray(out_u), np.asarray(out_p))
    np.testing.assert_array_equal(np.asarray(lse_u), np.asarray(lse_p))
    for name, a, b in zip("dq dk dv".split(), g_u, g_p):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=name
        )


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("g", [2, 4])
def test_gqa_packed_dq_matches_unpacked(monkeypatch, seed, g):
    """MAGI_ATTENTION_FFA_GQA_PACK_DQ parity: the packed dq kernel must be
    BIT-IDENTICAL to the unpacked one (same per-row math and accumulation
    order — only the grid layout and the host-side lse/delta tile packing
    differ) on random band slices; dk/dv are untouched by the flag."""
    rng = np.random.default_rng(300 + seed)
    sq = sk = 320
    hk, d = 2, 64
    hq = hk * g
    qr, kr, lo, hi = _random_band_meta(rng, sq, sk, 4)
    q = jnp.asarray(rng.standard_normal((sq, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((sk, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((sk, hk, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((sq, hq, d)), jnp.float32)

    def run():
        def loss(q_, k_, v_):
            o, _ = ffa_attn(q_, k_, v_, qr, kr, d_lo=lo, d_hi=hi,
                            block_q=64, block_k=128)
            return jnp.sum(o * w)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    monkeypatch.setenv("MAGI_ATTENTION_FFA_GQA_PACK_DQ", "0")
    g_u = run()
    monkeypatch.setenv("MAGI_ATTENTION_FFA_GQA_PACK_DQ", "1")
    g_p = run()
    for name, a, b in zip("dq dk dv".split(), g_u, g_p):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=name
        )


def test_gqa_packed_dq_softcap_and_bwd_overrides(monkeypatch):
    """Packed dq with softcap, dv != dk and dq-specific tile overrides —
    grads vs the dense fp32 oracle."""
    rng = np.random.default_rng(11)
    sq = sk = 256
    hq, hk, d, dv = 4, 2, 64, 128
    qr = np.array([[0, sq]], np.int32)
    kr = np.array([[0, sk]], np.int32)
    tm = np.array([1], np.int32)
    q = jnp.asarray(rng.standard_normal((sq, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((sk, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((sk, hk, dv)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((sq, hq, dv)), jnp.float32)
    monkeypatch.setenv("MAGI_ATTENTION_FFA_GQA_PACK_DQ", "1")
    monkeypatch.setenv("MAGI_ATTENTION_FFA_BLOCK_Q_DQ", "64")
    monkeypatch.setenv("MAGI_ATTENTION_FFA_BLOCK_K_DQ", "256")

    def loss_k(q_, k_, v_):
        o, _ = ffa_attn(q_, k_, v_, qr, kr, tm, softcap=20.0,
                        block_q=128, block_k=128)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))

    def loss_r(q_, k_, v_):
        o, _ = sdpa_attn(q_, k_, v_, qr, kr, tm, softcap=20.0,
                         compute_dtype=jnp.float32)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))

    g_k = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), g_k, g_r):
        assert_close(a, b, atol=2e-5, rtol=2e-5, norm_rtol=2e-6)


def test_gqa_packed_softcap_and_dv(monkeypatch):
    """Packed path with softcap and dv != dk against the dense oracle."""
    rng = np.random.default_rng(7)
    sq = sk = 256
    hq, hk, d, dv = 4, 2, 64, 128
    qr = np.array([[0, sq]], np.int32)
    kr = np.array([[0, sk]], np.int32)
    tm = np.array([1], np.int32)
    q = jnp.asarray(rng.standard_normal((sq, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((sk, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((sk, hk, dv)), jnp.float32)
    monkeypatch.setenv("MAGI_ATTENTION_FFA_GQA_PACK", "1")
    out, lse = ffa_attn(q, k, v, qr, kr, tm, softcap=20.0,
                        block_q=128, block_k=128)
    out_ref, lse_ref = sdpa_attn(q, k, v, qr, kr, tm, softcap=20.0,
                                 compute_dtype=jnp.float32)
    assert_close(out, out_ref, atol=1e-5, rtol=1e-5, norm_rtol=1e-5)
    assert_close(lse, lse_ref, atol=1e-5, rtol=1e-5, norm_rtol=1e-5)
