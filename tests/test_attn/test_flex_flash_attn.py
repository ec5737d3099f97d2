"""FFA / SDPA backend correctness vs the fp64 dense reference.

Modeled on the reference's tests/test_attn/test_flex_flash_attn.py: every
backend replays the same AttnSlice metadata and must match `ref_attn` (explicit
dense mask, fp64) in out, lse, and input gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.common.enum import AttnMaskType
from magiattention_tpu.common.mask import AttnMask
from magiattention_tpu.common.ranges import AttnRanges
from magiattention_tpu.functional.flex_flash_attn import flex_flash_attn_func
from magiattention_tpu.testing import assert_close, ref_attn

S = 128
HQ, HK, D = 4, 2, 64

FULL, CAUSAL, INV, BI = 0, 1, 2, 3

MASK_CASES = {
    "full": ([[0, S]], [[0, S]], [FULL]),
    "causal": ([[0, S]], [[0, S]], [CAUSAL]),
    "inv_causal": ([[0, S]], [[0, S]], [INV]),
    "varlen_full": (
        [[0, 37], [37, 64], [64, S]],
        [[0, 37], [37, 64], [64, S]],
        [FULL, FULL, FULL],
    ),
    "varlen_causal": (
        [[0, 37], [37, 64], [64, S]],
        [[0, 37], [37, 64], [64, S]],
        [CAUSAL, CAUSAL, CAUSAL],
    ),
    "sliding_window": (
        [[0, 32], [32, S]],
        [[0, 32], [0, S]],
        [CAUSAL, BI],
    ),
    "shared_question": (  # two slices sharing q rows, disjoint k ranges
        [[0, 64], [0, 64], [64, S]],
        [[0, 32], [96, S], [0, S]],
        [FULL, FULL, CAUSAL],
    ),
    "empty_rows": (  # q rows [96, 128) attend nothing
        [[0, 96]],
        [[0, 64]],
        [CAUSAL],
    ),
    "block_causal": (
        [[0, 64], [64, S]],
        [[0, 64], [0, S]],
        [FULL, FULL],
    ),
}


def make_inputs(dtype, seed=0, sq=S, sk=S):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((sq, HQ, D)), dtype=dtype)
    k = jnp.asarray(rng.standard_normal((sk, HK, D)), dtype=dtype)
    v = jnp.asarray(rng.standard_normal((sk, HK, D)), dtype=dtype)
    return q, k, v


def mask_case(case, s=S):
    """The case's slices with every boundary scaled from S to ``s``."""
    qr, kr, tm = MASK_CASES[case]
    return (np.array(qr) * (s // S)).tolist(), (
        np.array(kr) * (s // S)).tolist(), tm


def dense_mask(case, s=S):
    qr, kr, tm = mask_case(case, s)
    return AttnMask.from_ranges(
        AttnRanges.from_ranges(qr),
        AttnRanges.from_ranges(kr),
        [AttnMaskType.from_int_type(t) for t in tm],
        total_seqlen_q=s,
        total_seqlen_k=s,
    ).mask_array


# long enough for four q tiles and two k tiles of default_blocks
S_LONG = 1024
UNPACKED = {"MAGI_ATTENTION_FFA_GQA_PACK": "0",
            "MAGI_ATTENTION_FFA_GQA_PACK_DQ": "0"}
# (backend, length, env) -> the per-pass tiles and bodies the ffa backend
# must run: every pass GQA-packed on default_blocks (g x its rows a grid
# step), or, with the q-major packs off, fwd and dq plain at the same tile
BACKENDS_AND_TILES = [
    pytest.param("sdpa", S, {}, None, id="sdpa"),
    pytest.param("ffa", S, {}, "fwd128x128g2 dq128x128g2 dkv128x128g2",
                 id="ffa"),
    pytest.param("ffa", S_LONG, {}, "fwd256x512g2 dq256x512g2 dkv256x512g2",
                 id="ffa_long_packed"),
    pytest.param("ffa", S_LONG, UNPACKED, "fwd256x512 dq256x512 dkv256x512g2",
                 id="ffa_long_plain_q_major"),
]


def _setenv(monkeypatch, env):
    for key, val in env.items():
        monkeypatch.setenv(key, val)


def _ran_tiles(tiles):
    from magiattention_tpu.kernels import registry

    assert tiles is None or registry.last_choice("ffa_tiles") == tiles


@pytest.mark.parametrize("case", sorted(MASK_CASES))
@pytest.mark.parametrize("backend", ["sdpa", "sdpa_online", "ffa"])
def test_forward_matches_ref(case, backend):
    qr, kr, tm = MASK_CASES[case]
    q, k, v = make_inputs(jnp.float32)
    out, meta = flex_flash_attn_func(
        q, k, v, np.array(qr), np.array(kr), np.array(tm), backend=backend
    )
    out_ref, lse_ref = ref_attn(q, k, v, dense_mask(case))
    assert_close(out, out_ref, atol=1e-4, rtol=1e-4, norm_rtol=2e-5, msg=f"{case} out")
    assert_close(meta.lse, lse_ref, atol=1e-4, rtol=1e-4, norm_rtol=2e-5,
                 msg=f"{case} lse")


@pytest.mark.parametrize("case", ["causal", "varlen_causal", "sliding_window",
                                  "shared_question", "empty_rows"])
@pytest.mark.parametrize("backend,s,env,tiles", BACKENDS_AND_TILES)
def test_backward_matches_ref(monkeypatch, case, backend, s, env, tiles):
    _setenv(monkeypatch, env)
    qr, kr, tm = mask_case(case, s)
    q, k, v = make_inputs(jnp.float32, seed=1, sq=s, sk=s)
    mask = dense_mask(case, s)
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.standard_normal((s, HQ, D)), dtype=jnp.float32)

    def loss_backend(q, k, v):
        out, meta = flex_flash_attn_func(
            q, k, v, np.array(qr), np.array(kr), np.array(tm), backend=backend
        )
        return jnp.sum(out.astype(jnp.float32) * w), (out, meta.lse)

    def loss_ref(q, k, v):
        out, lse = ref_attn(q, k, v, mask, compute_dtype=jnp.float32)
        return jnp.sum(out.astype(jnp.float32) * w), (out, lse)

    g, fwd = jax.grad(loss_backend, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    g_ref, fwd_ref = jax.grad(loss_ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    _ran_tiles(tiles)
    for name, a, b in zip("out lse".split(), fwd, fwd_ref):
        assert_close(a, b, atol=1e-4, rtol=1e-4, norm_rtol=2e-5,
                     msg=f"{case} {name}")
    for name, a, b in zip("dq dk dv".split(), g, g_ref):
        assert_close(a, b, atol=1e-3, rtol=1e-3, norm_rtol=2e-4,
                     msg=f"{case} {name}")


@pytest.mark.parametrize("backend,s,env,tiles", BACKENDS_AND_TILES)
def test_bf16_forward(monkeypatch, backend, s, env, tiles):
    _setenv(monkeypatch, env)
    qr, kr, tm = mask_case("varlen_causal", s)
    q, k, v = make_inputs(jnp.bfloat16, seed=3, sq=s, sk=s)
    out, meta = flex_flash_attn_func(
        q, k, v, np.array(qr), np.array(kr), np.array(tm), backend=backend
    )
    _ran_tiles(tiles)
    out_ref, lse_ref = ref_attn(q, k, v, dense_mask("varlen_causal", s))
    assert_close(out, out_ref, atol=3e-2, rtol=3e-2, norm_rtol=2e-2,
                 mismatch_thres=0.01, msg="bf16 out")
    assert_close(meta.lse, lse_ref, atol=3e-2, rtol=3e-2, norm_rtol=2e-2,
                 mismatch_thres=0.01, msg="bf16 lse")


def test_softcap():
    qr, kr, tm = MASK_CASES["causal"]
    q, k, v = make_inputs(jnp.float32, seed=4)
    for backend in ["sdpa", "ffa"]:
        out, meta = flex_flash_attn_func(
            q, k, v, np.array(qr), np.array(kr), np.array(tm),
            backend=backend, softcap=10.0,
        )
        out_ref, lse_ref = ref_attn(q, k, v, dense_mask("causal"), softcap=10.0)
        assert_close(out, out_ref, atol=1e-4, rtol=1e-4, norm_rtol=2e-5,
                     msg=f"{backend} softcap out")


def test_gqa_groups():
    # hq == hk (MHA) sanity alongside the default GQA shapes above
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((S, 2, D)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((S, 2, D)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((S, 2, D)), dtype=jnp.float32)
    qr, kr, tm = MASK_CASES["causal"]
    for backend in ["sdpa", "ffa"]:
        out, _ = flex_flash_attn_func(
            q, k, v, np.array(qr), np.array(kr), np.array(tm), backend=backend
        )
        out_ref, _ = ref_attn(q, k, v, dense_mask("causal"))
        assert_close(out, out_ref, atol=1e-4, rtol=1e-4, norm_rtol=2e-5,
                     msg=f"{backend} mha out")


def test_cross_attn_rectangular():
    # sq != sk (cross attention shape)
    sq, sk = 64, 192
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((sq, HQ, D)), dtype=jnp.float32)
    k = jnp.asarray(rng.standard_normal((sk, HK, D)), dtype=jnp.float32)
    v = jnp.asarray(rng.standard_normal((sk, HK, D)), dtype=jnp.float32)
    qr, kr, tm = [[0, sq]], [[0, sk]], [CAUSAL]
    from magiattention_tpu.common.mask import slice_mask_block
    from magiattention_tpu.common.range import AttnRange

    mask = slice_mask_block(AttnRange(0, sq), AttnRange(0, sk), AttnMaskType.CAUSAL)
    for backend in ["sdpa", "sdpa_online", "ffa"]:
        out, meta = flex_flash_attn_func(
            q, k, v, np.array(qr), np.array(kr), np.array(tm), backend=backend
        )
        out_ref, lse_ref = ref_attn(q, k, v, mask)
        assert_close(out, out_ref, atol=1e-4, rtol=1e-4, norm_rtol=2e-5,
                     msg=f"{backend} cross out")
        assert_close(meta.lse, lse_ref, atol=1e-4, rtol=1e-4, norm_rtol=2e-5,
                     msg=f"{backend} cross lse")


@pytest.mark.parametrize(
    "case", ["causal", "varlen_full", "sliding_window", "empty_rows",
             "shared_question"]
)
@pytest.mark.parametrize("backend", ["sdpa", "sdpa_online", "ffa"])
def test_max_logits_matches_ref(case, backend):
    from magiattention_tpu.testing import ref_max_logits

    qr, kr, tm = MASK_CASES[case]
    q, k, v = make_inputs(jnp.float32, seed=5)
    _, meta = flex_flash_attn_func(
        q, k, v, np.array(qr), np.array(kr), np.array(tm), backend=backend,
        return_max_logits=True,
    )
    ml_ref = ref_max_logits(q, k, dense_mask(case))
    assert meta.max_logits is not None
    assert meta.max_logits.shape == (HQ,)
    np.testing.assert_allclose(
        np.asarray(meta.max_logits), np.asarray(ml_ref), atol=1e-5, rtol=1e-5
    )


def test_max_logits_softcap():
    from magiattention_tpu.testing import ref_max_logits

    qr, kr, tm = MASK_CASES["causal"]
    q, k, v = make_inputs(jnp.float32, seed=6)
    for backend in ["sdpa", "ffa"]:
        _, meta = flex_flash_attn_func(
            q, k, v, np.array(qr), np.array(kr), np.array(tm),
            backend=backend, softcap=5.0, return_max_logits=True,
        )
        ml_ref = ref_max_logits(q, k, dense_mask("causal"), softcap=5.0)
        np.testing.assert_allclose(
            np.asarray(meta.max_logits), np.asarray(ml_ref),
            atol=1e-5, rtol=1e-5,
        )


@pytest.mark.parametrize("hq,hk", [(4, 4), (4, 2), (8, 2)])
def test_gqa_group_ratios(hq, hk):
    """GQA grouping grid (ref kernel tests sweep head configs)."""
    qr, kr, tm = MASK_CASES["varlen_causal"]
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((S, hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((S, hk, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((S, hk, D)), jnp.float32)
    out, meta = flex_flash_attn_func(
        q, k, v, np.array(qr), np.array(kr), np.array(tm), backend="ffa"
    )
    out_ref, lse_ref = ref_attn(q, k, v, dense_mask("varlen_causal"))
    assert_close(out, out_ref, atol=1e-4, rtol=1e-4, norm_rtol=2e-5,
                 msg=f"gqa {hq}/{hk} out")
    assert_close(meta.lse, lse_ref, atol=1e-4, rtol=1e-4, norm_rtol=2e-5,
                 msg=f"gqa {hq}/{hk} lse")


def test_asymmetric_dv():
    """dv != dk (MLA-style value dim) through the kernel + grads."""
    qr, kr, tm = MASK_CASES["causal"]
    dv = 32
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.standard_normal((S, HQ, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((S, HK, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((S, HK, dv)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((S, HQ, dv)), jnp.float32)

    def loss(q, k, v):
        out, _ = flex_flash_attn_func(
            q, k, v, np.array(qr), np.array(kr), np.array(tm), backend="ffa"
        )
        return jnp.sum(out * w), out

    (l, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    out_ref, _ = ref_attn(q, k, v, dense_mask("causal"))

    def ref_loss(q, k, v):
        o, _ = ref_attn(q, k, v, dense_mask("causal"),
                        compute_dtype=jnp.float32)
        return jnp.sum(o * w)

    rgrads = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    assert_close(out, out_ref, atol=1e-4, rtol=1e-4, norm_rtol=2e-5,
                 msg="dv!=dk out")
    for name, a, b in zip("dq dk dv".split(), grads, rgrads):
        assert_close(a, b, atol=1e-3, rtol=1e-3, norm_rtol=3e-4,
                     msg=f"dv!=dk {name}")
