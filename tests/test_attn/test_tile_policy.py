"""Auto tile selection (kernels/tile_policy.py — ref tile-table analogue)."""

import re

import numpy as np
import pytest

from magiattention_tpu.kernels.mask_utils import types_to_bands
from magiattention_tpu.kernels.tile_policy import (
    CANDIDATES,
    VMEM_BUDGET,
    _vmem_bytes,
    choose_blocks,
)


def _bands(qr, kr, tm):
    qr = np.asarray(qr, np.int32)
    kr = np.asarray(kr, np.int32)
    lo, hi = types_to_bands(qr, kr, np.asarray(tm, np.int32))
    return qr, kr, lo, hi


def test_returns_valid_candidate_dense_causal():
    qr, kr, lo, hi = _bands([[0, 4096]], [[0, 4096]], [1])
    bq, bk = choose_blocks(qr, kr, lo, hi, 4096, 4096, 128, 128)
    assert bq % 16 == 0 and bk % 128 == 0
    assert _vmem_bytes(bq, bk, 128, 128, 2) <= VMEM_BUDGET
    # dense causal at 4k: a mid/large tile must win over the smallest one
    assert (bq, bk) != (128, 512)


def test_narrow_band_prefers_smaller_tiles_than_dense():
    s = 8192
    # sliding window of 256: rows attend a narrow diagonal band
    qr = np.array([[0, s]], np.int32)
    kr = np.array([[0, s]], np.int32)
    lo = np.array([-256], np.int32)
    hi = np.array([0], np.int32)
    bq_n, bk_n = choose_blocks(qr, kr, lo, hi, s, s, 128, 128)
    qr2, kr2, lo2, hi2 = _bands([[0, s]], [[0, s]], [0])
    bq_d, bk_d = choose_blocks(qr2, kr2, lo2, hi2, s, s, 128, 128)
    # the narrow band must not choose a LARGER tile area than full-dense
    assert bq_n * bk_n <= bq_d * bk_d
    # and dense full prefers the largest surviving candidate
    assert bq_d * bk_d == max(
        bq * bk for bq, bk in CANDIDATES
        if _vmem_bytes(bq, bk, 128, 128, 2) <= VMEM_BUDGET
    )


def test_small_problem_clamps():
    qr, kr, lo, hi = _bands([[0, 100]], [[0, 80]], [0])
    bq, bk = choose_blocks(qr, kr, lo, hi, 100, 80, 64, 64)
    assert bq <= 112 and bk <= 128  # round_up(100,16), round_up(80,128)


def test_vmem_guard_excludes_big_tiles_at_big_head_dim():
    qr, kr, lo, hi = _bands([[0, 4096]], [[0, 4096]], [0])
    # d=dv=512 fp32: (1024,1024) blocks alone are ~2*(4 tiles*512*4B*1024)
    bq, bk = choose_blocks(qr, kr, lo, hi, 4096, 4096, 512, 512, itemsize=4)
    assert _vmem_bytes(bq, bk, 512, 512, 4) <= VMEM_BUDGET


def test_auto_tile_e2e_matches_reference(monkeypatch):
    """MAGI_ATTENTION_FFA_AUTO_TILE=1 end-to-end: same numbers as the
    default tiling path (tile size is performance-only)."""
    import jax.numpy as jnp

    from magiattention_tpu.kernels.ffa import ffa_attn
    from magiattention_tpu.testing.ref_attn import ref_attn
    from magiattention_tpu.common.mask import AttnMask
    from magiattention_tpu.common.ranges import AttnRanges
    from magiattention_tpu.common.enum import AttnMaskType

    s, h, d = 512, 2, 32
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    qr, kr, tm = [[0, s]], [[0, s]], [1]

    monkeypatch.setenv("MAGI_ATTENTION_FFA_AUTO_TILE", "1")
    # the gate defers to pinned env blocks — clear them so the policy
    # branch actually executes even on machines with persistent exports
    monkeypatch.delenv("MAGI_ATTENTION_FFA_BLOCK_Q", raising=False)
    monkeypatch.delenv("MAGI_ATTENTION_FFA_BLOCK_K", raising=False)
    out, lse = ffa_attn(q, k, v, qr, kr, tm)
    mask = AttnMask.from_ranges(
        AttnRanges.from_ranges(qr), AttnRanges.from_ranges(kr),
        [AttnMaskType.CAUSAL], total_seqlen_q=s, total_seqlen_k=s,
    ).mask_array
    out_ref, lse_ref = ref_attn(q, k, v, mask, compute_dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(out_ref), atol=2e-5, rtol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(lse_ref), atol=2e-5, rtol=2e-5
    )


def test_count_matches_builder_on_random_slices():
    """count_ffa_work (the cache-free scorer) == build_ffa_plan's num_work
    across random band-slice sets and tilings."""
    from magiattention_tpu.kernels.ffa_plan import build_ffa_plan
    from magiattention_tpu.kernels.tile_policy import count_ffa_work

    rng = np.random.default_rng(0)
    for trial in range(20):
        s = int(rng.integers(100, 1200))
        n = int(rng.integers(1, 6))
        qr, kr, tm = [], [], []
        for _ in range(n):
            a, b = np.sort(rng.integers(0, s, 2))
            c, e = np.sort(rng.integers(0, s, 2))
            qr.append([a, b + 1])
            kr.append([c, e + 1])
            tm.append(int(rng.integers(0, 4)))
        qrn, krn, lo, hi = _bands(qr, kr, tm)
        for bq, bk in [(64, 128), (128, 256), (256, 512)]:
            plan = build_ffa_plan(qrn, krn, lo, hi, s, s, bq, bk)
            cnt = count_ffa_work(qrn, krn, lo, hi, s, s, bq, bk)
            assert cnt == plan.num_work, (
                trial, s, qr, kr, tm, bq, bk, cnt, plan.num_work
            )


@pytest.mark.parametrize("chooser", ["auto_tile", "default_g4", "dkv_pin_g1"])
def test_cp_runtime_honors_the_tile_choice(monkeypatch, chooser):
    """The static CP runtime consults the chooser (not only ffa_attn): the
    auto-tile policy when its flag is on, else ``default_blocks`` for every
    pass — with a GQA group each pass packs at it (a 6-array stacked plan,
    g x 256 rows a step) — and a pass's own env tile rides the stacked cp
    path as 12 arrays (fwd6 + dq3 + dkv3) and reads as a pin."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from magiattention_tpu.api import (
        calc_attn, dispatch, magi_attn_flex_key, undispatch,
    )
    from magiattention_tpu.api.magi_attn_interface import _mgr
    from magiattention_tpu.common.enum import AttnMaskType
    from magiattention_tpu.common.mask import AttnMask
    from magiattention_tpu.common.ranges import AttnRanges
    from magiattention_tpu.kernels import registry
    from magiattention_tpu.testing.ref_attn import ref_attn

    auto = chooser == "auto_tile"
    monkeypatch.setenv("MAGI_ATTENTION_FFA_AUTO_TILE", "1" if auto else "0")
    monkeypatch.delenv("MAGI_ATTENTION_FFA_BLOCK_Q", raising=False)
    monkeypatch.delenv("MAGI_ATTENTION_FFA_BLOCK_K", raising=False)
    if chooser == "dkv_pin_g1":
        monkeypatch.setenv("MAGI_ATTENTION_FFA_BLOCK_Q_DKV", "128")
    # a full default tile a rank: cp = 4 x 512
    s, hq, hk, d, chunk = {
        "auto_tile": (512, 2, 2, 32, 32),
        "default_g4": (2048, 4, 1, 32, 512),
        "dkv_pin_g1": (2048, 2, 2, 32, 512),
    }[chooser]
    mesh = Mesh(np.array(jax.devices("cpu")[:4]), axis_names=("cp",))
    key = magi_attn_flex_key(
        [[0, s]], [[0, s]], [1], s, s, mesh=mesh, chunk_size=chunk,
    )
    rt = _mgr(key).runtime
    if auto:
        # auto-tile DEFERS plan building to the first calc_attn, where the
        # real head dims/dtype feed the VMEM guard (r3 advisor finding)
        assert rt._auto_tile_pending and not hasattr(rt, "_bq")
    else:
        # the default tile needs no data: the plans are built at once
        assert (rt._bq, rt._bk) == (256, 512)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((s, hk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((s, hk, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((s, hq, d)), jnp.float32)

    def fwd(q, k, v):
        out_d, _ = calc_attn(
            dispatch(q, key), dispatch(k, key, role="kv"),
            dispatch(v, key, role="kv"), key,
        )
        return undispatch(out_d, key)

    out = fwd(q, k, v)
    mask = AttnMask.from_ranges(
        AttnRanges.from_ranges([[0, s]]), AttnRanges.from_ranges([[0, s]]),
        [AttnMaskType.CAUSAL], total_seqlen_q=s, total_seqlen_k=s,
    ).mask_array
    out_ref, _ = ref_attn(q, k, v, mask, compute_dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(out_ref), atol=2e-5, rtol=2e-5
    )
    if auto:
        # the choice ran with the REAL dims signature and is TPU-aligned
        assert rt._plan_sig == (d, d, 4, hq // hk, False)
        assert rt._bq % 16 == 0 and rt._bk % 128 == 0
        return
    overrides = rt._merged_dims[4]
    # every plan group carries its list's revisit distance beside the tiles
    assert overrides["min_revisit_distance"] >= 2
    if chooser == "default_g4":
        assert set(overrides) == {"min_revisit_distance"}
        assert len(rt._merged_arrays) == 6
        want, source = "fwd256x512g4 dq256x512g4 dkv256x512g4", "default"
    else:
        # the 12 arrays are fwd6 + dq3 + dkv3 with only dkv on its own tile
        assert len(rt._merged_arrays) == 12
        assert overrides["block_q_dkv"] == 128
        assert "block_q_dq" not in overrides
        want, source = "fwd256x512 dq256x512 dkv128x512", "pin"
    assert rt._tile_source == source
    assert registry.last_choice("ffa_tiles") == want
    g = jax.grad(lambda q, k, v: jnp.sum(fwd(q, k, v) * w), (0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(
            ref_attn(q, k, v, mask, compute_dtype=jnp.float32)[0] * w),
        (0, 1, 2),
    )(q, k, v)
    for name, a, b in zip("dq dk dv".split(), g, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4, err_msg=name)


def test_explicit_blocks_override_auto(monkeypatch):
    """Explicit args beat the policy (the env-override contract)."""
    import jax.numpy as jnp

    from magiattention_tpu.kernels import ffa as ffa_mod

    monkeypatch.setenv("MAGI_ATTENTION_FFA_AUTO_TILE", "1")
    calls = []
    orig = ffa_mod.get_ffa_plan

    def spy(qr, kr, lo, hi, sq, sk, bq, bk):
        calls.append((bq, bk))
        return orig(qr, kr, lo, hi, sq, sk, bq, bk)

    monkeypatch.setattr(ffa_mod, "get_ffa_plan", spy)
    s, h, d = 256, 1, 32
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
    ffa_mod.ffa_attn(q, k, v, [[0, s]], [[0, s]], [1],
                     block_q=64, block_k=128)
    assert calls and all(c == (64, 128) for c in calls), calls


def test_count_t_matches_builder_on_random_slices():
    """count_ffa_work_t (the k-major scorer the dkv pass uses) ==
    build_ffa_plan's num_work_t across random band-slice sets/tilings."""
    from magiattention_tpu.kernels.ffa_plan import build_ffa_plan
    from magiattention_tpu.kernels.tile_policy import count_ffa_work_t

    rng = np.random.default_rng(1)
    for trial in range(20):
        s = int(rng.integers(100, 1200))
        n = int(rng.integers(1, 6))
        qr, kr, tm = [], [], []
        for _ in range(n):
            a, b = np.sort(rng.integers(0, s, 2))
            c, e = np.sort(rng.integers(0, s, 2))
            qr.append([a, b + 1])
            kr.append([c, e + 1])
            tm.append(int(rng.integers(0, 4)))
        qrn, krn, lo, hi = _bands(qr, kr, tm)
        for bq, bk in [(64, 128), (128, 256), (256, 512)]:
            plan = build_ffa_plan(qrn, krn, lo, hi, s, s, bq, bk)
            cnt = count_ffa_work_t(qrn, krn, lo, hi, s, s, bq, bk)
            assert cnt == plan.num_work_t, (
                trial, s, qr, kr, tm, bq, bk, cnt, plan.num_work_t
            )


def test_per_pass_choice_thin_band_and_divisibility():
    """The per-pass chooser: thin bands pick a smaller block_k than dense
    full, and any bwd pick divides the fwd-padded geometry (the
    resolve_bwd_overrides gate must never silently drop a policy pick)."""
    from magiattention_tpu.kernels.tile_policy import (
        _round_up, choose_blocks_per_pass,
    )

    s = 8192
    qr = np.array([[0, s]], np.int32)
    kr = np.array([[0, s]], np.int32)
    lo = np.array([-256], np.int32)
    hi = np.array([0], np.int32)
    fwd, dq, dkv = choose_blocks_per_pass(qr, kr, lo, hi, s, s, 128, 128)
    qrd, krd, lod, hid = _bands([[0, s]], [[0, s]], [0])
    fwd_d, dq_d, dkv_d = choose_blocks_per_pass(
        qrd, krd, lod, hid, s, s, 128, 128
    )
    # thin band: block_k no larger than the dense pick, for every pass
    assert fwd[1] <= fwd_d[1]
    for pick, dense_pick, fwd_pick in ((dq, dq_d, fwd), (dkv, dkv_d, fwd_d)):
        eff = pick or fwd
        eff_d = dense_pick or fwd_d
        assert eff[1] <= eff_d[1]
    # divisibility contract vs the fwd-padded geometry
    for f, picks in ((fwd, (dq, dkv)), (fwd_d, (dq_d, dkv_d))):
        sqp, skp = _round_up(s, f[0]), _round_up(s, f[1])
        for p in picks:
            if p is not None:
                assert sqp % p[0] == 0 and skp % p[1] == 0, (f, p)


# -- rows a grid step, per pass: which body runs at which tile ---------------

PACKED = "fwd256x512g4 dq256x512g4 dkv256x512g4"
UNPACK_Q_MAJOR = {"MAGI_ATTENTION_FFA_GQA_PACK": "0",
                  "MAGI_ATTENTION_FFA_GQA_PACK_DQ": "0"}
BF16_G4 = (1024, 1024, 4, 1, 128, 128, "bfloat16")
# name: (sq, sk, hq, hk, d, dv, dtype, ffa_attn kwargs, env) -> the tiles'
# name as registry.last_choice("ffa_tiles") has it, and who chose
TILE_CASES = {
    # the four cells' FFA calls, a chip's share (PERF.md section 4): every
    # pass packed, g x 256 rows a step, one tile, a 6-array plan
    "nemo12b.longdoc.cp1": (
        (16384, 16384, 32, 8, 128, 128, "bfloat16", {}, {}), PACKED,
        "default"),
    "nemo12b.packed.cp1": (
        (16384, 16384, 32, 8, 128, 128, "bfloat16", {}, {}), PACKED,
        "default"),
    "mistral7b.swa32k.cp1": (
        (32768, 32768, 32, 8, 128, 128, "bfloat16", {}, {}), PACKED,
        "default"),
    "nemo12b.longdoc.cp4": (
        (8192, 32768, 32, 8, 128, 128, "bfloat16", {}, {}), PACKED,
        "default"),
    # short sequences: default_blocks' clamp, one tile for every pass
    "sq128": ((128, 128, 4, 1, 128, 128, "bfloat16", {}, {}),
              "fwd128x128g4 dq128x128g4 dkv128x128g4", "default"),
    "sq384": ((384, 384, 4, 1, 128, 128, "bfloat16", {}, {}),
              "fwd256x384g4 dq256x384g4 dkv256x384g4", "default"),
    "sq384_unpacked": ((384, 384, 4, 1, 128, 128, "bfloat16", {},
                        UNPACK_Q_MAJOR),
                       "fwd256x384 dq256x384 dkv256x384g4", "default"),
    "sq512": ((512, 512, 4, 1, 128, 128, "bfloat16", {}, {}), PACKED,
              "default"),
    # no group to pack: the plain bodies, at the same tile
    "g1": ((1024, 1024, 2, 2, 128, 128, "bfloat16", {}, {}),
           "fwd256x512 dq256x512 dkv256x512", "default"),
    "g1_sq512": ((512, 512, 2, 2, 128, 128, "bfloat16", {}, {}),
                 "fwd256x512 dq256x512 dkv256x512", "default"),
    "g2": ((1024, 1024, 4, 2, 128, 128, "bfloat16", {}, {}),
           "fwd256x512g2 dq256x512g2 dkv256x512g2", "default"),
    # what the v5e compiler refuses of the packed q-major bodies runs
    # plain: over 1024 packed rows, or a modeled residency over 9 MiB
    # (tests/test_attn/test_pack_guard_compiles.py compiles both sides)
    "g8_at_256_rows": (
        (1024, 1024, 8, 1, 128, 128, "bfloat16", {"block_q": 256}, {}),
        "fwd256x512 dq256x512 dkv256x512g8", "pin"),
    # so block_q follows the group (tile_policy.group_block_q, PR 36): a
    # packed step of 1024 rows at g = 8 and at g = 16 as at g = 4
    "g8": ((1024, 1024, 8, 1, 128, 128, "bfloat16", {}, {}),
           "fwd128x512g8 dq128x512g8 dkv128x512g8", "shape_rule"),
    "g16": ((1024, 1024, 16, 1, 128, 128, "bfloat16", {}, {}),
            "fwd64x512g16 dq64x512g16 dkv64x512g16", "shape_rule"),
    "g8_sq384": ((384, 384, 8, 1, 128, 128, "bfloat16", {}, {}),
                 "fwd128x384g8 dq128x384g8 dkv128x384g8", "shape_rule"),
    # no tile of the set packs 32 heads; a short sequence's clamped tile
    # packs as it is
    "g32": ((1024, 1024, 32, 1, 128, 128, "bfloat16", {}, {}),
            "fwd256x512 dq256x512 dkv256x512", "default"),
    "g8_sq128": ((128, 128, 8, 1, 128, 128, "bfloat16", {}, {}),
                 "fwd128x128g8 dq128x128g8 dkv128x128g8", "default"),
    # the BYTE budget refuses the packed dq at d = 256, at 128 rows too:
    # the default tile stays
    "g8_d256": ((1024, 1024, 8, 1, 256, 256, "bfloat16", {}, {}),
                "fwd256x512 dq256x512 dkv256x512", "default"),
    # the table guard: a 16384-token causal document is 2112 work items
    # at 128 x 512, over ffa.PLAN_TABLE_MAX_WORK (1056 at 256 x 512)
    "g8_table_guard": (
        (16384, 16384, 8, 1, 128, 128, "bfloat16", {}, {}),
        "fwd256x512 dq256x512 dkv256x512g8", "table_guard"),
    # the packed forward emits no max-logits: nothing for block_q to follow
    "g8_max_logits": (
        (1024, 1024, 8, 1, 128, 128, "bfloat16",
         {"return_max_logits": True}, {}),
        "fwd256x512 dq256x512 dkv256x512g8", "default"),
    # an argument, any FFA_BLOCK_* key and the auto-tile policy win over
    # the group's rule as over the default
    "g8_block_k_argument": (
        (1024, 1024, 8, 1, 128, 128, "bfloat16", {"block_k": 256}, {}),
        "fwd256x256 dq256x256 dkv256x256g8", "pin"),
    "g8_env_pin": (
        (1024, 1024, 8, 1, 128, 128, "bfloat16", {},
         {"MAGI_ATTENTION_FFA_BLOCK_K": "512"}),
        "fwd256x512 dq256x512 dkv256x512g8", "pin"),
    "g8_env_pin_of_one_pass": (
        (1024, 1024, 8, 1, 128, 128, "bfloat16", {},
         {"MAGI_ATTENTION_FFA_BLOCK_Q_DKV": "128"}),
        "fwd256x512 dq256x512 dkv128x512g8", "pin"),
    "g8_auto_tile": (
        (1024, 1024, 8, 1, 128, 128, "bfloat16", {},
         {"MAGI_ATTENTION_FFA_AUTO_TILE": "1"}),
        None, "auto_tile"),
    "d256_packed_dq_too_large": (
        (1024, 1024, 4, 1, 256, 256, "bfloat16", {}, {}),
        "fwd256x512g4 dq256x512 dkv256x512g4", "default"),
    "d64": ((1024, 1024, 4, 1, 64, 64, "bfloat16", {}, {}), PACKED,
            "default"),
    "qk192_v128": ((1024, 1024, 4, 1, 192, 128, "bfloat16", {}, {}),
                   PACKED, "default"),
    "fp32": ((1024, 1024, 4, 1, 128, 128, "float32", {}, {}), PACKED,
             "default"),
    # the packed forward emits no max-logits: it runs plain
    "emit_max_logits": (
        (*BF16_G4, {"return_max_logits": True}, {}),
        "fwd256x512 dq256x512g4 dkv256x512g4", "default"),
    # no packed residency fits: every pass plain
    "fp32_d512_g8_packed_does_not_fit": (
        (1024, 1024, 8, 1, 512, 512, "float32", {}, {}),
        "fwd256x512 dq256x512 dkv256x512", "default"),
    "fp32_d640_g1": (
        (1024, 1024, 2, 2, 640, 640, "float32", {}, {}),
        "fwd256x512 dq256x512 dkv256x512", "default"),
    # a flag at 0 brings that pass's plain body back, at the same tile
    "fwd_unpacked_by_flag": (
        (*BF16_G4, {}, {"MAGI_ATTENTION_FFA_GQA_PACK": "0"}),
        "fwd256x512 dq256x512g4 dkv256x512g4", "default"),
    "fwd_and_dq_unpacked_by_flag": (
        (*BF16_G4, {}, UNPACK_Q_MAJOR),
        "fwd256x512 dq256x512 dkv256x512g4", "default"),
    "dkv_unpacked_by_flag": (
        (*BF16_G4, {}, {"MAGI_ATTENTION_FFA_GQA_PACK_DKV": "0"}),
        "fwd256x512g4 dq256x512g4 dkv256x512", "default"),
    # explicit settings win exactly as before, and read as pins: a packed
    # body follows the pinned tile while the guard admits it
    "argument_pin": (
        (*BF16_G4, {"block_q": 128, "block_k": 256}, {}),
        "fwd128x256g4 dq128x256g4 dkv128x256g4", "pin"),
    "argument_pin_of_512": (
        (*BF16_G4, {"block_q": 512}, {}),
        "fwd512x512 dq512x512 dkv512x512g4", "pin"),
    "env_pin": (
        (*BF16_G4, {}, {"MAGI_ATTENTION_FFA_BLOCK_Q": "128"}),
        "fwd128x512g4 dq128x512g4 dkv128x512g4", "pin"),
    "env_pin_of_one_pass": (
        (*BF16_G4, {}, {"MAGI_ATTENTION_FFA_BLOCK_Q_DKV": "128"}),
        "fwd256x512g4 dq256x512g4 dkv128x512g4", "pin"),
    "auto_tile": (
        (*BF16_G4, {}, {"MAGI_ATTENTION_FFA_AUTO_TILE": "1"}),
        None, "auto_tile"),
}


@pytest.mark.parametrize("case", sorted(TILE_CASES))
def test_tiles_and_bodies_per_pass(monkeypatch, case):
    """Shape -> per-pass tiles and bodies of an ``ffa_attn`` call, as the
    registry's ``ffa_tiles`` decision records them: every pass runs at
    ``default_blocks`` and buys its q rows a grid step by GQA-packing (g x
    the tile's rows) wherever there is a group and the guard admits the
    packed step; pins and the auto-tile policy choose tiles as before."""
    import jax
    import jax.numpy as jnp

    from magiattention_tpu import telemetry
    from magiattention_tpu.kernels import ffa_plan, registry
    from magiattention_tpu.kernels.ffa import ffa_attn

    (sq, sk, hq, hk, d, dv, dtype, kwargs, env), want, source = TILE_CASES[case]
    for key in ("MAGI_ATTENTION_FFA_BLOCK_Q", "MAGI_ATTENTION_FFA_BLOCK_K"):
        monkeypatch.delenv(key, raising=False)
    for key, val in env.items():
        monkeypatch.setenv(key, val)
    registry.reset_registry()
    ffa_plan._cached_plan.cache_clear()  # a cached plan writes no record
    announced = []
    monkeypatch.setattr(telemetry, "enabled", lambda: True)
    monkeypatch.setattr(
        telemetry, "record_event",
        lambda kind, **rec: announced.append((kind, rec)))
    qr = np.array([[0, sq]], np.int32)
    kr = np.array([[0, sk]], np.int32)
    shapes = [jax.ShapeDtypeStruct((n, h, e), getattr(jnp, dtype))
              for n, h, e in ((sq, hq, d), (sk, hk, d), (sk, hk, dv))]
    # traced, not run: the choice is made from shapes alone
    jax.eval_shape(
        lambda q, k, v: ffa_attn(q, k, v, qr, kr, [1], **kwargs), *shapes)
    got = registry.last_choice("ffa_tiles")
    if want is not None:
        assert got == want
    tiles = [rec for kind, rec in announced
             if kind == "backend_select" and rec["decision"] == "ffa_tiles"]
    assert [(t["choice"], t["source"]) for t in tiles] == [(got, source)]
    # each pass's own plan shows in the ffa_plan records, tiles and counts
    plans = {(rec["block_q"], rec["block_k"]) for kind, rec in announced
             if kind == "ffa_plan"}
    assert {(int(bq), int(bk))
            for bq, bk in re.findall(r"(\d+)x(\d+)", got)} == plans


# -- the backward mode, from the static shapes alone -------------------------

# (W of the q-major list, W of the k-major list, group): what the four cells
# and PR 27's g = 1 probe resolve (tests/test_support/test_registry.py has
# the whole keys), and the mode the chip measured best there — the one-pass
# backward, by 31 to 39% of the pair's time (my chip runs, PR 30: fwd + bwd
# of a layer 88.6 -> 64.2 ms longdoc, 32.4 -> 25.5 packed, 99.2 -> 74.8 on
# the window, 30.9 -> 22.8 at g = 1 and 8 kv heads)
BWD_CELL_SHAPES = {
    "nemo12b.longdoc.cp1": (1056, 1056, 4),
    "nemo12b.longdoc.cp4": (1040, 1043, 4),
    "mistral7b.swa32k.cp1": (1096, 1096, 4),
    "nemo12b.packed.cp1": (315, 315, 4),
    "probe.g1": (1056, 1056, 1),
}


@pytest.mark.parametrize("cell", sorted(BWD_CELL_SHAPES))
def test_bwd_rule_returns_what_the_chip_measured_best(cell):
    from magiattention_tpu.kernels.tile_policy import (
        bwd_step_us, choose_bwd_mode,
    )

    w, wt, g = BWD_CELL_SHAPES[cell]
    assert choose_bwd_mode(
        w, 256, 512, wt, 256, 512, 128, 128, itemsize=2, group=g) == "fused"
    # and by the margin the chip read, not by a hair: the pair's two steps
    # against the one-pass step, 7.3 v. 4.5 us at g = 4
    pair = bwd_step_us("dq", 256, 512, 128, 128, 2, g) + bwd_step_us(
        "dkv", 256, 512, 128, 128, 2, g)
    one = bwd_step_us("fused", 256, 512, 128, 128, 2, g)
    assert 0.55 < one / pair < 0.70


# q rows a chip (the padded q length ``ffa_bwd_mode`` is given)
BWD_CELL_ROWS = {"mistral7b.swa32k.cp1": 32768, "nemo12b.longdoc.cp4": 8192}


@pytest.mark.parametrize("cell", sorted(BWD_CELL_SHAPES))
def test_bwd_mode_of_a_cell_follows_its_plans_revisit_distance(
    cell, monkeypatch
):
    """Through ``ffa_bwd_mode``: the cells' lists clear the distance the
    pipeline needs and resolve to fused, whatever the rows a chip (the
    window cell's 32768 too: what its step peaks at in HBM is the step's
    schedule, ``models/llama.py:TPU_STEP_COMPILER_OPTIONS``, and no row count
    in the kernel's rule); the same shapes over a list under the distance
    (or one nobody measured) resolve to split."""
    from magiattention_tpu.kernels import ffa, registry

    w, wt, g = BWD_CELL_SHAPES[cell]
    rows = BWD_CELL_ROWS.get(cell, 16384)
    unpinned = "fused"

    def mode(dist):
        params = ffa.FFAParams(
            num_work=w, num_work_t=wt, num_q_tiles=rows // 256,
            num_k_tiles=32, block_q=256, block_k=512, softmax_scale=1.0,
            softcap=0.0, group=g, interpret=True, min_revisit_distance=dist)
        return ffa.resolved_bwd_mode(params, rows, 128, 128, 2)

    registry.reset_registry()
    assert mode(4) == unpinned
    assert registry.last_choice("ffa_bwd") == unpinned
    assert mode(ffa.FUSED_DQ_REVISIT_DISTANCE) == unpinned
    assert mode(ffa.FUSED_DQ_REVISIT_DISTANCE - 1) == "split"
    assert registry.last_choice("ffa_bwd") == "split"
    assert mode(0) == "split"
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_FFA_BWD", "fused")
    assert mode(4) == "fused"
    assert mode(0) == "split"  # no pin lifts a feasibility guard


# -- block_q follows the GQA group (tile_policy.group_block_q, PR 36) --------

BF16_128 = (128, 128, 2)
# name: (group, (d, dv, itemsize), default tile, largest work count at the
# candidate tile) -> (block_q, source)
GROUP_RULE = {
    # up to g = 4 the default tile packs: nothing to follow
    "g1": (1, BF16_128, (256, 512), 10, (256, "default")),
    "g2": (2, BF16_128, (256, 512), 10, (256, "default")),
    "g4": (4, BF16_128, (256, 512), 10, (256, "default")),
    # 1024 packed rows a step at every larger group that has such a tile
    "g8": (8, BF16_128, (256, 512), 10, (128, "shape_rule")),
    "g16": (16, BF16_128, (256, 512), 10, (64, "shape_rule")),
    "g32_no_tile_of_the_set_packs": (
        32, BF16_128, (256, 512), 10, (256, "default")),
    "g8_float32": (8, (128, 128, 4), (256, 512), 10, (128, "shape_rule")),
    "g8_qk192_v128": (8, (192, 128, 2), (256, 512), 10, (128, "shape_rule")),
    # refused by BYTES at the default, not by rows: not this rule's
    "g4_d256": (4, (256, 256, 2), (256, 512), 10, (256, "default")),
    # a body that does not fit at 128 rows either (the packed dq)
    "g8_d256": (8, (256, 256, 2), (256, 512), 10, (256, "default")),
    "g16_d256": (16, (256, 256, 2), (256, 512), 10, (256, "default")),
    "g8_float32_qk192": (8, (192, 128, 4), (256, 512), 10, (256, "default")),
    # the plan's table: at the capacity it moves, one item more it stays
    "g8_table_full": (8, BF16_128, (256, 512), 1984, (128, "shape_rule")),
    "g8_table_over": (8, BF16_128, (256, 512), 1985, (256, "table_guard")),
    "g16_table_over": (16, BF16_128, (256, 512), 2809, (256, "table_guard")),
    # a short sequence's clamped tile: 8 x 128 packs as it is; 16 x 128
    # halves once; a tile whose half is no multiple of 16 rows stays
    "g8_clamped_128": (8, BF16_128, (128, 128), 10, (128, "default")),
    "g16_clamped_128": (16, BF16_128, (128, 128), 10, (64, "shape_rule")),
    "g16_clamped_112": (16, BF16_128, (112, 128), 10, (112, "default")),
    # a narrower key tile does not change the row bound
    "g8_bk128": (8, BF16_128, (256, 128), 10, (128, "shape_rule")),
}


@pytest.mark.parametrize("case", sorted(GROUP_RULE))
def test_block_q_follows_the_group(monkeypatch, case):
    from magiattention_tpu import telemetry
    from magiattention_tpu.kernels import ffa
    from magiattention_tpu.kernels.tile_policy import group_block_q

    group, (d, dv, itemsize), (bq, bk), work, want = GROUP_RULE[case]
    assert ffa.PLAN_TABLE_MAX_WORK == 1984  # the cases' table sizes
    asked, said = [], []
    monkeypatch.setattr(telemetry, "enabled", lambda: True)
    monkeypatch.setattr(
        telemetry, "record_event",
        lambda kind, **rec: kind == "tile_policy" and said.append(rec))

    def max_work(blk_q, blk_k):
        asked.append((blk_q, blk_k))
        return work

    assert group_block_q(group, d, dv, itemsize, bq, bk, max_work) == want
    if want[1] == "default":
        # the plan is counted, and a record written, only for a tile the
        # rule would move to
        assert not asked and not said
        return
    [rec] = said
    candidate = asked[0][0]
    assert asked == [(candidate, bk)] and group * candidate == 1024
    assert rec["mode"] == "group"
    # the guard's reason: W and the capacity beside the tile kept
    assert (rec["num_work"], rec["table_capacity"]) == (work, 1984)
    assert rec["candidate_blocks"] == [candidate, bk]
    assert rec["fwd_blocks"] == [want[0], bk]


@pytest.mark.parametrize("flag", [
    "MAGI_ATTENTION_FFA_GQA_PACK", "MAGI_ATTENTION_FFA_GQA_PACK_DQ",
    "MAGI_ATTENTION_FFA_GQA_PACK_DKV"])
def test_a_pass_unpacked_by_its_flag_keeps_the_default_tile(monkeypatch, flag):
    """A plain body at the rule's tile would run fewer rows a step than at
    the default (and the plain dq does not lower at 64): every pass packs,
    or the tile stays."""
    from magiattention_tpu.kernels.tile_policy import group_block_q

    monkeypatch.setenv(flag, "0")
    for group in (8, 16):
        assert group_block_q(
            group, 128, 128, 2, 256, 512, lambda bq, bk: 10
        ) == (256, "default")


# cell -> {key label: (hq, hk, W at 256 x 512, the rule's (block_q, source),
# W at the tile the rule would move to)}: ISSUE 36's table, the largest of
# the q-major and k-major lists of the merged plan (the largest rank's at cp
# 4). Only the Trinity window key moves; the full layer and the hybrid
# cell's g = 16 call wait for a table laid lane-major (ROADMAP A3).
CELL_TILES = {
    "nemo12b.longdoc.cp1": {"": (32, 8, 1056, (None, "default"), None)},
    "nemo12b.packed.cp1": {"": (32, 8, 315, (None, "default"), None)},
    "mistral7b.swa32k.cp1": {"": (32, 8, 1096, (None, "default"), None)},
    "nemo12b.longdoc.cp4": {"": (32, 8, 1043, (None, "default"), None)},
    "nemotron3nano.packed32k.cp1": {
        "": (32, 2, 732, (None, "table_guard"), 2809)},
    "trinitymini.longdocs32k.cp1": {
        "full": (32, 4, 1342, (None, "table_guard"), 2664),
        "window": (32, 4, 617, (128, "shape_rule"), 1189)},
}


@pytest.mark.parametrize("cell_name", sorted(CELL_TILES))
def test_the_rule_on_the_cells_real_masks(cell_name):
    """Each cell's own mask at its own tokens and head layout through its
    runtime's plan groups (numpy only: no kernel is traced)."""
    import jax
    from jax.sharding import Mesh

    from cellbench import manifest, run, traffic_gen
    from magiattention_tpu.api.magi_attn_interface import _mgr
    from magiattention_tpu.kernels import ffa

    cell = manifest.load_cell(manifest.ROOT, cell_name)
    family = manifest.load_family(manifest.ROOT, cell.config["family"])
    cfg, tokens, window, _ = run.cell_sizes(cell, family, 0)
    family.model_config(cfg)  # the afmoe family's window is its layers'
    spec = traffic_gen.make_mask(
        cell.traffic, tokens, window, 0,
        manifest.load_generator(manifest.ROOT, cell.traffic["generator"]))
    mesh = Mesh(np.array(jax.devices("cpu")[:cell.chips]), ("cp",))
    key = family.make_key(spec, mesh)
    keys = key._asdict() if hasattr(key, "_asdict") else {"": key}
    layouts = {(g["hq"], g["hk"], g["d_qk"], g["d_v"])
               for g in family.ffa_calls(cfg) if g["layers"]}
    assert set(keys) == set(CELL_TILES[cell_name])
    for label, (hq, hk, w_default, want, w_moved) in CELL_TILES[
            cell_name].items():
        assert layouts == {(hq, hk, 128, 128)}
        rt = _mgr(keys[label]).runtime
        assert (rt._bq, rt._bk, rt._tile_source) == (256, 512, "default")
        assert rt._max_plan_work(256, 512) == w_default
        assert max(rt._merged_dims[2:4]) == w_default  # the plans built
        assert rt._group_tile(128, 128, 2, hq // hk, False) == want
        assert rt._group_tile(128, 128, 2, hq // hk, True) == (
            None, "default")  # the packed forward emits no max-logits
        if w_moved is not None:
            assert rt._max_plan_work(1024 * hk // hq, 512) == w_moved
            assert (w_moved <= ffa.PLAN_TABLE_MAX_WORK) == (
                want[1] == "shape_rule")


def test_one_key_at_two_groups_gets_each_its_own_tile(monkeypatch):
    """A key declares no heads: a runtime called at g = 4, at g = 8 and at
    g = 4 again REBUILDS its plans on each change (it keeps the last
    signature's, not one per signature), each call at its own tile and
    right, forward and gradients, against the dense reference at cp = 2."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from magiattention_tpu.api import (
        calc_attn, dispatch, magi_attn_flex_key, undispatch,
    )
    from magiattention_tpu.api.magi_attn_interface import _mgr
    from magiattention_tpu.common.enum import AttnMaskType
    from magiattention_tpu.common.mask import AttnMask
    from magiattention_tpu.common.ranges import AttnRanges
    from magiattention_tpu.kernels import registry
    from magiattention_tpu.testing.ref_attn import ref_attn

    for key in ("MAGI_ATTENTION_FFA_BLOCK_Q", "MAGI_ATTENTION_FFA_BLOCK_K",
                "MAGI_ATTENTION_FFA_AUTO_TILE"):
        monkeypatch.delenv(key, raising=False)
    s, d = 1024, 64
    docs = [[0, 600], [600, 1024]]
    mesh = Mesh(np.array(jax.devices("cpu")[:2]), axis_names=("cp",))
    key = magi_attn_flex_key(docs, docs, [1, 1], s, s, mesh=mesh,
                             chunk_size=256)
    rt = _mgr(key).runtime
    builds = []
    build = rt._build_plans
    monkeypatch.setattr(
        rt, "_build_plans", lambda bq, bk: (builds.append(bq), build(bq, bk)))
    mask = AttnMask.from_ranges(
        AttnRanges.from_ranges(docs), AttnRanges.from_ranges(docs),
        [AttnMaskType.CAUSAL] * 2, total_seqlen_q=s, total_seqlen_k=s,
    ).mask_array

    def fwd(q, k, v):
        out_d, _ = calc_attn(
            dispatch(q, key), dispatch(k, key, role="kv"),
            dispatch(v, key, role="kv"), key)
        return undispatch(out_d, key)

    rng = np.random.default_rng(0)
    for hq, tiles, source in (
            (4, "fwd256x512g4 dq256x512g4 dkv256x512g4", "default"),
            (8, "fwd128x512g8 dq128x512g8 dkv128x512g8", "shape_rule"),
            (8, "fwd128x512g8 dq128x512g8 dkv128x512g8", "shape_rule"),
            (4, "fwd256x512g4 dq256x512g4 dkv256x512g4", "default")):
        q, k, v, w = (
            jnp.asarray(rng.standard_normal((s, h, d)), jnp.float32)
            for h in (hq, 1, 1, hq))
        loss = lambda f: lambda q, k, v: jnp.sum(f(q, k, v) * w)  # noqa: E731
        got = jax.value_and_grad(loss(fwd), (0, 1, 2))(q, k, v)
        ref = jax.value_and_grad(loss(
            lambda q, k, v: ref_attn(
                q, k, v, mask, compute_dtype=jnp.float32)[0]), (0, 1, 2))(
            q, k, v)
        assert registry.last_choice("ffa_tiles") == tiles
        assert registry.last_source("ffa_tiles") == source
        assert rt._tile_source == source
        assert rt._plan_sig == (d, d, 4, hq, False)
        for name, a, b in zip(("loss", "dq", "dk", "dv"),
                              jax.tree.leaves(got), jax.tree.leaves(ref)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4,
                err_msg=f"hq {hq}: {name}")
    # built at the key (before this test listened), then once a change
    assert builds == [128, None]
