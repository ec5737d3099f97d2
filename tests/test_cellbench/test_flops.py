"""The FLOP and byte arithmetic against brute force."""

import numpy as np
import pytest

from cellbench import flops
from cellbench.traffic_gen import MaskSpec

MASKS = {
    "causal": MaskSpec(257, (0, 257)),
    "varlen": MaskSpec(300, (0, 1, 90, 91, 200, 300)),
    "window": MaskSpec(300, (0, 300), 64),
    "window_wider_than_doc": MaskSpec(100, (0, 100), 4096),
    "varlen_window": MaskSpec(400, (0, 30, 250, 400), 50),
    "window_of_one": MaskSpec(64, (0, 64), 1),
}


def _brute(spec):
    idx = np.arange(spec.tokens)
    doc = np.searchsorted(np.asarray(spec.cu_seqlens[1:]), idx, side="right")
    back = idx[:, None] - idx[None, :]
    mask = (doc[:, None] == doc[None, :]) & (back >= 0)
    if spec.window is not None:
        mask &= back < spec.window
    return mask


@pytest.mark.parametrize("name", MASKS)
def test_band_area_equals_a_count_over_the_boolean_mask(name):
    spec = MASKS[name]
    mask = _brute(spec)
    assert np.array_equal(flops.mask_array(spec), mask)
    assert flops.band_area(spec) == int(mask.sum())
    assert np.array_equal(flops.rows_area(spec), mask.sum(axis=1))


@pytest.mark.parametrize("name", MASKS)
def test_keys_needed_equals_the_columns_the_rows_touch(name):
    spec = MASKS[name]
    mask = _brute(spec)
    rng = np.random.default_rng(0)
    for rows in (np.arange(spec.tokens), np.arange(10, 40),
                 np.sort(rng.choice(spec.tokens, 25, replace=False))):
        assert flops.keys_needed(spec, rows) == int(
            mask[rows].any(axis=0).sum())


NEMO = {"hidden_size": 5120, "num_attention_heads": 32,
        "num_key_value_heads": 8, "head_dim": 128,
        "intermediate_size": 14336, "vocab_size": 16384,
        "num_hidden_layers": 3}


def test_model_flops_are_the_published_widths_arithmetic():
    # 272.6 M weights a layer (ISSUE 22): wq 5120x4096, wk/wv 5120x1024,
    # wo 4096x5120, three 5120x14336 MLP matrices
    assert flops.layer_matmul_params(NEMO) == 272_629_760
    spec = MaskSpec(1024, (0, 1024))
    area = 1024 * 1025 // 2
    assert flops.attn_fwd_flops(NEMO, area) == 4 * area * 128 * 32
    want = 6 * 1024 * (3 * 272_629_760 + 5120 * 16384) + int(
        3 * 3.5 * 4 * area * 128 * 32)
    assert flops.model_flops_per_step(NEMO, spec) == want


def test_ffa_least_time_takes_the_larger_bound_per_call():
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    spec = MaskSpec(4096, (0, 4096))
    rows = np.arange(4096)
    dense = flops.ffa_least_seconds(NEMO, spec, rows, peaks)
    area = 4096 * 4097 // 2
    assert dense["flops_s"] == pytest.approx(
        3 * 4.5 * 4 * area * 128 * 32 / 197e12)
    # a window of one key: no FLOPs to speak of, so the bytes bind
    thin = flops.ffa_least_seconds(
        NEMO, MaskSpec(4096, (0, 4096), 1), rows, peaks)
    assert thin["bytes_s"] > thin["flops_s"]
    assert thin["least_s"] == pytest.approx(thin["bytes_s"])
    assert dense["least_s"] >= max(dense["flops_s"], dense["bytes_s"]) * 0.999
    # half the rows of a causal mask: the later half holds 3/4 of the area
    late = flops.ffa_least_seconds(NEMO, spec, np.arange(2048, 4096), peaks)
    assert late["flops_s"] / dense["flops_s"] == pytest.approx(0.75, abs=1e-3)
