"""The FLOP and byte arithmetic against brute force, and the llama
family's counts against the published widths and against the values the
four cells have always been read with."""

import json
import os

import numpy as np
import pytest

from cellbench import flops, manifest, peaks, run, traffic_gen
from cellbench.traffic_gen import MaskSpec

LLAMA = manifest.load_family(manifest.ROOT, "llama")

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
    assert LLAMA.layer_matmul_params(NEMO) == 272_629_760
    spec = MaskSpec(1024, (0, 1024))
    area = 1024 * 1025 // 2
    assert flops.attn_fwd_flops(area, 32, 128, 128) == 4 * area * 128 * 32
    # latent heads: QK^T over 192, PV over 128
    assert flops.attn_fwd_flops(area, 32, 192, 128) == 2 * area * 32 * 320
    assert flops.matmul_flops(7, 11) == 6 * 7 * 11
    want = 6 * 1024 * (3 * 272_629_760 + 5120 * 16384) + int(
        3 * 3.5 * 4 * area * 128 * 32)
    assert LLAMA.required_flops_per_step(NEMO, spec) == want


def test_ffa_least_time_takes_the_larger_bound_per_call():
    pk = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    spec = MaskSpec(4096, (0, 4096))
    rows = np.arange(4096)
    calls = LLAMA.ffa_calls(NEMO)
    dense = flops.ffa_least_seconds(calls, spec, rows, pk)
    area = 4096 * 4097 // 2
    assert dense["flops_s"] == pytest.approx(
        3 * 4.5 * 4 * area * 128 * 32 / 197e12)
    # a window of one key: no FLOPs to speak of, so the bytes bind
    thin = flops.ffa_least_seconds(
        calls, MaskSpec(4096, (0, 4096), 1), rows, pk)
    assert thin["bytes_s"] > thin["flops_s"]
    assert thin["least_s"] == pytest.approx(thin["bytes_s"])
    assert dense["least_s"] >= max(dense["flops_s"], dense["bytes_s"]) * 0.999
    # half the rows of a causal mask: the later half holds 3/4 of the area
    late = flops.ffa_least_seconds(calls, spec, np.arange(2048, 4096), pk)
    assert late["flops_s"] / dense["flops_s"] == pytest.approx(0.75, abs=1e-3)


def test_ffa_least_time_sums_over_the_layers_that_call():
    """A family with FFA in one layer of four, beside layers of another
    head shape: the groups add, and a layer that makes no call adds
    nothing (the count is never ``num_hidden_layers`` times one layer)."""
    pk = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    spec, rows = MaskSpec(4096, (0, 4096)), np.arange(4096)
    one = {"layers": 1, "passes": ("fwd", "fwd", "bwd"),
           "hq": 32, "hk": 8, "d_qk": 128, "d_v": 128}
    latent = {"layers": 2, "passes": ("fwd", "bwd"),
              "hq": 16, "hk": 16, "d_qk": 192, "d_v": 128}
    a = flops.ffa_least_seconds([one], spec, rows, pk)
    b = flops.ffa_least_seconds([latent], spec, rows, pk)
    both = flops.ffa_least_seconds([one, latent], spec, rows, pk)
    for k in ("least_s", "flops_s", "bytes_s"):
        assert both[k] == pytest.approx(a[k] + b[k])
    assert a["least_s"] == pytest.approx(flops.ffa_least_seconds(
        LLAMA.ffa_calls({**NEMO, "num_hidden_layers": 4}), spec, rows,
        pk)["least_s"] / 4)
    area = 4096 * 4097 // 2
    # forward QK^T 192 + PV 128; backward three matmuls over 192, two over 128
    assert b["flops_s"] == pytest.approx(
        2 * 2 * area * 16 * ((192 + 128) + (3 * 192 + 2 * 128)) / 197e12)
    # bytes of one latent forward: q and k at 192, o and v at 128, lse
    fwd_bytes = 4096 * 16 * (192 + 128) * 2 * 2 + 4096 * 16 * 4
    assert b["bytes_s"] == pytest.approx(2 * 3 * fwd_bytes / 819e9)
    assert flops.ffa_least_seconds([], spec, rows, pk) == {
        "least_s": 0.0, "flops_s": 0.0, "bytes_s": 0.0}


# model_flops_per_step and ffa_least_seconds of the parent commit (PR 25's
# tree, where flops.py itself counted the llama block), on the four cells'
# traffic files: required FLOPs of a step, and the least seconds of the
# whole sequence's rows and of its last quarter's, as float.hex().
PINNED = {
    "nemo12b.longdoc.cp1": (
        180734572625920,
        ("0x1.013371cda55a2p-2", "0x1.013371cda55a2p-2",
         "0x1.0e2ddc12cbd08p-7"),
        ("0x1.c21703999a2ddp-4", "0x1.c21703999a2ddp-4",
         "0x1.af479d4f4bb43p-9")),
    "nemo12b.packed.cp1": (
        151061161451520,
        ("0x1.d71faf693d32ap-5", "0x1.d71faf693d32ap-5",
         "0x1.0e2ddc12cbd08p-7"),
        ("0x1.0e46df1528d82p-5", "0x1.0e46df1528d82p-5",
         "0x1.2bc1968ce74b5p-9")),
    "mistral7b.swa32k.cp1": (
        203607689396224,
        ("0x1.81c8bf16fe8dcp-3", "0x1.81c8bf16fe8dcp-3",
         "0x1.b049601e12e74p-7"),
        ("0x1.9b7f14e64e908p-5", "0x1.9b7f14e64e908p-5",
         "0x1.db3c7de273ffbp-9")),
    "nemo12b.longdoc.cp4": (
        438434959196160,
        ("0x1.01316f6ecb3a3p+0", "0x1.01316f6ecb3a3p+0",
         "0x1.0e2ddc12cbd08p-6"),
        ("0x1.c215013ac00dfp-2", "0x1.c215013ac00dfp-2",
         "0x1.af479d4f4bb43p-8")),
}


def test_the_pins_are_of_cells_the_benchmark_has():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    assert set(PINNED) <= set(cells) and len(PINNED) == 4


@pytest.mark.parametrize("name", PINNED)
def test_the_llama_counts_read_the_cells_as_they_always_have(name):
    """To the last bit: moving the counts behind the family file moved no
    ``model_flops_utilization`` and no ``ffa_roofline``."""
    cell = manifest.load_cell(manifest.ROOT, name)
    family = manifest.load_family(manifest.ROOT, cell.config["family"])
    cfg, tokens, window, _ = run.cell_sizes(cell, family, 0)
    spec = traffic_gen.make_mask(
        cell.traffic, tokens, window, 0,
        manifest.load_generator(manifest.ROOT, cell.traffic["generator"]))
    need, whole, last = PINNED[name]
    assert family.required_flops_per_step(cfg, spec) == need
    pk, calls = peaks.peaks_for("TPU v5 lite"), family.ffa_calls(cfg)
    quarter = spec.tokens // 4
    for rows, want in ((np.arange(spec.tokens), whole),
                       (np.arange(3 * quarter, spec.tokens), last)):
        got = flops.ffa_least_seconds(calls, spec, rows, pk)
        assert tuple(got[k].hex() for k in (
            "least_s", "flops_s", "bytes_s")) == want
