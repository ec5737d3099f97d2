"""The ``mistral4`` family as the benchmark sees it: the cell rehearsed from
a root that holds data files alone, no knowledge of the latent block outside
the family's two files, its FLOP and byte counts at the published widths,
and the full-head (g = 1) FFA call of ``mistralsmall4.longdocs.cp1`` at its
real shapes: the plan's size, the tile the group rule leaves it, and the
compile for a described v5e."""

import json
import os
import re
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the real (not interpreted) kernel path and the described v5e chip: the
# per-cell file's fixtures, each module its own instance
from test_cells_lower_for_tpu import compiled_kernels, one_chip  # noqa: F401

from cellbench import family_llama, flops, manifest, peaks, run, traffic_gen
from cellbench.traffic_gen import MaskSpec

CELL = "mistralsmall4.longdocs.cp1"
FAMILY_FILES = ("family_mistral4.py", "reference_mistral4.py")
# the latent block's leaves and width keys: its family's and reference's
LATENT_WORDS = re.compile(
    r"\b(w_q_a|w_q_b|w_kv_a|w_kv_b|q_a_norm|kv_a_norm|q_lora_rank|"
    r"kv_lora_rank|qk_nope_head_dim|qk_rope_head_dim|v_head_dim|"
    r"rope_parameters|llama_4_scaling_beta|mscale_all_dim|"
    r"n_routed_experts|routed_scaling_factor)\b")


@pytest.fixture(scope="module")
def cell():
    c = manifest.load_cell(manifest.ROOT, CELL)
    family = manifest.load_family(manifest.ROOT, c.config["family"])
    cfg, tokens, window, _ = run.cell_sizes(c, family, 0)
    assert cfg == c.config and window is None  # the file's widths
    spec = traffic_gen.make_mask(
        c.traffic, tokens, window, 0,
        manifest.load_generator(manifest.ROOT, c.traffic["generator"]))
    return c, family, cfg, spec


def test_the_cell_rehearses_from_data_files_alone(clean_env, capsys, tmp_path):
    """A root with the manifest and the data directories and no code: the
    family's two files are found in this checkout by name, the traced
    rehearsal runs the counts' readers, and ``mla_assemble_ms_per_step``,
    with no device trace to read, is left out and does not raise."""
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(manifest.ROOT, "cellbench", sub),
                        tmp_path / "cellbench" / sub)
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    code = run.main(["--root", str(tmp_path), "--workload", CELL, "--seed",
                     "2147483659", "--trace", "1", "--rehearse-cpu", "1"])
    lines = capsys.readouterr().out.splitlines()
    assert code == 0, lines[-3:]
    report = json.loads(lines[-2].split("report: ", 1)[1])
    assert all(report["flags"].values()), report["flags"]
    assert list(report["checks"]) == [
        "loss", "logits", "route_choice", "route_scores", "attn_blocks",
        "expert_blocks", "head_logits", "grad_w_q_a", "grad_w_q_b",
        "grad_w_kv_a", "grad_w_kv_b", "grad_router", "grad_expert_w_up"]
    got = report["metrics"]
    assert {"step_ms_p50", "programs_compiled", "moe_routed_rows_per_step",
            "moe_expert_load_max_over_mean"} <= set(got)
    assert "mla_assemble_ms_per_step" not in got and "ffa_roofline" not in got
    ran = report["what_ran"]
    assert ran["moe_route"] == "softmax_topk"
    assert ran["attention_form"].startswith("expanded: 512 key-value")
    assert ran["ffa_tiles_source"] == "default"
    routing, tier = ran["routing"], ran["tight_tier"]
    assert routing["rows_dropped"] == 0
    # the tight tier's share is in the line: every token block fitted
    assert tier["blocks_fitted"] == tier["blocks"] == (
        routing["batches"] * 2 * 2)  # a batch: two layers of two blocks
    assert 0 < tier["block_rows_max"] <= 96  # rows96of1024
    names = [m["name"] for m in manifest.load_cell(
        str(tmp_path), CELL).per_layer]
    assert names[-1] == "mla_assemble_ms_per_step"
    for shared in ("ffa_roofline", "moe_grouped_roofline",
                   "moe_rows_ms_per_step", "attn_proj_ms_per_step"):
        assert shared in names
    assert not {"ffa_window_roofline", "ssd_roofline"} & set(names)


def _other_files():
    base = os.path.join(manifest.ROOT, "cellbench")
    return sorted(
        os.path.join(d, f) for d in (base, os.path.join(base, "metrics"))
        for f in os.listdir(d)
        if f.endswith(".py") and f not in FAMILY_FILES)


@pytest.mark.parametrize(
    "path", _other_files(), ids=lambda p: os.path.relpath(p, manifest.ROOT))
def test_no_other_file_knows_the_latent_block(path):
    """Harness, metric readers and the other families' files alike (the
    hybrid family's own ``n_routed_experts`` and ``routed_scaling_factor``
    are its configuration's keys too)."""
    allowed = {"n_routed_experts", "routed_scaling_factor"} if (
        "nemotron_h" in path) else set()
    found = [(n, m.group(1)) for n, line in enumerate(open(path), 1)
             for m in LATENT_WORDS.finditer(line)
             if m.group(1) not in allowed]
    assert not found, found


def test_counts_at_the_published_widths(cell):
    _, family, cfg, spec = cell
    per_layer = family.layer_matmul_params(cfg)
    # q chain 4096 x 1024 + 1024 x 4096, kv chain 4096 x 320 + 256 x (32 x
    # 192), output 4096 x 4096: 28.05 M (ISSUE 37)
    assert per_layer["attention"] == 28_049_408
    # router 4096 x 128; the shared expert and 4 x 8 / 128 of a routed one,
    # 3 x 4096 x 2048 each
    assert per_layer["experts"] == 4096 * 128 + 1.25 * 25_165_824
    area = flops.band_area(spec)
    assert (spec.tokens, area) == (32768, 157_164_135)
    want = 6 * 32768 * (4 * (28_049_408 + 31_981_568) + 4096 * 16384) + int(
        4 * 3.5 * 4 * area * 128 * 32)
    assert family.required_flops_per_step(cfg, spec) == want
    [group] = family.ffa_calls(cfg)
    assert (group["hq"], group["hk"], group["d_qk"], group["d_v"],
            group["layers"], group["passes"]) == (
        32, 32, 128, 128, 4, ("fwd", "fwd", "bwd"))
    [grouped] = family.grouped_calls(cfg)
    assert (grouped["layers"], grouped["held"], grouped["token_block"]) == (
        4, 8, 8192)
    assert [(p["k"], p["n"]) for p in grouped["products"]] == [
        (4096, 4096), (2048, 4096)]


def test_the_full_head_calls_roofline_is_bound_by_compute(cell):
    """4.5 forwards a layer of 2.57 TFLOP: at g = 1 every head reads keys
    and values of its own, eight times a g = 8 layer's bytes, and the call
    is still bound by the matrix unit."""
    c, family, cfg, spec = cell
    pk = peaks.peaks_for("TPU v5 lite")
    rows = np.arange(spec.tokens)
    least = flops.ffa_least_seconds(family.ffa_calls(cfg), spec, rows, pk)
    fwd = flops.attn_fwd_flops(flops.band_area(spec), 32, 128, 128)
    assert fwd == 2 * 157_164_135 * 32 * 256
    assert least["flops_s"] == pytest.approx(4 * 4.5 * fwd / pk["bf16_flops"])
    fwd_bytes = 2 * 32768 * 32 * 256 * 2 + 32768 * 32 * 4
    assert least["bytes_s"] == pytest.approx(
        4 * 4 * fwd_bytes / pk["hbm_bytes_per_s"])
    assert least["least_s"] == least["flops_s"] > 10 * least["bytes_s"]
    gqa = [{**family.ffa_calls(cfg)[0], "hk": 4}]
    assert flops.ffa_least_seconds(gqa, spec, rows, pk)["bytes_s"] < (
        0.6 * least["bytes_s"])


def _slices(spec):
    # the program's public mask compilers, which are no family's
    qr, kr, types = family_llama.mask_slices(spec)
    return (np.asarray(qr.to_naive_ranges(), np.int32),
            np.asarray(kr.to_naive_ranges(), np.int32),
            np.asarray([t.to_int_type() for t in types], np.int32))


def test_the_plan_is_trinitys_full_layers_and_g1_keeps_the_default_tile(cell):
    """The same mask as ``trinitymini.longdocs32k.cp1``'s full layer: W =
    1342 at 256 x 512, under the table's capacity; at g = 1 the group rule
    has nothing to say and the source is ``default``."""
    from magiattention_tpu.kernels import ffa, ffa_plan, tile_policy
    from magiattention_tpu.kernels.mask_utils import types_to_bands

    spec = cell[3]
    qr, kr, tm = _slices(spec)
    lo, hi = types_to_bands(qr, kr, tm)
    bq, bk = ffa.default_blocks(spec.tokens, spec.tokens)
    assert (bq, bk) == (256, 512)
    plan = ffa_plan.build_ffa_plan(
        qr, kr, lo, hi, spec.tokens, spec.tokens, bq, bk)
    assert max(plan.num_work, plan.num_work_t) == 1342 < (
        ffa.PLAN_TABLE_MAX_WORK)

    def never(*_):
        raise AssertionError("g = 1 asked for a plan's size")

    assert tile_policy.group_block_q(1, 128, 128, 2, bq, bk, never) == (
        256, "default")


def _full_heads(cell):
    from magiattention_tpu.kernels import ffa

    spec = cell[3]
    qr, kr, tm = _slices(spec)

    def loss(q, k, v):
        out, _ = ffa.ffa_attn(q, k, v, qr, kr, tm)
        return out.astype(jnp.float32).sum()

    shapes = [(spec.tokens, 32, 128)] * 3
    return jax.value_and_grad(loss, argnums=(0, 1, 2)), shapes


def test_the_full_head_call_lowers_with_plain_bodies(compiled_kernels, cell):
    from cellbench import kernel_times
    from magiattention_tpu.kernels import registry

    fn, shapes = _full_heads(cell)
    traced = jax.jit(fn).trace(
        *[jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes])
    kernels = cell[1].pallas_kernels(traced.jaxpr)
    assert not any(kernels.values()), kernels  # none interpreted
    assert not [body for body in kernels if body.endswith("_gqa")], kernels
    kinds = {kernel_times.kind_of(kernel_times.PREFIX + b) for b in kernels}
    assert {"fwd", "delta", "bwd_fused"} <= kinds, kernels
    assert registry.last_choice("ffa_tiles") == (
        "fwd256x512 dq256x512 dkv256x512")
    assert registry.last_source("ffa_tiles") == "default"


def test_the_full_head_call_compiles_for_v5e(compiled_kernels, one_chip, cell):
    fn, shapes = _full_heads(cell)
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 3


def test_a_short_check_holds_no_position_the_scale_reads(cell):
    """``correct``'s blind spot, pinned: the check's 8192 tokens are four
    documents under 8192 tokens each, so the position scale is 1 there;
    the timed step's documents reach past 8192 twice."""
    c, _, cfg, spec = cell
    limit = cfg["rope_parameters"]["original_max_position_embeddings"]
    check = traffic_gen.make_mask(
        c.traffic, c.traffic["check_tokens_per_chip"], None, 0,
        manifest.load_generator(manifest.ROOT, c.traffic["generator"]))
    assert isinstance(check, MaskSpec) and check.doc_lengths().max() < limit
    assert int((spec.doc_lengths() > limit).sum()) == 2
