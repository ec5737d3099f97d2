"""The Solar Open 2 cell as data: the configuration's widths are the
published ones (the catalog's), its cuts are the three ``reduced`` lists,
its parameters count what ``PERF.md`` section 4 reckons, and the new
per-layer metrics load and read nothing where there is nothing to read."""

import json
import os

import jax
import pytest

from cellbench import manifest, metrics_read, reference_solar_open2
from magiattention_tpu.models import hybrid

CELL = "solaropen2.packed.cp1"
MANIFEST = json.load(open(os.path.join(manifest.ROOT, "BENCHMARK.json")))
NEW_METRICS = ("kda_ms_per_step", "kda_bwd_ms_per_step", "kda_roofline",
               "kda_glue_ms_per_step")
# the catalog's config of upstage/Solar-Open2-250B: every number at the top
# level, the nested groups whole
PUBLISHED = {
    "partial_rotary_factor": 1, "hidden_size": 4096,
    "num_hidden_layers": 48, "num_attention_heads": 64, "head_dim": 128,
    "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "gqa_interval": 3, "n_routed_experts": 320, "n_shared_experts": 1,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "gqa_layers": list(range(0, 48, 4)),
    "use_rope": False, "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "tie_word_embeddings": False,
}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(manifest.ROOT, CELL)


def test_the_configuration_keeps_every_published_width(cell):
    cfg = (entry := next(c for c in MANIFEST["configs"]
                         if c["name"] == "solar-open2-250b"))
    assert entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    data = cell.config
    changed = {k for k, v in PUBLISHED.items() if data[k] != v}
    assert changed == set(cfg["reduced"]) == set(data["reduced"])
    assert {k: data["published"][k] for k in changed} == {
        k: PUBLISHED[k] for k in changed}
    assert (data["num_hidden_layers"], data["n_routed_experts"],
            data["vocab_size"], data["router_experts"]) == (4, 8, 24576, 320)
    assert data["deployment"] and data["assumed"] and data["precision"]


def test_the_program_holds_the_files_experts_and_scale(cell):
    """The family reads the experts held from the deployment's expert
    parallelism and runs the program's default routed scale: both are the
    file's own numbers."""
    family = manifest.load_family(manifest.ROOT, cell.config["family"])
    mcfg = family.model_config(cell.config)
    assert mcfg.experts_held == cell.config["n_routed_experts"] == 320 // 40
    assert mcfg.routed_scale == cell.config["routed_scaling_factor"] == (
        reference_solar_open2._expert_cfg(cell.config)["route_scale"])


def test_the_pattern_is_one_period(cell):
    assert reference_solar_open2.pattern(cell.config) == "*EKEKEKE"


def test_the_parameters_are_what_the_arithmetic_says(cell):
    """1295.1 M (PERF.md section 4): four layers with 8 experts held, the
    untied embedding and head of the vocabulary slice."""
    family = manifest.load_family(manifest.ROOT, cell.config["family"])
    mcfg = family.model_config(cell.config)
    shapes = jax.eval_shape(
        lambda: hybrid.init_params(mcfg, jax.random.PRNGKey(0)))
    count = sum(x.size for x in jax.tree.leaves(shapes))
    assert count == pytest.approx(1295.1e6, rel=1e-3)
    kda_block = sum(x.size for x in jax.tree.leaves(shapes["layers"][2]))
    assert kda_block == pytest.approx(137.74e6, rel=1e-3)


def test_the_cell_is_one_chip_of_packed_traffic(cell):
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (entry["chips"], entry["traffic"], cell.traffic["tokens"]) == (
        1, "packed", 16384)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_the_new_metrics_load_and_read_nothing_without_a_trace(cell, name):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "kda", "tokens_per_s", "device_trace")
    spec, read = manifest.load_metric(manifest.ROOT, name)
    assert callable(read) and spec["unit"] == entry["unit"]
    ctx = metrics_read.Context(
        cell=cell, family=None, config=cell.config, spec=None, peaks={},
        facts={}, trace=None)
    assert read(ctx) is None


def test_the_cell_is_in_the_shared_metrics_it_reports(cell):
    names = {m["name"] for m in cell.per_layer}
    assert {"ffa_roofline", "moe_grouped_ms_per_step",
            "moe_routed_rows_per_step", "moe_expert_load_max_over_mean",
            "moe_grouped_roofline", "moe_route_ms_per_step",
            "moe_rows_ms_per_step", *NEW_METRICS} <= names
    assert not {"ssd_roofline", "mla_assemble_ms_per_step",
                "ffa_window_roofline", "group_comm_ms_per_step"} & names
