"""BENCHMARK.json names only what exists, every cell resolves to its files,
and the manifest stays inside the limits its contract sets."""

import json
import os
import re

import pytest

from cellbench import manifest

ROOT = manifest.ROOT
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# what a config may never list as reduced: a width
WIDTH = re.compile(
    r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim|"
    r"expansion|experts_per_tok")


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    for path in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path)), path
    assert not any(
        a.startswith("/") or ".." in a for a in MANIFEST["command"])
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)


def test_names_are_plain_and_unique():
    names = [
        x["name"] for key in ("configs", "workloads", "end_to_end",
                              "per_layer") for x in MANIFEST[key]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    whys = [x["why"] for key in ("configs", "workloads")
            for x in MANIFEST[key]]
    assert all(len(w) <= 200 for w in whys), [len(w) for w in whys]


@pytest.mark.parametrize("entry", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_file_holds_the_configuration_as_run(entry):
    path = os.path.join(ROOT, entry["file"])
    assert entry["file"].startswith(tuple(MANIFEST["paths"]))
    cfg = json.load(open(path))
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert entry["source"].startswith("https://")
    # every reduced key is listed with its published value and its reason,
    # names no width, and nothing else differs from what was published
    assert set(entry["reduced"]) == set(cfg["reduced"]) == set(cfg["published"])
    assert not any(WIDTH.search(k) for k in entry["reduced"])
    for k in entry["reduced"]:
        assert cfg[k] < cfg["published"][k]
    assert any(w["config"] == entry["name"] for w in MANIFEST["workloads"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = manifest.load_cell(ROOT, name)
    assert cell.chips in (1, 4)
    assert manifest.load_family(ROOT, cell.config["family"]) is not None
    assert callable(manifest.load_generator(ROOT, cell.traffic["generator"]))
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "tokens_per_s"}
    assert cell.per_layer
    for m in cell.per_layer:
        spec, read = manifest.load_metric(ROOT, m["name"])
        assert (spec["reader"] == "py") == (read is not None), m["name"]
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            m["unit"], m["layer"], m["moves"])


@pytest.mark.parametrize(
    "metric", MANIFEST["end_to_end"] + MANIFEST["per_layer"],
    ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert metric["source"] in SOURCES
    assert metric["better"] in ("higher", "lower")
    if "bound" in metric:  # end to end
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        e2e = {m["name"] for m in MANIFEST["end_to_end"]}
        assert metric["moves"] in e2e
        assert metric["layer"]
    for cell in metric.get("workloads", ()):
        assert cell in CELLS
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def test_unknown_names_raise():
    with pytest.raises(KeyError, match="no workload"):
        manifest.load_cell(ROOT, "no.such.cell")
    with pytest.raises(KeyError, match="no traffic generator"):
        manifest.load_generator(ROOT, "no_such_generator")
    with pytest.raises(KeyError, match="no step builder"):
        manifest.load_family(ROOT, "no_such_family")


def test_a_family_file_that_lacks_a_member_fails_at_load(tmp_path):
    """Naming what is missing, before anything is built or measured: not
    an ``AttributeError`` after the window."""
    src = os.path.join(ROOT, "cellbench", "family_llama.py")
    text = open(src).read()
    assert "def ffa_calls(" in text and "CHECKS = " in text
    text = text.replace("def ffa_calls(", "def ffa_kalls(").replace(
        "CHECKS = ", "CHEKS = ")
    os.makedirs(tmp_path / "cellbench")
    (tmp_path / "cellbench" / "family_partial.py").write_text(text)
    with pytest.raises(AttributeError, match=r"family_partial\.py lacks "
                       r"CHECKS, ffa_calls: .*FAMILY_INTERFACE"):
        manifest.load_family(str(tmp_path), "partial")
    whole = manifest.load_family(ROOT, "llama")
    assert all(hasattr(whole, name) for name in manifest.FAMILY_INTERFACE)


def test_the_readme_lists_the_whole_family_interface():
    text = open(os.path.join(ROOT, "cellbench", "README.md")).read()
    table = text[text.index("## The family interface"):]
    for name in manifest.FAMILY_INTERFACE:
        assert f"| `{name}" in table, name


# What belongs to one block's equations, and may be named only in its
# family file and its reference: parameter leaves, the llama block's width
# keys and projection count, the depth (a product of it and one layer's FFA
# calls is how a hybrid would read four times too high), a block's
# reference and its compared names.
BLOCK_WORDS = re.compile(
    r"\b(wq|wk|wv|wo|w_gate|w_up|w_down|attn_norm|mlp_norm|lm_head|"
    r"grad_wq0|grad_wk0|num_hidden_layers|intermediate_size|"
    r"num_attention_heads|num_key_value_heads|layer_matmul_params|"
    r"model_flops_per_step|loss_logits_grads|reference_[a-z0-9]+|"
    r"family_[a-z0-9]+)\b")
HARNESS = ["run.py", "flops.py", "metrics_read.py", "trace_reduce.py",
           "reference.py", "manifest.py", "kernel_times.py", "peaks.py"]


def _harness_files():
    metrics = os.path.join(ROOT, "cellbench", "metrics")
    return [os.path.join(ROOT, "cellbench", f) for f in HARNESS] + sorted(
        os.path.join(metrics, f) for f in os.listdir(metrics)
        if f.endswith(".py"))


@pytest.mark.parametrize(
    "path", _harness_files(), ids=lambda p: os.path.relpath(p, ROOT))
def test_the_harness_names_nothing_of_a_block(path):
    """``family_<family>`` may appear as the pattern the loader fills in
    and in prose that says where a block's things live, never as a module
    that is imported."""
    found = []
    for n, line in enumerate(open(path), 1):
        for m in BLOCK_WORDS.finditer(line):
            word = m.group(1)
            if word.startswith(("family_", "reference_")) and not re.search(
                    r"^\s*(from|import)\b.*\b" + word, line):
                continue
            found.append((n, word))
    assert not found, found
