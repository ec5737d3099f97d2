"""Cells the benchmark does not have, added to a copy of it as files alone.

Two of them, for the tests that prove the harness and its per-cell tests
take a cell they were not written for:

* ``mixer.packed.cp1``: the second family of ``data/second_family/``
  (attention in one layer of four, no ``mask_slices``, toy-sized widths of
  its own) under the real ``packed`` mix;
* ``probe.packed32k.cp1``: the ``llama`` family at the widths of
  Olmo-Hybrid-7B's full-attention layers, 30 q = 30 kv heads (g = 1, where
  the program's cost model picks the fused backward), under ``packed``'s
  law at 32768 tokens.

Nothing here is a cell of the benchmark: the files are written into the
directory the caller names and nowhere else.
"""

from __future__ import annotations

import json
import os
import shutil

from cellbench import manifest

SECOND_FAMILY = os.path.join(os.path.dirname(__file__), "data", "second_family")

MIXER_CELL = "mixer.packed.cp1"
MIXER_ENTRIES = {
    "configs": [{
        "name": "mixer-toy", "source": "https://example.org/mixer-toy",
        "file": "cellbench/configs/mixer-toy.json", "reduced": [],
        "why": "test"}],
    "workloads": [{
        "name": MIXER_CELL, "config": "mixer-toy", "traffic": "packed",
        "chips": 1, "why": "test"}],
    "per_layer": [{
        "name": "gate_ms_per_step", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "gate", "moves": "tokens_per_s",
        "workloads": [MIXER_CELL]}],
}

PROBE_CELL = "probe.packed32k.cp1"
PROBE_SOURCE = "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
PROBE_CONFIG = {
    "name": "probe-mha30", "family": "llama", "source": PROBE_SOURCE,
    "hidden_size": 3840, "num_hidden_layers": 4,
    "num_attention_heads": 30, "num_key_value_heads": 30, "head_dim": 128,
    "intermediate_size": 11008, "vocab_size": 12544, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-06, "sliding_window": None,
    "published": {"num_hidden_layers": 32, "vocab_size": 100352},
    "reduced": {
        "num_hidden_layers": "32 -> 4, one period of the hybrid's layers",
        "vocab_size": "100352 -> 12544, one chip's share of eight"},
    "assumed": [
        "every layer is the source's full-attention block (the llama "
        "family has no other): a probe of the g = 1 kernels at its widths, "
        "not the hybrid",
        "head_dim 128 = hidden_size / num_attention_heads",
        "rope_theta 10000.0: the source gives none"],
}
PROBE_TRAFFIC = {
    "generator": "packed_lognormal", "tokens": 32768,
    "params": {"median": 1536, "sigma": 1.0, "min": 128, "max": 16384,
               "order_seed": 0},
    "window": None, "batches": 4, "check_tokens_per_chip": 4096,
}
PROBE_ENTRIES = {
    "configs": [{
        "name": "probe-mha30", "source": PROBE_SOURCE,
        "file": "cellbench/configs/probe-mha30.json",
        "reduced": ["num_hidden_layers", "vocab_size"], "why": "test"}],
    "workloads": [{
        "name": PROBE_CELL, "config": "probe-mha30", "traffic": "packed_32k",
        "chips": 1, "why": "test"}],
}


def _extend(entries: dict, more: dict) -> None:
    for section, added in more.items():
        entries[section].extend(added)


def add_second_family(root, entries: dict) -> None:
    """The mixer family's files into ``root`` and its configuration, cell
    and metric into the manifest ``entries``."""
    shutil.copytree(SECOND_FAMILY, root, dirs_exist_ok=True)
    _extend(entries, MIXER_ENTRIES)


def add_probe(root, entries: dict) -> None:
    """The probe's configuration and traffic files into ``root`` and its
    configuration and cell into the manifest ``entries``."""
    data = os.path.join(root, "cellbench")
    with open(os.path.join(data, "configs", "probe-mha30.json"), "w") as f:
        json.dump(PROBE_CONFIG, f, indent=1)
    with open(os.path.join(data, "traffic", "packed_32k.json"), "w") as f:
        json.dump(PROBE_TRAFFIC, f, indent=1)
    _extend(entries, PROBE_ENTRIES)


def build_root(root) -> list[str]:
    """A copy of the benchmark (``BENCHMARK.json``, ``cellbench/``,
    ``tests/test_cellbench/``) in the empty directory ``root`` with both
    cells added; returns their names."""
    ignore = shutil.ignore_patterns("__pycache__", "*.pyc")
    for sub in ("cellbench", os.path.join("tests", "test_cellbench")):
        shutil.copytree(os.path.join(manifest.ROOT, sub),
                        os.path.join(root, sub), ignore=ignore)
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        entries = json.load(f)
    add_second_family(root, entries)
    add_probe(root, entries)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(entries, f, indent=1)
    return [MIXER_CELL, PROBE_CELL]

