"""Find a cell's files by name. No file here knows a cell, a configuration,
a traffic mix or a metric: ``BENCHMARK.json`` names them and each lives in a
file of its own under ``cellbench/`` (see README.md).
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(root: str, stem: str):
    """``cellbench/<stem>.py`` as a module, from ``root`` or else from this
    checkout; None if neither has the file. Loaded by path, so that a file
    a later PR adds is found with no import or registry to edit."""
    for base in (root, ROOT):
        path = os.path.join(base, "cellbench", stem + ".py")
        if os.path.isfile(path):
            break
    else:
        return None
    spec = importlib.util.spec_from_file_location(
        "cellbench_file_" + stem.replace("/", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its configuration
    and traffic files read; raises ``KeyError`` naming what is missing."""
    manifest = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(
            f"no workload {name!r} in BENCHMARK.json (known: {sorted(cells)})")
    entry = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    if entry["config"] not in configs:
        raise KeyError(
            f"workload {name!r} names config {entry['config']!r}, which "
            f"BENCHMARK.json does not list")
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=_read_json(os.path.join(root, configs[entry["config"]]["file"])),
        traffic=_read_json(os.path.join(
            root, "cellbench", "traffic", entry["traffic"] + ".json")),
        end_to_end=tuple(
            m for m in manifest["end_to_end"] if _applies(m, name)),
        per_layer=tuple(
            m for m in manifest["per_layer"] if _applies(m, name)),
    )


def load_generator(root: str, name: str):
    """The traffic generator ``name``: a function of ``traffic_gen.py``, or
    ``generate`` of ``traffic_gen_<name>.py``."""
    from . import traffic_gen

    if hasattr(traffic_gen, name):
        return getattr(traffic_gen, name)
    mod = load_module(root, "traffic_gen_" + name)
    if mod is None:
        raise KeyError(
            f"no traffic generator {name!r}: neither a function of "
            f"cellbench/traffic_gen.py nor a file "
            f"cellbench/traffic_gen_{name}.py")
    return mod.generate


# Every member the harness and the metric readers take from a family file
# (README.md, "The family interface", says what each is).
FAMILY_INTERFACE = (
    "TOY", "model_config", "init_params", "make_key", "timed_plan",
    "plan_facts", "train_step", "check_program", "reference", "CHECKS",
    "required_flops_per_step", "ffa_calls", "pallas_kernels", "what_ran",
)


def load_family(root: str, family: str):
    """The step builder of a model family, ``family_<family>.py``; a file
    that lacks a member of ``FAMILY_INTERFACE`` fails here, naming it, and
    not after the measured window."""
    mod = load_module(root, "family_" + family)
    if mod is None:
        raise KeyError(f"no step builder cellbench/family_{family}.py")
    missing = [name for name in FAMILY_INTERFACE if not hasattr(mod, name)]
    if missing:
        raise AttributeError(
            f"cellbench/family_{family}.py lacks {', '.join(missing)}: a "
            f"family file has every member of manifest.FAMILY_INTERFACE "
            f"(cellbench/README.md)")
    return mod


def load_metric(root: str, name: str):
    """A per-layer metric's reader: ``(spec, read)`` where ``spec`` is
    ``metrics/<name>.json`` and ``read`` is ``metrics/<name>.py``'s ``read``
    (``None`` when the data file says it all)."""
    spec = _read_json(
        os.path.join(root, "cellbench", "metrics", name + ".json"))
    mod = load_module(root, "metrics/" + name)
    return spec, (mod.read if mod is not None else None)
