"""Unified backend registry (kernels/registry.py): a choice is pin > the
call site's rule over shapes, memoized per key; telemetry is told of every
choice and asked nothing — a store on disk changes none; a removed env key
is refused by name (docs/observability.md, docs/env_variables.md)."""

import ast
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import magiattention_tpu
from magiattention_tpu import telemetry
from magiattention_tpu.env import backend as env_backend
from magiattention_tpu.env import general as env_general
from magiattention_tpu.kernels import registry as kreg
from magiattention_tpu.telemetry import store as tstore
from magiattention_tpu.utils.canonical import canonical_key

PACKAGE = os.path.dirname(magiattention_tpu.__file__)


@pytest.fixture(autouse=True)
def _fresh_observatory():
    telemetry.reset()
    tstore.reset()
    kreg.reset_registry()
    yield
    telemetry.reset()
    tstore.reset()
    kreg.reset_registry()


# -- precedence -------------------------------------------------------------


def test_pin_beats_heuristic():
    key = (7, 128, 256)
    pinned = kreg.resolve("ffa_bwd", key, lambda: "split", pin="fused")
    assert (pinned.name, pinned.source) == ("fused", "pin")
    # a pin is not memoized: the same key unpinned runs the rule
    fresh = kreg.resolve("ffa_bwd", key, lambda: "split")
    assert (fresh.name, fresh.source) == ("split", "heuristic")
    assert kreg.stats()["heuristic_calls"] == 1
    assert kreg.stats()["pins"] == 1


def test_heuristic_memoized_per_key():
    calls = []

    def heuristic():
        calls.append(1)
        return "fused"

    for _ in range(3):
        assert kreg.resolve("ffa_bwd", (1, 2, 3), heuristic).name == "fused"
    assert len(calls) == 1
    assert kreg.stats()["memo_hits"] == 2
    assert kreg.resolve("ffa_bwd", (4, 5, 6), heuristic).name == "fused"
    assert len(calls) == 2


def test_heuristic_only_when_telemetry_off():
    """Telemetry off => no store, pure rule resolution."""
    choice = kreg.resolve("calc_attn", ("k",), lambda: "ffa")
    assert (choice.name, choice.source) == ("ffa", "heuristic")
    assert tstore.get_store() is None


def test_dict_keys_resolve_and_memoize():
    """calc_attn's key is a dict — unhashable, canonicalized for the memo."""
    key = {"mask_sig": "mA", "mesh_sig": "cp4", "env_sig": "eA"}
    calls = []
    kreg.resolve("calc_attn", key, lambda: calls.append(1) or "ffa")
    # key order must not matter (canonical sorted-JSON memo key)
    reordered = {"env_sig": "eA", "mask_sig": "mA", "mesh_sig": "cp4"}
    kreg.resolve("calc_attn", reordered, lambda: calls.append(1) or "ffa")
    assert len(calls) == 1


def test_calc_attn_backend_pin(monkeypatch):
    monkeypatch.setenv("MAGI_ATTENTION_KERNEL_BACKEND", "sdpa")
    assert kreg.calc_attn_backend({"mask_sig": "x"}) == "sdpa"
    monkeypatch.delenv("MAGI_ATTENTION_KERNEL_BACKEND")
    assert kreg.calc_attn_backend({"mask_sig": "x"}) == "ffa"


# -- a store on disk changes no choice ----------------------------------------

# the ffa_bwd keys the four cells resolve — (w_dq, bq, bk, wt, bq, bk, d, dv,
# itemsize, group), read off registry.resolve while tracing calc_attn's
# gradient over each cell's own slices at its real sizes — and longdoc's
# at g = 1
BWD_KEYS = {
    "nemo12b.longdoc.cp1": (1056, 256, 512, 1056, 256, 512, 128, 128, 2, 4),
    "nemo12b.packed.cp1": (315, 256, 512, 315, 256, 512, 128, 128, 2, 4),
    "mistral7b.swa32k.cp1": (1096, 256, 512, 1096, 256, 512, 128, 128, 2, 4),
    "nemo12b.longdoc.cp4": (1040, 256, 512, 1043, 256, 512, 128, 128, 2, 4),
    "longdoc.g1": (1056, 256, 512, 1056, 256, 512, 128, 128, 2, 1),
}


def _bwd_mode(key):
    from magiattention_tpu.kernels.ffa import FFAParams, resolved_bwd_mode

    w_dq, bq, bk, wt, _, _, d, dv, itemsize, group = key
    params = FFAParams(
        num_work=w_dq, num_work_t=wt, num_q_tiles=64, num_k_tiles=32,
        block_q=bq, block_k=bk, softmax_scale=1.0, softcap=0.0,
        group=group, interpret=True,
        # the cells' k-major lists leave a q tile for 4 steps and more (2
        # at cp 4): the guard before the rule lets them through
        min_revisit_distance=4,
    )
    return resolved_bwd_mode(params, 64 * bq, d, dv, itemsize)


def _mixed_dispatch():
    from magiattention_tpu.kernels.mask_utils import types_to_bands
    from magiattention_tpu.kernels.tile_policy import choose_mixed_dispatch

    seq, h, blk = 2048, 1024, 128  # a dense half, then a 128-wide diagonal
    qr = np.asarray(
        [[0, h]] + [[s, s + blk] for s in range(h, seq, blk)], np.int32)
    lo, hi = types_to_bands(qr, qr, np.zeros(len(qr), np.int32))
    mix = choose_mixed_dispatch(qr, qr.copy(), lo, hi, seq, seq)
    return None if mix is None else (mix.coarse_blocks, mix.fine_blocks)


def _serve_rungs():
    from magiattention_tpu.serving import decode

    key = (8, 4, 2, 64, 64, "float32", False, 1)
    return decode._rungs(
        SimpleNamespace(quantized=False), key, "paged_decode", hk=2,
        shards=1, multi_row=False)


STORE_CASES = {
    "calc_attn": lambda: kreg.calc_attn_backend(
        {"mask_sig": "mA", "mesh_sig": "cp4", "env_sig": "eA"}),
    **{f"ffa_bwd-{cell}": (lambda key=key: _bwd_mode(key))
       for cell, key in BWD_KEYS.items()},
    "ffa_dispatch": _mixed_dispatch,
    "serve_decode": _serve_rungs,
    "nsa_slc": lambda: kreg.nsa_slc_backend((2, 4, 8, 4, 64, 32)),
}


def _seed_parent_store(directory, decision, key, other, chosen):
    """The rows the parent of PR 29 wrote and read back: a ``policy`` row
    and enough ok ``measure`` rows to make ``other`` the measured-best, as a
    history file and as a compacted snapshot."""
    os.makedirs(directory)
    ck = canonical_key(key)
    rows = [{"rk": "policy", "decision": decision, "key": ck,
             "choice": other, "source": "measured", "v": 1, "ts": 1.0}]
    rows += [{"rk": "measure", "decision": decision, "key": ck,
              "backend": name, "wall_ms": ms, "ok": True, "v": 1, "ts": 1.0}
             for name, ms in ((other, 1.0), (other, 2.0),
                              (chosen, 50.0), (chosen, 60.0))]
    with open(os.path.join(directory, "history-old-1-abcd1234.jsonl"),
              "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)
    by_backend = {
        name: {"count": 2, "ok": 2, "wall_ms_sum": s, "wall_ms_min": m}
        for name, s, m in ((other, 3.0, 1.0), (chosen, 110.0, 50.0))}
    with open(os.path.join(directory, "store.json"), "w") as f:
        json.dump({
            "v": 1,
            "entries": {f"{decision}|{ck}": {
                "count": 4, "by_backend": by_backend}},
            "policy": {f"{decision}|{ck}": {
                "choice": other, "source": "measured", "ts": 1.0}},
            "calibration": {"overhead_elems": {"value": 1.0, "n": 9},
                            "dcn_per_row": {"value": 99.0, "n": 9}},
        }, f)


@pytest.mark.parametrize("case", sorted(STORE_CASES))
def test_a_store_on_disk_changes_no_choice(case, tmp_path, monkeypatch):
    """Through each decision's real call site: what it resolves with
    telemetry off is what it resolves with telemetry on over a store
    directory whose rows name the other backend."""
    decision = case.split("-")[0]
    call = STORE_CASES[case]
    monkeypatch.delenv("MAGI_ATTENTION_TELEMETRY", raising=False)
    expected = call()
    key, chosen = kreg.get_registry().last(decision)
    assert kreg.stats()["heuristic_calls"] == 1  # the rule was reached
    other = next(b for b in kreg.backends_for(decision) if b != chosen)

    kreg.reset_registry()
    store_dir = str(tmp_path / "store")
    _seed_parent_store(store_dir, decision, key, other, chosen)
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY", "1")
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.setenv("MAGI_ATTENTION_STORE_DIR", store_dir)
    assert tstore.store_active()
    assert call() == expected
    assert kreg.last_choice(decision) == chosen
    assert kreg.stats()["heuristic_calls"] == 1


# -- removed keys -----------------------------------------------------------------


@pytest.mark.parametrize("removed", sorted(env_general.REMOVED_ENV_KEYS))
def test_removed_key_is_refused(removed, monkeypatch):
    """A key that left the package is refused where a runtime key is made
    and where the pin it aliased is read, with what to set instead."""
    instead = env_general.REMOVED_ENV_KEYS[removed]
    env_general.snapshot_env()  # clean environment: no complaint
    monkeypatch.setenv(removed, "0")
    for read in (env_general.snapshot_env, env_backend.ffa_bwd_pin,
                 env_backend.mixed_blocks_pin, env_backend.serve_decode_pin):
        with pytest.raises(ValueError) as e:
            read()
        assert removed in str(e.value) and instead in str(e.value)
    assert removed not in env_general.ENV_KEYS_AFFECTING_RUNTIME


# -- one-way arrows -----------------------------------------------------------------


def _imports(path):
    """Every module name a file imports, relative ones with their dots."""
    with open(path) as f:
        tree = ast.parse(f.read())
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = "." * node.level + (node.module or "")
            found += [base] + [f"{base}.{a.name}" for a in node.names]
    return found


def _py_files(subpackage):
    for root, _, files in os.walk(os.path.join(PACKAGE, subpackage)):
        for name in files:
            if name.endswith(".py"):
                yield os.path.join(root, name)


@pytest.mark.parametrize(
    "subpackage", ["kernels", "meta", "functional", "comm"])
def test_choosers_import_no_observer(subpackage):
    """env -> kernels / meta / functional / comm -> telemetry: what chooses
    a kernel, a tile or a solver constant imports neither the store nor a
    drift layer, and telemetry imports nothing from kernels/."""
    files = list(_py_files(subpackage))
    assert files
    for path in files:
        for mod in _imports(path):
            assert not mod.endswith(("telemetry.store", "telemetry.drift")), (
                path, mod)
    for path in _py_files("telemetry"):
        for mod in _imports(path):
            assert "kernels" not in mod.split("."), (path, mod)
    assert not os.path.exists(os.path.join(PACKAGE, "telemetry", "drift.py"))


# -- ladders ----------------------------------------------------------------


def test_ladders_expose_fallback_order():
    assert kreg.ladder("calc_attn") == ("ffa", "sdpa", "sdpa_online")
    assert kreg.ladder("serve_decode") == (
        "paged_decode_sharded", "paged_decode_spec", "paged_decode_int8",
        "paged_decode", "gather_ffa", "dense")
    assert kreg.ladder("serve_decode", "paged_decode") == (
        "paged_decode", "gather_ffa", "dense")
    assert kreg.ladder("serve_decode", "gather_ffa") == (
        "gather_ffa", "dense")
    assert kreg.ladder("serve_decode", "unknown") == (
        "paged_decode_sharded", "paged_decode_spec", "paged_decode_int8",
        "paged_decode", "gather_ffa", "dense")
    # the resilience module's reference rung is the calc_attn ladder's last
    from magiattention_tpu.resilience.fallback import reference_backend
    assert reference_backend() == "sdpa_online"


def test_every_decision_documents_its_pin_keys():
    for decision in kreg.decisions():
        assert kreg.backends_for(decision), decision
        assert decision in kreg.PIN_KEYS, decision
    # one key per pin: only the tile pins, six block keys and the policy
    # switch, list more
    assert [d for d, keys in kreg.PIN_KEYS.items() if len(keys) > 1] == [
        "ffa_tiles"]


# -- pins ---------------------------------------------------------------------


def test_ffa_bwd_pin_matrix(monkeypatch):
    from magiattention_tpu.kernels.ffa import (
        FFAParams, bwd_mode_key, fused_bwd_feasible, resolved_bwd_mode,
    )
    from magiattention_tpu.kernels.tile_policy import choose_bwd_mode

    params = FFAParams(
        num_work=4, num_work_t=4, num_q_tiles=2, num_k_tiles=2,
        block_q=128, block_k=128, softmax_scale=1.0, softcap=0.0,
        group=1, interpret=True, min_revisit_distance=3,
    )
    sqp, d, dv, itemsize = 256, 32, 32, 4
    assert fused_bwd_feasible(params, sqp, d, dv, itemsize)

    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_FFA_BWD", "split")
    assert resolved_bwd_mode(params, sqp, d, dv, itemsize) == "split"
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_FFA_BWD", "fused")
    assert resolved_bwd_mode(params, sqp, d, dv, itemsize) == "fused"

    # unset: the registry's rule is exactly the cost model
    monkeypatch.delenv("MAGI_ATTENTION_BACKEND_FFA_BWD")
    key = bwd_mode_key(params, d, dv, itemsize)
    expected = choose_bwd_mode(*key[:7], dv, itemsize=itemsize, group=1)
    assert resolved_bwd_mode(params, sqp, d, dv, itemsize) == expected


def test_pin_getters_read_their_backend_key(monkeypatch):
    assert env_backend.mixed_blocks_pin() is None
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_MIXED_BLOCKS", "mixed")
    assert env_backend.mixed_blocks_pin() == "mixed"
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_MIXED_BLOCKS", "single")
    assert env_backend.mixed_blocks_pin() == "single"

    assert env_backend.serve_decode_pin() is None
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", "gather_ffa")
    assert env_backend.serve_decode_pin() == "gather_ffa"
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", "dense")
    assert env_backend.serve_decode_pin() == "dense"
    # a value that names no rung is no pin at all
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", "auto")
    assert env_backend.serve_decode_pin() is None


# -- provenance -------------------------------------------------------------


def test_resolution_announces_backend_select(tmp_path, monkeypatch):
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY", "1")
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY_DIR", str(tmp_path))

    for _ in range(3):  # announce dedupes repeats of one (key, choice)
        kreg.resolve("ffa_bwd", (1, 2), lambda: "fused")
    telemetry.reset()  # flush

    records = []
    for fp in sorted(tmp_path.glob("*.jsonl")):
        with open(fp) as f:
            records.extend(json.loads(line) for line in f if line.strip())
    selects = [r for r in records if r["kind"] == "backend_select"]
    assert len(selects) == 1
    assert selects[0]["decision"] == "ffa_bwd"
    assert selects[0]["choice"] == "fused"
    assert selects[0]["source"] == "heuristic"
    assert selects[0]["key"] == [1, 2]


def test_a_label_keeps_every_distinct_choice_with_who_made_it():
    """A labelled key called at two sizes (a model's step, then a smaller
    check program) can choose twice: ``labelled_choices`` — what a result
    line prints — keeps both in order with their sources, ``last_choice``
    and ``last_source`` the last."""
    step = "fwd256x512 dq256x512 dkv256x512g8"
    check = "fwd128x512g8 dq128x512g8 dkv128x512g8"
    for _ in range(2):  # a step repeats: one entry
        kreg.note_choice("ffa_tiles", (32768,), step, "table_guard",
                             label="full")
    kreg.note_choice("ffa_tiles", (8192,), check, "shape_rule",
                         label="full")
    kreg.note_choice("ffa_tiles", (8192,), check, "shape_rule",
                         label="window")
    kreg.note_choice("ffa_tiles", (1024,), "fwd256x512", "default")
    assert kreg.labelled_choices("ffa_tiles") == {
        "full": f"{step} (table_guard); {check} (shape_rule)",
        "window": f"{check} (shape_rule)"}
    assert kreg.last_choice("ffa_tiles", label="full") == check
    assert kreg.last_source("ffa_tiles", "full") == "shape_rule"
    assert kreg.last_choice("ffa_tiles") == "fwd256x512"
    assert kreg.last_source("ffa_tiles") == "default"
    assert kreg.labelled_choices("ffa_bwd") == {}
    assert kreg.last_source("ffa_bwd") is None
