"""``--rehearse-cpu``: every cell end to end at toy widths with interpreted
kernels, and a cell added as data alone. A rehearsal proves the control
flow, the plan counts and the agreement with the reference; it measures
nothing and prints no result line."""

import json
import os
import shutil
import statistics

import pytest
from magiattention_tpu.models import llama

import foreign_cells
from cellbench import flops, manifest, run, trace_reduce, traffic_gen

MANIFEST = json.load(open(os.path.join(manifest.ROOT, "BENCHMARK.json")))


def _rehearse(capsys, *argv) -> dict:
    code = run.main([*argv])
    lines = capsys.readouterr().out.splitlines()
    assert code == 0, lines[-3:]
    assert all(line.startswith("[cpu rehearsal] ") for line in lines)
    assert not any(line.lstrip().startswith('{"correct"') for line in lines)
    assert lines[-1].endswith("no result line on the cpu")
    return json.loads(lines[-2].split("report: ", 1)[1])


@pytest.mark.parametrize(
    "cell,chips,trace",
    [(w["name"], w["chips"], "1" if w["traffic"] == "packed" else "0")
     for w in MANIFEST["workloads"]],
    ids=lambda v: str(v))
def test_every_cell_rehearses_end_to_end(clean_env, capsys, cell, chips, trace):
    report = _rehearse(
        capsys, "--workload", cell, "--seed", "5", "--trace", trace,
        "--rehearse-cpu", str(chips))
    assert all(report["flags"].values()), report["flags"]
    assert all(c["ok"] for c in report["checks"].values())
    assert report["steps"] == run.REHEARSAL_STEPS
    assert any(k.startswith("_fwd_kernel") for k in report["kernels"])
    assert any(k.startswith("_bwd_") for k in report["kernels"])
    plan = report["plan"]
    assert len(plan["rank_areas"]) == chips
    if chips > 1:
        # the dispatch solver balanced the causal area, and something
        # travels between the ranks
        assert max(plan["rank_areas"]) <= 1.05 * min(plan["rank_areas"])
        assert plan["payload_rows"] > 0 and plan["overlap_degree"] >= 1
    if trace == "1":
        # the counts and host-clock readers work without a device trace;
        # the device-trace readers find nothing and are left out
        got = report["metrics"]
        assert {"step_ms_p50", "plan_ms", "dispatch_balance_ratio",
                "compile_or_load_s", "programs_compiled"} <= set(got)
        assert "ffa_ms_per_step" not in got and "device_idle_share" not in got
        assert len(report["traced_step_ms"]) == run.TRACED_STEPS
    else:
        assert set(report["metrics"]) == {"tokens_per_s", "setup_s"}


def test_correct_judges_the_resilience_events_of_the_run_itself(
    clean_env, capsys, monkeypatch
):
    """An earlier test of the same worker may have left the program's
    per-process counters non-zero (ROADMAP C0): the run counts from its own
    start, and an event inside it still fails ``no_resilience_event``."""
    from magiattention_tpu.resilience import fallback

    monkeypatch.setitem(fallback._EVENT_COUNTS, "retry@an_earlier_test", 2)
    report = _rehearse(
        capsys, "--workload", MANIFEST["workloads"][0]["name"], "--seed",
        "6", "--rehearse-cpu", "1")
    assert report["flags"]["no_resilience_event"]
    assert report["what_ran"]["resilience_events"] == {}
    assert run.events_since({"a@b": 2}, {"a@b": 2}) == {}
    assert run.events_since({"a@b": 2}, {"a@b": 3, "c@d": 1}) == {
        "a@b": 1, "c@d": 1}


_MASKED_CE, _ROPE = llama.masked_ce, llama._rope


def _half_the_batch_left_out(logits, labels):
    """The mean over the first half of the positions alone."""
    return _MASKED_CE(logits, labels.at[labels.shape[0] // 2:].set(-1))


def _positions_dropped(x, pos, theta):
    return _ROPE(x, pos * 0, theta)


@pytest.mark.parametrize("name,broken,fails", [
    ("masked_ce", _half_the_batch_left_out, {"grad_wq0", "grad_wk0"}),
    ("_rope", _positions_dropped, {"logits", "grad_wq0", "grad_wk0"}),
])
def test_a_broken_program_is_not_correct(
    clean_env, capsys, monkeypatch, name, broken, fails
):
    """The rest of a run with the program broken underneath: half of the
    batch left out of the loss (the mean taken over the rest), and every
    token at position 0. ``correct`` comes out false, by the numbers
    named, and the rehearsal's exit code says so."""
    monkeypatch.setattr(llama, name, broken)
    cell = next(w for w in MANIFEST["workloads"] if w["traffic"] == "packed")
    code = run.main(["--workload", cell["name"], "--seed", "8",
                     "--rehearse-cpu", "1"])
    lines = capsys.readouterr().out.splitlines()
    assert code == 1 and "rehearsal FAILED" in lines[-1]
    report = json.loads(lines[-2].split("report: ", 1)[1])
    assert not report["flags"]["reference"]
    assert fails <= {k for k, c in report["checks"].items() if not c["ok"]}
    assert all(v for k, v in report["flags"].items() if k != "reference")


def test_a_cell_is_added_as_data_alone(clean_env, capsys, tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a cell in
    files of their own plus one manifest entry each: no code, and no edit
    of a file that was there."""
    root = tmp_path
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(manifest.ROOT, "cellbench", sub),
                        root / "cellbench" / sub)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    (root / "cellbench/configs/some-dense-1b.json").write_text(json.dumps({
        "name": "some-dense-1b", "family": "llama",
        "source": "https://example.org/some-dense-1b",
        "hidden_size": 2048, "num_hidden_layers": 2,
        "num_attention_heads": 16, "num_key_value_heads": 4, "head_dim": 128,
        "intermediate_size": 8192, "vocab_size": 8192, "rope_theta": 1e4,
        "rms_norm_eps": 1e-5, "sliding_window": 2048,
        "published": {"num_hidden_layers": 16}, "reduced": {
            "num_hidden_layers": "16 -> 2"}}))
    (root / "cellbench/traffic/short_docs_swa.json").write_text(json.dumps({
        "generator": "packed_lognormal", "tokens": 8192,
        "params": {"median": 300, "sigma": 0.5, "min": 64, "max": 1024,
                   "order_seed": 7},
        "window": "config", "batches": 2, "check_tokens_per_chip": 1024}))
    (root / "cellbench/metrics/mask_slices.json").write_text(json.dumps({
        "name": "mask_slices", "layer": "plan", "unit": "count",
        "moves": "setup_s", "source": "program_counter",
        "reader": "fact", "key": "slices"}))
    m = json.loads(json.dumps(MANIFEST))
    m["configs"].append({
        "name": "some-dense-1b", "source": "https://example.org/some-dense-1b",
        "file": "cellbench/configs/some-dense-1b.json",
        "reduced": ["num_hidden_layers"], "why": "test"})
    m["workloads"].append({
        "name": "dense1b.short_docs_swa.cp1", "config": "some-dense-1b",
        "traffic": "short_docs_swa", "chips": 1, "why": "test"})
    m["per_layer"].append({
        "name": "mask_slices", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "plan", "moves": "setup_s",
        "workloads": ["dense1b.short_docs_swa.cp1"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))

    cell = manifest.load_cell(str(root), "dense1b.short_docs_swa.cp1")
    assert cell.config["hidden_size"] == 2048
    assert [x["name"] for x in cell.per_layer][-1] == "mask_slices"
    old = manifest.load_cell(str(root), MANIFEST["workloads"][0]["name"])
    assert "mask_slices" not in [x["name"] for x in old.per_layer]

    report = _rehearse(
        capsys, "--root", str(root), "--workload",
        "dense1b.short_docs_swa.cp1", "--trace", "1", "--rehearse-cpu", "1")
    assert all(report["flags"].values())
    # the new traffic made the mask (many short documents under a window:
    # more slices than documents), and the new metric read it
    assert report["metrics"]["mask_slices"] == report["plan"]["slices"] > 3
    assert {p: p.read_bytes() for p in before} == before


def test_a_family_is_added_as_files_alone(clean_env, capsys, tmp_path):
    """A second model family — attention in one layer of three, its own
    reference, its own names compared, its own FLOP counts, an event class
    and a metric for the kernel it would bring — as added files and one
    manifest entry each: no code of ``cellbench/`` but what the family's
    own files bring, and no edit of a file that was there."""
    root = tmp_path
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(manifest.ROOT, "cellbench", sub),
                        root / "cellbench" / sub)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    m = json.loads(json.dumps(MANIFEST))
    foreign_cells.add_second_family(root, m)
    (root / "BENCHMARK.json").write_text(json.dumps(m))

    report = _rehearse(
        capsys, "--root", str(root), "--workload", "mixer.packed.cp1",
        "--seed", "3", "--trace", "1", "--rehearse-cpu", "1")
    assert all(report["flags"].values()), report["flags"]
    # its own names were compared, against its own reference
    assert list(report["checks"]) == [
        "loss", "logits", "grad_gate0", "grad_value"]
    assert all(c["ok"] for c in report["checks"].values())
    assert 0 < report["checks"]["grad_value"]["err"] < 1e-4  # float32
    assert any(k.startswith("_fwd_kernel") for k in report["kernels"])
    # the whole step's share of the peak stands on its own count: FFA's
    # area in one layer of the toy's three, none of llama's projections
    family = manifest.load_family(str(root), "mixer")
    cell = manifest.load_cell(str(root), "mixer.packed.cp1")
    cfg, tokens, window, _ = run.cell_sizes(cell, family, 1)
    spec = traffic_gen.make_mask(
        cell.traffic, tokens, window, 3,
        manifest.load_generator(str(root), cell.traffic["generator"]))
    need = family.required_flops_per_step(cfg, spec)
    assert need == 6 * tokens * (
        4 * 128 * 256 + 2 * 3 * 128 * 256 + 128 * 512) + int(
        3.5 * 4 * flops.band_area(spec) * 128 * 2)
    step_s = statistics.median(report["step_ms"]) * 1e-3
    assert report["metrics"]["model_flops_utilization"] == pytest.approx(
        100.0 * need / (step_s * 197e12))
    assert [g["layers"] for g in family.ffa_calls(cfg)] == [1]
    # the kernel it would bring has a class of its own, tried first; its
    # metric found no device trace on the CPU and was left out
    classes = [c for c, _ in trace_reduce.load_classes(str(root))]
    assert classes[:3] == ["gate_fwd", "gate_bwd", "ffa_fwd"]
    assert "gate_fwd" not in [c for c, _ in trace_reduce.load_classes()]
    assert "gate_ms_per_step" not in report["metrics"]
    assert [x["name"] for x in cell.per_layer][-1] == "gate_ms_per_step"
    assert {p: p.read_bytes() for p in before} == before
