"""perf-history CSV: append semantics, schema evolution, delta report."""

import csv

import pytest

import magiattention_tpu.benchmarking.perf_report as pr


@pytest.fixture()
def history_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(pr, "HISTORY_DIR", str(tmp_path))
    return tmp_path


def test_append_and_report(history_dir):
    pr.append_row("k", {"mask": "causal", "seqlen": 4096, "tflops": 10.0})
    pr.append_row("k", {"mask": "causal", "seqlen": 4096, "tflops": 25.0})
    pr.append_row("k", {"mask": "video", "seqlen": 4096, "tflops": 40.0})
    path = history_dir / "k.csv"
    rows = list(csv.DictReader(open(path)))
    assert len(rows) == 3
    assert all(r["utc"] and r["commit"] for r in rows)
    report = pr.history_report("k", ["mask", "seqlen"], "tflops")
    assert "causal/4096" in report and "+150.0%" in report
    assert "video/4096" in report


def test_suspect_rows_never_set_a_baseline(history_dir):
    """A row the harness marked unphysical (rate above the chip ceiling
    even at the long-scan upper bound) stays in the CSV as raw data but
    must not appear in — or anchor the delta of — the report."""
    pr.append_row("k", {"mask": "full", "seqlen": 8192, "tflops": 80.0})
    pr.append_row(
        "k", {"mask": "full", "seqlen": 8192, "tflops": 250.5, "suspect": 1}
    )
    report = pr.history_report("k", ["mask", "seqlen"], "tflops")
    assert "250.5" not in report
    assert "tflops=80" in report
    assert len(list(csv.DictReader(open(history_dir / "k.csv")))) == 2


def test_phase_suspect_taints_only_that_phase(history_dir):
    """suspect_fwd bars a row from fwd_* reports but its valid fwdbwd
    measurement must still set the baseline (one bad slope pair must not
    discard the row's other, physical metric)."""
    pr.append_row("k", {
        "mask": "full", "seqlen": 8192,
        "fwd_tflops": 250.5, "fwdbwd_tflops": 81.5, "suspect_fwd": 1,
    })
    fwd = pr.history_report("k", ["mask", "seqlen"], "fwd_tflops")
    fwdbwd = pr.history_report("k", ["mask", "seqlen"], "fwdbwd_tflops")
    assert "250.5" not in fwd
    assert "81.5" in fwdbwd


def test_schema_evolution_rewrites_header(history_dir):
    pr.append_row("k", {"a": 1})
    pr.append_row("k", {"a": 2, "b": 3})  # new column
    rows = list(csv.DictReader(open(history_dir / "k.csv")))
    assert rows[0]["b"] == "" and rows[1]["b"] == "3"


def test_report_without_history_is_empty(history_dir):
    assert pr.history_report("missing", ["x"], "y") == ""


def test_append_failure_is_loud(history_dir, monkeypatch):
    # a measurement whose record cannot be written did not land: the
    # harness must stop, not report success over a lost row
    monkeypatch.setattr(pr, "HISTORY_DIR", "/proc/definitely/not/writable")
    with pytest.raises(OSError):
        pr.append_row("k", {"a": 1})


def test_commit_column_comes_from_the_caller(history_dir):
    # a chip run's copy of the tree is not a git repository
    pr.append_row("k", {"a": 1})
    pr.append_row("k", {"a": 2, "commit": "abc1234"})
    rows = list(csv.DictReader(open(history_dir / "k.csv")))
    assert [r["commit"] for r in rows] == ["unknown", "abc1234"]


def test_fwdbwd_floor_uses_executed_flops():
    """The fwd+bwd credibility floor must be computed from EXECUTED flops
    (4.5x fwd) — a reference-convention (3.5x) floor sits ~29% below the
    physical bound and waves through unphysical slopes (ADVICE r5 #1).

    Synthetic slope just above the executed-flops ceiling in model terms
    (~162 model-TF/s at the 208 TF/s anchor; the canonical "160 TF/s"
    example assumed the nominal 197 peak): the hardware would have to run
    its 4.5x matmul work above the measured chip ceiling, so the executed
    floor flags it — while the old 3.5x floor (model rate vs ceiling,
    162 < 208) passed it.
    """
    fwd_flops = 4 * (8192 * 8193 // 2) * 128 * 16  # the bench GQA shape
    flops_ref = fwd_flops * 3.5
    flops_hw = flops_ref * pr.HW_FWD_BWD_RATIO
    # model-convention rate 2% above the executed-flops ceiling
    model_tflops = (
        pr.MEASURED_CEILING_TFLOPS / pr.HW_FWD_BWD_RATIO
    ) * 1.02
    slope_ms = flops_ref / (model_tflops * 1e9)

    old_floor = pr.credible_floor_ms(flops_ref)   # 3.5x convention
    new_floor = pr.credible_floor_ms(flops_hw)    # executed flops
    assert slope_ms > old_floor, "old floor should have passed this slope"
    assert slope_ms < new_floor, "executed-flops floor must flag it"
    # the implied EXECUTED rate really is above the measured ceiling
    implied_hw = flops_hw / (slope_ms * 1e-3) / 1e12
    assert implied_hw > pr.MEASURED_CEILING_TFLOPS
    # and a genuinely physical slope (model rate at 80% of the executed
    # ceiling) clears the new floor
    ok_ms = flops_ref / (
        0.8 * pr.MEASURED_CEILING_TFLOPS / pr.HW_FWD_BWD_RATIO * 1e9
    )
    assert ok_ms > new_floor
