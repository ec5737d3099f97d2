"""The gates and the result line: no TPU, no result; the last line has
exactly the keys the driver reads."""

import json
import sys

import jax
import pytest

from cellbench import manifest, peaks, run


CELLS = [w["name"] for w in json.load(
    open(manifest.ROOT + "/BENCHMARK.json"))["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_a_run_without_a_tpu_fails_and_prints_no_result(
    clean_env, capsys, cell, trace
):
    assert jax.default_backend() == "cpu"
    code = run.main(["--workload", cell, "--seed", "0", "--seconds", "1",
                     "--trace", trace])
    out, err = capsys.readouterr()
    assert code == 1
    assert "needs" in err and "'cpu'" in err
    assert '"correct"' not in out and '"metrics"' not in out


@pytest.mark.parametrize("key", run.FORBIDDEN_ENV)
def test_a_variable_that_can_hide_the_device_is_refused(
    clean_env, monkeypatch, key
):
    monkeypatch.setenv(key, "1")
    with pytest.raises(SystemExit, match=key):
        run.main(["--workload", CELLS[0]])


def test_alone_without_the_package_it_exits_before_jax(clean_env, monkeypatch):
    monkeypatch.setattr(
        run.importlib.util, "find_spec", lambda name: None)
    with pytest.raises(SystemExit, match="not importable"):
        run.main(["--workload", CELLS[0]])


def test_unknown_workload_is_an_error(clean_env):
    with pytest.raises(KeyError, match="no workload"):
        run.main(["--workload", "no.such.cell"])


DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 9_000_000_000}


def test_result_line_has_exactly_the_contract_keys():
    units = {"tokens_per_s": "tokens/s", "setup_s": "s"}
    checks = {"loss": {"err": 1.25e-4, "tol": 2.3e-3, "ok": True}}
    line = run.result_line(
        True, 12, 0, {"tokens_per_s": 10321.123456789, "setup_s": 31.25},
        units, DEVICE, None, checks)
    got = json.loads(line)
    assert "\n" not in line
    # the driver's keys, then each number compared beside its limit, last
    assert list(got) == [
        "correct", "attempted", "failed", "metrics", "device", "checks"]
    assert got["checks"] == {"loss": {"err": 1.25e-4, "limit": 2.3e-3}}
    assert got["correct"] is True and got["attempted"] == 12
    assert got["metrics"]["tokens_per_s"] == {
        "value": 10321.123456789, "unit": "tokens/s"}  # every digit
    assert got["device"] == DEVICE
    traced = json.loads(run.result_line(
        False, 3, 1, {}, {}, {**DEVICE, "busy_s": 2.9, "window_s": 3.0},
        {"device_ops": [["ffa_fwd:_fwd_kernel", 1.0]], "idle_gaps": []},
        checks))
    assert list(traced) == [
        "correct", "attempted", "failed", "metrics", "device", "breakdown",
        "checks"]
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    assert traced["correct"] is False and traced["failed"] == 1


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_raise():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peak"):
        peaks.peaks_for("cpu")


def test_importing_the_entry_point_touches_nothing():
    # tests and the rehearsal import it: no parse, no device, no file
    assert "cellbench.run" in sys.modules
    assert run.WARMUP_STEPS >= 1 and run.TRACED_STEPS >= 1
