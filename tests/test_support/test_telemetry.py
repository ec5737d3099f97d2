"""Telemetry layer: JSONL records for one CPU dispatch+attention step, the
report CLI round trip, the zero-overhead-when-off contract, and the runtime
cache counters (docs/observability.md)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from magiattention_tpu import telemetry
from magiattention_tpu.telemetry import registry

from tests.test_support.script_loading import load_script

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REPORT = os.path.join(REPO, "scripts", "telemetry_report.py")

# distinctive shape so the module-global runtime dict can't already hold
# this key from another test (a cache hit would skip the plan records)
S, H, HK, D, CHUNK = 192, 2, 1, 32, 24


@pytest.fixture(autouse=True)
def _fresh_collector():
    telemetry.reset()
    yield
    telemetry.reset()  # close any JSONL handle into tmp_path


def _run_step(mask_types=(1,), chunk=CHUNK, overlap_degree=2):
    from magiattention_tpu import DistAttnConfig, OverlapConfig
    from magiattention_tpu.api import (
        calc_attn, dispatch, magi_attn_flex_key, undispatch,
    )

    mesh = Mesh(np.array(jax.devices("cpu")[:4]), axis_names=("cp",))
    key = magi_attn_flex_key(
        [[0, S]], [[0, S]], list(mask_types), S, S,
        mesh=mesh, cp_axis="cp", chunk_size=chunk,
        dist_attn_config=DistAttnConfig(
            overlap_config=OverlapConfig(degree=overlap_degree)
        ),
    )
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((S, HK, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((S, HK, D)), jnp.float32)
    q_d = dispatch(q, key)
    k_d = dispatch(k, key, role="kv")
    v_d = dispatch(v, key, role="kv")
    out_d, _ = calc_attn(q_d, k_d, v_d, key)
    return jax.block_until_ready(undispatch(out_d, key))


def _load_jsonl(tmp_path):
    files = sorted(tmp_path.glob("*.jsonl"))
    assert files, "telemetry run produced no JSONL file"
    records = []
    for fp in files:
        with open(fp) as f:
            records.extend(json.loads(line) for line in f if line.strip())
    return records


def test_step_emits_schema_records(tmp_path, monkeypatch):
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY", "1")
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY_DIR", str(tmp_path))
    _run_step()

    records = _load_jsonl(tmp_path)
    kinds = {r["kind"] for r in records}
    assert {"dispatch_meta", "plan_build", "ffa_plan", "attn_step",
            "runtime_cache"} <= kinds
    assert all(r["schema_version"] == telemetry.SCHEMA_VERSION
               for r in records)

    # dispatch: per-rank attention area + balance ratio
    meta = [r for r in records if r["kind"] == "dispatch_meta"][-1]
    assert len(meta["per_rank_area"]) == 4
    assert meta["max_area"] == max(meta["per_rank_area"])
    assert 0.0 < meta["balance_ratio"] <= 1.0

    # comm plan: per-stage payload vs wire rows incl alignment padding
    plan = [r for r in records if r["kind"] == "plan_build"][-1]
    assert plan["planner"] == "static"
    for s in plan["stages"]:
        assert s["wire_rows"] >= s["payload_rows"]
        assert s["padding_rows"] == s["wire_rows"] - s["payload_rows"]
        assert s["lowering_executed"] in ("a2a", "ppermute", "ragged", "hier")

    # attention step: overlap degree, host timing, blocks, byte volumes
    step = [r for r in records if r["kind"] == "attn_step"][-1]
    assert step["overlap_degree"] == len(step["stages"]) >= 1
    assert step["wall_ms"] > 0
    assert step["block_q"] > 0 and step["block_k"] > 0
    assert step["wire_bytes_total"] >= step["payload_bytes_total"] > 0
    assert (step["padding_bytes_total"]
            == step["wire_bytes_total"] - step["payload_bytes_total"])
    for s in step["stages"]:
        assert s["wire_bytes"] == s["wire_rows"] * step["row_bytes"]
        assert s["xprof_scope"].startswith("group_cast_stage")
    # estimated (band) vs executed (padded-grid) work
    assert step["padded_elems"] >= step["band_elems"] > 0
    assert step["padded_flops_fwd"] >= step["est_flops_fwd"] > 0
    # resolved backward execution mode rides every ffa attn_step
    assert step["bwd_mode"] in ("fused", "split")

    # runtime cache counters rode along
    cache = [r for r in records if r["kind"] == "runtime_cache"][-1]
    assert cache["misses"] >= 1 and cache["size"] >= 1

    # in-memory summary agrees with the stream
    flat = telemetry.flat_summary()
    assert flat["tel_balance_ratio"] == meta["balance_ratio"]
    assert flat["tel_events_attn_step"] >= 1


def test_report_cli_round_trip(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY", "1")
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY_DIR", str(tmp_path))
    # distinct chunking: the module-global runtime dict caches the other
    # test's key, and a cache hit would skip the plan-build records
    _run_step(chunk=48)
    # synthetic resilience records: the report's resilience section must
    # round-trip alongside the real step records
    telemetry.record_event(
        "resilience", action="inject", site="kernel_lowering", call=1
    )
    telemetry.record_event(
        "resilience", action="fallback", site="kernel_lowering",
        action_detail="ladder_start",
    )
    telemetry.reset()  # flush/close before the reader opens the file

    mod = load_script(REPORT, "telemetry_report")
    records = mod.load_records([str(tmp_path)])
    assert records and records == sorted(
        records, key=lambda r: (r["ts"], r["seq"])
    )
    agg = mod.aggregate(records)
    assert 0.0 < agg["dispatch"]["balance_ratio"] <= 1.0
    assert agg["attn_step"]["steps"] >= 1
    assert agg["runtime_cache"]["misses"] >= 1
    assert agg["resilience"] == {
        "events": 2, "injected": 1, "guard_trips": 0, "fallback_hops": 1,
        "retries": 0, "recovered": 0,
        "hops_by_site": {"kernel_lowering": 1},
    }
    text = mod.format_summary(agg)
    for token in ("balance_ratio", "attn steps", "runtime cache", "stage 0",
                  "resilience"):
        assert token in text

    assert mod.main([str(tmp_path)]) == 0
    assert "telemetry summary" in capsys.readouterr().out


def test_kernel_audit_report_round_trip(tmp_path, monkeypatch, capsys):
    """scripts/kernel_audit.py -> JSONL -> scripts/telemetry_report.py:
    the audit's telemetry record must survive the full round trip into a
    'kernel audit' summary section."""
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY", "1")
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY_DIR", str(tmp_path))

    audit = load_script(
        os.path.join(REPO, "scripts", "kernel_audit.py"), "kernel_audit"
    )
    assert audit.main(["--masks", "causal"]) == 0
    telemetry.reset()  # flush/close before the reader opens the file

    mod = load_script(REPORT, "telemetry_report")
    agg = mod.aggregate(mod.load_records([str(tmp_path)]))
    ka = agg["kernel_audit"]
    assert ka["runs"] == 1
    # 9 ffa + 3 paged-decode + 2 block-sparse + 2 scan + 2 grouped matmul
    assert ka["kernels"] == 18
    assert ka["configs"] >= 1
    assert ka["rules_run"] == ["K1", "K2", "K3", "K4", "K5"]
    assert ka["errors_total"] == 0 and ka["warnings_total"] == 0
    assert ka["fired_rules"] == []
    assert 0 < ka["vmem_worst_bytes"] <= ka["vmem_allowed_bytes"]

    text = mod.format_summary(agg)
    assert "kernel audit" in text and "vmem worst" in text
    capsys.readouterr()  # drop the audit CLI's own stdout


class _NoClock:
    """time stand-in that fails the test on ANY clock read."""

    @staticmethod
    def perf_counter():  # pragma: no cover - reaching here IS the failure
        raise AssertionError("timer read on the hot path with telemetry off")

    @staticmethod
    def time():  # pragma: no cover
        raise AssertionError("clock read on the hot path with telemetry off")


def test_off_means_no_io_and_no_timers(tmp_path, monkeypatch):
    monkeypatch.delenv("MAGI_ATTENTION_TELEMETRY", raising=False)
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY_DIR", str(tmp_path))
    # replace the registry module's clock binding (not the global time
    # module): any gated path that reads a timer now raises
    monkeypatch.setattr(registry, "time", _NoClock)

    # distinct chunking -> guaranteed runtime-dict miss, so the full
    # plan-build + step path runs under the poisoned clock
    _run_step(chunk=16, overlap_degree=1)

    with telemetry.stage_timer("x"):
        pass
    telemetry.inc("noop")
    telemetry.record_event("noop")
    assert registry._collector is None, "collector created with flag off"
    assert list(tmp_path.glob("*.jsonl")) == []
    assert telemetry.summary() == {}
    assert telemetry.flat_summary() == {}


def test_runtime_dict_stats(monkeypatch):
    import magiattention_tpu.dist_attn_runtime_mgr as mgr_mod

    monkeypatch.setattr(
        mgr_mod, "DistAttnRuntimeMgr", lambda key, mesh: object()
    )
    d = mgr_mod.DistAttnRuntimeDict(maxsize=2)
    for name in ("a", "b", "c"):  # 3 misses, 1 eviction (maxsize 2)
        d.get_or_create(name, None)
    d.get_or_create("c", None)  # hit
    d.get_or_create("a", None)  # evicted above -> miss again, evicts "b"
    assert d.get_stats() == {
        "hits": 1, "misses": 4, "evictions": 2, "size": 2, "maxsize": 2,
    }
    assert d.get("b") is None and d.get("c") is not None


def test_report_plan_control_plane_round_trip(tmp_path, monkeypatch, capsys):
    """Synthetic control-plane records (ISSUE: crash-safe plan control
    plane) must aggregate into the report's plan_control_plane section and
    survive the JSONL round trip."""
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY", "1")
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY_DIR", str(tmp_path))
    telemetry.record_event(
        "plan_solve", planner="static", event="solve", source="cold",
        incremental=False, wall_ms=1.5, rows_resolved=4, rows_total=4,
    )
    telemetry.record_event(
        "plan_solve", planner="static", event="cache_hit", source="disk",
        incremental=False, wall_ms=0.0, rows_resolved=0,
    )
    telemetry.record_event(
        "plan_solve", planner="dynamic", event="cache_hit",
        source="broadcast", incremental=False, wall_ms=0.0,
        rows_resolved=0, attempts=2, backoff_ms=3.0,
    )
    telemetry.record_event(
        "plan_store", op="read", outcome="hit", bytes=512
    )
    telemetry.record_event(
        "plan_store", op="read", outcome="miss", reason="checksum",
        detail="payload sha mismatch",
    )
    telemetry.record_event("plan_store", op="write", outcome="ok", bytes=512)
    telemetry.record_event("plan_store", op="cleanup", outcome="ok", removed=1)
    telemetry.record_event(
        "plan_broadcast", role="leader", outcome="ok", attempts=1,
        backoff_ms=0.0,
    )
    telemetry.record_event(
        "plan_broadcast", role="follower", outcome="exhausted", attempts=3,
        backoff_ms=12.0,
    )
    telemetry.reset()  # flush/close before the reader opens the file

    mod = load_script(REPORT, "telemetry_report")
    assert "plan_control_plane" in mod.SECTION_SCHEMAS
    records = mod.load_records([str(tmp_path)])
    agg = mod.aggregate(records)
    pcp = agg["plan_control_plane"]
    assert pcp["resolutions"] == 3
    assert pcp["by_source"] == {"broadcast": 1, "cold": 1, "disk": 1}
    assert pcp["store_reads"] == 2
    assert pcp["store_hits"] == 1 and pcp["store_misses"] == 1
    assert pcp["store_miss_reasons"] == {"checksum": 1}
    assert pcp["store_writes"] == 1
    assert pcp["store_orphans_removed"] == 1
    assert pcp["broadcasts"] == 2
    assert pcp["broadcast_by_role"] == {"follower": 1, "leader": 1}
    assert pcp["broadcast_exhausted"] == 1
    assert pcp["broadcast_attempts_total"] == 4
    assert pcp["broadcast_backoff_ms_total"] == 12.0
    text = mod.format_summary(agg)
    for token in ("plan control plane", "store:", "broadcast:"):
        assert token in text

    assert mod.main([str(tmp_path)]) == 0
    assert "plan control plane" in capsys.readouterr().out
