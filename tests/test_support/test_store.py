"""Persistent telemetry store (telemetry/store.py): round trips,
compaction, concurrent appends, run-history ingest, the rows an older build
left behind, and the report tool's view (docs/observability.md)."""

import json
import os
import threading

import pytest

from magiattention_tpu import telemetry
from magiattention_tpu.kernels import registry as kreg
from magiattention_tpu.telemetry import store as tstore
from magiattention_tpu.telemetry.store import TelemetryStore

from tests.test_support.script_loading import load_script

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REPORT = os.path.join(REPO, "scripts", "telemetry_report.py")


@pytest.fixture(autouse=True)
def _fresh_observatory():
    telemetry.reset()
    tstore.reset()
    kreg.reset_registry()
    yield
    telemetry.reset()
    tstore.reset()
    kreg.reset_registry()


@pytest.fixture
def active_store(tmp_path, monkeypatch):
    """Telemetry + store on, pointed into tmp. Returns the store dir."""
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY", "1")
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY_DIR", str(tmp_path))
    store_dir = str(tmp_path / "store")
    monkeypatch.setenv("MAGI_ATTENTION_STORE_DIR", store_dir)
    return store_dir


def test_store_round_trip(tmp_path):
    """Rows written by one handle are aggregated identically by a fresh
    handle reading the same directory (the cross-process contract)."""
    d = str(tmp_path / "s")
    st = TelemetryStore(d)
    key = {"mask_sig": "m1", "mesh_sig": "cp4", "env_sig": "e1"}
    st.record_history("attn_step", key, 10.0, steps=1)
    st.record_history("attn_step", key, 20.0)
    st.record_history("attn_step", key, None)  # a step with no wall time
    st.record_history("plan_solve", (4, 256, 512), 3.5)
    st.close()

    other = TelemetryStore(d)
    state = other.load()
    h = state.history[f"attn_step|{tstore.canonical_key(key)}"]
    assert h["count"] == 3
    assert (h["wall_ms_sum"], h["wall_ms_min"], h["wall_ms_max"]) == (
        30.0, 10.0, 20.0)
    assert state.history[
        f"plan_solve|{tstore.canonical_key((4, 256, 512))}"]["count"] == 1
    other.close()


def test_history_lines_are_jsonl_and_writer_unique(tmp_path):
    """Satellite 1: each writer gets its own history-<host>-<pid>-<token>
    file, every line parses standalone (O_APPEND line-atomic sink)."""
    d = str(tmp_path / "s")
    a, b = TelemetryStore(d), TelemetryStore(d)
    a.record_history("x", (1,), 1.0)
    b.record_history("x", (1,), 2.0)
    a.close()
    b.close()
    files = sorted(os.listdir(d))
    assert len(files) == 2
    for name in files:
        assert name.startswith("history-") and name.endswith(".jsonl")
        parts = name[len("history-"): -len(".jsonl")].rsplit("-", 2)
        assert len(parts) == 3 and parts[1] == str(os.getpid())
        with open(os.path.join(d, name)) as f:
            rows = [json.loads(line) for line in f]
        assert all(r["rk"] == "hist" and "ts" in r and "v" in r
                   for r in rows)


def test_compaction_folds_history_into_snapshot(tmp_path):
    d = str(tmp_path / "s")
    st = TelemetryStore(d)
    for ms in (5.0, 7.0, 9.0):
        st.record_history("attn_step", ("k",), ms)
    snap = st.compact()
    assert os.path.basename(snap) == "store.json"
    # history files consumed; appends after compaction go to a fresh file
    assert [f for f in os.listdir(d) if f.startswith("history-")] == []
    st.record_history("attn_step", ("k",), 11.0)
    st.close()

    fresh = TelemetryStore(d)
    h = fresh.load().history[f"attn_step|{tstore.canonical_key(('k',))}"]
    assert h["count"] == 4
    assert h["wall_ms_sum"] == pytest.approx(5.0 + 7.0 + 9.0 + 11.0)
    fresh.close()


def test_concurrent_appends_never_lose_rows(tmp_path):
    """Many threads, each with its own handle on the same directory: the
    merged view must contain every row (per-writer files + O_APPEND)."""
    d = str(tmp_path / "s")
    n_threads, n_rows = 8, 25

    def writer(i):
        st = TelemetryStore(d)
        for j in range(n_rows):
            st.record_history(f"b{i}", ("shared",), 1.0 + j)
        st.close()

    threads = [
        threading.Thread(target=writer, args=(i,)) for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    st = TelemetryStore(d)
    state = st.load()
    ck = tstore.canonical_key(("shared",))
    assert all(
        state.history[f"b{i}|{ck}"]["count"] == n_rows
        for i in range(n_threads)
    )
    st.close()


def test_store_inactive_without_telemetry(tmp_path, monkeypatch):
    monkeypatch.delenv("MAGI_ATTENTION_TELEMETRY", raising=False)
    monkeypatch.setenv("MAGI_ATTENTION_STORE_DIR", str(tmp_path / "s"))
    assert not tstore.store_active()
    assert tstore.get_store() is None
    tstore.record_quarantine("calc_attn", ("k",), "ffa", 2)
    tstore.ingest_event({"kind": "attn_step", "wall_ms": 1.0})
    assert tstore.quarantined_backends("calc_attn", ("k",)) == set()
    assert not os.path.exists(str(tmp_path / "s"))


def test_ingest_attn_step_feeds_run_history(active_store):
    """An attn_step record ingests into run history keyed by (mask, shape,
    dtype, mesh, env) signature — and into nothing else."""
    payload = {
        "backend": "ffa",
        "wall_ms": 8.0,
        "mask_sig": "mA", "mesh_sig": "cp4", "env_sig": "eA",
        "q_shape": [128, 2, 32], "kv_shape": [128, 1, 32],
        "dtype": "float32", "cp_size": 4,
        "plan_groups": [
            {"name": "merged", "block_q": 128, "block_k": 128,
             "num_work": 4, "padded_elems": 4 * 128 * 128},
        ],
        "bwd_mode": "split",
    }
    for _ in range(2):
        telemetry.record_event("attn_step", **payload)
    telemetry.record_event("attn_step", **{**payload, "dtype": "bfloat16"})
    st = tstore.get_store()
    state = st.load()
    hkeys = sorted(k for k in state.history if k.startswith("attn_step|"))
    assert len(hkeys) == 2
    assert sorted(state.history[k]["count"] for k in hkeys) == [1, 2]
    assert all(state.history[k]["wall_ms_min"] == 8.0 for k in hkeys)
    with open(st._sink.path) as f:
        assert {json.loads(line)["rk"] for line in f} == {"hist"}


def test_old_store_rows_are_skipped_on_load(tmp_path):
    """A directory the parent of PR 29 wrote — measure / policy / obs /
    calib / drift rows in its history files and their sections in
    store.json — loads with those skipped and everything else kept; a
    compaction then writes them out of the snapshot."""
    d = str(tmp_path / "s")
    os.makedirs(d)
    key = tstore.canonical_key(("k",))
    old_rows = [
        {"rk": "measure", "decision": "calc_attn", "key": key,
         "backend": "sdpa", "wall_ms": 1.0, "ok": True},
        {"rk": "policy", "decision": "calc_attn", "key": key,
         "choice": "sdpa", "source": "measured"},
        {"rk": "obs", "model": "tile_score", "predicted": 1.0,
         "measured_ms": 2.0, "extras": {"area": 1.0, "works": 1.0}},
        {"rk": "calib", "name": "overhead_elems", "value": 5000.0, "n": 5},
        {"rk": "drift", "model": "tile_score", "rel_err": 0.9},
        {"rk": "hist", "kind": "attn_step", "key": key, "wall_ms": 4.0},
        {"rk": "quarantine", "decision": "calc_attn", "key": key,
         "backend": "ffa", "trips": 2, "action": "add"},
    ]
    with open(os.path.join(d, "history-old-1-abcd1234.jsonl"), "w") as f:
        f.writelines(json.dumps({**r, "v": 1, "ts": 1.0}) + "\n"
                     for r in old_rows)
    with open(os.path.join(d, "store.json"), "w") as f:
        json.dump({
            "v": 1,
            "entries": {f"calc_attn|{key}": {"count": 1, "by_backend": {}}},
            "policy": {f"calc_attn|{key}": {"choice": "sdpa"}},
            "calibration": {"overhead_elems": {"value": 5000.0, "n": 5}},
            "observations": {"tile_score": []},
            "drift": [{"model": "tile_score"}],
            "history": {f"attn_step|{key}": {
                "kind": "attn_step", "count": 2, "wall_ms_sum": 6.0,
                "wall_ms_min": 2.0, "wall_ms_max": 4.0}},
            "rank_health": {"3": {"count": 1, "transitions": 0,
                                  "ewma_ms": 9.0, "capacity": 0.5,
                                  "degraded": True}},
            "quarantine": {},
        }, f)

    st = TelemetryStore(d)
    state = st.load()
    assert vars(state).keys() == {"history", "rank_health", "quarantine"}
    assert state.history[f"attn_step|{key}"]["count"] == 3
    assert state.rank_health["3"]["capacity"] == 0.5
    assert st.quarantined("calc_attn", ("k",)) == {"ffa"}
    with open(st.compact()) as f:
        assert json.load(f).keys() == {
            "v", "history", "rank_health", "quarantine"}
    assert st.quarantined("calc_attn", ("k",)) == {"ffa"}
    st.close()


def test_report_round_trips_store_history(active_store, tmp_path, capsys):
    """telemetry_report --json carries the store section (from --store)
    beside the JSONL stream's, every section schema-documented."""
    for ms in (4.0, 6.0):
        telemetry.record_event(
            "attn_step", backend="ffa", wall_ms=ms, mask_sig="mA",
            mesh_sig="cp1", env_sig="eA", q_shape=[128, 2, 32],
            kv_shape=[128, 1, 32], dtype="float32", cp_size=1)
    telemetry.reset()  # flush the JSONL stream
    tstore.reset()

    mod = load_script(REPORT, "telemetry_report_store_test")
    records = mod.load_records([str(tmp_path)])
    agg = mod.aggregate(records)
    store_dir = str(tmp_path / "store")
    agg["store"] = mod.aggregate_store(store_dir)
    assert agg["store"]["history"] == {"attn_step": 1}
    assert set(agg["store"]) == set(mod.SECTION_SCHEMAS["store"])

    # every emitted section is documented in SECTION_SCHEMAS
    assert set(agg) <= set(mod.SECTION_SCHEMAS)
    assert "store [" in mod.format_summary(agg)

    # CLI: --store + --json round trip, and --schema self-documentation
    assert mod.main(["--json", "--store", store_dir, str(tmp_path)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["store"]["history"] == {"attn_step": 1}
    assert mod.main(["--schema"]) == 0
    schema = json.loads(capsys.readouterr().out)
    assert set(schema) == set(mod.SECTION_SCHEMAS)


# ---------------------------------------------------------------------------
# degraded-rank rows: rank_health + quarantine + step_retry round trips
# ---------------------------------------------------------------------------


def test_rank_health_and_quarantine_round_trip(tmp_path):
    """rank_health folds per-rank aggregates, quarantine rows persist and
    clear, and a fresh handle reading the same directory agrees."""
    d = str(tmp_path / "s")
    st = TelemetryStore(d)
    st.record_rank_health(3, wall_ms=40.0, ewma_ms=40.0, capacity=1.0,
                          degraded=False)
    st.record_rank_health(3, wall_ms=40.0, ewma_ms=40.0, capacity=0.25,
                          degraded=True)
    key = {"mask_sig": "m1", "mesh_sig": "cp4"}
    st.record_quarantine("calc_attn", key, "ffa", 2)
    st.record_quarantine("calc_attn", key, "sdpa", 2)
    st.record_quarantine("calc_attn", key, "sdpa", 2, action="clear")
    st.close()

    other = TelemetryStore(d)
    view = other.rank_health_view()
    assert view["3"]["count"] == 2
    assert view["3"]["capacity"] == 0.25
    assert view["3"]["degraded"] is True
    assert view["3"]["transitions"] == 1  # 1.0 -> 0.25
    assert other.quarantined("calc_attn", key) == {"ffa"}

    # compaction folds both into the snapshot
    other.compact()
    other.close()
    third = TelemetryStore(d)
    assert third.rank_health_view()["3"]["capacity"] == 0.25
    assert third.quarantined("calc_attn", key) == {"ffa"}


def test_ingest_rank_health_and_step_retry_reach_report(
    active_store, tmp_path, capsys
):
    """Collector-emitted rank_health / step_retry records land in the
    store AND in the JSONL stream, and telemetry_report renders both
    sections (schema-documented)."""
    telemetry.record_event(
        "rank_health", rank=3, wall_ms=40.0, ewma_ms=40.0,
        capacity=0.25, degraded=True, transition="degraded",
    )
    telemetry.record_event(
        "rank_health", rank=0, wall_ms=10.0, ewma_ms=10.0,
        capacity=1.0, degraded=False,
    )
    telemetry.record_event(
        "step_retry", stage="DistAttnRuntime.calc_attn", attempt=0,
        from_backend="ffa", to_backend="sdpa",
        error="NumericGuardError", quarantined=False,
    )
    state = tstore.get_store().load()
    assert state.rank_health["3"]["degraded"] is True
    hkinds = {h.get("kind") for h in state.history.values()}
    assert "step_retry" in hkinds
    telemetry.reset()
    tstore.reset()

    mod = load_script(REPORT, "telemetry_report_rank_health_test")
    records = mod.load_records([str(tmp_path)])
    agg = mod.aggregate(records)
    rh = agg["rank_health"]
    assert rh["observations"] == 2
    assert rh["degraded_now"] == 1
    assert rh["transitions"] == {"degraded": 1}
    assert rh["per_rank"]["3"]["capacity"] == 0.25
    sr = agg["step_retry"]
    assert sr["events"] == 1
    assert sr["by_error"] == {"NumericGuardError": 1}
    assert set(agg) <= set(mod.SECTION_SCHEMAS)

    store_dir = str(tmp_path / "store")
    agg["store"] = mod.aggregate_store(store_dir)
    assert agg["store"]["rank_health_rows"] == 2
    text = mod.format_summary(agg)
    assert "rank health" in text and "step retries" in text
    assert mod.main(["--json", "--store", store_dir, str(tmp_path)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["rank_health"]["degraded_now"] == 1
    assert out["step_retry"]["events"] == 1
