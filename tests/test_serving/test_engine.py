"""Engine end-to-end invariants: bitwise equality vs the sequential
replay oracle, eviction-transparency, serve_step telemetry round trip
through scripts/telemetry_report.py, and the typed page-exhaustion path."""

import json
import os

import numpy as np
import pytest

from magiattention_tpu import telemetry
from magiattention_tpu.resilience.errors import PageExhaustedError
from magiattention_tpu.serving import (
    ServeConfig,
    ServeEngine,
    ServeRequest,
    ToyModel,
    run_reference,
)

from tests.test_support.script_loading import load_script

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
REPORT = os.path.join(REPO, "scripts", "telemetry_report.py")


@pytest.fixture(scope="module")
def model():
    return ToyModel.create()


def make_requests(model, spec, seed=100):
    return [
        ServeRequest(
            req_id=i, prompt=model.prompt(length=length, seed=seed + i),
            max_new_tokens=new_tokens,
        )
        for i, (length, new_tokens) in enumerate(spec)
    ]


def assert_bitwise(requests, reference):
    for req in requests:
        assert len(req.generated) == req.max_new_tokens, req.req_id
        for got, want in zip(req.generated, reference[req.req_id]):
            np.testing.assert_array_equal(got, want, err_msg=str(req.req_id))


def test_engine_matches_reference_bitwise(model, monkeypatch):
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", "gather_ffa")
    config = ServeConfig(
        page_size=8, num_pages=12, max_slots=3, max_pages_per_seq=4,
        prefill_chunk=8,
    )
    # ragged: single-token prompt, page-boundary prompt, slot turnover
    requests = make_requests(
        model, [(5, 3), (8, 2), (17, 2), (1, 4), (9, 3)]
    )
    engine = ServeEngine(model, config)
    finished = engine.run(requests)
    assert len(finished) == len(requests)
    assert_bitwise(requests, run_reference(model, requests, config))


def test_eviction_is_output_transparent(model, monkeypatch):
    """A pool tight enough to force eviction/restart must still produce
    bitwise-identical outputs — restarts recompute exactly."""
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", "gather_ffa")
    config = ServeConfig(
        page_size=4, num_pages=6, max_slots=3, max_pages_per_seq=6,
        prefill_chunk=8,
    )
    requests = make_requests(model, [(6, 5), (4, 4), (9, 6), (3, 8)], seed=50)
    engine = ServeEngine(model, config)
    finished = engine.run(requests)
    assert len(finished) == len(requests)
    assert sum(r.evictions for r in requests) > 0, (
        "workload no longer forces an eviction; tighten the pool"
    )
    assert_bitwise(requests, run_reference(model, requests, config))


def test_unservable_request_raises_typed(model, monkeypatch):
    """One request alone outgrowing the whole pool surfaces the typed
    PageExhaustedError (nothing else is evictable)."""
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", "gather_ffa")
    config = ServeConfig(
        page_size=4, num_pages=2, max_slots=2, max_pages_per_seq=4,
        prefill_chunk=8,
    )
    engine = ServeEngine(model, config)
    with pytest.raises(PageExhaustedError):
        engine.run(make_requests(model, [(8, 4)], seed=60))


def test_serve_step_telemetry_round_trip(model, monkeypatch, tmp_path):
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", "gather_ffa")
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY", "1")
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY_DIR", str(tmp_path))
    telemetry.reset()
    try:
        config = ServeConfig(
            page_size=8, num_pages=8, max_slots=2, max_pages_per_seq=4,
            prefill_chunk=8,
        )
        requests = make_requests(model, [(5, 2), (9, 3), (3, 2)], seed=80)
        engine = ServeEngine(model, config)
        engine.run(requests)
        steps = engine.step_count
        counters = telemetry.summary()["counters"]
        assert counters["events.serve_step"] == steps
        assert counters["serve.steps"] == steps
    finally:
        telemetry.reset()  # close the JSONL handle before reading

    records = []
    for fp in sorted(tmp_path.glob("*.jsonl")):
        with open(fp) as f:
            records.extend(json.loads(line) for line in f if line.strip())
    serve_recs = [r for r in records if r["kind"] == "serve_step"]
    assert len(serve_recs) == steps
    for key in ("wall_ms", "occupancy", "pages_in_use", "admitted",
                "evicted", "completed", "prefill_tokens", "decode_tokens"):
        assert key in serve_recs[0], key
    assert sum(r["completed"] for r in serve_recs) == len(requests)
    assert sum(r["admitted"] for r in serve_recs) >= len(requests)
    assert max(r["occupancy"] for r in serve_recs) <= 1.0

    mod = load_script(REPORT, "telemetry_report")
    agg = mod.aggregate(mod.load_records([str(tmp_path)]))
    sv = agg["serve"]
    assert sv["steps"] == steps
    assert sv["completed_total"] == len(requests)
    assert sv["decode_tokens_total"] == sum(
        r.max_new_tokens for r in requests
    )
    assert 0.0 < sv["occupancy_mean"] <= 1.0
    # scale stamps: a one-token-per-tick f32 engine accepts every "draft"
    assert sv["kv_dtype"] == "float32"
    assert sv["shards"] == 1 and sv["spec_k"] == 1
    assert sv["accept_rate"] == 1.0
    assert sv["accepted_per_tick"] >= 1.0
    text = mod.format_summary(agg)
    assert "serving steps=" in text and "tokens: prefill=" in text
    assert "scale: kv_dtype=float32" in text


def test_telemetry_off_is_zero_overhead(model, monkeypatch):
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", "gather_ffa")
    monkeypatch.delenv("MAGI_ATTENTION_TELEMETRY", raising=False)
    telemetry.reset()
    config = ServeConfig(
        page_size=8, num_pages=8, max_slots=2, max_pages_per_seq=4,
        prefill_chunk=8,
    )
    engine = ServeEngine(model, config)
    engine.run(make_requests(model, [(5, 2)], seed=90))
    assert not telemetry.summary().get("counters")
