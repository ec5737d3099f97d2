"""Serving-scale axes (docs/serving_scale.md): speculative verify commits
bitwise vs the one-token-per-tick replay oracle (accept/rollback included),
the int8 cache is bitwise vs an int8 oracle and within quantization
tolerance of f32, the sharded decode launch is bitwise vs single-device,
and the page-pool accounting certifies the >=2x residency claim."""

import jax
import numpy as np
import pytest

from magiattention_tpu.serving import (
    ServeConfig,
    ServeEngine,
    ServeRequest,
    ToyModel,
    oracle_draft_fn,
    run_reference,
)
from magiattention_tpu.serving.cache import kv_page_bytes, slot_residency

from tests.test_serving.test_engine import assert_bitwise, make_requests


@pytest.fixture(scope="module")
def model():
    return ToyModel.create()


SPEC_CONFIG = ServeConfig(
    page_size=8, num_pages=12, max_slots=3, max_pages_per_seq=4,
    prefill_chunk=8, spec_tokens=2,
)
INT8_CONFIG = ServeConfig(
    page_size=8, num_pages=12, max_slots=3, max_pages_per_seq=4,
    prefill_chunk=8, kv_dtype="int8",
)
F32_CONFIG = ServeConfig(
    page_size=8, num_pages=12, max_slots=3, max_pages_per_seq=4,
    prefill_chunk=8,
)
# ragged mix: page-boundary prompt, single-token prompt, slot turnover
WORKLOAD = [(5, 3), (8, 2), (17, 2), (1, 4), (9, 3)]


def run_collect(engine, requests):
    """engine.run() but keeping every tick's stats dict."""
    for req in requests:
        engine.submit(req)
    stats = []
    while engine.scheduler.has_work():
        stats.append(engine.step())
        assert engine.step_count < 10_000
    return stats


# -- speculative verify ------------------------------------------------------


@pytest.mark.slow  # full-workload twin of the serve-smoke pass
def test_spec_greedy_draft_commits_bitwise_with_rollback(model, monkeypatch):
    """The greedy self-draft misses often (it ignores the cache), so this
    run exercises REAL rollbacks — and the committed tokens must still be
    a bitwise replay of the sequential oracle."""
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", "gather_ffa")
    requests = make_requests(model, WORKLOAD)
    engine = ServeEngine(model, SPEC_CONFIG)
    stats = run_collect(engine, requests)
    assert len(engine.finished) == len(requests)
    assert_bitwise(requests, run_reference(model, requests, SPEC_CONFIG))
    attempted = sum(s["draft_attempted"] for s in stats)
    accepted = sum(s["draft_accepted"] for s in stats)
    assert accepted < attempted, (
        "greedy draft accepted everything; rollback path not exercised"
    )
    assert accepted >= 1


@pytest.mark.slow  # full-workload twin of the serve-smoke pass
def test_spec_oracle_draft_accepts_every_row(model, monkeypatch):
    """With the oracle draft (true next inputs) every verify row commits:
    accept_rate == 1 on every tick that decoded, and the engine finishes
    in fewer decode ticks than one-token-per-tick."""
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", "gather_ffa")
    requests = make_requests(model, WORKLOAD)
    reference = run_reference(model, requests, SPEC_CONFIG)
    engine = ServeEngine(
        model, SPEC_CONFIG, draft_fn=oracle_draft_fn(reference)
    )
    stats = run_collect(engine, requests)
    assert_bitwise(requests, reference)
    decoding = [s for s in stats if s["draft_attempted"]]
    assert decoding
    for s in decoding:
        # eviction restarts may cap a request's final commit below spec_k
        # (remaining budget), so compare against the commit-capped bound
        assert s["draft_accepted"] == s["decode_tokens"]
        assert s["accept_rate"] > 0.0


@pytest.mark.slow  # full-workload twin of the serve-smoke pass
def test_spec_kernel_rung_within_tolerance(model, monkeypatch):
    """Unpinned spec engine (multi-row Pallas verify rung) vs the replay
    oracle: same token COUNT, outputs within kernel tolerance (the rung is
    not bitwise vs gather, so accept decisions may differ — commits still
    track the oracle trajectory to fp32 accumulation error)."""
    monkeypatch.delenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", raising=False)
    requests = make_requests(model, WORKLOAD)
    engine = ServeEngine(model, SPEC_CONFIG)
    run_collect(engine, requests)
    reference = run_reference(model, requests, SPEC_CONFIG)
    for req in requests:
        assert len(req.generated) == req.max_new_tokens
        for got, want in zip(req.generated, reference[req.req_id]):
            np.testing.assert_allclose(
                got, want, rtol=0, atol=1e-5, err_msg=str(req.req_id)
            )


# -- int8 KV cache -----------------------------------------------------------


@pytest.mark.slow  # full-workload twin of the serve-smoke pass
def test_int8_engine_bitwise_vs_int8_oracle(model, monkeypatch):
    """Quantized append is a pure function of a page's append history, so
    the int8 engine on the gather rung replays the int8 oracle bitwise."""
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", "gather_ffa")
    requests = make_requests(model, WORKLOAD)
    ServeEngine(model, INT8_CONFIG).run(requests)
    assert_bitwise(requests, run_reference(model, requests, INT8_CONFIG))


def test_int8_within_tolerance_of_f32(model, monkeypatch):
    """int8-vs-f32 is the quantization error itself — bounded, not
    bitwise. Covers both the kernel rung (unpinned) and the f32 oracle."""
    monkeypatch.delenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", raising=False)
    requests = make_requests(model, WORKLOAD)
    ServeEngine(model, INT8_CONFIG).run(requests)
    f32_ref = run_reference(model, requests, F32_CONFIG)
    worst = 0.0
    for req in requests:
        assert len(req.generated) == req.max_new_tokens
        for got, want in zip(req.generated, f32_ref[req.req_id]):
            worst = max(worst, float(np.max(np.abs(got - want))))
    assert worst < 0.1, f"int8 quantization error {worst} out of tolerance"
    assert worst > 0.0, "int8 run was bitwise-equal to f32: not quantizing?"


def test_int8_at_least_doubles_slot_residency():
    """The page-pool accounting behind the tokens/sec/chip lever: under a
    fixed HBM budget, int8 pages hold >= 2x the slots of bf16 pages (and
    ~4x of f32 — 'approximately', the per-page scale rows eat a sliver)."""
    args = dict(page_size=16, n_kv_heads=8, head_dim=128)
    budget = 64 * 1024 * 1024
    pages_per_slot = 64
    slots = {
        dt: slot_residency(
            budget, kv_page_bytes(kv_dtype=dt, **args), pages_per_slot
        )
        for dt in ("float32", "bfloat16", "int8")
    }
    assert slots["int8"] >= 2 * slots["bfloat16"] - 1
    assert slots["int8"] >= 3 * slots["float32"]
    ratio = kv_page_bytes(kv_dtype="bfloat16", **args) / kv_page_bytes(
        kv_dtype="int8", **args
    )
    assert 1.9 < ratio <= 2.0


# -- sharded decode ----------------------------------------------------------


@pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="sharded rung needs >=2 devices (serve-smoke forces a CPU mesh)",
)
def test_sharded_engine_bitwise_vs_single_device(model, monkeypatch):
    monkeypatch.delenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", raising=False)
    single = make_requests(model, WORKLOAD)
    ServeEngine(model, F32_CONFIG).run(single)

    sharded_cfg = ServeConfig(
        page_size=8, num_pages=12, max_slots=3, max_pages_per_seq=4,
        prefill_chunk=8, decode_shards=2, pool_shards=2,
    )
    sharded = make_requests(model, WORKLOAD)
    ServeEngine(model, sharded_cfg).run(sharded)
    for a, b in zip(single, sharded):
        assert len(a.generated) == len(b.generated)
        for x, y in zip(a.generated, b.generated):
            np.testing.assert_array_equal(x, y, err_msg=str(a.req_id))


# -- telemetry stamps --------------------------------------------------------


def test_serve_step_stats_carry_scale_stamps(model, monkeypatch):
    """Every tick's stats (== the serve_step telemetry record) must stamp
    the scale knobs so the telemetry report can segment by them."""
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", "gather_ffa")
    engine = ServeEngine(model, SPEC_CONFIG)
    stats = run_collect(engine, make_requests(model, [(5, 2)], seed=110))
    for s in stats:
        assert s["kv_dtype"] == "float32"
        assert s["shards"] == 1
        assert s["spec_k"] == 2
        assert 0.0 <= s["accept_rate"] <= 1.0
    decoding = [s for s in stats if s["draft_attempted"]]
    assert decoding and all(s["accept_rate"] > 0 for s in decoding)
