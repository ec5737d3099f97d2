"""Chaos coverage for the ``serve_decode`` injection site (serving/decode.py):
every Pallas serving rung — base paged decode, speculative verify, int8
dequant, and the kv-head-sharded launch — either RECOVERS through the
gather+FFA rung with outputs BITWISE-identical to the pinned reference
configuration, or RAISES the typed InjectedFault when fallback is off —
never silent corruption. (Lint MAGI-L005 requires every registered site
exercised here; the sharded matrix additionally needs a >=2-device mesh,
which ``make chaos`` provides via XLA_FLAGS host-device forcing.)"""

import jax
import numpy as np
import pytest

from magiattention_tpu import telemetry
from magiattention_tpu.resilience.errors import InjectedFault
from magiattention_tpu.serving import (
    ServeConfig,
    ServeEngine,
    ServeRequest,
    ToyModel,
)

pytestmark = pytest.mark.chaos

CONFIG = ServeConfig(
    page_size=8, num_pages=8, max_slots=2, max_pages_per_seq=4,
    prefill_chunk=8,
)
CONFIG_SPEC = ServeConfig(
    page_size=8, num_pages=8, max_slots=2, max_pages_per_seq=4,
    prefill_chunk=8, spec_tokens=2,
)
CONFIG_INT8 = ServeConfig(
    page_size=8, num_pages=8, max_slots=2, max_pages_per_seq=4,
    prefill_chunk=8, kv_dtype="int8",
)
CONFIG_SHARDED = ServeConfig(
    page_size=8, num_pages=8, max_slots=2, max_pages_per_seq=4,
    prefill_chunk=8, decode_shards=2,
)

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="sharded rung needs >=2 devices (make chaos forces 4 host devices)",
)


@pytest.fixture(autouse=True)
def _telemetry_into_tmp(monkeypatch, tmp_path):
    """These tests turn telemetry on; its JSONL and persisted store (run
    history, chaos quarantines) must not land in ./telemetry of the
    checkout, where a later chip run's copy of the tree would carry them."""
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY_DIR", str(tmp_path / "tel"))


def make_requests(model):
    return [
        ServeRequest(
            req_id=i, prompt=model.prompt(length=length, seed=70 + i),
            max_new_tokens=new_tokens,
        )
        for i, (length, new_tokens) in enumerate([(5, 2), (8, 3)])
    ]


def assert_recovers_bitwise(monkeypatch, config, hops_per_inject_step=1):
    """Shared recover-or-corrupt probe: run the engine pinned to the
    gather+FFA reference rung, then rerun with every kernel-rung launch
    faulted and fallback armed. Recovery must be bitwise-identical and
    every injection must be matched by exactly ``hops_per_inject_step``
    recorded fallback hops per faulted launch (sharded descends
    sharded -> paged_decode -> gather, so its faulted steps inject and
    hop twice; every other backend lands on gather in one hop)."""
    model = ToyModel.create()
    monkeypatch.setenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", "gather_ffa")
    base = make_requests(model)
    ServeEngine(model, config).run(base)

    monkeypatch.delenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", raising=False)
    monkeypatch.setenv("MAGI_ATTENTION_FAULT_INJECT", "serve_decode")
    monkeypatch.setenv("MAGI_ATTENTION_FALLBACK", "1")
    monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY", "1")
    telemetry.reset()
    try:
        faulted = make_requests(model)
        finished = ServeEngine(model, config).run(faulted)
        counters = dict(telemetry.summary()["counters"])
    finally:
        telemetry.reset()

    assert len(finished) == len(base)
    for a, b in zip(base, faulted):
        assert len(a.generated) == len(b.generated), a.req_id
        for x, y in zip(a.generated, b.generated):
            np.testing.assert_array_equal(x, y, err_msg=str(a.req_id))
    assert counters["resilience.injected"] >= hops_per_inject_step
    assert counters["resilience.fallback"] == counters["resilience.injected"]
    return counters


def assert_raises_typed(monkeypatch, config):
    model = ToyModel.create()
    monkeypatch.delenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", raising=False)
    monkeypatch.setenv("MAGI_ATTENTION_FAULT_INJECT", "serve_decode")
    monkeypatch.delenv("MAGI_ATTENTION_FALLBACK", raising=False)
    engine = ServeEngine(model, config)
    with pytest.raises(InjectedFault, match="serve_decode"):
        engine.run(make_requests(model))


class TestServeDecode:
    def test_recovers_via_gather_rung_bitwise(self, monkeypatch):
        """Every decode step's kernel rung faulted: the ladder lands on
        gather+FFA, which is exactly the rung the pinned configuration
        runs — so recovery is not just finite but bitwise-identical."""
        model = ToyModel.create()
        monkeypatch.setenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", "gather_ffa")
        base = make_requests(model)
        ServeEngine(model, CONFIG).run(base)

        monkeypatch.setenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", "paged_decode")
        monkeypatch.setenv("MAGI_ATTENTION_FAULT_INJECT", "serve_decode")
        monkeypatch.setenv("MAGI_ATTENTION_FALLBACK", "1")
        monkeypatch.setenv("MAGI_ATTENTION_TELEMETRY", "1")
        telemetry.reset()
        try:
            faulted = make_requests(model)
            engine = ServeEngine(model, CONFIG)
            finished = engine.run(faulted)
            counters = dict(telemetry.summary()["counters"])
        finally:
            telemetry.reset()

        assert len(finished) == len(base)
        for a, b in zip(base, faulted):
            for x, y in zip(a.generated, b.generated):
                np.testing.assert_array_equal(x, y, err_msg=str(a.req_id))
        # one inject + one fallback hop per decode step, all recorded
        assert counters["resilience.injected"] >= 1
        assert counters["resilience.fallback"] >= 1
        assert counters["resilience.fallback"] == counters[
            "resilience.injected"
        ]

    def test_raises_typed_without_fallback(self, monkeypatch):
        model = ToyModel.create()
        monkeypatch.setenv("MAGI_ATTENTION_BACKEND_SERVE_DECODE", "paged_decode")
        monkeypatch.setenv("MAGI_ATTENTION_FAULT_INJECT", "serve_decode")
        monkeypatch.delenv("MAGI_ATTENTION_FALLBACK", raising=False)
        engine = ServeEngine(model, CONFIG)
        with pytest.raises(InjectedFault, match="serve_decode"):
            engine.run(make_requests(model))


class TestServeDecodeSpec:
    """Speculative verify (spec_tokens=2): the multi-row verify launch is
    the faulted rung; descent lands on the multi-row gather+FFA call,
    whose per-row online-softmax invariance keeps commits bitwise."""

    def test_recovers_via_gather_rung_bitwise(self, monkeypatch):
        assert_recovers_bitwise(monkeypatch, CONFIG_SPEC)

    def test_raises_typed_without_fallback(self, monkeypatch):
        assert_raises_typed(monkeypatch, CONFIG_SPEC)


class TestServeDecodeInt8:
    """Quantized cache (kv_dtype='int8'): the dequant-in-kernel rung is
    faulted; gather_kv dequantizes on the way out with the SAME per-page
    scales, so the gather recovery is bitwise vs the pinned int8 gather
    reference (quantization error never enters the comparison)."""

    def test_recovers_via_gather_rung_bitwise(self, monkeypatch):
        assert_recovers_bitwise(monkeypatch, CONFIG_INT8)

    def test_raises_typed_without_fallback(self, monkeypatch):
        assert_raises_typed(monkeypatch, CONFIG_INT8)


@needs_mesh
class TestServeDecodeSharded:
    """Mesh-sharded launch (decode_shards=2): the faulted descent is
    sharded -> paged_decode -> gather_ffa (the spec/int8 rungs between
    them are infeasible for an unquantized single-row step), so each
    faulted step records TWO inject+fallback pairs — the matched-counter
    assertion covers the whole descent chain."""

    def test_recovers_via_gather_rung_bitwise(self, monkeypatch):
        counters = assert_recovers_bitwise(
            monkeypatch, CONFIG_SHARDED, hops_per_inject_step=2
        )
        assert counters["resilience.injected"] % 2 == 0

    def test_raises_typed_without_fallback(self, monkeypatch):
        assert_raises_typed(monkeypatch, CONFIG_SHARDED)
