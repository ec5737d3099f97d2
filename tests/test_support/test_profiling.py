"""Profiling helpers: MAGI_ATTENTION_PROFILE_MODE gating (off = identity,
no annotation objects constructed) and the switch_profile context-manager
protocol (exception-safe trace window)."""

import jax
import pytest

from magiattention_tpu.utils import profiling
from magiattention_tpu.utils.profiling import (
    instrument_host,
    instrument_scope,
    profile_scope,
    switch_profile,
)


@pytest.fixture
def spies(monkeypatch):
    calls = {"named_scope": 0, "trace_annotation": 0}

    class _Ctx:
        def __init__(self, kind):
            calls[kind] += 1

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(
        profiling.jax, "named_scope", lambda name: _Ctx("named_scope")
    )
    monkeypatch.setattr(
        profiling.jax.profiler, "TraceAnnotation",
        lambda name: _Ctx("trace_annotation"),
    )
    return calls


def _exercise_all():
    @instrument_scope
    def traced(x):
        return x + 1

    @instrument_host(name="host_fn")
    def hosted(x):
        return x + 1

    assert traced(1) == 2
    assert hosted(1) == 2
    with profile_scope("scope"):
        pass


def test_flag_off_is_identity(monkeypatch, spies):
    monkeypatch.delenv("MAGI_ATTENTION_PROFILE_MODE", raising=False)
    _exercise_all()
    assert spies == {"named_scope": 0, "trace_annotation": 0}


def test_flag_on_annotates(monkeypatch, spies):
    monkeypatch.setenv("MAGI_ATTENTION_PROFILE_MODE", "1")
    _exercise_all()
    # instrument_scope + profile_scope; instrument_host
    assert spies == {"named_scope": 2, "trace_annotation": 1}


@pytest.fixture
def trace_spy(monkeypatch):
    events = []
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d: events.append(("start", d))
    )
    monkeypatch.setattr(
        jax.profiler, "stop_trace", lambda: events.append(("stop",))
    )
    return events


def test_switch_profile_context_manager(trace_spy):
    with switch_profile(log_dir="/tmp/t1") as prof:
        assert prof._running
    assert trace_spy == [("start", "/tmp/t1"), ("stop",)]


def test_switch_profile_exception_safe(trace_spy):
    with pytest.raises(RuntimeError, match="boom"):
        with switch_profile(log_dir="/tmp/t2"):
            raise RuntimeError("boom")
    assert trace_spy == [("start", "/tmp/t2"), ("stop",)]


def test_switch_profile_explicit_api_still_idempotent(trace_spy):
    prof = switch_profile(log_dir="/tmp/t3")
    prof.start()
    prof.start()  # no double start
    prof.stop()
    prof.stop()  # no double stop
    assert trace_spy == [("start", "/tmp/t3"), ("stop",)]
