"""The per-body FFA metrics: instruction names -> kernel bodies -> times.

A synthetic trace of one step on two devices with the named kernels the
program now compiles to (``magi_fwd_kernel.1``, ...), carrying the HLO texts
of the recorded chip traces (PR 22: the result types of longdoc.cp4's
kernels), worked out by hand."""

import json
import os

import numpy as np
import pytest

from cellbench import kernel_times as kt
from cellbench import manifest, metrics_read, peaks
from cellbench import trace_reduce as tr
from cellbench.trace_reduce import Event
from cellbench.traffic_gen import MaskSpec

CLASSES = tr.load_classes()
# the kernels' result types at cp 4, as the recorded traces have them
FWD = "custom-call tpu_custom_call -> (bf16[32,8192,128], f32[32,8192,128])"
F32_Q = "custom-call tpu_custom_call -> f32[32,8192,128]"  # delta and dq
DKV = "custom-call tpu_custom_call -> (f32[8,32768,128], f32[8,32768,128])"
FUSED = ("custom-call tpu_custom_call -> (f32[32,8192,128], "
         "f32[8,32768,128], f32[8,32768,128])")
FUSION = "fusion kOutput -> bf16[8192,5120]"
NEW_METRICS = (
    "ffa_delta_ms_per_step", "ffa_bwd_dq_ms_per_step",
    "ffa_bwd_dkv_ms_per_step", "ffa_bwd_fused_ms_per_step",
    "ffa_bodies_sum_over_ffa")
MS = 1e6  # nanoseconds


def _device(names: dict[str, str], scale: float = 1.0) -> list[Event]:
    """One step: forward 30 ms, re-forward 30, delta 2, dq 40, dkv 28,
    a fusion 50 and a group cast 5, back to back from 10 ms."""
    plan = [
        (names["fwd"] + ".1", 30, FWD), ("fusion.5", 50, FUSION),
        ("ragged_all_to_all.1", 5, "ragged-all-to-all -> bf16[24576,8,256]"),
        (names["fwd"] + ".2", 30, FWD), (names["delta"] + ".2", 2, F32_Q),
        (names["dq"] + ".3", 40, F32_Q), (names["dkv"] + ".4", 28, DKV),
    ]
    events, at = [], 10 * MS
    for name, ms, text in plan:
        events.append(Event(name, at, ms * scale * MS, text))
        at += ms * scale * MS
    return events


NAMED = {"fwd": "magi_fwd_kernel", "delta": "magi_delta_kernel",
         "dq": "magi_bwd_dq_kernel", "dkv": "magi_bwd_dkv_kernel_gqa"}


def _ctx(devices: dict[int, list[Event]] | None, steps: int = 1,
         classes=CLASSES):
    trace = None
    if devices is not None:
        host = [Event("step_dispatch", 0, 2 * MS),
                Event("loss_readback", 2 * MS, 398 * MS)]
        trace = tr.reduce_trace(tr.Trace(devices, {}, host), classes, steps)
    # the readers of this file look at the trace alone
    return metrics_read.Context(
        cell=None, family=None, config={}, spec=None, peaks={}, facts={},
        trace=trace)


def _read(ctx) -> dict:
    return {name: metrics_read.read_metric(manifest.ROOT, name, ctx)
            for name in NEW_METRICS}


def test_the_five_readers_by_hand():
    # device 1 runs everything 10% slower: the values are means
    ctx = _ctx({0: _device(NAMED), 1: _device(NAMED, 1.1)})
    got = _read(ctx)
    assert got["ffa_delta_ms_per_step"] == pytest.approx(2 * 1.05)
    assert got["ffa_bwd_dq_ms_per_step"] == pytest.approx(40 * 1.05)
    assert got["ffa_bwd_dkv_ms_per_step"] == pytest.approx(28 * 1.05)
    assert got["ffa_bwd_fused_ms_per_step"] == 0.0  # measured: none ran
    assert got["ffa_bodies_sum_over_ffa"] == pytest.approx(100.0)
    # the closure: the bodies' names against the result-type classes
    bwd = metrics_read.read_metric(manifest.ROOT, "ffa_bwd_ms_per_step", ctx)
    fwd = metrics_read.read_metric(manifest.ROOT, "ffa_fwd_ms_per_step", ctx)
    assert bwd == pytest.approx(70 * 1.05) and fwd == pytest.approx(60 * 1.05)
    assert sum(got[m] for m in NEW_METRICS[:4]) == pytest.approx(bwd)
    assert kt.ms_per_step(ctx, "fwd") == pytest.approx(fwd)


def test_times_are_per_step():
    one, three = _ctx({0: _device(NAMED)}), _ctx({0: _device(NAMED)}, steps=3)
    assert _read(three)["ffa_bwd_dq_ms_per_step"] == pytest.approx(
        _read(one)["ffa_bwd_dq_ms_per_step"] / 3)
    assert _read(three)["ffa_bodies_sum_over_ffa"] == pytest.approx(100.0)


def test_no_trace_reads_nothing():
    """The CPU rehearsal has no device plane."""
    assert _read(_ctx(None)) == dict.fromkeys(NEW_METRICS)


def test_a_program_without_the_names_reads_nothing():
    """The parent commit: every kernel is ``shard_map.N`` (four chips) or
    ``jvp_DistAttnRuntime.calc_attn_.N`` (one). The readers return nothing,
    the fused one too, and do not raise."""
    for old in ("shard_map", "jvp_DistAttnRuntime.calc_attn_", "custom-call"):
        ctx = _ctx({0: _device(dict.fromkeys(NAMED, old))})
        assert _read(ctx) == dict.fromkeys(NEW_METRICS)
        assert metrics_read.read_metric(
            manifest.ROOT, "ffa_bwd_ms_per_step", ctx) == pytest.approx(70)


def test_a_call_site_that_lost_its_name_shows():
    ctx = _ctx({0: _device({**NAMED, "dq": "shard_map"})})
    got = _read(ctx)
    assert got["ffa_bodies_sum_over_ffa"] == pytest.approx(100 * 90 / 130)
    assert got["ffa_bwd_dq_ms_per_step"] == 0.0
    bwd = metrics_read.read_metric(manifest.ROOT, "ffa_bwd_ms_per_step", ctx)
    assert sum(got[m] for m in NEW_METRICS[:4]) == pytest.approx(bwd - 40)


def test_a_kernel_in_the_wrong_class_breaks_the_closure():
    """A backward body that returned the activation type would be classed
    forward by ``event_classes.json``: the names still add up to the FFA
    classes, and the backward's four no longer to ``ffa_bwd``."""
    devices = {0: [
        Event(e.name, e.start, e.dur, FWD if "delta" in e.name else e.text)
        for e in _device(NAMED)]}
    ctx = _ctx(devices)
    got = _read(ctx)
    bwd = metrics_read.read_metric(manifest.ROOT, "ffa_bwd_ms_per_step", ctx)
    assert got["ffa_bodies_sum_over_ffa"] == pytest.approx(100.0)
    assert sum(got[m] for m in NEW_METRICS[:4]) == pytest.approx(bwd + 2)


def test_decorated_names_read_the_same():
    """Where the kernel's scope is the outermost, JAX decorates it with the
    transform, as it did ``jvp_DistAttnRuntime.calc_attn_``."""
    plain = _read(_ctx({0: _device(NAMED)}))
    decorated = _read(_ctx({0: _device({
        "fwd": "jvp_magi_fwd_kernel_",
        "delta": "transpose_jvp_magi_delta_kernel__",
        "dq": "transpose_jvp_magi_bwd_dq_kernel__",
        "dkv": "transpose_jvp_magi_bwd_dkv_kernel_gqa__"})}))
    assert decorated == plain


def test_the_fused_backward_and_the_packed_bodies():
    names = {"fwd": "magi_fwd_kernel_gqa", "delta": "magi_delta_kernel",
             "dq": "magi_bwd_dq_kernel_gqa", "dkv": "magi_bwd_dkv_kernel"}
    events = _device(names)
    events.append(Event("magi_bwd_fused_kernel_gqa.9", 300 * MS, 60 * MS, FUSED))
    events.append(Event("magi_bwd_fused_kernel.8", 360 * MS, 7 * MS, FUSED))
    got = _read(_ctx({0: events}))
    assert got["ffa_bwd_dq_ms_per_step"] == pytest.approx(40)
    assert got["ffa_bwd_dkv_ms_per_step"] == pytest.approx(28)
    assert got["ffa_bwd_fused_ms_per_step"] == pytest.approx(67)
    assert got["ffa_bodies_sum_over_ffa"] == pytest.approx(100.0)


@pytest.mark.parametrize("name,kind", [
    ("magi_fwd_kernel", "fwd"), ("magi_fwd_kernel_gqa", "fwd"),
    ("magi_delta_kernel", "delta"),
    ("magi_bwd_dq_kernel", "bwd_dq"), ("magi_bwd_dq_kernel_gqa", "bwd_dq"),
    ("magi_bwd_dkv_kernel", "bwd_dkv"),
    ("magi_bwd_dkv_kernel_gqa", "bwd_dkv"),
    ("magi_bwd_fused_kernel", "bwd_fused"),
    ("magi_bwd_fused_kernel_gqa", "bwd_fused"),
    ("transpose_jvp_magi_bwd_dq_kernel_gqa__", "bwd_dq"),
    # the library's, but no FFA body: counted in the sum over magi_* alone
    ("magi_paged_decode_kernel", "other"),
    ("shard_map", None), ("jvp_DistAttnRuntime.calc_attn_", None),
    ("fusion", None), ("ragged_all_to_all", None), ("custom-call", None),
])
def test_kind_of(name, kind):
    assert kt.kind_of(name) == kind
    label = f"ffa_bwd:{name} {F32_Q}"
    assert kt.instruction_name(label) == name


@pytest.mark.parametrize("name,text,cls", [
    ("magi_fwd_kernel.1", FWD, "ffa_fwd"),
    ("magi_fwd_kernel_gqa.1", FWD, "ffa_fwd"),
    ("magi_delta_kernel.2", F32_Q, "ffa_bwd"),
    ("magi_bwd_dq_kernel.3", F32_Q, "ffa_bwd"),
    ("magi_bwd_dkv_kernel_gqa.4", DKV, "ffa_bwd"),
    ("magi_bwd_fused_kernel_gqa.5", FUSED, "ffa_bwd"),
    ("transpose_jvp_magi_bwd_dq_kernel__.3", F32_Q, "ffa_bwd"),
])
def test_the_named_kernels_keep_their_classes(name, text, cls):
    """``event_classes.json`` is as it was: it classes by target and result
    type, and no kernel's name reads as a collective's."""
    assert tr.classify(Event(name, 0, 1, text), CLASSES) == cls


def test_the_names_known_here_are_the_programs():
    """One source of truth: the bodies this file looks for are functions of
    ``kernels/ffa.py``, and the prefix is the program's."""
    from magiattention_tpu.kernels import _named, ffa

    assert kt.PREFIX == _named.KERNEL_SCOPE_PREFIX
    for bodies in kt.BODIES.values():
        for body in bodies:
            assert _named.kernel_scope_name(getattr(ffa, body)) == (
                kt.PREFIX + body)


def test_the_manifest_lists_the_five_for_every_cell():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW_METRICS:
        entry = per_layer[name]
        assert "workloads" not in entry
        assert (entry["layer"], entry["source"], entry["moves"]) == (
            "ffa", "device_trace", "tokens_per_s")
    assert per_layer["ffa_bodies_sum_over_ffa"]["better"] == "higher"


# -- a kernel that is not FFA ------------------------------------------------

SECOND_FAMILY = os.path.join(os.path.dirname(__file__), "data", "second_family")
GATE = "custom-call tpu_custom_call -> (bf16[8192,30,192], f32[30,8192,96])"
GATE_BWD = "custom-call tpu_custom_call -> f32[8192,30,192]"
FFA_METRICS = (
    "ffa_ms_per_step", "ffa_fwd_ms_per_step", "ffa_bwd_ms_per_step",
    "ffa_roofline", *NEW_METRICS)


def _with_gate_kernels(events: list[Event]) -> list[Event]:
    """The step with a forward and a backward call of another Pallas
    kernel after it, 11 and 17 ms: same target, result types that read as
    FFA's forward and backward, and the library's prefix."""
    at = max(e.end for e in events)
    return events + [
        Event("magi_gate_fwd_kernel.7", at, 11 * MS, GATE),
        Event("magi_gate_bwd_kernel.8", at + 11 * MS, 17 * MS, GATE_BWD)]


def _roofline_ctx(devices, classes):
    """A context ``ffa_roofline`` can read too: the second family's counts,
    a causal document and the chip's peaks."""
    ctx = _ctx(devices, classes=classes)
    ctx.family = manifest.load_family(SECOND_FAMILY, "mixer")
    with open(os.path.join(
            SECOND_FAMILY, "cellbench/configs/mixer-toy.json")) as f:
        ctx.config = json.load(f)
    ctx.spec = MaskSpec(8192, (0, 8192))
    ctx.peaks = peaks.peaks_for("TPU v5 lite")
    ctx.facts = {"rank_rows": [np.arange(8192)]}
    return ctx


def test_a_kernel_outside_ffa_moves_no_ffa_metric():
    """With its class file, the other kernel's calls are in classes of
    their own, its own metric reads them, and every ``ffa_*`` metric reads
    as it does on the step without them."""
    classes = tr.load_classes(SECOND_FAMILY)
    alone = _roofline_ctx({0: _device(NAMED)}, classes)
    beside = _roofline_ctx({0: _with_gate_kernels(_device(NAMED))}, classes)
    want = {m: metrics_read.read_metric(manifest.ROOT, m, alone)
            for m in FFA_METRICS}
    assert want["ffa_ms_per_step"] == pytest.approx(130)
    assert want["ffa_bodies_sum_over_ffa"] == pytest.approx(100.0)
    # one attending layer of four, two calls a step: the least time of 3.5
    # forwards over a causal 8192 at 8 heads of 128, over 130 ms
    area = 8192 * 8193 // 2
    assert want["ffa_roofline"] == pytest.approx(
        100 * 3.5 * 4 * area * 128 * 8 / 197e12 / 0.130)
    assert {m: metrics_read.read_metric(manifest.ROOT, m, beside)
            for m in FFA_METRICS} == want
    times = beside.trace.devices[0]
    assert times.self_ns["gate_fwd"] == 11 * MS
    assert times.self_ns["gate_bwd"] == 17 * MS
    assert metrics_read.read_metric(
        SECOND_FAMILY, "gate_ms_per_step", beside) == pytest.approx(28)
    assert alone.trace.devices[0].self_ns["gate_fwd"] == 0.0
    # and the step still closes: every event is in exactly one class
    assert sum(times.self_ns.values()) == pytest.approx(times.busy_ns)


def test_without_its_class_file_the_other_kernel_reads_as_ffa():
    """What the file is for: by target and result type alone the calls
    are ``ffa_fwd`` and ``ffa_bwd`` (they carry the prefix and no FFA
    body's name, so the bodies' closure stays at 100 and hides nothing)."""
    ctx = _ctx({0: _with_gate_kernels(_device(NAMED))})
    assert metrics_read.read_metric(
        manifest.ROOT, "ffa_ms_per_step", ctx) == pytest.approx(130 + 28)
    assert kt.ms_per_step(ctx, "other") == pytest.approx(28)


def test_the_classes_of_event_classes_json_are_the_parents():
    """``event_classes.json``'s classes and patterns as PR 25 had them, last
    in the list, and before them only what files of ``event_classes.d/``
    bring (none today): the recorded traces class as they did."""
    assert [(c, p.pattern) for c, p in CLASSES][-5:] == [
        ("ffa_fwd", r"(?:custom-call tpu_custom_call -> \((bf16|f16)\[)"),
        ("ffa_bwd", "(?:custom-call tpu_custom_call)"),
        ("group_comm",
         r"(?:^(ragged_all_to_all|all_to_all|ppermute|collective_permute|"
         r"psum|pmax|pmin|all_gather|reduce_scatter|psum_scatter)\b)|"
         "(?: ragged-all-to-all)"),
        ("param_comm",
         "(?: (all-gather|all-reduce|reduce-scatter|all-to-all|"
         "collective-permute|collective-broadcast)(-start|-done)? )"),
        ("other_compute", "(?:)"),
    ]
    folder = os.path.join(manifest.ROOT, "cellbench", "event_classes.d")
    added = 0
    for name in os.listdir(folder) if os.path.isdir(folder) else ():
        if name.endswith(".json"):
            with open(os.path.join(folder, name)) as f:
                added += len(json.load(f)["classes"])
    assert len(CLASSES) == added + 5
