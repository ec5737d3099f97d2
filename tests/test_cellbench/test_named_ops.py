"""The scan's calls and the compiler's grouped products have no class of
their own (``event_classes.d/60-kernels-by-name.json`` says why): they stay
out of the FFA classes, and their metrics read them by instruction name."""

import json
import os
import types

import numpy as np
import pytest

from cellbench import manifest, metrics_read, named_ops, peaks
from cellbench import trace_reduce as tr
from cellbench.trace_reduce import Event
from cellbench.traffic_gen import MaskSpec

CELL = "nemotron3nano.packed32k.cp1"
MS = 1e6
CALL = "custom-call tpu_custom_call -> "
FWD = CALL + "(bf16[32,32768,128], f32[32,32768,128])"
F32 = CALL + "(f32[32,32768,128], f32[2,32768,128], f32[2,32768,128])"


def _events(with_new_kernels: bool) -> list[Event]:
    plan = [("magi_fwd_kernel.1", 28, FWD), ("fusion.5", 50, "fusion kOutput -> bf16[8,8]"),
            ("magi_delta_kernel.1", 2, CALL + "f32[32,32768,128]"),
            ("magi_bwd_fused_kernel.1", 40, F32)]
    if with_new_kernels:
        plan += [
            ("magi_ssd_fwd_kernel.2", 9, CALL + "bf16[32768,4096]"),
            ("jvp_magi_ssd_fwd_kernel_.1", 9, CALL + "(bf16[32768,4096], f32[256,8,128,512])"),
            ("transpose_jvp_magi_ssd_bwd_kernel__.1", 21, CALL + "(bf16[32768,4096], bf16[32768,1024], f32[64,32768])"),
            ("ragged-dot-metadata.3", 1, CALL + "(s32[33], s32[143], s32[143], s32[1])"),
            ("ragged-dot-none.7", 11, CALL + "f32[49152,1856]")]
    events, at = [], 10 * MS
    for name, ms, text in plan:
        events.append(Event(name, at, ms * MS, text))
        at += ms * MS
    return events


def _ctx(events):
    cell = manifest.load_cell(manifest.ROOT, CELL)
    host = [Event("step_dispatch", 0, 2 * MS), Event("loss_readback", 2 * MS, 398 * MS)]
    ctx = types.SimpleNamespace(
        cell=cell, family=manifest.load_family(manifest.ROOT, "nemotron_h"),
        config=cell.config, spec=MaskSpec(32768, (0, 32768)),
        peaks=peaks.peaks_for("TPU v5 lite"),
        facts={"rank_rows": [np.arange(32768)], "step_ms": [400.0],
               "traced_step_ms": [400.0]},
        trace=tr.reduce_trace(
            tr.Trace({0: events}, {}, host), tr.load_classes(), steps=1))
    return ctx


def _read(ctx, *names):
    return {m: metrics_read.read_metric(manifest.ROOT, m, ctx) for m in names}


FFA = ("ffa_ms_per_step", "ffa_fwd_ms_per_step", "ffa_bwd_ms_per_step",
       "ffa_roofline", "ffa_delta_ms_per_step", "ffa_bwd_fused_ms_per_step",
       "ffa_bodies_sum_over_ffa")
NEW = ("ssd_ms_per_step", "ssd_bwd_ms_per_step", "ssd_roofline",
       "moe_grouped_ms_per_step")


def test_the_new_kernels_move_no_ffa_metric_and_the_step_closes():
    alone, beside = _ctx(_events(False)), _ctx(_events(True))
    want = _read(alone, *FFA)
    assert want["ffa_ms_per_step"] == pytest.approx(70)
    assert want["ffa_bodies_sum_over_ffa"] == pytest.approx(100.0)
    assert _read(beside, *FFA) == want
    times = beside.trace.devices[0]
    assert times.self_ns["other_compute"] == (50 + 9 + 9 + 21 + 1 + 11) * MS
    assert _read(beside, "layers_sum_over_step")[
        "layers_sum_over_step"] == pytest.approx(100.0)


def test_their_times_are_read_by_instruction_name():
    got = _read(_ctx(_events(True)), *NEW)
    assert got["ssd_ms_per_step"] == pytest.approx(39)
    assert got["ssd_bwd_ms_per_step"] == pytest.approx(21)
    assert got["moe_grouped_ms_per_step"] == pytest.approx(12)
    # four M blocks, three calls each: the forward bound by its bytes
    # (20736 a token), the backward by its bytes too (33280)
    cfg = json.load(open(os.path.join(
        manifest.ROOT, "cellbench/configs/nemotron-3-nano-30b-a3b.json")))
    [calls] = manifest.load_family(manifest.ROOT, "nemotron_h").ssd_calls(cfg)
    assert calls["bytes_per_token"] == {"fwd": 20736, "bwd": 33280}
    assert calls["flops_per_token"]["fwd"] * 128 == 8 * 2 * 128 * 8256 + 64 * (
        2 * 64 * 8256 + 4 * 128 * 128 * 64)
    least = 4 * 32768 * (2 * 20736 + 33280) / 819e9
    assert got["ssd_roofline"] == pytest.approx(100 * least / 0.039)


def test_a_program_without_the_kernels_reads_nothing():
    """The parent commit under this PR's benchmark files: the readers find
    no such instruction, return ``None`` and do not raise."""
    ctx = _ctx(_events(False))
    assert _read(ctx, *NEW) == dict.fromkeys(NEW)
    assert named_ops.ms_per_step(None, named_ops.GROUPED) is None
    ctx.trace = None
    assert _read(ctx, *NEW) == dict.fromkeys(NEW)
