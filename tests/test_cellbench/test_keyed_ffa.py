"""The ``afmoe`` cell's per-key FFA metrics and the grouped products'
roofline, on a trace made by hand: a key's label after the body's name picks
the time, the family's ``ffa_calls`` group of that kind on ITS window prices
it, and the accepted FFA metrics see the same instructions as before."""

import dataclasses
import types

import numpy as np
import pytest

from cellbench import flops, keyed_ffa, manifest, metrics_read, peaks
from cellbench import trace_reduce as tr
from cellbench.trace_reduce import Event
from cellbench.traffic_gen import MaskSpec

CELL = "trinitymini.longdocs32k.cp1"
MS = 1e6
CALL = "custom-call tpu_custom_call -> "
FWD = CALL + "(bf16[32,32768,128], f32[32,32768,128])"
F32 = CALL + "(f32[32,32768,128], f32[4,32768,128], f32[4,32768,128])"
SPEC = MaskSpec(32768, (0, 8803, 13026, 19426, 32768))
NEW = ("ffa_window_ms_per_step", "ffa_full_ms_per_step",
       "ffa_window_roofline", "ffa_full_roofline", "moe_grouped_roofline")


def _events(labelled: bool) -> list[Event]:
    w, f = ("_window", "_full") if labelled else ("", "")
    plan = [
        (f"magi_fwd_kernel{w}.1", 20, FWD), (f"magi_fwd_kernel{w}.2", 20, FWD),
        (f"jvp_magi_fwd_kernel{f}_.3", 30, FWD),
        ("fusion.5", 50, "fusion kOutput -> bf16[8,8]"),
        (f"magi_delta_kernel{w}.1", 2, CALL + "f32[32,32768,128]"),
        (f"transpose_jvp_magi_bwd_fused_kernel{w}__.1", 40, F32),
        (f"magi_bwd_fused_kernel{f}.4", 60, F32),
        (f"magi_delta_kernel{f}.2", 1, CALL + "f32[32,32768,128]"),
        ("magi_ragged_dot_kernel.7", 11, CALL + "f32[65536,2048]"),
        ("magi_ragged_dot_dw_kernel.2", 5, CALL + "bf16[32,2048,2048]")]
    events, at = [], 10 * MS
    for name, ms, text in plan:
        events.append(Event(name, at, ms * MS, text))
        at += ms * MS
    return events


def _ctx(events, counters):
    cell = manifest.load_cell(manifest.ROOT, CELL)
    family = manifest.load_family(manifest.ROOT, cell.config["family"])
    family.routing_counters = lambda: counters
    host = [Event("step_dispatch", 0, 2 * MS),
            Event("loss_readback", 2 * MS, 398 * MS)]
    return types.SimpleNamespace(
        cell=cell, family=family, config=cell.config, spec=SPEC,
        peaks=peaks.peaks_for("TPU v5 lite"),
        facts={"rank_rows": [np.arange(32768)], "step_ms": [400.0],
               "traced_step_ms": [400.0]},
        trace=tr.reduce_trace(
            tr.Trace({0: events}, {}, host), tr.load_classes(), steps=1))


def _read(ctx, *names):
    return {m: metrics_read.read_metric(manifest.ROOT, m, ctx) for m in names}


def test_the_label_picks_a_keys_time_and_the_two_add_up_to_ffa():
    ctx = _ctx(_events(True), {"routed_rows": 4 * 65536.0})
    got = _read(ctx, *NEW, "ffa_ms_per_step", "ffa_bodies_sum_over_ffa",
                "ffa_bwd_fused_ms_per_step", "ffa_delta_ms_per_step",
                "moe_grouped_ms_per_step")
    assert got["ffa_window_ms_per_step"] == pytest.approx(82)
    assert got["ffa_full_ms_per_step"] == pytest.approx(91)
    assert got["ffa_ms_per_step"] == pytest.approx(82 + 91)
    # the accepted readers still find the bodies under the labels
    assert got["ffa_bodies_sum_over_ffa"] == pytest.approx(100.0)
    assert got["ffa_bwd_fused_ms_per_step"] == pytest.approx(100)
    assert got["ffa_delta_ms_per_step"] == pytest.approx(3)
    assert got["moe_grouped_ms_per_step"] == pytest.approx(16)
    # each kind on its own mask: four window layers on the 2048-key band,
    # one full layer on the triangle, 4.5 forwards a layer under remat
    pk = ctx.peaks
    for kind, layers, window, spent in (
            ("window", 4, 2048, 0.082), ("full", 1, None, 0.091)):
        area = flops.band_area(dataclasses.replace(SPEC, window=window))
        least = layers * 4.5 * flops.attn_fwd_flops(
            area, 32, 128, 128) / pk["bf16_flops"]
        assert got[f"ffa_{kind}_roofline"] == pytest.approx(
            100 * least / spent, rel=1e-6)
    assert got["ffa_window_roofline"] < 200 and got["ffa_full_roofline"] < 100
    # the grouped products: five calls a product a token block, FLOP-bound
    least = 4 * 5 * (2 * 65536 * 2048 * 2048 + 2 * 65536 * 1024 * 2048) / (
        pk["bf16_flops"])
    assert got["moe_grouped_roofline"] == pytest.approx(100 * least / 0.016)


def test_ffa_roofline_is_not_read_in_this_cell():
    """Its reader prices every group on the cell's one ``spec`` (window
    null): four window layers as full ones. The entry lists its cells."""
    cell = manifest.load_cell(manifest.ROOT, CELL)
    names = {m["name"] for m in cell.per_layer}
    assert "ffa_roofline" not in names and set(NEW) <= names
    assert {"moe_grouped_ms_per_step", "moe_routed_rows_per_step",
            "moe_expert_load_max_over_mean", "ffa_ms_per_step"} <= names


def test_a_program_that_labels_nothing_reads_nothing():
    """The parent commit under this PR's benchmark files: no instruction
    carries a label, the readers return ``None`` and do not raise."""
    ctx = _ctx(_events(False), None)
    assert _read(ctx, *NEW) == dict.fromkeys(NEW)
    assert keyed_ffa.ms_per_step(ctx, "window") is None
    ctx.trace = None
    assert _read(ctx, *NEW) == dict.fromkeys(NEW)


@pytest.mark.parametrize("name, label, hit", [
    ("magi_fwd_kernel_window", "window", True),
    ("magi_fwd_kernel_gqa_window", "window", True),
    ("transpose_jvp_magi_bwd_dq_kernel_window__", "window", True),
    ("magi_fwd_kernel_window", "full", False),
    ("magi_fwd_kernel", "window", False),
    ("magi_fwd_kernel_fuller", "full", False),
    ("magi_ragged_dot_kernel", "full", False),
])
def test_the_labels_pattern(name, label, hit):
    import re

    assert bool(re.search(keyed_ffa.pattern(label), name)) is hit
