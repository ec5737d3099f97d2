"""The reduction from trace to metrics: a small case worked out by hand,
then a recorded chip trace."""

import os

import pytest

from cellbench import trace_reduce as tr
from cellbench.trace_reduce import Event

CLASSES = tr.load_classes()
FWD = "custom-call tpu_custom_call -> (bf16[32,1024,128], f32[32,1024,128])"
DQ = "custom-call tpu_custom_call -> f32[32,1024,128]"
FUSION = "fusion kOutput -> bf16[1024,5120]"


def test_parse_hlo_keeps_name_opcode_target_and_type():
    hlo = (
        "%jvp_DistAttnRuntime.calc_attn_.3 = (bf16[32,16384,128]{2,1,0:T(8,"
        "128)(2,1)}, f32[32,16384,128]{2,1,0:T(8,128)}) custom-call(s32[1056]"
        "{0:T(1024)S(1)} %copy-done.272, bf16[32,16384,128]{2,1,0} %x), "
        'custom_call_target="tpu_custom_call", operand_layout_constraints={'
        "s32[1056]{0}}, frontend_attributes={kernel_metadata={}}")
    assert tr.parse_hlo(hlo) == (
        "jvp_DistAttnRuntime.calc_attn_.3",
        "custom-call tpu_custom_call -> "
        "(bf16[32,16384,128], f32[32,16384,128])")
    assert tr.parse_hlo(
        "%fusion.5 = bf16[16384,14336]{1,0:T(8,128)(2,1)} fusion(bf16[16384,"
        "5120]{1,0} %a), kind=kOutput, calls=%fused_computation.3"
    ) == ("fusion.5", "fusion kOutput -> bf16[16384,14336]")
    assert tr.parse_hlo(
        "%all-gather-start.7 = (f32[1280,1024]{1,0}, f32[5120,1024]{1,0}) "
        "all-gather-start(f32[1280,1024]{1,0} %p), dimensions={0}"
    ) == ("all-gather-start.7",
          "all-gather-start -> (f32[1280,1024], f32[5120,1024])")
    assert tr.parse_hlo("not an instruction") == ("not an instruction", "")


@pytest.mark.parametrize("name,text,cls", [
    ("jvp_DistAttnRuntime.calc_attn_.3", FWD, "ffa_fwd"),
    ("DistAttnRuntime.calc_attn.9", FWD, "ffa_fwd"),      # the re-forward
    ("custom-call.4", FWD, "ffa_fwd"),                    # profile mode off
    ("DistAttnRuntime.calc_attn.22", DQ, "ffa_bwd"),
    ("custom-call.7", "custom-call tpu_custom_call -> (f32[8,1024,128], "
     "f32[8,1024,128])", "ffa_bwd"),
    ("custom-call.1", "custom-call ConcatBitcast -> f32[5120,1024]",
     "other_compute"),
    # the library's collectives keep their JAX primitive's name
    ("ragged_all_to_all.1", "ragged-all-to-all -> bf16[24576,8,256]",
     "group_comm"),
    ("all_to_all.2", "all-to-all -> s32[4,1,1]", "group_comm"),
    ("ppermute.3", "collective-permute-start -> (bf16[8], bf16[8])",
     "group_comm"),
    ("psum.4", "all-reduce -> f32[32]", "group_comm"),
    ("custom-call.9", "ragged-all-to-all -> bf16[8]", "group_comm"),
    # the partitioner's, for the ZeRO-sharded parameters, have XLA's
    ("collective-permute-start.3", "collective-permute-start -> (bf16[48,"
     "5120], bf16[48,5120], u32[], u32[])", "param_comm"),
    ("collective-permute-done.3", "collective-permute-done -> bf16[48,5120]",
     "param_comm"),
    ("all-to-all.2", "all-to-all -> (f32[4], f32[4])", "param_comm"),
    ("all-gather-start.7", "all-gather-start -> (f32[1280], f32[5120])",
     "param_comm"),
    ("all-reduce.2", "all-reduce -> f32[]", "param_comm"),
    ("reduce-scatter.1", "reduce-scatter -> f32[1280,1024]", "param_comm"),
    ("fusion.5", FUSION, "other_compute"),
    ("copy-start.3", "copy-start -> (f32[64], f32[64], u32[])",
     "other_compute"),
])
def test_classes(name, text, cls):
    assert tr.classify(Event(name, 0, 1, text), CLASSES) == cls


def _hand_trace():
    """One device, window 0..1000 (two host spans), nanoseconds.

        op line:   [100 fusion 300) [300 all-gather-start 320)
                   [320 fwd kernel 600) [600 all-gather-done 650)
                   [650 while 850) containing [660 dq kernel 760) and
                   [760 fusion 840);  [900 ragged-all-to-all 960)
                   [990 fusion 1100)  <- runs past the window, clipped
        async:     [300 all-gather-start ......... 650)
    """
    dev = [
        Event("fusion.1", 100, 200, FUSION),
        Event("all-gather-start.7", 300, 20, "all-gather-start -> (f32[8])"),
        Event("jvp_calc_attn_.3", 320, 280, FWD),
        Event("all-gather-done.7", 600, 50, "all-gather-done -> f32[8]"),
        Event("while.2", 650, 200, "while -> (s32[], f32[8])"),
        Event("calc_attn.22", 660, 100, DQ),
        Event("fusion.9", 760, 80, FUSION),
        Event("ragged_all_to_all.1", 900, 60, "ragged-all-to-all -> bf16[8]"),
        Event("fusion.11", 990, 110, FUSION),
    ]
    asy = [Event("all-gather-start.7", 300, 350, "all-gather-start -> (f32[8])")]
    host = [Event("step_dispatch", 0, 40), Event("loss_readback", 40, 960)]
    return tr.Trace({0: dev}, {0: asy}, host)


def test_by_hand():
    r = tr.reduce_trace(_hand_trace(), CLASSES, steps=2)
    d = r.devices[0]
    assert r.window_s == pytest.approx(1000e-9)
    # busy: [100,850) + [900,960) + [990,1000) = 750 + 60 + 10
    assert d.busy_ns == 820 and d.idle_ns == 180
    assert r.idle_share() == pytest.approx(0.18)
    # self times: the while keeps 200 - 100 - 80 = 20 for itself
    assert d.self_ns == {
        "ffa_fwd": 280, "ffa_bwd": 100, "group_comm": 60,
        "param_comm": 20 + 50, "other_compute": 200 + 20 + 80 + 10}
    assert sum(d.self_ns.values()) == d.busy_ns  # the classes close
    # the all-gather was open 300..650 and kept the core for 70 of it
    assert d.in_flight_ns["param_comm"] == 350
    assert r.exposed_share(["param_comm"]) == pytest.approx(70 / 350)
    # a synchronous collective is all exposed
    assert r.exposed_share(["group_comm"]) == pytest.approx(1.0)
    assert r.exposed_share(["ffa_fwd"]) == pytest.approx(1.0)
    assert r.self_ms_per_step(["ffa_fwd", "ffa_bwd"]) == pytest.approx(
        380e-6 / 2)
    assert r.in_flight_ms_per_step(["param_comm"]) == pytest.approx(350e-6 / 2)
    assert r.idle_ms_per_step() == pytest.approx(180e-6 / 2)
    assert r.busy_s() == pytest.approx(820e-9)
    # gaps [0,100) [850,900) [960,990): the first has its middle (50) in
    # loss_readback too, since step_dispatch ended at 40
    assert r.idle_by_host_span() == [["loss_readback", pytest.approx(180e-9)]]
    ops = dict(map(tuple, r.top_ops(3)))
    assert list(ops.values()) == pytest.approx([290e-9, 280e-9, 100e-9])
    assert list(ops)[0] == f"other_compute:fusion {FUSION}"
    assert list(ops)[1].startswith("ffa_fwd:jvp_calc_attn_ custom-call")


def test_gaps_go_to_the_host_span_open_at_their_middle():
    t = _hand_trace()
    t.host = [Event("batch_handover", 0, 60), Event("step_dispatch", 60, 810),
              Event("loss_readback", 970, 30)]
    r = tr.reduce_trace(t, CLASSES, steps=1)
    # gaps [0,100) [850,900) [960,990), middles 50, 875, 975; no span is
    # open at 875 (step_dispatch ended at 870)
    assert r.idle_by_host_span() == [
        ["batch_handover", pytest.approx(100e-9)],
        ["host:other", pytest.approx(50e-9)],
        ["loss_readback", pytest.approx(30e-9)],
    ]


def test_worst_device_sets_the_idle_share_and_sums_are_means():
    t = _hand_trace()
    t.devices[1] = [Event("fusion.1", 0, 500, FUSION)]
    r = tr.reduce_trace(t, CLASSES, steps=1)
    assert r.idle_share() == pytest.approx(0.5)  # device 1 idles half
    assert r.self_ms_per_step(["other_compute"]) == pytest.approx(
        (310 + 500) / 2 * 1e-6)  # 310 with the while
    assert r.busy_s() == pytest.approx((820 + 500) / 2 * 1e-9)


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError, match="no device operation"):
        tr.reduce_trace(tr.Trace({}, {}, []), CLASSES, 3)
    with pytest.raises(ValueError, match="no device operation"):
        tr.reduce_trace(tr.Trace({0: []}, {}, []), CLASSES, 3)


def test_events_round_trip_through_the_recorded_format(tmp_path):
    t = _hand_trace()
    tr.save_events(t, str(tmp_path / "t.json"))
    back = tr.load_events(str(tmp_path / "t.json"))
    assert back == t


# -- a recorded chip trace ----------------------------------------------------
# nemo12b.longdoc.cp4 on four v5e chips (PR 22), cut to the 142 ms around the
# boundary between two steps, chips 0 and 1: the end of a backward pass (the
# embedding's scatter and its all-reduce, the last weight updates with the
# partitioner's collective-permutes), the 3.9 ms in which the device waits
# for the host, and the next step up to the end of layer 0's forward FFA
# kernel (embedding gather, all-reduce, the projections, the KV group cast as
# one ragged-all-to-all). Times are nanoseconds from the cut's start.
DATA = os.path.join(os.path.dirname(__file__), "data", "cp4_step_boundary.json")


def _swept_union(events):
    """Busy nanoseconds by a sweep over the sorted end points (written apart
    from trace_reduce.union on purpose)."""
    points = sorted(
        [(e.start, 1) for e in events] + [(e.end, -1) for e in events],
        key=lambda p: (p[0], -p[1]))
    busy = depth = 0
    last = None
    for at, step in points:
        if depth > 0:
            busy += at - last
        depth += step
        last = at
    return busy


def test_recorded_chip_trace():
    t = tr.load_events(DATA)
    assert sorted(t.devices) == [0, 1] and sorted(t.async_ops) == [0]
    assert [len(t.devices[d]) for d in (0, 1)] == [542, 544]
    r = tr.reduce_trace(t, CLASSES, steps=1)
    # the host spans cover the cut exactly: loss_readback 0 .. 83.77 ms,
    # batch_handover, step_dispatch, loss_readback again to the end
    assert [h.name for h in t.host] == [
        "loss_readback", "batch_handover", "step_dispatch", "loss_readback"]
    assert r.window_s == pytest.approx(142_245_930e-9)

    d0, d1 = r.devices[0], r.devices[1]
    assert d0.busy_ns == _swept_union(t.devices[0]) == 138_304_527
    assert d1.busy_ns == _swept_union(t.devices[1]) == 138_307_259
    assert d0.idle_ns == 3_941_403 and d1.idle_ns == 3_938_671
    assert r.idle_share() == pytest.approx(3_941_403 / 142_245_930)  # chip 0
    # the one long gap is the step boundary, and the host was still inside
    # loss_readback (it learns 3.8 ms late that the device has finished)
    (g0, g1), *rest = d0.gaps
    assert (g0, g1) == (79_956_166, 83_896_926)  # 3.94 ms
    assert sum(b - a for a, b in rest) == 3_941_403 - (g1 - g0) == 643
    assert r.idle_by_host_span()[0] == [
        "loss_readback", pytest.approx(3_941_397e-9)]

    # classes, picked here by plain string tests instead of the patterns;
    # nothing nests in a v5e trace, so a class is the sum of its events
    def total(pick):
        return sum(e.dur for e in t.devices[0] if pick(e))

    kernel = total(lambda e: "tpu_custom_call" in e.text)
    ragged = total(lambda e: e.name.startswith("ragged_all_to_all"))
    tiny_a2a = total(lambda e: e.name.startswith("all_to_all"))
    partitioner = total(lambda e: e.text.split(" ")[0] in (
        "all-reduce", "all-gather", "collective-permute-start",
        "collective-permute-done"))
    assert kernel == d0.self_ns["ffa_fwd"] == 28_884_525  # one forward body
    assert d0.self_ns["ffa_bwd"] == 0
    assert ragged + tiny_a2a == d0.self_ns["group_comm"] == 1_444_786
    assert partitioner == d0.self_ns["param_comm"] == 17_716_476
    assert d0.self_ns["other_compute"] == (
        138_304_527 - 28_884_525 - 1_444_786 - 17_716_476)
    assert sum(d0.self_ns.values()) == d0.busy_ns

    # the library's ragged-all-to-all is synchronous: all of it exposed
    assert r.exposed_share(["group_comm"]) == pytest.approx(1.0)
    # the partitioner's permutes are asynchronous: open for 91.7 ms of the
    # cut on chip 0 (the only chip whose asynchronous line the profiler
    # writes), keeping the core for 17.7 ms of it
    spans = [e for e in t.async_ops[0] if e.name.startswith("collective-")]
    sync = [e for e in t.devices[0] if e.text.split(" ")[0] in (
        "all-reduce", "all-gather", "collective-permute-start",
        "collective-permute-done")]
    assert d0.in_flight_ns["param_comm"] == _swept_union(spans + sync)
    assert d0.in_flight_ns["param_comm"] == 91_680_011
    assert not d1.has_async_line and d0.has_async_line
    assert r.in_flight_ms_per_step(["param_comm"]) == pytest.approx(91.680011)
    assert r.exposed_share(["param_comm"]) == pytest.approx(
        17_716_476 / 91_680_011)
    # sums are means over the chips
    assert r.self_ms_per_step(["param_comm"]) == pytest.approx(
        (17_716_476 + 18_709_225) / 2 * 1e-6)
    top = r.top_ops(2)
    assert top[0][0] == "other_compute:fusion fusion kCustom -> bf16[32768,5120]"
    assert top[1][0].startswith("ffa_fwd:shard_map custom-call tpu_custom_call")
