"""``cellbench/regions.py`` and its twelve readers, by hand: a synthetic trace
whose events are the instructions of a toy hybrid step compiled here on the
CPU under ``MAGI_ATTENTION_PROFILE_MODE`` (what ``compiled_step_texts`` hands
out after the step ran), each with a duration chosen here, plus a kernel and
the collectives of a cp 4 step as the chip names them. One compile, shared."""

import json
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from cellbench import manifest, regions, trace_reduce
from cellbench.trace_reduce import Event, Trace
from magiattention_tpu import api
from magiattention_tpu.models import hybrid
from magiattention_tpu.utils import profiling

S, STEPS = 256, 2
CU = [0, 100, 256]
CELL = "toy.cell"
STEP = "magiattention_tpu.models.hybrid.train_step"
# a vocabulary of its own: no other test's jit cache entry (test_step_regions)
TOY = hybrid.HybridConfig(
    vocab_size=139, dim=64, pattern="ME*D", n_heads=4, n_kv_heads=1,
    head_dim=64, dense_ffn=128, mamba_heads=2, mamba_head_dim=64,
    ssm_groups=1, ssm_state=32, n_experts=8, experts_held=4, top_k=2,
    expert_ffn=64, shared_ffn=64, moe_token_block=128, remat=True)
FUSION = "fusion kLoop -> f32[8]"  # classed other_compute
KERNEL = "custom-call tpu_custom_call -> (bf16[8], f32[8])"  # ffa_fwd
SCAN = "custom-call tpu_custom_call -> f32[8]"
A2A = "ragged-all-to-all -> bf16[8]"  # group_comm
CAST = (profiling.ATTN_REGION, "shard_map", "group_cast_stage0",
        "group_cast_ragged")
# what the toy's CPU text cannot hold, entered as the chip's would read
BY_HAND = {
    "magi_fwd_kernel.1": ((profiling.ATTN_REGION, "magi_fwd_kernel"), "fwd"),
    "jvp_magi_ssd_fwd_kernel_.2": (("ssm", "magi_ssd_fwd_kernel"), "refwd"),
    "ragged_all_to_all.1": (CAST, "fwd"),
    "ragged_all_to_all.2": (("checkpoint", "rematted_computation", *CAST),
                            "refwd"),
    "ragged_all_to_all.3": (("checkpoint", *CAST), "bwd"),
    "ragged_all_to_all.4": ((profiling.ATTN_REGION, "group_reduce_ragged",
                             "group_cast_ragged"), "fwd"),
}


@pytest.fixture(scope="module")
def flag_on():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("MAGI_ATTENTION_PROFILE_MODE", "1")
        mp.setattr(profiling, "_STEPS_SEEN", {})
        yield mp
    hybrid.train_step.last_call = None


@pytest.fixture(scope="module")
def toy(flag_on):
    """The toy step run once under the flag; ``(table, one instruction name
    a (region, pass))`` of the text the program then hands out."""
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("cp",))
    key = api.magi_attn_varlen_key(
        CU, CU, causal=True, mesh=mesh, chunk_size=16)
    toks = jnp.arange(S, dtype=jnp.int32) % TOY.vocab_size
    _, loss = hybrid.train_step(
        hybrid.init_params(TOY, jax.random.key(0)), TOY, toks,
        jnp.roll(toks, -1), key)
    assert np.isfinite(float(loss))
    table, _ = profiling.instruction_scopes(
        profiling.compiled_step_texts()[STEP])
    one = {}
    for name, (scopes, which) in sorted(table.items()):
        if scopes is not None and "." in name:
            one.setdefault((profiling.region_of(scopes), which), name)
    return table, one


def _trace(one, outside: bool = False) -> tuple[Trace, dict]:
    """A device's line: one event a (region, pass) the toy has, ``10 *
    (n + 1)`` ns the n-th, a gap after each; the kernel, the scan's call and
    the four collectives; with ``outside`` also the toy's instructions under
    no region and a ``copy`` XLA added. ``ns`` a (region, pass, class)."""
    events, ns, t = [], {}, 1000.0

    def add(name, text, key, dur):
        nonlocal t
        events.append(Event(name, t, dur, text))
        ns[key] = ns.get(key, 0.0) + dur
        t += dur + 5.0

    for n, ((region, which), name) in enumerate(sorted(
            one.items(), key=str)):
        if region is not None or outside:
            add(name, FUSION, (region or "unscoped", which, "other_compute"),
                10.0 * (n + 1))
    add("magi_fwd_kernel.1", KERNEL,
        (profiling.ATTN_REGION, "fwd", "ffa_fwd"), 700.0)
    add("jvp_magi_ssd_fwd_kernel_.2", SCAN,
        ("ssm", "refwd", "other_compute"), 300.0)
    for n, which in enumerate(("fwd", "refwd", "bwd", "fwd"), 1):
        add(f"ragged_all_to_all.{n}", A2A,
            (profiling.ATTN_REGION, which, "group_comm"), 100.0 * n)
    if outside:
        add("copy.99999", "copy -> bf16[8]",
            ("unnamed", "none", "other_compute"), 50.0)
    host = [Event("step_dispatch", 900.0, 50.0),
            Event("loss_readback", 950.0, t - 950.0)]
    return Trace(devices={0: events}, host=host), ns


def _ctx(trace: Trace):
    reduction = trace_reduce.reduce_trace(
        trace, trace_reduce.load_classes(), STEPS)
    return SimpleNamespace(
        trace=reduction, cell=SimpleNamespace(name=CELL))


@pytest.fixture()
def traced(toy, monkeypatch, tmp_path):
    """``regions.joined`` led to the synthetic trace and the toy's table:
    ``(ctx, ns a (region, pass, class), read(metric name))``."""
    table, one = toy
    trace, ns = _trace(one)
    path = tmp_path / "t.xplane.pb"
    monkeypatch.setattr(regions, "find_xplane", lambda cell: str(path))
    monkeypatch.setattr(
        regions.trace_reduce, "load_xplane", lambda p, spans: trace)
    whole = profiling.instruction_scopes
    monkeypatch.setattr(profiling, "instruction_scopes", lambda text: (
        {**whole(text)[0], **BY_HAND}, whole(text)[1]))
    monkeypatch.setattr(regions, "_JOINED", [])
    ctx = _ctx(trace)

    def read(metric):
        return manifest.load_metric(manifest.ROOT, metric)[1](ctx)

    return ctx, ns, read


def _ms(ns, pick) -> float:
    return sum(v for k, v in ns.items() if pick(*k)) * 1e-6 / STEPS


TWELVE = [
    "regions_sum_over_busy", "remat_recompute_share", "attn_proj_ms_per_step",
    "attn_glue_ms_per_step", "mlp_ms_per_step", "head_loss_ms_per_step",
    "embed_ms_per_step", "ssm_glue_ms_per_step", "moe_route_ms_per_step",
    "moe_rows_ms_per_step", "group_cast_ms_per_step",
    "group_reduce_ms_per_step"]
with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as _f:
    NEW = [m for m in json.load(_f)["per_layer"] if m["name"] in TWELVE]


def test_the_benchmark_lists_the_twelve_readers():
    assert [m["name"] for m in NEW] == TWELVE  # appended, in this order
    for m in NEW:
        spec, read = manifest.load_metric(manifest.ROOT, m["name"])
        assert callable(read)
        assert (m["source"], m["moves"]) == ("device_trace", "tokens_per_s")
        assert {k: spec[k] for k in ("unit", "layer")} == {
            k: m[k] for k in ("unit", "layer")}
        assert m["better"] == (
            "higher" if m["name"] == "regions_sum_over_busy" else "lower")


def test_the_toy_has_every_region_to_read(toy):
    _, one = toy
    assert {region for region, _ in one} - {None} == {
        *profiling.MODEL_REGIONS, profiling.ATTN_REGION}


REGION_METRICS = {
    "attn_proj_ms_per_step": ("attn_qkv", "attn_out"),
    "mlp_ms_per_step": ("mlp", "moe_shared"),
    "head_loss_ms_per_step": ("head_loss",),
    "embed_ms_per_step": ("embed",),
    "moe_route_ms_per_step": ("moe_route",),
    "moe_rows_ms_per_step": ("moe_rows",),
}


@pytest.mark.parametrize("metric", sorted(REGION_METRICS))
def test_a_regions_time_is_its_events_of_every_pass(traced, metric):
    _, ns, read = traced
    want = _ms(ns, lambda region, which, cls: region in REGION_METRICS[metric])
    assert want > 0 and read(metric) == pytest.approx(want)


def test_the_join_closes_on_its_own_instructions(traced):
    """Every event is an instruction of the program in a region."""
    ctx, ns, read = traced
    assert read("regions_sum_over_busy") == pytest.approx(100.0)
    assert sum(ns.values()) == pytest.approx(ctx.trace.busy_s() * 1e9)
    j = regions.joined(ctx)
    assert j.facts["names_covered"] == 1.0 and j.facts["step"] == STEP


def test_remat_share_is_the_re_forward_of_every_class(traced):
    _, ns, read = traced
    refwd = sum(v for (_, which, _), v in ns.items() if which == "refwd")
    assert refwd > 300.0 + 200.0  # the scan's call and a cast among them
    assert read("remat_recompute_share") == pytest.approx(
        100.0 * refwd / sum(ns.values()))


def test_glue_is_other_compute_round_the_kernels(traced):
    _, ns, read = traced
    # calc_attn: not the FFA kernel (class ffa_fwd), not the collectives
    assert read("attn_glue_ms_per_step") == pytest.approx(_ms(
        ns, lambda region, which, cls: region == profiling.ATTN_REGION
        and cls == "other_compute"))
    # the M block less the scan's own call
    assert read("ssm_glue_ms_per_step") == pytest.approx(_ms(
        ns, lambda region, which, cls: region == "ssm") - 300e-6 / STEPS)


def test_a_casts_transpose_is_a_reduce(traced):
    """The ragged reduce is ``jax.vjp`` of the cast: under AD a cast's
    backward carries ``group_cast*`` alone and pass ``bwd``; called by hand
    it carries ``group_reduce*`` round it."""
    ctx, ns, read = traced
    assert read("group_cast_ms_per_step") == pytest.approx(
        (100.0 + 200.0) * 1e-6 / STEPS)
    assert read("group_reduce_ms_per_step") == pytest.approx(
        (300.0 + 400.0) * 1e-6 / STEPS)
    assert (read("group_cast_ms_per_step") + read("group_reduce_ms_per_step")
            ) == pytest.approx(ctx.trace.self_ms_per_step(["group_comm"]))


def test_other_compute_adds_up_over_regions_unscoped_and_unnamed(
        toy, monkeypatch):
    """Σ regions + ``unscoped`` + ``unnamed`` of class ``other_compute`` is
    the class's time as ``other_compute_ms_per_step`` reads it; what XLA
    added (no ``op_name``, or no such instruction) is ``unnamed`` and
    outside the closure."""
    table, one = toy
    trace, ns = _trace(one, outside=True)
    j = regions.join(
        trace, (900.0, max(e.end for e in trace.devices[0])),
        trace_reduce.load_classes(),
        {STEP: ({**table, **BY_HAND, "copy.99999": (None, "none")}, {})},
        STEPS, profiling.region_of)
    report = regions.report(j, CELL)
    other = report["other_compute_ms_per_step"]
    assert other["sum"] == pytest.approx(
        _ctx(trace).trace.self_ms_per_step(["other_compute"]))
    assert other["by_region"]["unnamed"] == pytest.approx(50e-6 / STEPS)
    assert other["by_region"]["unscoped"] == pytest.approx(_ms(
        ns, lambda region, which, cls: region == "unscoped"))
    assert set(other["by_region"]) == {
        *profiling.MODEL_REGIONS, profiling.ATTN_REGION, "unscoped",
        "unnamed"}
    left = {label.split(" ")[0] for label, _ in report["outside_closure"]}
    assert "unnamed:none:other_compute:copy.99999" in left
    assert all(label.startswith(("unnamed:", "unscoped:")) for label in left)
    busy = sum(ns.values())
    inside = sum(v for (region, _, _), v in ns.items()
                 if region not in ("unscoped", "unnamed"))
    assert report["regions_sum_over_busy"] == pytest.approx(
        100.0 * inside / busy)
    assert report["group_comm_without_scope_ms_per_step"] == 0.0
    assert json.loads(json.dumps(report)) == report


def test_the_report_is_left_beside_the_trace(traced, tmp_path):
    ctx, _, read = traced
    read("regions_sum_over_busy")
    report = json.load(open(tmp_path / "regions.json"))
    assert report["cell"] == CELL and report["step"] == STEP
    assert report["compiled_step_texts_s"] >= 0 and report["join_s"] >= 0
    assert report["ms_per_step"]["moe_rows"]["bwd"]["other_compute"] > 0


def _all_none(ctx):
    return [m["name"] for m in NEW if manifest.load_metric(
        manifest.ROOT, m["name"])[1](ctx) is not None] == []


def test_nothing_to_read_is_none_and_never_raises(traced, monkeypatch):
    ctx, _, read = traced
    assert read("regions_sum_over_busy") is not None
    # without a trace (--trace 0, the CPU rehearsal)
    assert _all_none(SimpleNamespace(trace=None, cell=ctx.cell))
    # the xplane is not there, or there are two
    monkeypatch.setattr(regions, "_JOINED", [])
    monkeypatch.setattr(regions, "find_xplane", lambda cell: None)
    assert _all_none(ctx)
    # no device plane in it
    monkeypatch.setattr(regions, "_JOINED", [])
    monkeypatch.setattr(regions, "find_xplane", lambda cell: "x")
    monkeypatch.setattr(
        regions.trace_reduce, "load_xplane", lambda p, spans: Trace())
    assert _all_none(ctx)


@pytest.mark.parametrize("program", ["hands out no text", "has no function"])
def test_a_program_from_before_the_regions_is_none(
        traced, monkeypatch, program):
    """The parent with these files laid over it: it runs, and the twelve
    metrics are left out of its line."""
    ctx, _, read = traced
    monkeypatch.setattr(regions, "_JOINED", [])
    if program == "hands out no text":
        monkeypatch.setattr(profiling, "compiled_step_texts", lambda: {})
    else:
        monkeypatch.delattr(profiling, "compiled_step_texts")
    assert _all_none(ctx)


def test_a_window_no_text_covers_is_none_rather_than_a_guess(toy):
    table, one = toy
    trace, _ = _trace(one)
    window = (900.0, max(e.end for e in trace.devices[0]))
    classes = trace_reduce.load_classes()
    whole = {**table, **BY_HAND}
    other = {name + ".x": entry for name, entry in whole.items()}
    join = regions.join
    assert join(trace, window, classes, {"a": (other, {})}, STEPS,
                profiling.region_of) is None
    assert join(trace, window, classes, {"a": (whole, {}), "b": (whole, {})},
                STEPS, profiling.region_of) is None
    assert join(trace, window, classes, {"a": (whole, {}), "b": (other, {})},
                STEPS, profiling.region_of).facts["step"] == "a"


def test_find_xplane_wants_exactly_one(monkeypatch, tmp_path):
    monkeypatch.setattr(regions.manifest, "ROOT", str(tmp_path))
    assert regions.find_xplane(CELL) is None
    run = tmp_path / ".cellbench_trace" / CELL / "plugins" / "profile" / "t0"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(b"")
    assert regions.find_xplane(CELL) == str(run / "host.xplane.pb")
    (run / "other.xplane.pb").write_bytes(b"")
    assert regions.find_xplane(CELL) is None
