"""The hybrid block builder (``models/hybrid.py``) and the dropless expert
layer (``models/moe.py``) at toy widths, against the plain reference of the
``nemotron_h`` family (``cellbench/reference_nemotron_h.py``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from cellbench import manifest, reference, reference_nemotron_h, run, traffic_gen
from magiattention_tpu import api
from magiattention_tpu.kernels import registry, ssd
from magiattention_tpu.models import hybrid, moe

CELL = "nemotron3nano.packed32k.cp1"


@pytest.fixture(scope="module")
def toy():
    """The cell's family and its configuration at rehearsal widths."""
    cell = manifest.load_cell(manifest.ROOT, CELL)
    family = manifest.load_family(manifest.ROOT, cell.config["family"])
    cfg, *_ = run.cell_sizes(cell, family, 1)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("cp",))
    return family, cfg, mesh


def _compare(family, cfg, mesh, lens, seed, mcfg=None):
    """``reference.compare`` of the family's check program and its plain
    reference on documents of ``lens`` tokens."""
    cu = tuple(np.cumsum([0, *lens]).tolist())
    spec = traffic_gen.MaskSpec(tokens=cu[-1], cu_seqlens=cu, window=None)
    mcfg = mcfg or family.model_config(cfg)
    params = family.init_params(mcfg, mesh, seed)
    toks, labels = (jnp.asarray(x) for x in traffic_gen.token_batches(
        spec, cfg["vocab_size"], seed, 1)[0])
    got = family.check_program(mcfg, family.make_key(spec, mesh))(
        params, toks, labels)
    ref = family.reference(params, cfg, toks, labels, spec)
    return reference.compare(
        jax.device_get(got), jax.device_get(ref), family.CHECKS,
        targets=int((np.asarray(labels) >= 0).sum()))


def test_the_step_agrees_with_the_plain_reference(toy):
    """Loss, logits, the experts chosen and every ``CHECKS`` gradient on a
    packed mask whose boundaries fall inside the scan's chunks."""
    checks = _compare(*toy, lens=[100, 50, 129, 105], seed=3)
    assert list(checks) == list(reference_nemotron_h.CHECKS)
    assert all(c["ok"] for c in checks.values()), checks
    assert registry.last_choice("ssd") == "pallas_chunked"
    assert registry.last_choice("moe_grouped") == "pallas_grouped"
    assert registry.last_choice("moe_grouped_tiles") == "rows16"


def test_a_scan_accumulated_in_bf16_fails_a_named_check(toy, monkeypatch):
    """The kernel's carried state, its saved states and its matmuls'
    accumulators in bf16 instead of float32: ``correct`` is false, by the
    scan's own gradient and more."""
    monkeypatch.setattr(ssd, "_F32", jnp.bfloat16)
    monkeypatch.setattr(
        ssd, "_dot", lambda a, b, dims=(((1,), (0,)), ((), ())):
        jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.bfloat16))
    checks = _compare(*toy, lens=[384], seed=3)
    failed = {name for name, c in checks.items() if not c["ok"]}
    assert "grad_A_log" in failed, checks


def test_a_dropped_routed_row_fails_a_named_check(toy, monkeypatch):
    """One routed row of the first held expert left out of the grouped
    product in every block of tokens."""
    grouped = moe.grouped_matmul

    def one_row_short(rows, w, group_sizes, **kw):
        out = grouped(rows, w, group_sizes, **kw)
        return out.at[0].set(0)  # the first sorted row is expert 0's

    monkeypatch.setattr(moe, "grouped_matmul", one_row_short)
    checks = _compare(*toy, lens=[100, 50, 129, 105], seed=3)
    failed = {name for name, c in checks.items() if not c["ok"]}
    assert "grad_expert_w_up" in failed, checks


def _expert_layer(seed, dim=64, n_experts=32, ffn=32, shared=48):
    cfg = hybrid.HybridConfig(
        dim=dim, n_experts=n_experts, experts_held=n_experts, top_k=6,
        expert_ffn=ffn, shared_ffn=shared)
    return hybrid._init_experts(cfg, jax.random.PRNGKey(seed))


REF_CFG = {"num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
           "expert_offset": 0}


def test_the_shares_add_up():
    """The routed parts of the four 8-expert shares plus the shared expert
    counted once equal the uncut 32-expert reference layer."""
    lyr = _expert_layer(0)
    h = jax.random.normal(jax.random.PRNGKey(1), (200, 64))
    with jax.default_matmul_precision("highest"):
        whole, *_ = reference_nemotron_h._experts(h, lyr, None, REF_CFG)
        shared = reference_nemotron_h._relu2_mlp(
            h, lyr["ws_up"], lyr["ws_down"])
    total = shared
    for offset in (0, 8, 16, 24):
        share = {**lyr, "w_up": lyr["w_up"][offset:offset + 8],
                 "w_down": lyr["w_down"][offset:offset + 8]}
        y, routes = moe.dropless_moe_ffn(
            h, share, top_k=6, scale=2.5, expert_offset=offset,
            token_block=100)
        total = total + (y - shared)  # this share's routed part
        rows = moe.held_expert_rows(routes["topi"], 8, offset)
        assert int(rows.sum()) == int(
            ((routes["topi"] >= offset) & (routes["topi"] < offset + 8)).sum())
    assert float(jnp.linalg.norm(total - whole) / jnp.linalg.norm(whole)) < 1e-5


def test_no_routed_row_is_dropped_when_every_token_picks_the_same_experts():
    """The worst case of the row buffer: all 6 choices of all tokens fall on
    held experts, the same 6."""
    lyr = _expert_layer(2)
    lyr["e_bias"] = lyr["e_bias"].at[jnp.asarray([1, 2, 3, 5, 6, 7])].set(9.0)
    share = {**lyr, "w_up": lyr["w_up"][:8], "w_down": lyr["w_down"][:8]}
    h = jax.random.normal(jax.random.PRNGKey(3), (128, 64))
    y, routes = moe.dropless_moe_ffn(
        h, share, top_k=6, scale=2.5, token_block=64)
    rows = np.asarray(moe.held_expert_rows(routes["topi"], 8))
    np.testing.assert_array_equal(rows, [0, 128, 128, 128, 0, 128, 128, 128])
    with jax.default_matmul_precision("highest"):
        whole, *_ = reference_nemotron_h._experts(h, lyr, None, REF_CFG)
    assert float(jnp.linalg.norm(y - whole) / jnp.linalg.norm(whole)) < 1e-5


def _one_batch(family, cfg, mesh, seed, cu=(0, 100, 256)):
    mcfg = family.model_config(cfg)
    spec = traffic_gen.MaskSpec(tokens=cu[-1], cu_seqlens=cu, window=None)
    key = family.make_key(spec, mesh)
    params = family.init_params(mcfg, mesh, seed)
    toks, labels = (jnp.asarray(x) for x in traffic_gen.token_batches(
        spec, cfg["vocab_size"], seed, 1)[0])
    return mcfg, key, params, toks, labels


def test_routing_counters(toy):
    mcfg, key, params, toks, _ = _one_batch(*toy, seed=0)
    counted = jax.device_get(hybrid.routing_counters(params, mcfg, toks, key))
    blocks = mcfg.pattern.count("E")
    assert counted["rows_per_expert"].shape == (blocks, mcfg.experts_held)
    # what the grouped products took is what was routed to the experts held
    np.testing.assert_array_equal(
        counted["rows_per_expert"].sum(axis=-1), counted["rows_routed"])
    # 6 of 32 picked, 8 held: a quarter of the choices, give or take
    assert 0.15 < counted["rows_routed"].sum() / (blocks * 256 * 6) < 0.35
    # a block of tokens' rows, and how many blocks fitted the buffer sized
    # by that quarter (the rule's 576 of 1536 pairs): all of them
    token_blocks = 256 // min(mcfg.moe_token_block, 256)
    assert counted["block_rows"].shape == (blocks, token_blocks)
    np.testing.assert_array_equal(
        counted["block_rows"].sum(axis=-1), counted["rows_routed"])
    assert registry.last_choice("moe_row_buffer") == (
        f"rows{576 // token_blocks}of{1536 // token_blocks}")
    np.testing.assert_array_equal(
        counted["blocks_fitted"], [token_blocks] * blocks)


def test_routing_counters_see_a_grouped_product_that_takes_fewer_rows(
        toy, monkeypatch):
    """A capacity of 40 rows an expert, planted: the rows routed and the
    rows taken no longer agree."""
    rows = moe.held_expert_rows
    monkeypatch.setattr(
        moe, "held_expert_rows", lambda *a: jnp.minimum(rows(*a), 40))
    mcfg, key, params, toks, _ = _one_batch(*toy, seed=0)
    counted = jax.device_get(
        hybrid.routing_counters.__wrapped__(params, mcfg, toks, key))
    assert (counted["rows_per_expert"].sum(axis=-1)
            < counted["rows_routed"]).all(), counted


def test_cp_above_one_is_refused_by_name(toy):
    family, cfg, _ = toy
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("cp",))
    spec = traffic_gen.MaskSpec(tokens=512, cu_seqlens=(0, 200, 512),
                                window=None)
    key = family.make_key(spec, mesh)
    mcfg = family.model_config(cfg)
    params = jax.eval_shape(
        lambda: hybrid.init_params(mcfg, jax.random.PRNGKey(0)))
    with pytest.raises(NotImplementedError, match=(
            r"cp = 2: pattern 'ME\*M' has the Mamba-2 scan \('M'\), whose "
            r"state .* dispatch permutes .*ROADMAP B1")):
        jax.eval_shape(
            lambda p, t: hybrid.forward(p, mcfg, t, key), params,
            jax.ShapeDtypeStruct((512,), jnp.int32))


def test_pattern_and_shares_are_validated():
    with pytest.raises(ValueError, match="pattern 'MXE'"):
        hybrid.HybridConfig(pattern="MXE")
    with pytest.raises(ValueError, match="experts 4..\\+8 are not among"):
        hybrid.HybridConfig(n_experts=8, experts_held=8, expert_offset=4)
    with pytest.raises(ValueError, match="chunk_size 64"):
        hybrid.HybridConfig(chunk_size=64)


def test_document_starts_follow_the_dispatched_order():
    """Varlen documents, and a sliding-window document whose slices chain
    into one; at cp 2 the rows come in ``get_position_ids``'s order."""
    cu = [0, 100, 228, 512]
    qr, kr, types = api.infer_attn_mask_from_cu_seqlens(cu, cu, causal=True)
    one = Mesh(np.asarray(jax.devices()[:1]), ("cp",))
    key = api.magi_attn_flex_key(qr, kr, types, 512, 512, mesh=one)
    want = np.repeat(cu[:-1], np.diff(cu))
    np.testing.assert_array_equal(api.get_document_starts(key), want)

    two = Mesh(np.asarray(jax.devices()[:2]), ("cp",))
    key2 = api.magi_attn_flex_key(qr, kr, types, 512, 512, mesh=two)
    pos = np.asarray(api.get_position_ids(key2))
    np.testing.assert_array_equal(api.get_document_starts(key2), want[pos])

    from magiattention_tpu.common.enum import AttnMaskType
    from magiattention_tpu.common.ranges import AttnRanges

    docs = AttnRanges.from_cu_seqlens([0, 300, 512])
    swa = api.infer_attn_mask_from_sliding_window(
        docs, docs, [AttnMaskType.CAUSAL] * 2, (63, 0))
    key3 = api.magi_attn_flex_key(*swa, 512, 512, mesh=one)
    np.testing.assert_array_equal(
        api.get_document_starts(key3), np.repeat([0, 300], [300, 212]))


def test_attn_block_without_a_rotary_embedding(toy):
    """``rope_theta=None`` skips ``_rope`` and nothing else."""
    from magiattention_tpu.models import llama

    family, cfg, mesh = toy
    spec = traffic_gen.MaskSpec(tokens=256, cu_seqlens=(0, 256), window=None)
    key = family.make_key(spec, mesh)
    mcfg = family.model_config(cfg)
    lyr = hybrid._init_attention(mcfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (256, mcfg.dim), jnp.bfloat16)
    pos = api.get_position_ids(key)
    bare = llama.attn_block(x, lyr, mcfg, pos, key)
    # theta -> infinity leaves every angle but the first pair's at zero
    turned = llama.attn_block(
        x, lyr, dataclasses.replace(mcfg, rope_theta=1e4), pos, key)
    assert bare.shape == x.shape
    assert not np.allclose(np.asarray(bare, np.float32),
                           np.asarray(turned, np.float32), atol=1e-2)


def test_balancing_bias_evens_a_skewed_load(toy):
    """Scores with a popularity skew (a common offset an expert): the bias
    brings every expert's load to within a few rows of the mean, and the
    held experts' share of the rows to a quarter."""
    family = toy[0]
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    scores = jax.nn.sigmoid(
        jax.random.normal(k[0], (4096, 32)) + 1.5 * jax.random.normal(k[1], (32,)))
    before = np.asarray(moe.held_expert_rows(
        jax.lax.top_k(scores, 6)[1], 32))
    bias = family.balancing_bias(scores, 6)
    after = np.asarray(moe.held_expert_rows(
        jax.lax.top_k(scores + bias, 6)[1], 32))
    assert before.max() / before.mean() > 2.5
    assert after.max() / after.mean() < 1.03 and after.sum() == 4096 * 6
    assert abs(after[:8].sum() / after.sum() - 0.25) < 0.005


def _worst_load(counted):
    rows = counted["rows_per_expert"].astype(np.float64)
    return (rows.max(axis=-1) / rows.mean(axis=-1)).max()


def test_the_family_fits_the_biases_on_a_batch_of_its_own(toy):
    """The first step fits every expert block's bias on a batch drawn from
    the seed, none of the timed ones; the counters then read what is left
    of the imbalance on every timed batch, which is not nothing."""
    family, cfg, mesh = toy
    spec = traffic_gen.MaskSpec(
        tokens=512, cu_seqlens=(0, 200, 512), window=None)
    mcfg = family.model_config(cfg)
    key = family.make_key(spec, mesh)
    params = family.init_params(mcfg, mesh, 1)
    assert family.routing_counters() is None
    batches = [tuple(jnp.asarray(x) for x in b) for b in
               traffic_gen.token_batches(spec, cfg["vocab_size"], 1, 3)]
    skewed = _worst_load(jax.device_get(
        hybrid.routing_counters(params, mcfg, batches[0][0], key)))
    for toks, labels in batches + batches[:1]:
        params, _ = family.train_step(params, mcfg, toks, labels, key)
    for kind, lyr in zip(mcfg.pattern, params["layers"]):
        if kind == "E":
            assert float(jnp.abs(lyr["e_bias"]).max()) > 0
    counted = family.routing_counters()
    assert counted["batches"] == 3 and counted["rows_dropped"] == 0
    assert 1.0 < counted["load_max_over_mean"] < skewed
    # 8 of 32 experts held: a quarter of the 512 x 6 choices a block
    blocks = mcfg.pattern.count("E")
    assert abs(counted["routed_rows"] / (blocks * 512 * 6) - 0.25) < 0.03
    assert family.what_ran()["routing"] == counted


def test_balance_routers_evens_the_batch_it_is_given(toy):
    family = toy[0]
    mcfg, key, params, toks, _ = _one_batch(
        *toy, seed=1, cu=(0, 200, 512))
    skewed = jax.device_get(hybrid.routing_counters(params, mcfg, toks, key))
    params = family.balance_routers(params, mcfg, toks, key)
    evened = jax.device_get(hybrid.routing_counters(params, mcfg, toks, key))
    assert _worst_load(evened) < 1.15 < _worst_load(skewed)
    assert abs(evened["rows_routed"] / (512 * 6) - 0.25).max() < 0.01


def test_a_token_sent_to_a_far_expert_fails_route_choice(toy, monkeypatch):
    """One token's sixth expert replaced by its worst-scored one, planted
    in the program's top-k: ``route_choice`` fails, whatever the others
    read."""
    route = moe.route_sigmoid_topk

    def one_token_astray(h, router, bias, top_k, scale):
        topi, _, s = route(h, router, bias, top_k, scale)
        topi = topi.at[7, -1].set(jnp.argmin(s[7] + bias).astype(topi.dtype))
        chosen = jnp.take_along_axis(s, topi, axis=-1)
        return topi, chosen / chosen.sum(-1, keepdims=True) * scale, s

    checks = _compare(*toy, lens=[100, 50, 129, 105], seed=3)
    assert checks["route_choice"]["ok"] and checks["route_choice"]["err"] == 0
    monkeypatch.setattr(moe, "route_sigmoid_topk", one_token_astray)
    checks = _compare(*toy, lens=[100, 50, 129, 105], seed=3)
    assert not checks["route_choice"]["ok"], checks


def test_the_reference_refuses_to_run_before_the_check_program(toy):
    family, cfg, mesh = toy
    mcfg, key, params, toks, labels = _one_batch(*toy, seed=0)
    spec = traffic_gen.MaskSpec(tokens=256, cu_seqlens=(0, 100, 256),
                                window=None)
    with pytest.raises(RuntimeError, match="teacher-forced"):
        family.reference(params, cfg, toks, labels, spec)
