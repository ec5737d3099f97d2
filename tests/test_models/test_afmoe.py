"""The ``afmoe`` family (Arcee Trinity) at toy widths on the CPU: the hybrid
block builder with window and full attention layers under two runtime keys
in one step, gated q/k-normed attention, sandwich norms and SwiGLU experts,
against the plain reference ``cellbench/reference_afmoe.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from cellbench import (
    flops,
    kernel_times,
    manifest,
    reference,
    reference_afmoe,
    run,
    traffic_gen,
)
from magiattention_tpu import api
from magiattention_tpu.api.magi_attn_interface import _mgr
from magiattention_tpu.kernels import registry
from magiattention_tpu.kernels.grouped_matmul import grouped_matmul
from magiattention_tpu.models import hybrid, llama, moe
from magiattention_tpu.testing import assert_close, ref_attn

CELL = "trinitymini.longdocs32k.cp1"
LENS = [100, 50, 129, 105]  # the toy window (64) cuts three of the four


@pytest.fixture(scope="module")
def toy():
    """The cell's family and its configuration at rehearsal widths."""
    cell = manifest.load_cell(manifest.ROOT, CELL)
    family = manifest.load_family(manifest.ROOT, cell.config["family"])
    cfg, *_ = run.cell_sizes(cell, family, 1)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("cp",))
    return family, cfg, mesh


def _spec(lens):
    cu = tuple(np.cumsum([0, *lens]).tolist())
    return traffic_gen.MaskSpec(tokens=cu[-1], cu_seqlens=cu, window=None)


def _compare(family, cfg, mesh, lens=LENS, seed=3, mcfg=None, keys=None):
    """``reference.compare`` of the family's check program and its plain
    reference on documents of ``lens`` tokens; ``keys(good keys)`` plants
    other keys in the program."""
    spec = _spec(lens)
    mcfg_ref = family.model_config(cfg)
    mcfg = mcfg or mcfg_ref
    params = family.init_params(mcfg_ref, mesh, seed)
    toks, labels = (jnp.asarray(x) for x in traffic_gen.token_batches(
        spec, cfg["vocab_size"], seed, 1)[0])
    good = family.make_key(spec, mesh)
    got = family.check_program(mcfg, keys(good) if keys else good)(
        params, toks, labels)
    ref = family.reference(params, cfg, toks, labels, spec)
    return reference.compare(
        jax.device_get(got), jax.device_get(ref), family.CHECKS,
        targets=int((np.asarray(labels) >= 0).sum()))


def _failed(checks) -> set:
    return {name for name, c in checks.items() if not c["ok"]}


@pytest.mark.parametrize("remat", [True, False])
def test_the_step_agrees_with_the_plain_reference(toy, remat):
    """Loss, logits, the experts chosen and every ``CHECKS`` gradient, the
    window layers' and the full layer's apart, under both keys."""
    family, cfg, mesh = toy
    mcfg = dataclasses.replace(family.model_config(cfg), remat=remat)
    assert mcfg.pattern == "WDWE*E"
    checks = _compare(family, cfg, mesh, mcfg=mcfg)
    assert list(checks) == list(reference_afmoe.CHECKS)
    assert not _failed(checks), checks
    assert registry.last_choice("moe_grouped") == "pallas_grouped"
    # what ran is stated per key: tiles with their packing, backward mode
    ran = family.what_ran()
    assert set(ran["ffa_tiles"]) == set(ran["ffa_bwd_mode_by_key"]) == {
        "full", "window"}
    assert all(tiles.startswith("fwd") for tiles in ran["ffa_tiles"].values())


def test_the_window_key_in_the_full_layer_fails_a_named_check(toy):
    checks = _compare(
        *toy, keys=lambda k: type(k)(full=k.window, window=k.window))
    assert {"logits", "grad_wq_full"} <= _failed(checks), checks


def test_the_full_key_in_a_sliding_layer_fails_a_named_check(toy):
    checks = _compare(
        *toy, keys=lambda k: type(k)(full=k.full, window=k.full))
    assert {"logits", "grad_wq_sliding"} <= _failed(checks), checks


def test_a_rotation_in_the_full_layer_fails_a_named_check(toy):
    family, cfg, mesh = toy
    mcfg = dataclasses.replace(family.model_config(cfg), rope_in="*W")
    checks = _compare(family, cfg, mesh, mcfg=mcfg)
    assert {"logits", "grad_wq_full"} <= _failed(checks), checks


def test_an_output_gate_left_out_fails_a_named_check(toy, monkeypatch):
    def ungated(x, lyr, *args, **kwargs):
        lyr = {k: v for k, v in lyr.items() if k != "w_attn_gate"}
        return llama.attn_block(x, lyr, *args, **kwargs)

    monkeypatch.setattr(hybrid, "attn_block", ungated)
    checks = _compare(*toy)
    assert {"logits", "grad_attn_gate"} <= _failed(checks), checks


def test_the_reference_in_bf16_fails_against_itself_in_float32(toy):
    """The lower-precision control: by one of the limits, not by each."""
    family, cfg, mesh = toy
    spec = _spec(LENS)
    mcfg = family.model_config(cfg)
    params = family.init_params(mcfg, mesh, 3)
    toks, labels = (jnp.asarray(x) for x in traffic_gen.token_batches(
        spec, cfg["vocab_size"], 3, 1)[0])
    family.check_program(mcfg, family.make_key(spec, mesh))(
        params, toks, labels)  # the routes both sides are forced on
    routes = family._RUN.pop("check_routes")
    sides = [jax.device_get(reference_afmoe.reference(
        params, cfg, toks, labels, spec, routes=routes, dtype=dtype))
        for dtype in (jnp.bfloat16, jnp.float32)]
    checks = reference.compare(
        *sides, family.CHECKS, targets=int((np.asarray(labels) >= 0).sum()))
    assert _failed(checks) == {"route_scores"}, checks


def test_a_dropped_routed_row_fails_a_named_check(toy, monkeypatch):
    grouped = moe.grouped_matmul

    def one_row_short(rows, w, group_sizes, **kw):
        out = grouped(rows, w, group_sizes, **kw)
        return out.at[0].set(0)  # the first sorted row is expert 0's

    monkeypatch.setattr(moe, "grouped_matmul", one_row_short)
    checks = _compare(*toy)
    assert "grad_expert_w_up" in _failed(checks), checks
    print("one routed row dropped a block:", checks["grad_expert_w_up"])


# -- the expert layer ------------------------------------------------------

REF_CFG = {"num_experts_per_tok": 8, "route_scale": 2.826, "expert_offset": 0}


def _expert_layer(seed, act, dim=64, n_experts=128, ffn=32, shared=32):
    cfg = hybrid.HybridConfig(
        dim=dim, n_experts=n_experts, experts_held=n_experts, top_k=8,
        expert_ffn=ffn, shared_ffn=shared, expert_act=act)
    return hybrid._init_experts(cfg, jax.random.PRNGKey(seed))


def test_the_shares_add_up():
    """The routed parts of the four 32-expert shares (offsets 0, 32, 64, 96
    of 128) plus the shared expert counted once equal the uncut reference
    layer."""
    lyr = _expert_layer(0, "swiglu")
    h = jax.random.normal(jax.random.PRNGKey(1), (200, 64))
    with jax.default_matmul_precision("highest"):
        whole, *_ = reference_afmoe._experts(h, lyr, None, REF_CFG)
        shared = reference_afmoe._swiglu(h, lyr["ws_up"], lyr["ws_down"])
    total, rows = shared, 0
    for offset in (0, 32, 64, 96):
        share = {**lyr, "w_up": lyr["w_up"][offset:offset + 32],
                 "w_down": lyr["w_down"][offset:offset + 32]}
        y, routes = moe.dropless_moe_ffn(
            h, share, top_k=8, scale=2.826, expert_offset=offset,
            token_block=100, act="swiglu")
        total = total + (y - shared)  # this share's routed part
        rows += int(moe.held_expert_rows(routes["topi"], 32, offset).sum())
    assert rows == 200 * 8  # every (token, choice) pair in exactly one share
    assert float(jnp.linalg.norm(total - whole) / jnp.linalg.norm(whole)) < 1e-5


def _relu2_layer_as_it_was(h, lyr, *, top_k, scale, token_block):
    """``dropless_moe_ffn`` as PR 32 left it, before it knew an ``act``."""
    dt, (s, dim) = h.dtype, h.shape
    topi, weights, _ = moe.route_sigmoid_topk(
        h, lyr["router"], lyr["e_bias"], top_k, scale)
    w_up, w_down = lyr["w_up"].astype(dt), lyr["w_down"].astype(dt)
    tile_rows = moe.tile_policy.grouped_row_tile(
        token_block * top_k // lyr["router"].shape[-1])

    def block(args):
        h, topi, weights = args
        (sb, k), held = topi.shape, w_up.shape[0]
        mine, gid = moe._local_expert_ids(topi, held, 0)
        order = jnp.argsort(gid.reshape(-1), stable=True)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(sb * k, dtype=order.dtype))
        sizes = moe.held_expert_rows(topi, held, 0)
        live = (jnp.arange(sb * k) < jnp.sum(sizes))[:, None]
        rows = jnp.where(live, jnp.repeat(h, k, axis=0)[order], 0)
        up = grouped_matmul(rows, w_up, sizes, tile_rows=tile_rows)
        act = jnp.where(live, jnp.square(jax.nn.relu(up)), 0).astype(dt)
        out = jnp.where(live, grouped_matmul(
            act, w_down, sizes, tile_rows=tile_rows, out_dtype=dt), 0)
        back = out[inverse].reshape(sb, k, -1)
        gate = jnp.where(mine, weights, 0.0).astype(dt)
        return jnp.einsum("sk,skd->sd", gate, back,
                          preferred_element_type=jnp.float32).astype(dt)

    routed = jax.lax.map(jax.checkpoint(block), tuple(
        v.reshape(s // token_block, token_block, -1)
        for v in (h, topi, weights)))
    shared = jnp.square(jax.nn.relu(h @ lyr["ws_up"].astype(dt))) @ (
        lyr["ws_down"].astype(dt))
    return routed.reshape(s, dim) + shared


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2 ** -6)])
def test_relu2_through_the_changed_layer_equals_before(dtype, tol):
    """The layer and its gradients as they were before the layer knew an
    ``act``, to ``dtype``'s rounding: the way back now sums a token's choices
    in another order, its weights and the sum's transpose in float32 where
    they were rounded to ``dtype``."""
    lyr = _expert_layer(4, "relu2", n_experts=32)
    h = jax.random.normal(jax.random.PRNGKey(5), (256, 64)).astype(dtype)
    kw = dict(top_k=6, scale=2.5, token_block=128)

    def now(h, lyr):
        return moe.dropless_moe_ffn(h, lyr, **kw)[0].astype(jnp.float32).sum()

    def before(h, lyr):
        return _relu2_layer_as_it_was(h, lyr, **kw).astype(jnp.float32).sum()

    got = (moe.dropless_moe_ffn(h, lyr, **kw)[0],
           jax.grad(now, argnums=(0, 1))(h, lyr))
    want = (_relu2_layer_as_it_was(h, lyr, **kw),
            jax.grad(before, argnums=(0, 1))(h, lyr))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        np.testing.assert_allclose(a, b, rtol=0, atol=tol * (
            np.abs(b).max() or 1.0))


def test_an_unknown_expert_activation_is_refused():
    with pytest.raises(ValueError, match="expert_act 'gelu'"):
        hybrid.HybridConfig(expert_act="gelu")
    lyr = _expert_layer(0, "relu2", n_experts=8)
    with pytest.raises(ValueError, match="act 'gelu'"):
        moe.dropless_moe_ffn(
            jnp.ones((8, 64)), lyr, top_k=2, scale=1.0, act="gelu")


# -- two keys a step ---------------------------------------------------------

S, CHUNK, WINDOW = 512, 16, 64
CU = [0, 200, 330, 512]


def _two_keys(cp):
    mesh = Mesh(np.array(jax.devices("cpu")[:cp]), axis_names=("cp",))
    full = api.magi_attn_varlen_key(
        CU, CU, causal=True, mesh=mesh, chunk_size=CHUNK, label="full")
    window = api.make_varlen_key_for_new_mask_after_dispatch(
        CU, CU, full, causal=False, window_size=(WINDOW - 1, 0),
        label="window")
    return full, window


@pytest.mark.parametrize("cp", [2, 4])
def test_the_window_key_shares_the_full_keys_dispatch_at_cp(cp):
    """Made after dispatch, the window key holds the full key's partitions,
    and ``calc_attn`` under it on tensors dispatched under the full key is
    the dense attention under the window's mask, forward and backward."""
    full, window = _two_keys(cp)
    assert (_mgr(window).dispatch_meta_q.partitions
            == _mgr(full).dispatch_meta_q.partitions)
    assert window.fixed_partitions is not None and full != window
    assert api.same_dispatch(full, window)
    assert (full.label, window.label) == ("full", "window")
    rng = np.random.default_rng(cp)
    q, k, v = (jnp.asarray(rng.standard_normal((S, h, 32)), jnp.float32)
               for h in (8, 1, 1))
    mask = jnp.asarray(flops.mask_array(traffic_gen.MaskSpec(
        S, tuple(CU), WINDOW)))

    def program(q, k, v):
        qd = api.dispatch(q, full)
        kd, vd = (api.dispatch(t, full, role="kv") for t in (k, v))
        out, _ = api.calc_attn(qd, kd, vd, window)
        return api.undispatch(out, full)

    def dense(q, k, v):
        return ref_attn(q, k, v, mask, compute_dtype=jnp.float32)[0]

    out, ref = jax.jit(program)(q, k, v), dense(q, k, v)
    assert_close(out, ref, atol=1e-4, rtol=1e-4, norm_rtol=3e-5,
                 msg=f"window key at cp {cp}")
    w = jnp.asarray(rng.standard_normal(out.shape), jnp.float32)
    got, want = (jax.grad(lambda *a: (f(*a) * w).sum(), argnums=(0, 1, 2))(
        q, k, v) for f in (jax.jit(program), dense))
    for name, a, b in zip("qkv", got, want):
        assert_close(a, b, atol=1e-3, rtol=1e-3, norm_rtol=1e-4,
                     msg=f"d{name} under the window key at cp {cp}")


@pytest.mark.parametrize("cp", [2, 4])
def test_the_model_under_two_keys_at_cp_above_one(toy, cp):
    """Nothing in the blocks assumes natural order: with the chunks
    permuted over the ranks (a pattern without a scan is not refused) the
    whole model under both keys still agrees with the plain reference."""
    family, cfg, _ = toy
    mesh = Mesh(np.asarray(jax.devices()[:cp]), ("cp",))
    spec = _spec(LENS + [128])
    mcfg = family.model_config(cfg)
    params = family.init_params(mcfg, mesh, 3)
    toks, labels = (jnp.asarray(x) for x in traffic_gen.token_batches(
        spec, cfg["vocab_size"], 3, 1)[0])
    keys = family.make_key(spec, mesh)
    parts = _mgr(keys.full).dispatch_meta_q.partitions
    assert [list(p) for p in parts] != [sorted(p) for p in parts] or any(
        p[-1] - p[0] >= len(p) for p in parts)  # not natural order
    got = family.check_program(mcfg, keys)(params, toks, labels)
    one = jax.devices()[0]
    ref = family.reference(
        jax.device_put(params, one), cfg, jax.device_put(toks, one),
        jax.device_put(labels, one), spec)
    checks = reference.compare(
        jax.device_get(got), jax.device_get(ref), family.CHECKS,
        targets=int((np.asarray(labels) >= 0).sum()))
    assert not _failed(checks), checks


def test_a_pattern_with_window_blocks_wants_its_window_key(toy):
    family, cfg, mesh = toy
    mcfg = family.model_config(cfg)
    keys = family.make_key(_spec(LENS), mesh)
    params = jax.eval_shape(
        lambda: hybrid.init_params(mcfg, jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((sum(LENS),), jnp.int32)
    with pytest.raises(ValueError, match="window_key"):
        jax.eval_shape(lambda p, t: hybrid.forward(p, mcfg, t, keys.full),
                       params, toks)
    other = family.make_key(_spec([sum(LENS)]), Mesh(
        np.asarray(jax.devices()[:2]), ("cp",)))
    with pytest.raises(ValueError, match="lays the sequence out otherwise"):
        jax.eval_shape(lambda p, t: hybrid.forward(
            p, mcfg, t, keys.full, window_key=other.window), params, toks)
    with pytest.raises(ValueError, match="rope_in"):
        hybrid.HybridConfig(rope_in="E")


def test_the_family_plans_both_keys_and_counts_both(toy):
    family, cfg, mesh = toy
    family.model_config(cfg)
    spec = _spec(LENS)
    keys, ms = family.timed_plan(spec, mesh)
    assert ms > 0 and (keys.full.label, keys.window.label) == (
        "full", "window")
    facts = family.plan_facts(keys, flops.rows_area(spec))
    assert facts["slices_by_key"]["full"] == 4
    assert facts["slices"] == sum(facts["slices_by_key"].values())
    # each layer's own band area: the window layers' band is the smaller
    with_window = family.required_flops_per_step(cfg, spec)
    wide = family.required_flops_per_step(
        {**cfg, "sliding_window": max(LENS)}, spec)
    assert with_window < wide
    groups = {g["kind"]: g for g in family.ffa_calls(cfg)}
    assert (groups["window"]["layers"], groups["full"]["layers"]) == (2, 1)
    assert (groups["window"]["window"], groups["full"]["window"]) == (
        cfg["sliding_window"], None)
    with pytest.raises(ValueError, match="traffic window"):
        family.make_key(dataclasses.replace(spec, window=8), mesh)


def test_the_biases_are_fitted_on_the_rings_batches(toy):
    """The ring's ids are the harness's own (they do not depend on the
    documents), and a bias fitted on them together evens the ring as a
    whole: the rows the held experts get are the expected ones."""
    family, cfg, mesh = toy
    mcfg = family.model_config(cfg)
    spec = _spec(LENS)
    ring = family.ring_batches(mcfg, spec.tokens, 11)
    timed = traffic_gen.token_batches(spec, cfg["vocab_size"], 11, 6)
    for mine, (theirs, _) in zip(ring, timed):
        np.testing.assert_array_equal(mine, theirs)
    assert ring.shape == (family.FIT_BATCHES, spec.tokens)
    keys = family.make_key(spec, mesh)
    params = family.init_params(mcfg, mesh, 11)
    fitted = family.balance_routers(params, mcfg, ring, keys)

    def rows(p):
        counted = [jax.device_get(hybrid.routing_counters(
            p, mcfg, toks, keys.full, window_key=keys.window))
            for toks in ring]
        return np.sum([c["rows_per_expert"] for c in counted], axis=0)

    before, after = rows(params), rows(fitted)  # (expert blocks, held)
    expected = len(ring) * spec.tokens * mcfg.top_k * (
        mcfg.experts_held / mcfg.n_experts)
    np.testing.assert_allclose(after.sum(axis=1), expected, rtol=0.02)
    assert (after.max(axis=1) / after.mean(axis=1)).max() < 1.1 < (
        before.max(axis=1) / before.mean(axis=1)).max()
    assert all(float(jnp.abs(lyr["e_bias"]).max()) > 0
               for lyr, kind in zip(fitted["layers"], mcfg.pattern)
               if kind == "E")


# -- labels -------------------------------------------------------------------


def _pallas_scopes(jaxpr) -> list[str]:
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(str(eqn.source_info.name_stack).split("/")[-1])
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found += _pallas_scopes(inner)
    return found


def test_a_labelled_keys_kernel_names_still_match_the_bodies():
    """The label follows the body's name in the scope: ``kernel_times``
    still finds the body, ``keyed_ffa`` finds the label, and a key without
    a label names its kernels as before."""
    import re

    from cellbench import keyed_ffa

    mesh = Mesh(np.array(jax.devices("cpu")[:1]), axis_names=("cp",))
    plain = api.magi_attn_varlen_key(
        CU, CU, causal=True, mesh=mesh, chunk_size=CHUNK)
    full, window = _two_keys(1)
    q, k, v = (jnp.ones((S, h, 128), jnp.bfloat16) for h in (8, 1, 1))

    def scopes(key):
        def loss(q, k, v):
            return api.calc_attn(q, k, v, key)[0].astype(jnp.float32).sum()
        return _pallas_scopes(jax.make_jaxpr(
            jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr)

    bare = scopes(plain)
    assert bare and all(
        name in {kernel_times.PREFIX + body for bodies in
                 kernel_times.BODIES.values() for body in bodies}
        for name in bare), bare
    for key in (full, window):
        named = scopes(key)
        assert [n.removesuffix("_" + key.label) for n in named] == bare
        assert ([kernel_times.kind_of(n) for n in named]
                == [kernel_times.kind_of(n) for n in bare])
        assert all(re.search(keyed_ffa.pattern(key.label), n) for n in named)
        other = "full" if key.label == "window" else "window"
        assert not any(re.search(keyed_ffa.pattern(other), n) for n in named)
    assert set(registry.labelled_choices("ffa_tiles")) >= {"full", "window"}
    assert registry.last_choice("ffa_bwd", label="window") in (
        "fused", "split")


# -- block_q follows the group (PR 36): the packed tile against 256 rows -----

def _program_and_reference(cell_name, lens, monkeypatch, block_q=None, seed=3):
    """``(program's values, reference's, targets, what ran)`` of a cell's
    family at rehearsal widths on documents of ``lens`` tokens; ``block_q``
    pins the q tile of every key by ``MAGI_ATTENTION_FFA_BLOCK_Q`` (the
    keys carry the environment's snapshot: a pin makes keys of its own)."""
    if block_q is None:
        monkeypatch.delenv("MAGI_ATTENTION_FFA_BLOCK_Q", raising=False)
    else:
        monkeypatch.setenv("MAGI_ATTENTION_FFA_BLOCK_Q", str(block_q))
    cell = manifest.load_cell(manifest.ROOT, cell_name)
    family = manifest.load_family(manifest.ROOT, cell.config["family"])
    cfg, *_ = run.cell_sizes(cell, family, 1)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("cp",))
    spec = _spec(lens)
    mcfg = family.model_config(cfg)
    params = family.init_params(mcfg, mesh, seed)
    toks, labels = (jnp.asarray(x) for x in traffic_gen.token_batches(
        spec, cfg["vocab_size"], seed, 1)[0])
    registry.reset_registry()
    got = family.check_program(mcfg, family.make_key(spec, mesh))(
        params, toks, labels)
    ref = family.reference(params, cfg, toks, labels, spec)
    ran = {
        "tiles": registry.labelled_choices("ffa_tiles")
        or {"": registry.last_choice("ffa_tiles")},
        "bwd": registry.labelled_choices("ffa_bwd")
        or {"": registry.last_choice("ffa_bwd")},
        "tiles_source": registry.last_source("ffa_tiles"),
        "bwd_source": registry.last_source("ffa_bwd"),
    }
    return (jax.device_get(got), jax.device_get(ref),
            int((np.asarray(labels) >= 0).sum()), family.CHECKS, ran)


# 2 x 256 rows and a tail: the 256-row default is unclamped, so g = 8 has a
# tile to move from; the toy window (64) cuts every document
RULE_LENS = [300, 129, 211]


@pytest.mark.parametrize("cell_name,group,moved", [
    (CELL, 8, True), ("nemo12b.longdoc.cp1", 4, False)])
def test_the_groups_tile_agrees_with_256_rows_and_the_reference(
    monkeypatch, cell_name, group, moved
):
    """The family's check program (forward, loss, every ``CHECKS``
    gradient) through the runtime at the tile the rule chooses and at
    ``block_q`` 256 pinned: at g = 8 both keys — window and full layers in
    one step — move to 128 rows with every pass packed and the one-pass
    backward (no guard's outcome), and agree with the pinned run and with
    the plain reference at the family's limits; the ``llama`` toy at g = 4
    is the control: the same tile, and the same numbers to the bit."""
    got, ref, targets, limits, ran = _program_and_reference(
        cell_name, RULE_LENS, monkeypatch)
    pinned, _, _, _, ran_pinned = _program_and_reference(
        cell_name, RULE_LENS, monkeypatch, block_q=256)
    packed = "fwd{0}x512g{1} dq{0}x512g{1} dkv{0}x512g{1}"
    if moved:
        # per label: every distinct choice, with who made it
        assert ran["tiles"] == {
            label: packed.format(128, 8) + " (shape_rule)"
            for label in ("full", "window")}
        assert ran_pinned["tiles"] == {
            label: "fwd256x512 dq256x512 dkv256x512g8 (pin)"
            for label in ("full", "window")}
        assert set(ran["bwd"].values()) == set(
            ran_pinned["bwd"].values()) == {"fused (heuristic)"}
    else:
        assert ran["tiles"] == ran_pinned["tiles"] == {
            "": packed.format(256, 4)}
        assert ran["tiles_source"] == "default"
        assert ran_pinned["tiles_source"] == "pin"
        assert ran["bwd"] == ran_pinned["bwd"] == {"": "fused"}
        assert ran["bwd_source"] == ran_pinned["bwd_source"] == "heuristic"
    for name, sides in (("the rule's tile against the reference", (got, ref)),
                        ("256 rows against the reference", (pinned, ref)),
                        ("the rule's tile against 256 rows", (got, pinned))):
        checks = reference.compare(*sides, limits, targets=targets)
        assert not _failed(checks), (name, checks)
    if not moved:
        for name in got:
            np.testing.assert_array_equal(
                np.asarray(got[name]), np.asarray(pinned[name]), err_msg=name)
