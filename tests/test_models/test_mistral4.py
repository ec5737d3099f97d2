"""The ``mistral4`` family (Mistral Small 4) at toy widths on the CPU: the
hybrid block builder with latent attention (low-rank q and kv chains, one
rotary key a token, YaRN frequencies, the softmax and position scales on q)
expanded to full heads through ``calc_attn`` at g = 1, and softmax-routed
SwiGLU experts, against the plain reference
``cellbench/reference_mistral4.py``. The toy's YaRN original length is 64,
under its documents' lengths: the frequency ramp and the position scale are
both at work."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from cellbench import (
    flops,
    manifest,
    reference,
    reference_mistral4,
    run,
    traffic_gen,
)
from magiattention_tpu import api
from magiattention_tpu.kernels import registry
from magiattention_tpu.models import hybrid, llama, moe
from magiattention_tpu.utils.profiling import REGION, profile_scope

CELL = "mistralsmall4.longdocs.cp1"
LENS = [100, 50, 129, 105]  # three of the four reach past position 64


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


def _batch(cfg, spec, seed):
    return tuple(jnp.asarray(x) for x in traffic_gen.token_batches(
        spec, cfg["vocab_size"], seed, 1)[0])


def _compare(family, cfg, mesh, lens=LENS, seed=3, mcfg=None):
    """``reference.compare`` of the family's check program (under ``mcfg``,
    where a fault is planted in the configuration) and its plain reference
    on documents of ``lens`` tokens."""
    spec = _spec(lens)
    mcfg_ref = family.model_config(cfg)
    params = family.init_params(mcfg_ref, mesh, seed)
    toks, labels = _batch(cfg, spec, seed)
    got = family.check_program(mcfg or mcfg_ref, family.make_key(spec, mesh))(
        params, toks, labels)
    one = jax.devices()[0]
    ref = family.reference(
        jax.device_put(params, one), cfg, jax.device_put(toks, one),
        jax.device_put(labels, one), spec)
    return reference.compare(
        jax.device_get(got), jax.device_get(ref), family.CHECKS,
        targets=int((np.asarray(labels) >= 0).sum()))


def _failed(checks) -> set:
    return {name for name, c in checks.items() if not c["ok"]}


@pytest.mark.parametrize("remat", [True, False])
def test_the_step_agrees_with_the_plain_reference(toy, remat):
    family, cfg, mesh = toy
    mcfg = dataclasses.replace(family.model_config(cfg), remat=remat)
    assert mcfg.pattern == "*E*E" and mcfg.route == "softmax_topk"
    checks = _compare(family, cfg, mesh, mcfg=mcfg)
    assert list(checks) == list(reference_mistral4.CHECKS)
    assert not _failed(checks), checks
    assert checks["route_choice"]["err"] == 0
    ran = family.what_ran()
    assert ran["moe_route"] == "softmax_topk"
    assert ran["attention_form"].startswith("expanded")
    # g = 1: nothing packs, and the group rule leaves the default tile
    assert "g" not in ran["ffa_tiles"] and ran["ffa_tiles_source"] == "default"


@pytest.mark.parametrize("cp", [2, 4])
def test_the_model_at_cp_above_one(toy, cp):
    """Nothing in the latent block assumes natural order: the positions in
    the documents follow the dispatch."""
    family, cfg, _ = toy
    mesh = Mesh(np.asarray(jax.devices()[:cp]), ("cp",))
    checks = _compare(family, cfg, mesh, lens=LENS + [128])
    assert not _failed(checks), checks


@pytest.mark.parametrize("cp", [1, 2, 4])
def test_every_gradient_in_float32(toy, cp):
    """The program in float32 IS the reference: loss, logits and the
    gradient of every leaf, the routes teacher-forced, at cp 1, 2 and 4."""
    family, cfg, _ = toy
    mesh = Mesh(np.asarray(jax.devices()[:cp]), ("cp",))
    spec = _spec(LENS + [128])
    mcfg = dataclasses.replace(family.model_config(cfg), dtype="float32")
    params = family.init_params(mcfg, mesh, 7)
    toks, labels = _batch(cfg, spec, 7)
    key = family.make_key(spec, mesh)

    @jax.jit
    def program(params):
        def f(p):
            logits, routes = hybrid.forward(
                p, mcfg, toks, key, with_routes=True)
            loss = hybrid.masked_ce(logits, api.dispatch(labels, key))
            return loss, (api.undispatch(logits, key), [
                api.undispatch(r["topi"], key) for r in routes])
        return jax.value_and_grad(f, has_aux=True)(params)

    @jax.jit
    def plain(params, routes):
        return jax.value_and_grad(
            reference_mistral4.loss_and_logits, has_aux=True)(
            params, cfg, toks, labels, jnp.asarray(flops.mask_array(spec)),
            jnp.asarray(reference_mistral4.positions_in_documents(spec)),
            routes)

    with jax.default_matmul_precision("highest"):
        (loss, (logits, routes)), grads = program(params)
        one = jax.devices()[0]
        (ref_loss, (ref_logits, *_)), ref_grads = plain(
            jax.device_put(params, one), jax.device_put(routes, one))
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    assert reference.rel_err(logits, ref_logits) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        if "e_bias" in name:  # a buffer: no gradient on either side
            assert not np.any(got) and not np.any(want), name
        else:
            assert reference.rel_err(got, want) < 2e-5, name


# -- planted faults ------------------------------------------------------------


def _lat(mcfg, **changed):
    return dataclasses.replace(
        mcfg, latent=dataclasses.replace(mcfg.latent, **changed))


def _own_rotary_keys(monkeypatch):
    """Every head but the first rotates a key of its own (the shared one,
    negated): the rotary key is no longer ONE a token."""
    whole = llama._latent_qkv

    def qkv(h, lyr, cfg, pos):
        q, k, v = whole(h, lyr, cfg, pos)
        nope = cfg.head_dim - cfg.latent.rope_dim
        return q, k.at[:, 1:, nope:].multiply(-1), v

    monkeypatch.setattr(llama, "_latent_qkv", qkv)


def _plain_frequencies(monkeypatch):
    yarn = llama.yarn_inv_freq
    monkeypatch.setattr(llama, "yarn_inv_freq", lambda dim, theta, lat: yarn(
        dim, theta, dataclasses.replace(lat, yarn_factor=1.0)))


def _half_split_pairs(monkeypatch):
    """Channel ``i`` pairs with ``i + rope_dim / 2``, not with its
    neighbour."""
    def rotate(x, cos, sin):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    monkeypatch.setattr(llama, "_rotate_pairs", rotate)


def _no_kv_norm(monkeypatch):
    norm = llama._rms_norm
    monkeypatch.setattr(llama, "_rms_norm", lambda x, w, eps: (
        x if w.shape == (64,) else norm(x, w, eps)))  # the toy's kv_rank


FAULTS = {
    "half_split_rotary": (
        None, _half_split_pairs,
        {"logits", "attn_blocks", "grad_w_q_b", "grad_w_kv_a"}),
    "plain_frequencies": (
        None, _plain_frequencies,
        {"logits", "attn_blocks", "grad_w_q_b", "grad_w_kv_a"}),
    "softmax_scale_without_m2": (
        lambda m: _lat(m, mscale_all_dim=0.0), None,
        {"logits", "attn_blocks", "grad_w_q_b"}),
    "rotary_key_not_shared": (
        None, _own_rotary_keys, {"logits", "attn_blocks", "grad_w_kv_a"}),
    "kv_norm_dropped": (
        None, _no_kv_norm,
        {"logits", "attn_blocks", "grad_w_kv_a", "grad_w_kv_b"}),
    "position_scale_left_out": (
        lambda m: _lat(m, pos_scale_beta=0.0), None,
        {"attn_blocks", "grad_w_q_b"}),
    "sigmoid_for_softmax": (
        lambda m: dataclasses.replace(m, route="sigmoid_topk"), None,
        # with no bias fitted the two score functions choose alike
        {"route_scores", "expert_blocks", "grad_router"}),
}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_limit_meant_for_it(
    toy, monkeypatch, fault
):
    family, cfg, mesh = toy
    config, patch, fails = FAULTS[fault]
    mcfg = family.model_config(cfg)
    if patch is not None:
        patch(monkeypatch)
    checks = _compare(
        family, cfg, mesh, mcfg=config(mcfg) if config else mcfg)
    print(fault, {k: round(c["err"], 5) for k, c in checks.items()})
    assert fails <= _failed(checks), checks
    # a fault in one kind of block leaves the other kind's reading sound
    assert len({"attn_blocks", "expert_blocks"} & _failed(checks)) == 1
    assert checks["head_logits"]["ok"]


def test_the_reference_in_bf16_fails_against_itself_in_float32(
    toy, monkeypatch
):
    """The lower-precision control (bf16 throughout, the matmuls' running
    sums too): by some of the limits, not by each. The toy's contracted
    widths are a sixteenth of the published ones, and so is the pass after
    which a running sum is rounded: as many roundings a product."""
    family, cfg, mesh = toy
    monkeypatch.setattr(reference_mistral4, "ACC_CHUNK", 8)
    monkeypatch.setattr(reference_mistral4, "ACC_CHUNK_KEYS", 32)
    spec = _spec(LENS)
    mcfg = family.model_config(cfg)
    params = family.init_params(mcfg, mesh, 3)
    toks, labels = _batch(cfg, spec, 3)
    family.check_program(mcfg, family.make_key(spec, mesh))(
        params, toks, labels)  # the routes and the stream both are forced on
    forced = {"routes": family._RUN.pop("check_routes"),
              "stream": family._RUN.pop("check_stream")}
    sides = [jax.device_get(reference_mistral4.reference(
        params, cfg, toks, labels, spec, **forced, dtype=dtype))
        for dtype in (jnp.bfloat16, jnp.float32)]
    checks = reference.compare(
        *sides, family.CHECKS, targets=int((np.asarray(labels) >= 0).sum()))
    print({k: round(c["err"], 7) for k, c in checks.items()})
    failed = _failed(checks)
    # (at the published widths the whole-stream names fail too: CHECKS)
    assert {"attn_blocks", "expert_blocks", "head_logits"} <= failed
    assert failed != set(checks), checks  # the loss is a mean: it passes


def test_a_running_sum_in_bf16_is_rounded_every_pass():
    """``_mm`` in float32 is the plain product; in bf16 it is the product
    summed a pass at a time in bf16, further from the exact one than a
    result rounded once."""
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 1000))
    b = jax.random.normal(jax.random.PRNGKey(1), (1000, 48))
    with jax.default_matmul_precision("highest"):
        exact = a @ b
        np.testing.assert_array_equal(reference_mistral4._mm(a, b), exact)
        a16, b16 = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
        once = (a16.astype(jnp.float32) @ b16.astype(jnp.float32)).astype(
            jnp.bfloat16)
        summed = reference_mistral4._mm(a16, b16)
    assert summed.dtype == jnp.bfloat16 and summed.shape == exact.shape
    ref = a16.astype(jnp.float32) @ b16.astype(jnp.float32)
    assert reference.rel_err(summed, ref) > 1.5 * reference.rel_err(once, ref)
    assert reference.rel_err(summed, ref) < 2e-2


def test_the_stream_is_every_blocks_input(toy):
    """``with_stream``: the embedding, then what every block returned; the
    logits are the head on the last, and the step's jaxpr without the flag
    is what it was."""
    family, cfg, mesh = toy
    mcfg = family.model_config(cfg)
    spec = _spec(LENS)
    params = family.init_params(mcfg, mesh, 3)
    toks, _ = _batch(cfg, spec, 3)
    key = family.make_key(spec, mesh)
    logits, stream = jax.jit(lambda p: hybrid.forward(
        p, mcfg, toks, key, with_stream=True))(params)
    assert len(stream) == len(mcfg.pattern) + 1
    assert all(x.shape == (spec.tokens, mcfg.dim) for x in stream)
    np.testing.assert_array_equal(
        stream[0], params["embed"][toks].astype(jnp.bfloat16))
    head = llama._rms_norm(
        stream[-1], params["final_norm"], mcfg.norm_eps
    ) @ params["lm_head"].astype(jnp.bfloat16)
    assert reference.rel_err(logits, head) < 1e-2
    plain = hybrid.forward(params, mcfg, toks, key)
    assert plain.shape == logits.shape  # no tuple without a flag


def test_a_token_sent_to_a_far_expert_fails_route_choice(toy, monkeypatch):
    """One token's fourth expert replaced by its lowest-scored one, planted
    in the program's top-k: ``route_choice`` fails, whatever the others
    read."""
    route = moe.route_softmax_topk

    def one_token_astray(h, router, bias, top_k, scale):
        topi, _, s = route(h, router, bias, top_k, scale)
        topi = topi.at[7, -1].set(jnp.argmin(s[7] + bias).astype(topi.dtype))
        chosen = jnp.take_along_axis(s, topi, axis=-1)
        return topi, chosen / chosen.sum(-1, keepdims=True) * scale, s

    monkeypatch.setattr(moe, "route_softmax_topk", one_token_astray)
    checks = _compare(*toy)
    assert not checks["route_choice"]["ok"], checks


# -- the route and the share ---------------------------------------------------


def _expert_layer(seed, dim=64, n_experts=16, ffn=32):
    cfg = hybrid.HybridConfig(
        dim=dim, n_experts=n_experts, experts_held=n_experts, top_k=4,
        expert_ffn=ffn, shared_ffn=ffn, expert_act="swiglu",
        route="softmax_topk")
    return hybrid._init_experts(cfg, jax.random.PRNGKey(seed))


REF_CFG = {"num_experts_per_tok": 4, "routed_scaling_factor": 1.0,
           "expert_offset": 0}


def test_the_softmax_route_is_the_references():
    """The scores are the softmax over every expert, the experts chosen the
    reference's own, and the weights the softmax over the chosen logits."""
    lyr = _expert_layer(2)
    lyr["e_bias"] = 0.02 * jax.random.normal(jax.random.PRNGKey(9), (16,))
    h = jax.random.normal(jax.random.PRNGKey(3), (300, 64))
    with jax.default_matmul_precision("highest"):
        topi, weights, s = moe.route_softmax_topk(
            h, lyr["router"], lyr["e_bias"], 4, 1.0)
        _, own_biased, choice = reference_mistral4._experts(
            h, lyr, None, REF_CFG)
        logits = h @ lyr["router"]
    np.testing.assert_allclose(s.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(s, jax.nn.softmax(logits, -1), rtol=1e-5)
    np.testing.assert_array_equal(
        jnp.sum(jax.nn.one_hot(topi, 16), axis=1), choice)
    np.testing.assert_allclose(
        jnp.take_along_axis(s + lyr["e_bias"], topi, -1), own_biased,
        rtol=1e-5)
    np.testing.assert_allclose(weights, jax.nn.softmax(
        jnp.take_along_axis(logits, topi, -1), -1), rtol=1e-4)
    # the bias picks and weighs nothing: without it, other experts, and the
    # weights of the chosen are still their own scores'
    assert np.any(np.asarray(topi) != np.asarray(moe.route_softmax_topk(
        h, lyr["router"], 0 * lyr["e_bias"], 4, 1.0)[0]))


def test_the_shares_add_up():
    """The routed parts of the four 4-expert shares (offsets 0, 4, 8, 12 of
    16) plus the shared expert counted once equal the uncut reference
    layer."""
    lyr = _expert_layer(0)
    h = jax.random.normal(jax.random.PRNGKey(1), (200, 64))
    with jax.default_matmul_precision("highest"):
        whole, *_ = reference_mistral4._experts(h, lyr, None, REF_CFG)
        shared = reference_mistral4._swiglu(h, lyr["ws_up"], lyr["ws_down"])
    total, rows = shared, 0
    for offset in (0, 4, 8, 12):
        share = {**lyr, "w_up": lyr["w_up"][offset:offset + 4],
                 "w_down": lyr["w_down"][offset:offset + 4]}
        y, routes = moe.dropless_moe_ffn(
            h, share, top_k=4, scale=1.0, expert_offset=offset,
            token_block=100, act="swiglu", route="softmax_topk")
        total = total + (y - shared)  # this share's routed part
        rows += int(moe.held_expert_rows(routes["topi"], 4, offset).sum())
    assert rows == 200 * 4  # every (token, choice) pair in exactly one share
    assert float(jnp.linalg.norm(total - whole) / jnp.linalg.norm(whole)) < 1e-5
    assert registry.last_choice("moe_route") == "softmax_topk"


def test_an_unknown_route_or_a_latent_without_rotation_is_refused():
    with pytest.raises(ValueError, match="route 'tanh_topk'"):
        hybrid.HybridConfig(route="tanh_topk")
    with pytest.raises(ValueError, match="route 'tanh_topk'"):
        moe.dropless_moe_ffn(
            jnp.ones((8, 64)), _expert_layer(0), top_k=2, scale=1.0,
            route="tanh_topk")
    lat = llama.LatentAttention(q_rank=8, kv_rank=8, rope_dim=64)
    with pytest.raises(ValueError, match="latent attention rotates"):
        hybrid.HybridConfig(latent=lat, rope_theta=1e4)  # head_dim 64: all
    lat = dataclasses.replace(lat, rope_dim=32)
    with pytest.raises(ValueError, match="latent attention rotates"):
        hybrid.HybridConfig(latent=lat, rope_theta=None)
    # what the latent path would not read is refused, not ignored
    with pytest.raises(ValueError, match="n_kv_heads 1"):
        hybrid.HybridConfig(latent=lat, rope_theta=1e4)
    with pytest.raises(ValueError, match="rope_in 'W'"):
        hybrid.HybridConfig(
            latent=lat, rope_theta=1e4, n_kv_heads=4, rope_in="W")


def test_yarn_frequencies_at_the_published_parameters(toy):
    """theta 10000 over 64 rotary channels, 8192 positions, factor 128: the
    first 12 frequencies are kept, from the 25th on they are divided by the
    factor, and the program's are the reference's."""
    family, *_ = toy
    cfg = manifest.load_cell(manifest.ROOT, CELL).config
    lat = family.latent(cfg)
    got = llama.yarn_inv_freq(64, 1e4, lat)
    plain = 1e4 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:13], plain[:13], rtol=1e-6)
    np.testing.assert_allclose(got[25:], plain[25:] / 128, rtol=1e-6)
    assert np.all(np.diff(got) < 0) and got[13] < plain[13]
    np.testing.assert_allclose(
        got, reference_mistral4.yarn_frequencies(cfg), rtol=1e-6)
    assert lat.softmax_mscale == pytest.approx(1.4852 ** 2, rel=1e-4)
    assert reference_mistral4.softmax_scale(cfg) == pytest.approx(
        128 ** -0.5 * lat.softmax_mscale)
    assert llama.yarn_inv_freq(
        64, 1e4, dataclasses.replace(lat, yarn_factor=1.0)) == pytest.approx(
        plain)


# -- what the other families trace to -----------------------------------------


def _attn_block_as_it_was(x, lyr, cfg, pos, attn_key, rope=True):
    """``llama.attn_block`` as PR 36 left it, before it knew a latent leaf."""
    dt = x.dtype
    with profile_scope(REGION.attn_qkv):
        h = llama._rms_norm(x, lyr["attn_norm"], cfg.norm_eps)
        q = (h @ lyr["wq"].astype(dt)).reshape(-1, cfg.n_heads, cfg.head_dim)
        k = (h @ lyr["wk"].astype(dt)).reshape(
            -1, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ lyr["wv"].astype(dt)).reshape(
            -1, cfg.n_kv_heads, cfg.head_dim)
        if "q_norm" in lyr:
            q = llama._rms_norm(q, lyr["q_norm"], cfg.norm_eps)
            k = llama._rms_norm(k, lyr["k_norm"], cfg.norm_eps)
        if rope and cfg.rope_theta is not None:
            q = llama._rope(q, pos, cfg.rope_theta)
            k = llama._rope(k, pos, cfg.rope_theta)
    attn_out, _ = api.calc_attn(q, k, v, attn_key)
    with profile_scope(REGION.attn_out):
        attn_out = attn_out.reshape(-1, cfg.n_heads * cfg.head_dim)
        if "w_attn_gate" in lyr:
            gate = jax.nn.sigmoid(jnp.dot(
                h, lyr["w_attn_gate"].astype(dt),
                preferred_element_type=jnp.float32))
            attn_out = (attn_out.astype(jnp.float32) * gate).astype(dt)
        y = attn_out @ lyr["wo"].astype(dt)
        if "attn_post_norm" in lyr:
            y = llama._rms_norm(y, lyr["attn_post_norm"], cfg.norm_eps)
        return x + y


def _route_sigmoid_as_it_was(h, router, bias, top_k, scale):
    s = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), router, precision=jax.lax.Precision.HIGHEST))
    _, topi = jax.lax.top_k(jax.lax.stop_gradient(s + bias), top_k)
    topi = moe.checkpoint_name(topi, moe.ROUTES_NAME)
    chosen = jnp.take_along_axis(s, topi, axis=-1)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scale
    return topi, weights, s


# the three families' attention blocks: llama (GQA, rope), nemotron_h (g =
# 16, no rotation), afmoe (q/k norms, gate, post-norm; a layer without rope)
BLOCKS = {
    "llama": (dict(n_heads=4, n_kv_heads=1, rope_theta=1e4), True),
    "nemotron_h": (dict(n_heads=16, n_kv_heads=1, rope_theta=None), True),
    "afmoe_window": (dict(n_heads=8, n_kv_heads=1, rope_theta=1e4,
                          qk_norm=True, attn_gate=True, post_norm=True),
                     True),
    "afmoe_full": (dict(n_heads=8, n_kv_heads=1, rope_theta=1e4,
                        qk_norm=True, attn_gate=True, post_norm=True), False),
}


@pytest.mark.parametrize("block", BLOCKS)
def test_the_other_families_blocks_trace_as_before(block):
    """The plain ``attn_block`` is untouched by the latent leaves: the same
    jaxpr, forward and backward, as the block before them."""
    kw, rope = BLOCKS[block]
    cfg = hybrid.HybridConfig(dim=64, pattern="*", head_dim=64, **kw)
    lyr = hybrid._init_block(cfg, "*", jax.random.PRNGKey(0))
    assert "w_q_a" not in lyr and {"wq", "wk", "wv"} <= set(lyr)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("cp",))
    cu = [0, 100, 256]
    key = api.magi_attn_varlen_key(cu, cu, causal=True, mesh=mesh,
                                   chunk_size=16)
    x = jnp.ones((256, 64), jnp.bfloat16)
    pos = api.get_position_ids(key)

    def traced(fn):
        def loss(x, lyr):
            return fn(x, lyr, cfg, pos, key, rope=rope).astype(
                jnp.float32).sum()
        return str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(
            x, lyr))

    assert traced(llama.attn_block) == traced(_attn_block_as_it_was)


def test_the_sigmoid_route_traces_as_before():
    h = jnp.ones((64, 32), jnp.bfloat16)
    router, bias = jnp.ones((32, 8)), jnp.zeros((8,))

    def traced(fn):
        return str(jax.make_jaxpr(jax.value_and_grad(
            lambda h, r: fn(h, r, bias, 2, 2.5)[1].sum(), argnums=(0, 1)))(
            h, router))

    assert traced(moe.route_sigmoid_topk) == traced(_route_sigmoid_as_it_was)
    lyr = _expert_layer(0)

    def layer(**kw):
        return str(jax.make_jaxpr(lambda h: moe.dropless_moe_ffn(
            h, lyr, top_k=4, scale=1.0, token_block=32, act="swiglu",
            **kw)[0])(
            jnp.ones((64, 64), jnp.bfloat16)))

    assert layer() == layer(route="sigmoid_topk") != layer(
        route="softmax_topk")
