"""The ``K`` block (``models/hybrid.py``: the gated delta rule with a
per-channel decay) in the Solar Open 2 toy ``*EKEKEKE`` through the normal
path, against the plain reference of the ``solar_open2`` family
(``cellbench/reference_solar_open2.py``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from cellbench import (
    manifest,
    reference,
    reference_afmoe,
    reference_solar_open2,
    run,
    traffic_gen,
)
from magiattention_tpu.kernels import registry
from magiattention_tpu.models import hybrid, moe

CELL = "solaropen2.packed.cp1"
LENS = [100, 50, 129, 105]  # starts at 100, 150 and 279: inside chunks


@pytest.fixture(scope="module")
def toy():
    """The cell's family and its configuration at rehearsal widths."""
    cell = manifest.load_cell(manifest.ROOT, CELL)
    family = manifest.load_family(manifest.ROOT, cell.config["family"])
    cfg, *_ = run.cell_sizes(cell, family, 1)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("cp",))
    return family, cfg, mesh


def _batch(cfg, lens, seed):
    cu = tuple(np.cumsum([0, *lens]).tolist())
    spec = traffic_gen.MaskSpec(tokens=cu[-1], cu_seqlens=cu, window=None)
    toks, labels = (jnp.asarray(x) for x in traffic_gen.token_batches(
        spec, cfg["vocab_size"], seed, 1)[0])
    return spec, toks, labels


def test_the_toy_pattern_is_the_published_period(toy):
    family, cfg, _ = toy
    mcfg = family.model_config(cfg)
    assert mcfg.pattern == "*EKEKEKE"
    assert (mcfg.kda_neg_eigval, mcfg.attn_gate, mcfg.rope_theta,
            mcfg.route, mcfg.expert_act) == (
        True, True, None, "sigmoid_topk", "swiglu")


def test_the_train_step_is_the_reference_in_float32(toy):
    """``hybrid.train_step`` with float32 activations: its loss, and the
    update of every leaf the checks name (``-lr`` times the gradient), are
    the reference's own (which routes by its own scores: in float32 no
    near-tie splits the two)."""
    family, cfg, mesh = toy
    mcfg = dataclasses.replace(family.model_config(cfg), dtype="float32")
    spec, toks, labels = _batch(cfg, LENS, 4)
    key = family.make_key(spec, mesh)
    params = hybrid.init_params(mcfg, jax.random.PRNGKey(4))
    before = jax.tree.map(np.asarray, params)
    lr = 1.0
    new, loss = hybrid.train_step(params, mcfg, toks, labels, key, lr)
    ref = jax.device_get(reference_solar_open2.reference(
        jax.tree.map(jnp.asarray, before), cfg, toks, labels, spec))
    assert abs(float(loss) - float(ref["loss"])) < 1e-5
    where = reference_solar_open2.grad_leaves(mcfg.pattern)
    moved = reference_solar_open2.take_leaves(jax.tree.map(
        lambda a, b: (np.asarray(a) - np.asarray(b)) / lr, before, new), where)
    for name in where:
        got, want = np.asarray(moved[name]), np.asarray(ref[name])
        err = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert err < 1e-4, (name, err)
    logits = hybrid.forward(
        jax.tree.map(jnp.asarray, before), mcfg, toks, key)
    assert reference.rel_err(logits, ref["logits"]) < 1e-5
    assert registry.last_choice("kda") == "pallas_chunked"


def test_the_check_program_agrees_in_bf16(toy):
    """The harness's comparison at the toy: the bf16 program's check
    program against the float32 reference, teacher-forced on the routes,
    every model check within its limit."""
    family, cfg, mesh = toy
    spec, toks, labels = _batch(cfg, LENS, 3)
    mcfg = family.model_config(cfg)
    params = family.init_params(mcfg, mesh, 3)
    got = family.check_program(mcfg, family.make_key(spec, mesh))(
        params, toks, labels)
    ref = family.reference(params, cfg, toks, labels, spec)
    checks = reference.compare(
        jax.device_get(got), jax.device_get(ref),
        reference_solar_open2.CHECKS,
        targets=int((np.asarray(labels) >= 0).sum()))
    assert all(c["ok"] for c in checks.values()), checks


def test_a_k_block_at_cp_two_is_refused_by_name(toy):
    family, cfg, _ = toy
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("cp",))
    spec = traffic_gen.MaskSpec(tokens=512, cu_seqlens=(0, 200, 512),
                                window=None)
    key = family.make_key(spec, mesh)
    mcfg = family.model_config(cfg)
    params = jax.eval_shape(
        lambda: hybrid.init_params(mcfg, jax.random.PRNGKey(0)))
    with pytest.raises(NotImplementedError, match=(
            r"cp = 2: pattern '\*EKEKEKE' has the gated delta rule \('K'\), "
            r"whose state .* dispatch permutes .*ROADMAP B1")):
        jax.eval_shape(
            lambda p, t: hybrid.forward(p, mcfg, t, key), params,
            jax.ShapeDtypeStruct((512,), jnp.int32))


def test_the_shares_add_up():
    """The guide's share test at the published routing (sigmoid, top 8,
    SwiGLU, one shared expert): the routed parts of five 8-expert shares of
    a 40-expert layer, plus the shared expert counted once, equal the uncut
    reference layer."""
    cfg = hybrid.HybridConfig(
        dim=64, n_experts=40, experts_held=40, top_k=8, expert_ffn=32,
        shared_ffn=32, expert_act="swiglu")
    lyr = hybrid._init_experts(cfg, jax.random.PRNGKey(0))
    h = jax.random.normal(jax.random.PRNGKey(1), (200, 64))
    ref_cfg = {"num_experts_per_tok": 8, "route_scale": 1.0,
               "expert_offset": 0}
    with jax.default_matmul_precision("highest"):
        whole, *_ = reference_afmoe._experts(h, lyr, None, ref_cfg)
        shared = reference_afmoe._swiglu(h, lyr["ws_up"], lyr["ws_down"])
    total = shared
    for offset in range(0, 40, 8):
        share = {**lyr, "w_up": lyr["w_up"][offset:offset + 8],
                 "w_down": lyr["w_down"][offset:offset + 8]}
        y, _ = moe.dropless_moe_ffn(
            h, share, top_k=8, scale=1.0, expert_offset=offset,
            token_block=100, act="swiglu", route="sigmoid_topk")
        total = total + (y - shared)  # this share's routed part
    err = jnp.linalg.norm(total - whole) / jnp.linalg.norm(whole)
    assert float(err) < 1e-5
