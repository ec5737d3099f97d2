"""The model's regions and the join from a compiled step to a device trace
(``utils/profiling.py``): ``scope_path`` on the paths JAX and XLA write,
``instruction_scopes`` on a toy ``llama`` and a toy hybrid step compiled on
the CPU under ``MAGI_ATTENTION_PROFILE_MODE`` (the metadata is there on every
backend) and on hand-written fusions, ``compiled_step_texts`` from the
signature ``_StepJit`` keeps, and that with the flag off nothing of it is in
the program. One compile a step, shared by its cases."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from magiattention_tpu import api
from magiattention_tpu.models import hybrid, llama
from magiattention_tpu.utils import profiling
from magiattention_tpu.utils.profiling import (
    ATTN_REGION,
    MODEL_REGIONS,
    NESTED_REGIONS,
    REGION,
    instruction_scopes,
    region_of,
    scope_path,
)

S, CHUNK, WINDOW = 256, 16, 64
CU = [0, 100, 256]
# vocabularies no other test of the suite uses: a step traced by another
# test with the flag the other way is not this jit's cache entry
LLAMA = llama.LlamaConfig(
    vocab_size=136, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
    ffn_hidden=128, remat=True)
HYBRID = hybrid.HybridConfig(
    vocab_size=136, dim=64, pattern="MEW*D", n_heads=4, n_kv_heads=1,
    head_dim=64, dense_ffn=128, mamba_heads=2, mamba_head_dim=64,
    ssm_groups=1, ssm_state=32, n_experts=8, experts_held=4, top_k=2,
    expert_ffn=64, shared_ffn=64, moe_token_block=128, remat=True)
LLAMA_STEP = "magiattention_tpu.models.llama.train_step"
HYBRID_STEP = "magiattention_tpu.models.hybrid.train_step"


def _keys():
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("cp",))
    full = api.magi_attn_varlen_key(
        CU, CU, causal=True, mesh=mesh, chunk_size=CHUNK, label="full")
    window = api.make_varlen_key_for_new_mask_after_dispatch(
        CU, CU, full, causal=False, window_size=(WINDOW - 1, 0),
        label="window")
    return full, window


def _tokens(vocab):
    toks = jnp.arange(S, dtype=jnp.int32) % vocab
    return toks, jnp.roll(toks, -1)


def _llama_args(cfg):
    return (llama.init_params(cfg, jax.random.key(0)), cfg,
            *_tokens(cfg.vocab_size), _keys()[0])


def _hybrid_args(cfg):
    full, window = _keys()
    return (hybrid.init_params(cfg, jax.random.key(0)), cfg,
            *_tokens(cfg.vocab_size), full), {"window_key": window}


@pytest.fixture(scope="module")
def flag_on():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("MAGI_ATTENTION_PROFILE_MODE", "1")
        mp.setattr(profiling, "_STEPS_SEEN", {})
        yield
    llama.train_step.last_call = hybrid.train_step.last_call = None


@pytest.fixture(scope="module")
def tables(flag_on):
    """``{step name: (table, on_boundary)}`` of one toy step a family, each
    RUN under the flag and handed out by ``compiled_step_texts``; and of
    the ``llama`` step without remat, lowered apart."""
    _, loss = llama.train_step(*_llama_args(LLAMA))
    args, kwargs = _hybrid_args(HYBRID)
    _, loss_h = hybrid.train_step(*args, **kwargs)
    assert np.isfinite(float(loss)) and np.isfinite(float(loss_h))
    texts = profiling.compiled_step_texts()
    assert set(texts) == {LLAMA_STEP, HYBRID_STEP}
    plain = dataclasses.replace(LLAMA, remat=False)
    texts["no_remat"] = llama.train_step.lower(
        *_llama_args(plain)).compile().as_text()
    return {name: instruction_scopes(text) for name, text in texts.items()}


def _entries(table):
    return {(region_of(scopes), which)
            for scopes, which in table.values() if scopes is not None}


@pytest.mark.parametrize("op_name, scopes, which", [
    ("jit(train_step)/jvp(mlp)/dot_general", ("mlp",), "fwd"),
    ("jit(train_step)/jvp()/add", (), "fwd"),
    ("jit(train_step)/update/sub", ("update",), "none"),
    ("jit(train_step)/transpose(jvp(head_loss))/jit(log_softmax)/mul",
     ("head_loss",), "bwd"),
    # a jitted function as the last element is no primitive to leave out
    ("jit(train_step)/jvp(mlp)/jit(silu)", ("mlp",), "fwd"),
    ("jit(train_step)/transpose(jvp(jvp()))/checkpoint/mlp/dot_general",
     ("checkpoint", "mlp"), "bwd"),
    ("jit(train_step)/transpose(jvp(jvp()))/checkpoint/rematted_computation"
     "/DistAttnRuntime.calc_attn/magi_fwd_kernel_gqa/while/body/add",
     ("checkpoint", "rematted_computation", ATTN_REGION,
      "magi_fwd_kernel_gqa", "while", "body"), "refwd"),
    ("jit(train_step)/jvp(DistAttnRuntime.calc_attn)/shard_map/"
     "group_cast_stage0/group_cast_ragged/ragged_all_to_all",
     (ATTN_REGION, "shard_map", "group_cast_stage0", "group_cast_ragged"),
     "fwd"),
    # what XLA leaves of a gather it expands: the region, not the pass
    ("cond/branch_1_fun/moe_rows/gather",
     ("cond", "branch_1_fun", "moe_rows"), "none"),
    ("params['embed']", (), "none"),
])
def test_scope_path(op_name, scopes, which):
    assert scope_path(op_name) == (scopes, which)


def test_region_of_is_the_innermost_region():
    assert region_of(("checkpoint", "moe_rows", "while", "moe_experts",
                      "magi_ragged_dot_kernel")) == REGION.moe_experts
    assert region_of((ATTN_REGION, "magi_fwd_kernel")) == ATTN_REGION
    assert region_of(("checkpoint", "while")) is None
    assert region_of(None) is None
    # a path cut short that kept a cast's span is the runtime's
    assert region_of(("group_cast_stage0", "group_cast_pp")) == ATTN_REGION


def test_no_region_is_taken_for_a_kernel_or_a_collective():
    """``kernel_times.kind_of``, ``named_ops`` and the event classes match
    these anywhere in an instruction's name."""
    taken = re.compile(
        r"magi_|ragged[-_]dot|all[-_]to[-_]all|all[-_]gather|all[-_]reduce|"
        r"reduce[-_]scatter|ppermute|collective|psum|pmax|pmin")
    names = MODEL_REGIONS + NESTED_REGIONS
    assert not [name for name in names if taken.search(name)]
    assert vars(REGION) == {name: name for name in names}


LAYER_REGIONS = [REGION.attn_qkv, ATTN_REGION, REGION.attn_out, REGION.mlp]


@pytest.mark.parametrize("region", LAYER_REGIONS)
def test_a_rematted_llama_layer_has_all_three_passes(tables, region):
    found = _entries(tables[LLAMA_STEP][0])
    assert {(region, "fwd"), (region, "refwd"), (region, "bwd")} <= found


def test_what_is_outside_a_llama_layer_is_never_made_again(tables):
    found = _entries(tables[LLAMA_STEP][0])
    for region in (REGION.embed, REGION.head_loss):
        assert {(region, "fwd"), (region, "bwd")} <= found
        assert (region, "refwd") not in found
    assert {w for r, w in found if r == REGION.update} == {"none"}
    used = {r for r, _ in found} - {None}
    assert used == {*LAYER_REGIONS, REGION.embed, REGION.head_loss,
                    REGION.update}


def test_without_remat_no_instruction_is_a_re_forward(tables):
    """``rematted_computation`` is what ``jax.checkpoint``'s re-run of the
    forward puts in the path, and nothing else does."""
    passes = {which for _, which in tables["no_remat"][0].values()}
    assert passes == {"fwd", "bwd", "none"}
    assert {"fwd", "refwd", "bwd", "none"} == {
        which for _, which in tables[LLAMA_STEP][0].values()}
    def marked(table):
        return {which for scopes, which in table.values()
                if profiling.REMAT_MARKER in (scopes or ())}

    assert marked(tables[LLAMA_STEP][0]) == {"refwd"}
    assert marked(tables["no_remat"][0]) == set()


@pytest.mark.parametrize("region", [
    REGION.ssm, REGION.moe_route, REGION.moe_rows, REGION.moe_experts,
    REGION.moe_shared, REGION.mlp, REGION.attn_qkv, REGION.attn_out,
    ATTN_REGION])
def test_every_block_of_the_hybrid_pattern_has_its_regions(tables, region):
    """``M``, ``E``, ``W``, ``*``, ``D``: forward, re-forward, backward."""
    found = _entries(tables[HYBRID_STEP][0])
    assert {(region, "fwd"), (region, "bwd")} <= found
    # a block is checkpointed alone: what follows calc_attn in it feeds no
    # backward, and XLA drops its re-run
    assert ((region, "refwd") in found) == (region != REGION.attn_out)


def test_the_hybrid_step_uses_every_region(tables):
    """Every model region, and no nested one: those are opened only where a
    family's leaves bring the work."""
    found = _entries(tables[HYBRID_STEP][0])
    assert {r for r, _ in found} - {None} == {*MODEL_REGIONS, ATTN_REGION}
    assert {w for r, w in found if r == REGION.update} == {"none"}


def test_a_latent_attention_block_opens_its_nested_region(flag_on):
    """``mla_assemble`` lies inside ``attn_qkv`` in every pass, an
    instruction in it is the nested region's and not ``attn_qkv``'s, and the
    projections stay ``attn_qkv``'s."""
    cfg = dataclasses.replace(
        HYBRID, vocab_size=138, pattern="*E", n_heads=2, n_kv_heads=2,
        head_dim=64, rope_theta=1e4, latent=llama.LatentAttention(
            q_rank=32, kv_rank=16, rope_dim=32, yarn_factor=8.0,
            yarn_original_len=64, mscale_all_dim=1.0, pos_scale_beta=0.1))
    args, _ = _hybrid_args(cfg)
    table, _ = instruction_scopes(
        hybrid.train_step.lower(*args).compile().as_text())
    inside = [scopes for scopes, _ in table.values()
              if scopes and REGION.mla_assemble in scopes]
    assert inside and all(
        REGION.attn_qkv in scopes[:scopes.index(REGION.mla_assemble)]
        and region_of(scopes) == REGION.mla_assemble for scopes in inside)
    found = _entries(table)
    for region in (REGION.mla_assemble, REGION.attn_qkv):
        assert {(region, "fwd"), (region, "refwd"), (region, "bwd")} <= found


FUSIONS = """HloModule toy

%fused_computation.1 (p0: bf16[64,128], p1: bf16[64,32], p2: f32[128,32]) -> f32[128,32] {
  %p0 = bf16[64,128]{1,0} parameter(0)
  %p1 = bf16[64,32]{1,0} parameter(1)
  %dot.1 = f32[128,32]{1,0} convolution(%p0, %p1), dim_labels=fb_io->bf, metadata={op_name="jit(train_step)/transpose(jvp(jvp()))/checkpoint/mlp/dot_general" stack_frame_id=7}
  %p2 = f32[128,32]{1,0} parameter(2)
  %mul.1 = f32[128,32]{1,0} multiply(%dot.1, %dot.1), metadata={op_name="jit(train_step)/update/mul"}
  ROOT %sub.1 = f32[128,32]{1,0} subtract(%p2, %mul.1), metadata={op_name="jit(train_step)/update/sub"}
}

%fused_computation.2 (p0.1: bf16[64,8,16], p1.1: f32[64,16]) -> (bf16[64,8,16], bf16[64,8,16]) {
  %p0.1 = bf16[64,8,16]{2,1,0} parameter(0)
  %mul.2 = f32[64,8,16]{2,1,0} convert(%p0.1), metadata={op_name="jit(train_step)/jvp(attn_qkv)/mul"}
  %mul.3 = f32[64,8,16]{2,1,0} multiply(%mul.2, %mul.2), metadata={op_name="jit(train_step)/jvp(attn_qkv)/mul"}
  %convert.2 = bf16[64,8,16]{2,1,0} convert(%mul.3)
  ROOT %tuple.2 = (bf16[64,8,16]{2,1,0}, bf16[64,8,16]{2,1,0}) tuple(%convert.2, %convert.2)
}

%fused_computation.3 (p0.2: s32[64]) -> s32[64] {
  %p0.2 = s32[64]{0} parameter(0)
  ROOT %gather.3 = s32[64]{0} reshape(%p0.2), metadata={op_name="gather"}
}

%branch (p0.3: s32[64]) -> s32[64] {
  %p0.3 = s32[64]{0} parameter(0)
  %add.4 = s32[64]{0} add(%p0.3, %p0.3), metadata={op_name="jit(train_step)/transpose(jvp(jvp()))/checkpoint/while/body/moe_rows/add"}
  ROOT %gather.4 = s32[64]{0} negate(%add.4), metadata={op_name="cond/branch_1_fun/jvp(moe_rows)/gather"}
}

ENTRY %main (a: bf16[64,128], b: bf16[64,32], c: f32[128,32], d: bf16[64,8,16], e: s32[64]) -> f32[128,32] {
  %a = bf16[64,128]{1,0} parameter(0), metadata={op_name="params['w']"}
  %b = bf16[64,32]{1,0} parameter(1)
  %c = f32[128,32]{1,0} parameter(2)
  %d = bf16[64,8,16]{2,1,0} parameter(3)
  %e = s32[64]{0} parameter(4)
  %fusion.2 = (bf16[64,8,16]{2,1,0}, bf16[64,8,16]{2,1,0}) fusion(%d, %c), kind=kLoop, calls=%fused_computation.2
  %fusion.3 = s32[64]{0} fusion(%e), kind=kCustom, calls=%fused_computation.3, metadata={op_name="jit(train_step)/jvp(embed)/jit(_take)/gather"}
  %copy.5 = bf16[64,32]{0,1} copy(%b)
  %gather.6 = s32[64]{0} negate(%e), metadata={op_name="gather"}
  ROOT %multiply_subtract_fusion = f32[128,32]{1,0} fusion(%a, %b, %c), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/update/sub"}
}
"""


def test_a_fusion_is_where_its_matmul_is_and_says_when_it_holds_two():
    table, on_boundary = instruction_scopes(FUSIONS)
    # a weight gradient with the update as its epilogue: the matmul's
    assert table["multiply_subtract_fusion"] == (("checkpoint", "mlp"), "bwd")
    assert table["sub.1"] == (("update",), "none")  # inside, its own
    # several outputs, no metadata: what its instructions carry
    assert table["fusion.2"] == (("attn_qkv",), "fwd")
    # its root's path was cut short, its own is whole
    assert table["fusion.3"] == (("embed",), "fwd")
    assert on_boundary == {"multiply_subtract_fusion": True,
                           "fusion.2": False, "fusion.3": False}
    # what XLA added has no op_name; a path cut short keeps what it has
    assert table["copy.5"] == (None, "none")
    assert table["gather.6"] == ((), "none")
    # ... and inside another computation takes the pass of the whole paths
    assert table["gather.4"] == (
        ("cond", "branch_1_fun", "moe_rows"), "bwd")
    assert set(table) >= {"a", "dot.1", "tuple.2", "add.4"}


def test_the_update_fusions_of_the_compiled_step_are_found(tables):
    """Whatever the CPU compiler fuses: every fusion has an entry, and none
    that holds a region's matmul is the update's."""
    table, on_boundary = tables[LLAMA_STEP]
    assert on_boundary and set(on_boundary) <= set(table)


@pytest.mark.parametrize("family", ["llama", "hybrid"])
def test_with_the_flag_off_the_program_is_the_parents(monkeypatch, family):
    """No scope is entered, nothing is remembered: no ``op_name`` of the
    lowered step holds a region's name, ``compiled_step_texts`` is empty."""
    monkeypatch.delenv("MAGI_ATTENTION_PROFILE_MODE", raising=False)
    monkeypatch.setattr(profiling, "_STEPS_SEEN", {})
    if family == "llama":
        step, cfg = llama.train_step, dataclasses.replace(
            LLAMA, vocab_size=137, n_layers=1)
        args, kwargs = _llama_args(cfg), {}
    else:
        step, cfg = hybrid.train_step, dataclasses.replace(
            HYBRID, vocab_size=137)
        args, kwargs = _hybrid_args(cfg)
    monkeypatch.setattr(step, "last_call", None)
    text = step.lower(*args, **kwargs).as_text(debug_info=True)
    names = [n for n in re.findall(r'loc\("([^"]*)"', text)
             if not n.startswith("params[")]  # an argument's own name
    assert any("jvp" in n for n in names)  # the paths are there to read
    regions = re.compile(
        r"(?<![\w.])(" + "|".join(map(
            re.escape, MODEL_REGIONS + NESTED_REGIONS)) + r")(?![\w.])")
    assert not [n for n in names if regions.search(n)]
    _, loss = step(*args, **kwargs)
    assert np.isfinite(float(loss))
    assert step.last_call is None and step.signature is None
    assert profiling.compiled_step_texts() == {}
    assert profiling._STEPS_SEEN == {}


def test_the_signature_is_shapes_and_the_static_arguments(flag_on, tables):
    args, kwargs = llama.train_step.signature
    assert args[1] == LLAMA and kwargs == {}
    leaves = jax.tree.leaves(args[0])
    assert leaves and all(
        isinstance(x, jax.ShapeDtypeStruct) for x in leaves)
    assert args[2].shape == (S,) and args[2].dtype == jnp.int32
    (_, hkw) = hybrid.train_step.signature
    assert hkw["window_key"] == _keys()[1]
