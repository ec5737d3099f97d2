"""The train step's schedule on the TPU: ``models/llama.py`` names XLA's list
memory scheduler for the step's jit (``TPU_STEP_COMPILER_OPTIONS``), because
with the one-pass FFA backward the default scheduler keeps an order that holds
a layer's three ``[tokens, ffn]`` MLP buffers through its attention backward.
Compiled here for a described v5e chip; nothing runs."""

import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import manifest, run, traffic_gen
from magiattention_tpu.kernels import registry
from magiattention_tpu.models import llama

GIB = 2 ** 30


@pytest.mark.parametrize("backend, options", [
    ("cpu", {}), ("tpu", {"compiler_options": {"xla_memory_scheduler": "list"}})])
def test_step_jit_names_the_list_scheduler_on_a_tpu_alone(
    monkeypatch, backend, options
):
    """The CPU compiler refuses the option by name, and the backend is not
    known when the module is imported: the jit is built on first use."""
    built = []
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(
        jax, "jit", lambda fn, **kw: built.append(kw) or (lambda *a: a))
    step = llama._StepJit(lambda *a: a, static_argnums=(1,))
    assert built == []
    assert step(1, 2) == (1, 2)
    assert built[-1] == {"static_argnums": (1,), **options}
    assert llama.TPU_STEP_COMPILER_OPTIONS == {"xla_memory_scheduler": "list"}


def test_step_jit_under_another_trace_is_the_plain_jit(monkeypatch):
    """``cellbench/run.py`` reads the step's kernels off
    ``jax.make_jaxpr(step)`` before it runs it; JAX raises on compiler
    options met on a jit that is not the outermost."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = llama._StepJit(lambda x, n: x * n, static_argnums=(1,))
    jaxpr = jax.make_jaxpr(lambda x: step(x, 3))(jnp.ones(4))
    assert "mul" in str(jaxpr)
    assert "_jitted" not in vars(step)  # the one with options was not built
    np.testing.assert_array_equal(
        jax.jit(lambda x: step(x, 3))(jnp.ones(4)), 3 * np.ones(4))


def test_train_step_keeps_a_jitted_functions_surface():
    assert llama.train_step.__name__ == "train_step"
    for name in ("lower", "trace", "eval_shape"):
        assert callable(getattr(llama.train_step, name))


@pytest.fixture()
def compiled_for_one_v5e_chip(monkeypatch):
    """Real (not interpreted) kernels, and a v5e chip to compile for."""
    import magiattention_tpu.api  # noqa: F401
    import magiattention_tpu.functional.dist_attn as dist_attn
    from jax.experimental import topologies
    from jax.sharding import Mesh, SingleDeviceSharding
    from magiattention_tpu.kernels import ffa

    monkeypatch.setattr(ffa, "_should_interpret", lambda: False)
    monkeypatch.setattr(dist_attn, "_should_interpret", lambda: False)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = topo.devices[0]
    return Mesh(np.asarray([chip]), ("cp",)), SingleDeviceSharding(chip)


def _lowered_step(cell_name: str, mesh, sharding):
    """``llama.train_step`` of a cell at its timed size, lowered for the
    chip from shapes alone."""
    cell = manifest.load_cell(manifest.ROOT, cell_name)
    family = manifest.load_family(manifest.ROOT, cell.config["family"])
    cfg, tokens, window, _ = run.cell_sizes(cell, family, 0)
    spec = traffic_gen.make_mask(
        cell.traffic, tokens, window, 0,
        manifest.load_generator(manifest.ROOT, cell.traffic["generator"]))
    mcfg = family.model_config(cfg)
    key = family.make_key(spec, mesh)
    shapes = jax.eval_shape(
        partial(llama.init_params, mcfg), jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        shapes)
    toks = jax.ShapeDtypeStruct((tokens,), jnp.int32, sharding=sharding)
    lowered = llama.train_step.lower(params, mcfg, toks, toks, key)
    assert registry.last_choice("ffa_bwd") == "fused"
    return lowered


def test_list_order_frees_the_mlp_buffers_before_the_attention_backward(
    compiled_for_one_v5e_chip,
):
    """``mistral7b.swa32k.cp1`` (32768 tokens a chip, ffn 14336): the default
    order holds two ``[32768, 14336]`` bf16 buffers (0.875 GiB each) more
    than the list order at its peak. If the default stops doing so, this
    fails and ``TPU_STEP_COMPILER_OPTIONS`` can go."""
    lowered = _lowered_step("mistral7b.swa32k.cp1", *compiled_for_one_v5e_chip)
    temp = {
        name: lowered.compile(compiler_options=opts)
        .memory_analysis().temp_size_in_bytes / GIB
        for name, opts in (("default", None),
                           ("list", llama.TPU_STEP_COMPILER_OPTIONS))}
    assert temp["default"] - temp["list"] > 1.5, temp
    # what the step built on the split pair compiles to (PERF.md §6, PR 30)
    assert temp["list"] == pytest.approx(5.57, abs=0.06), temp


def test_list_order_is_the_default_order_at_16384_tokens_a_chip(
    compiled_for_one_v5e_chip,
):
    """``nemo12b.longdoc.cp1``: the same compiled text either way, so the
    option moves nothing there."""
    lowered = _lowered_step("nemo12b.longdoc.cp1", *compiled_for_one_v5e_chip)
    text = [
        re.sub(r", metadata=\{[^}]*\}", "",
               lowered.compile(compiler_options=opts).as_text())
        for opts in (None, llama.TPU_STEP_COMPILER_OPTIONS)]
    assert text[0] == text[1]


def test_the_hybrid_step_compiles_for_a_v5e_chip_and_fits_it(
    compiled_for_one_v5e_chip,
):
    """``nemotron3nano.packed32k.cp1`` at its timed size, 32768 tokens at the
    published widths: the scan's two Pallas bodies and FFA at 32 q / 2 kv
    heads pass Mosaic, and so do the held experts' grouped products
    (``kernels/grouped_matmul.py``, PR 32: no ragged-dot of the compiler's
    is left), every name the benchmark's event classes look for is there,
    and masters plus temporaries leave the chip's 15.75 GiB some room."""
    from magiattention_tpu.models import hybrid

    mesh, sharding = compiled_for_one_v5e_chip
    cell = manifest.load_cell(manifest.ROOT, "nemotron3nano.packed32k.cp1")
    family = manifest.load_family(manifest.ROOT, cell.config["family"])
    cfg, tokens, window, _ = run.cell_sizes(cell, family, 0)
    spec = traffic_gen.make_mask(
        cell.traffic, tokens, window, 0,
        manifest.load_generator(manifest.ROOT, cell.traffic["generator"]))
    mcfg = family.model_config(cfg)
    shapes = jax.eval_shape(
        partial(hybrid.init_params, mcfg), jax.random.PRNGKey(0))
    weights = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert weights == pytest.approx(1625e6, rel=1e-3)  # 6.05 GiB of fp32
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        shapes)
    toks = jax.ShapeDtypeStruct((tokens,), jnp.int32, sharding=sharding)
    compiled = hybrid.train_step.lower(
        params, mcfg, toks, toks, family.make_key(spec, mesh)
    ).compile(compiler_options=llama.TPU_STEP_COMPILER_OPTIONS)
    memory = compiled.memory_analysis()
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) / GIB < 15.0
    names = re.findall(r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                       compiled.as_text())
    kinds = {re.sub(r"[.][0-9]+$", "", n) for n in names}
    # forward, re-forward (decorated by the transform), backward a block
    assert sum("magi_ssd_fwd_kernel" in n for n in names) == 2 * 4
    assert sum("magi_ssd_bwd_kernel" in n for n in names) == 4
    assert {"magi_fwd_kernel", "magi_delta_kernel",
            "magi_bwd_fused_kernel"} <= kinds
    # an expert layer's products: up and down in the forward's loop over
    # token blocks; in the backward's, a block's re-forward of both, d act
    # and d rows, and the two dW (the layer's own re-forward saves nothing
    # the blocks' does not, and XLA drops it). Each stands in the program
    # twice since PR 34, at the row buffer a block expects and at the worst
    # case, the two branches of a block's ``conditional``, of which one
    # runs: the calls a step makes are the 96 + 32 of before
    assert registry.last_choice("moe_row_buffer") == "rows18432of49152"
    assert len(re.findall(r" conditional\(", compiled.as_text())) == 4 * 2
    assert sum("magi_ragged_dot_kernel" in n for n in names) == 2 * 4 * (2 + 4)
    assert sum("magi_ragged_dot_dw_kernel" in n for n in names) == 2 * 4 * 2
    assert all("magi_" in n for n in names), kinds
