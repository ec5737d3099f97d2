"""The WINDOW layers' FFA call of ``trinitymini.longdocs32k.cp1`` at its real
shapes through the TPU compiler from the CPU.

The per-cell tests (``test_cells_lower_for_tpu.py``) take a cell's mask from
its traffic file, whose ``window`` is null here: they reach the full layer's
dense triangle and not the thin band four of the five layers run, which the
family makes of the configuration's ``sliding_window`` through
``api.make_varlen_key_for_new_mask_after_dispatch``. This file lowers and,
where a ``v5e:2x2`` can be described, compiles that band at ``(32, 4, 128,
128)`` heads and 32768 tokens, forward and backward."""

import jax
import jax.numpy as jnp
import numpy as np

# the real (not interpreted) kernel path and the described v5e chip: the
# per-cell file's fixtures, each module its own instance
from test_cells_lower_for_tpu import compiled_kernels, one_chip  # noqa: F401

from cellbench import kernel_times, manifest, run, traffic_gen
from magiattention_tpu.api import infer_attn_mask_from_cu_seqlens

CELL = "trinitymini.longdocs32k.cp1"


def _window_band():
    """``(fn, shapes, family)``: a loss-like scalar of the FFA call over
    the cell's documents under the configuration's window, with its
    gradients, and the shapes of q, k, v."""
    from magiattention_tpu.kernels import ffa

    cell = manifest.load_cell(manifest.ROOT, CELL)
    family = manifest.load_family(manifest.ROOT, cell.config["family"])
    cfg, tokens, window, _ = run.cell_sizes(cell, family, 0)
    assert window is None and cfg["sliding_window"] == 2048
    spec = traffic_gen.make_mask(
        cell.traffic, tokens, None, 0,
        manifest.load_generator(manifest.ROOT, cell.traffic["generator"]))
    cu = list(spec.cu_seqlens)
    qr, kr, types = infer_attn_mask_from_cu_seqlens(
        cu, cu, causal=False, window_size=(cfg["sliding_window"] - 1, 0))
    qr = np.asarray(qr.to_naive_ranges(), np.int32)
    kr = np.asarray(kr.to_naive_ranges(), np.int32)
    tm = np.asarray([t.to_int_type() for t in types], np.int32)

    def loss(q, k, v):
        out, _ = ffa.ffa_attn(q, k, v, qr, kr, tm)
        return out.astype(jnp.float32).sum()

    [group] = [g for g in family.ffa_calls(cfg) if g["kind"] == "window"]
    assert (group["hq"], group["hk"], group["d_qk"], group["d_v"]) == (
        32, 4, 128, 128)
    shapes = [(tokens, 32, 128), (tokens, 4, 128), (tokens, 4, 128)]
    return jax.value_and_grad(loss, argnums=(0, 1, 2)), shapes, family


def test_the_window_band_lowers_for_tpu(compiled_kernels):
    fn, shapes, family = _window_band()
    traced = jax.jit(fn).trace(
        *[jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes])
    kernels = family.pallas_kernels(traced.jaxpr)
    kinds = {kernel_times.kind_of(kernel_times.PREFIX + b) for b in kernels}
    assert not any(kernels.values()), kernels  # none interpreted
    assert {"fwd", "delta"} <= kinds, kernels
    assert {"bwd_dq", "bwd_dkv"} <= kinds or "bwd_fused" in kinds, kernels
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 3


def test_the_window_band_compiles_for_v5e(compiled_kernels, one_chip):
    fn, shapes, _ = _window_band()
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in shapes]
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()
