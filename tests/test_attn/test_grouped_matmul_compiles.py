"""The chip's compiler takes the grouped matmul at the hybrid cell's shapes,
and the benchmark still sees the layer.

``kernels/grouped_matmul.py`` replaced the TPU compiler's own ``ragged-dot``
in ``models/moe.py`` (PR 32). The benchmark reads the layer's time by
instruction name (``cellbench/named_ops.py:GROUPED``) and keeps it out of
the FFA classes by the same name (``event_classes.d/60-kernels-by-name.json``:
a ``tpu_custom_call`` that no file claims is taken for an FFA body), so the
two bodies are named ``_ragged_dot_kernel`` and ``_ragged_dot_dw_kernel``.
This file compiles the up and the down product, forward and backward, for
one chip of a described ``v5e:2x2`` from the CPU, as
``test_pack_guard_compiles.py`` does for the FFA bodies: VMEM and SMEM are
checked there and cost no chip time. Skipped where no such topology can be
described.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from cellbench import family_llama, named_ops, trace_reduce

ROWS, HELD, DIM, FFN = 8192 * 6, 32, 2688, 1856
BODIES = {"_ragged_dot_kernel", "_ragged_dot_dw_kernel"}


@pytest.fixture()
def compiled_kernels(monkeypatch):
    """The real (not interpreted) kernel path."""
    import magiattention_tpu.api  # noqa: F401  (binds _should_interpret)
    from magiattention_tpu.kernels import ffa

    monkeypatch.setattr(ffa, "_should_interpret", lambda: False)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # other files of the suite describe the chip too, under xdist in
    # another process: take no libtpu lock here
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("k, n, out_dtype", [
    pytest.param(DIM, FFN, jnp.float32, id="up"),
    pytest.param(FFN, DIM, jnp.bfloat16, id="down"),
])
def test_the_cells_products_compile_for_v5e_under_the_names_the_benchmark_reads(
    compiled_kernels, one_chip, k, n, out_dtype
):
    from magiattention_tpu.kernels import grouped_matmul, tile_policy

    tile = tile_policy.grouped_row_tile(ROWS // 128)  # 128 experts routed

    def loss(rows, w, sizes):
        out = grouped_matmul.grouped_matmul(
            rows, w, sizes, tile_rows=tile, out_dtype=out_dtype)
        return out.astype(jnp.float32).sum()

    fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (((ROWS, k), jnp.bfloat16),
                                 ((HELD, k, n), jnp.bfloat16),
                                 ((HELD,), jnp.int32))]
    # neither body interpreted, in the traced program
    assert family_llama.pallas_kernels(fn.trace(*args).jaxpr) == dict.fromkeys(
        BODIES, False)
    compiled = fn.lower(*args).compile()  # fits VMEM and SMEM, or raises
    calls = [
        trace_reduce.parse_hlo(line.strip().removeprefix("ROOT "))
        for line in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in line]
    # forward, d rows (the same body, the weight read transposed) and dW
    assert len(calls) == 3
    classes = trace_reduce.load_classes()
    for name, text in calls:
        assert re.search(named_ops.GROUPED, name), name
        assert "tpu_custom_call" in text
        assert trace_reduce.classify(
            trace_reduce.Event(name, 0.0, 1.0, text), classes
        ) == "other_compute", (name, text)
    # the body's scope, which JAX decorates where it is the outermost one
    # (jvp_magi_ragged_dot_kernel_, transpose_jvp_..._dw_kernel__)
    names = sorted(name for name, _ in calls)
    assert sum("magi_ragged_dot_kernel" in name for name in names) == 2, names
    assert sum("magi_ragged_dot_dw_kernel" in name for name in names) == 1, names
