"""The chip's compiler takes the gated delta rule's two bodies at the Solar
cell's shapes, and the benchmark sees them under their own names.

``kernels/kda.py`` is one head's chunk a grid step, every intermediate in
float32 with matmuls at ``HIGHEST`` and its pair matrices and forward
substitution unrolled; this file lowers the forward and the backward for one
chip of a described ``v5e:2x2`` from the CPU, as
``test_grouped_matmul_compiles.py`` does (VMEM and SMEM are checked there
and cost no chip time), at the cell's 16384 tokens and 64 heads of 128.
``event_classes.d/70-kda-kernels.json`` must keep both out of the FFA
classes, and ``metrics/kda_*.py`` read them by instruction name. Skipped
where no such topology can be described.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from cellbench import family_llama, trace_reduce
from magiattention_tpu.kernels import kda

TOKENS, HEADS, D = 16384, 64, 128
BODIES = {"_kda_fwd_kernel", "_kda_bwd_kernel"}


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
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_both_bodies_compile_for_v5e_under_the_names_the_benchmark_reads(
    compiled_kernels, one_chip
):
    def loss(q, k, v, g, beta, rows):
        return kda.kda_scan(q, k, v, g, beta, rows).astype(
            jnp.float32).sum()

    fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))
    shape = (TOKENS, HEADS, D)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        (shape, jnp.bfloat16), (shape, jnp.bfloat16), (shape, jnp.bfloat16),
        (shape, jnp.float32), ((TOKENS, HEADS), jnp.float32),
        ((8, TOKENS), jnp.float32))]
    # neither body interpreted, in the traced program
    assert family_llama.pallas_kernels(fn.trace(*args).jaxpr) == dict.fromkeys(
        BODIES, False)
    compiled = fn.lower(*args).compile()  # fits VMEM and SMEM, or raises
    calls = [
        trace_reduce.parse_hlo(line.strip().removeprefix("ROOT "))
        for line in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in line]
    # the forward that saves the states, and the backward
    assert len(calls) == 2, calls
    classes = trace_reduce.load_classes()
    for name, text in calls:
        assert trace_reduce.classify(
            trace_reduce.Event(name, 0.0, 1.0, text), classes
        ) == "other_compute", (name, text)
    names = sorted(name for name, _ in calls)
    assert sum("magi_kda_fwd_kernel" in n for n in names) == 1, names
    assert sum("magi_kda_bwd_kernel" in n for n in names) == 1, names
