"""The chip's compiler takes the Mamba-2 scan's two bodies at the hybrid
cell's shapes, and the benchmark sees them under their own names.

``kernels/ssd.py`` runs a chunk of ``ssd.heads_per_step`` heads of a group a
grid step (all 8 of the cell's groups of 64-channel heads) over a leading
head axis; this file lowers the forward that saves its states and the
backward for one chip of a described ``v5e:2x2`` from the CPU, as
``test_kda_compiles.py`` does (VMEM is checked there and costs no chip
time), at the cell's 32768 tokens, 64 heads of 64 in 8 groups and a state
of 128. ``event_classes.d/60-kernels-by-name.json`` must keep both out of
the FFA classes, and ``cellbench/named_ops.py`` reads them by instruction
name. Skipped where no such topology can be described.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from cellbench import family_llama, trace_reduce
from magiattention_tpu.kernels import registry, ssd

TOKENS, HEADS, P, GROUPS, N = 32768, 64, 64, 8, 128
BODIES = {"_ssd_fwd_kernel", "_ssd_bwd_kernel"}


def _grids(jaxpr) -> dict:
    """``{kernel body name: grid}`` of every ``pallas_call`` in a jaxpr."""
    found = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["jaxpr"].debug_info.func_name
            found[name] = tuple(eqn.params["grid_mapping"].grid)
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found.update(_grids(inner))
    return found


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
    def loss(x, dt, a, b, c, rows):
        return ssd.ssd_scan(x, dt, a, b, c, rows).astype(jnp.float32).sum()

    fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((TOKENS, HEADS, P), jnp.bfloat16), ((TOKENS, HEADS), jnp.float32),
        ((HEADS,), jnp.float32), ((TOKENS, GROUPS, N), jnp.bfloat16),
        ((TOKENS, GROUPS, N), jnp.bfloat16),
        ((ssd.SEG_ROWS, TOKENS), jnp.float32))]
    jaxpr = fn.trace(*args).jaxpr
    # neither body interpreted, in the traced program
    assert family_llama.pallas_kernels(jaxpr) == dict.fromkeys(BODIES, False)
    # a group's eight heads in one step: the grid stays (groups, chunks)
    assert registry.last_choice("ssd_heads") == "heads8"
    assert _grids(jaxpr.jaxpr) == dict.fromkeys(
        BODIES, (GROUPS, TOKENS // ssd.CHUNK))
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
    assert sum("magi_ssd_fwd_kernel" in n for n in names) == 1, names
    assert sum("magi_ssd_bwd_kernel" in n for n in names) == 1, names
