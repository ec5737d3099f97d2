"""The held experts' way back makes no array of a block's (token, choice)
pairs on the chip.

``models/moe.py:_held_experts_block`` takes a block's rows from the buffer in
expert order back to its tokens, weighed by the gates. Gathered back to one
row a pair and summed over a ``k`` axis, at k = 6 that is a ``[8192 x 6,
dim]`` gather and, since 6 is no tile of the second-minor dimension, a
float32 relayout of every pair ``[8192, 6, dim]``; its transpose gathers the
pairs again. This file compiles the block, forward and ``jax.vjp``, at the
hybrid cell's shapes (8192 tokens a block, top 6 of 128 experts, 32 held,
``dim`` 2688, a buffer of 18432 rows) for one chip of a described
``v5e:2x2`` from the CPU, and reads the optimized HLO: no instruction is
shaped by the pairs. Skipped where no such topology can be described.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

SB, K, HELD, DIM, FFN, CAPACITY = 8192, 6, 32, 2688, 1856, 18432
SHAPE = re.compile(r"\b(?:bf16|f32|s32|u32|pred)\[([0-9,]+)\]")


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


def test_the_way_back_compiles_for_v5e_with_no_array_of_the_pairs(
    compiled_kernels, one_chip
):
    from magiattention_tpu.kernels import tile_policy
    from magiattention_tpu.models import moe

    tile = tile_policy.grouped_row_tile(SB * K // 128)
    assert tile_policy.grouped_row_capacity(
        SB * K * HELD / 128, SB * K, tile) == CAPACITY

    def block(h, topi, weights, w_up, w_down, sizes, dy):
        y, vjp = jax.vjp(lambda h, weights, w_up, w_down: (
            moe._held_experts_block(
                h, topi, weights, w_up, w_down, sizes, offset=0,
                tile_rows=tile, act="relu2", capacity=CAPACITY)),
            h, weights, w_up, w_down)
        return y, vjp(dy)

    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (((SB, DIM), jnp.bfloat16),
                                 ((SB, K), jnp.int32),
                                 ((SB, K), jnp.float32),
                                 ((HELD, DIM, FFN), jnp.bfloat16),
                                 ((HELD, FFN, DIM), jnp.bfloat16),
                                 ((HELD,), jnp.int32),
                                 ((SB, DIM), jnp.bfloat16))]
    text = jax.jit(block).lower(*args).compile().as_text()
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in SHAPE.findall(text)}
    assert (SB, DIM) in shapes and (CAPACITY, DIM) in shapes  # read right
    # the pairs as rows (the sort's own int32 keys are one-dimensional)
    assert not [s for s in shapes if len(s) >= 2 and s[0] == SB * K], shapes
    # the pairs' rows as [tokens, k, ..] or [k, tokens, ..] (an index
    # array of the scalar gathers is [tokens, k, 1])
    assert not [s for s in shapes if len(s) >= 3 and set(s[:2]) == {SB, K}
                and max(s[2:]) > 1], shapes
