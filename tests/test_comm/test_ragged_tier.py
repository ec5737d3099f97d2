"""Ragged GroupCast tier: plan-array parity + TPU lowering + AUTO choice.

``jax.lax.ragged_all_to_all`` is UNIMPLEMENTED on XLA:CPU (verified, jax
0.9), so the tier cannot execute on the CPU test mesh. Its correctness is
gated three ways instead:

1. the ragged plan arrays (functional/dist_attn._ragged_arrays) are
   simulated in numpy against the a2a tier's receive buffer on real solver
   plans — exact equality (the device op itself is jax's, trusted);
2. the full CP fwd step with the ragged tier lowers for the TPU platform
   (cross-platform lowering) and the ragged op is present in the HLO;
3. the solver's per-stage AUTO choice records ``lowering="ragged"`` exactly
   when the tier is available, with wire_rows == true payload (the
   zero-padding claim, ref csrc/comm/grpcoll's zero-redundant wire).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from magiattention_tpu.common.enum import AttnMaskType
from magiattention_tpu.common.ranges import AttnRanges
from magiattention_tpu.functional.dist_attn import _ragged_arrays
from jax import shard_map
from magiattention_tpu.meta import (
    make_attn_meta_from_dispatch_meta,
    make_dispatch_meta_from_qk_ranges,
)


def _stages(seqlen=4096, cp=4, mask=None, ragged=True, monkeypatch=None):
    if monkeypatch is not None:
        monkeypatch.setenv(
            "MAGI_ATTENTION_RAGGED_GRPCOLL", "1" if ragged else "0"
        )
    if mask is None:
        qr = AttnRanges.from_ranges([[0, seqlen]])
        kr = AttnRanges.from_ranges([[0, seqlen]])
        tm = [AttnMaskType.CAUSAL]
    else:
        qr, kr, tm = mask
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        qr, kr, tm, seqlen, seqlen, seqlen // 256, cp,
    )
    cmm, _ = make_attn_meta_from_dispatch_meta(bucket, mq)
    return cmm


def _simulate_ragged(s, xs):
    """numpy semantics of ragged_all_to_all over the stage's plan arrays."""
    (send_row_idx, input_offsets, send_sizes, output_offsets,
     recv_sizes) = (np.asarray(a) for a in _ragged_arrays(s))
    cp = send_sizes.shape[0]
    outs = [np.zeros((s.r_max, xs[0].shape[1]), xs[0].dtype)
            for _ in range(cp)]
    for src in range(cp):
        send = xs[src][send_row_idx[src]]
        for dst in range(cp):
            n = int(send_sizes[src, dst])
            if not n:
                continue
            i0 = int(input_offsets[src, dst])
            o0 = int(output_offsets[src, dst])
            outs[dst][o0: o0 + n] = send[i0: i0 + n]
    return outs


def _simulate_a2a(s, xs):
    """numpy semantics of the padded all_to_all tier (group_cast_rows)."""
    cp = s.send_counts.shape[0]
    outs = []
    for dst in range(cp):
        flat = np.concatenate(
            [xs[src][s.send_idx[src, dst]] for src in range(cp)]
        )  # (cp * a_cap, d)
        outs.append(flat[s.recv_sel[dst]])
    return outs


@pytest.mark.parametrize(
    "mask",
    [
        None,  # causal
        (
            AttnRanges.from_ranges([[0, 1024], [1024, 4096]]),
            AttnRanges.from_ranges([[0, 1024], [0, 4096]]),
            [AttnMaskType.FULL, AttnMaskType.CAUSAL],
        ),
    ],
)
def test_ragged_receive_buffer_matches_a2a(monkeypatch, mask):
    cmm = _stages(mask=mask, monkeypatch=monkeypatch)
    rng = np.random.default_rng(0)
    assert cmm.kv_stages, "expected at least one comm stage"
    for s in cmm.kv_stages:
        cp = s.send_counts.shape[0]
        shard = int(s.send_idx.max()) + 1
        xs = [rng.standard_normal((shard, 4)).astype(np.float32)
              for _ in range(cp)]
        ragged = _simulate_ragged(s, xs)
        a2a = _simulate_a2a(s, xs)
        for dst in range(cp):
            n = int(s.recv_len[dst])
            np.testing.assert_array_equal(
                ragged[dst][:n], a2a[dst][:n], err_msg=f"dst={dst}"
            )


def test_auto_choice_records_ragged(monkeypatch):
    cmm = _stages(monkeypatch=monkeypatch, ragged=True)
    for s in cmm.kv_stages:
        assert s.lowering == "ragged"
        # zero padding on the wire: wire == payload exactly
        assert s.wire_rows() == s.payload_rows()
        assert s.wire_rows() <= s.wire_rows("ppermute")
        assert s.wire_rows() <= s.wire_rows("a2a")


def test_auto_choice_without_ragged_is_portable(monkeypatch):
    cmm = _stages(monkeypatch=monkeypatch, ragged=False)
    for s in cmm.kv_stages:
        assert s.lowering in ("a2a", "ppermute")
        assert s.lowering == min(
            ["ppermute", "a2a"] if s.pp_caps else ["a2a"], key=s.wire_rows
        )


@pytest.mark.skipif(
    not hasattr(jax.lax, "ragged_all_to_all"),
    reason="jax.lax.ragged_all_to_all not in this JAX build",
)
def test_ragged_cast_lowers_for_tpu(monkeypatch):
    """cast_rows(kind='ragged') cross-platform-lowers to the TPU op."""
    from magiattention_tpu.comm.primitives import cast_rows

    cmm = _stages(monkeypatch=monkeypatch, ragged=True)
    s = cmm.kv_stages[0]
    cp = s.send_counts.shape[0]
    if cp > len(jax.devices()):
        pytest.skip("needs the virtual 8-device mesh")
    shard = int(s.send_idx.max()) + 1
    ops = _ragged_arrays(s)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:cp]), ("cp",))
    P = jax.sharding.PartitionSpec

    def step(x, *ops):
        # per-rank views of the whole-mesh stacked plan arrays, as the
        # runtime does (DistAttnRuntime._cast)
        return cast_rows(
            x, tuple(o[0] for o in ops), ("ragged", s.r_max), "cp"
        )

    fn = jax.jit(
        shard_map(
            step, mesh=mesh,
            in_specs=(P("cp"),) * (1 + len(ops)),
            out_specs=P("cp"),
        )
    )
    x = jnp.zeros((cp * shard, 4), jnp.float32)
    stacked = tuple(o for o in ops)
    text = fn.trace(x, *stacked).lower(
        lowering_platforms=("tpu",)
    ).as_text()
    assert "ragged_all_to_all" in text


@pytest.mark.skipif(
    not hasattr(jax.lax, "ragged_all_to_all"),
    reason="jax.lax.ragged_all_to_all not in this JAX build",
)
def test_hp_cast_over_ragged_lowers_for_tpu(monkeypatch):
    """hp_group_cast (fp32 wire reduce) over the ragged tier: the grad
    program must cross-platform-lower with ragged_all_to_all in BOTH
    directions (fwd cast + fp32 backward reduce) — the combination that
    ships on TPU by default when MAGI_ATTENTION_BWD_HIGH_PRECISION_REDUCE
    is on."""
    from magiattention_tpu.functional.dist_attn import hp_group_cast

    cmm = _stages(monkeypatch=monkeypatch, ragged=True)
    s = cmm.kv_stages[0]
    cp = s.send_counts.shape[0]
    if cp > len(jax.devices()):
        pytest.skip("needs the virtual 8-device mesh")
    shard = int(s.send_idx.max()) + 1
    ops = _ragged_arrays(s)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:cp]), ("cp",))
    P = jax.sharding.PartitionSpec

    def loss(x, *ops):
        y = hp_group_cast(
            x, tuple(o[0] for o in ops), ("ragged", s.r_max), "cp",
            shard, "bfloat16",
        )
        return jnp.sum(y ** 2)

    def step(x, *ops):
        return jax.grad(loss)(x, *ops)

    fn = jax.jit(
        shard_map(
            step, mesh=mesh,
            in_specs=(P("cp"),) * (1 + len(ops)),
            out_specs=P("cp"),
            check_vma=False,
        )
    )
    x = jnp.zeros((cp * shard, 4), jnp.bfloat16)
    text = fn.trace(x, *ops).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("ragged_all_to_all") >= 2, "fwd + bwd ragged ops"
    # the backward ragged op carries fp32 (the wire-reduce contract)
    import re

    assert re.search(r"ragged_all_to_all[^\n]*xf32>", text)
