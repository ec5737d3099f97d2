"""Sliding-window attention with sinks, single-device and CP-distributed.

Demonstrates the round-4 mask-compiler surface (ref
magi_attention/api/functools.py:180 general windows;
extensions/fa*_interface_with_sink sink layouts):

1. compile a general (left, right) window + sink over packed segments into
   exact slice metadata,
2. run it through the single-device FFA kernel,
3. run the SAME metadata through the distributed CP engine on a virtual
   8-device mesh,
4. an FA-style call with per-query 'ssh' sink logits.

    python examples/sliding_window_sink.py
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

# the 8-device virtual CPU mesh by default (kernels interpreted); pass
# --tpu to run on the attached TPUs with the kernels compiled
if "--tpu" not in sys.argv:
    jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from magiattention_tpu.api import (
    calc_attn, dispatch, magi_attn_flex_key, magi_attn_varlen_key,
    undispatch,
)
from magiattention_tpu.api.functools import (
    infer_attn_mask_from_sliding_window,
)
from magiattention_tpu.common.enum import AttnMaskType
from magiattention_tpu.common.ranges import AttnRanges
from magiattention_tpu.extensions.fa_interface_with_sink import (
    fa3_func_with_sink,
)
from magiattention_tpu.functional.flex_flash_attn import flex_flash_attn_func


def main() -> None:
    S, H, D = 512, 2, 32
    segs = [[0, S // 2], [S // 2, S]]

    # 1. compile: every query sees 48 tokens back, 24 forward, plus an
    # 8-token sink strip at the start of its segment
    oq, ok, ot = infer_attn_mask_from_sliding_window(
        AttnRanges.from_ranges(segs), AttnRanges.from_ranges(segs),
        [AttnMaskType.FULL] * len(segs), window_size=(48, 24), sink_size=8,
    )
    tm = np.asarray([t.to_int_type() for t in ot], np.int32)
    print(f"compiled {len(segs)} windowed segments -> {len(oq)} slices")

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((S, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((S, H, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((S, H, D)), jnp.bfloat16)

    # 2. single-device kernel
    out1, _ = flex_flash_attn_func(q, k, v, oq, ok, tm)
    print("single-device out:", out1.shape, out1.dtype)

    # 3. the same mask through the CP engine (8-way context parallel)
    mesh = Mesh(np.array(jax.devices()[:8]), ("cp",))
    key = magi_attn_flex_key(
        [[r.start, r.end] for r in oq], [[r.start, r.end] for r in ok],
        list(tm), S, S, mesh=mesh, chunk_size=64,
    )
    od, _ = calc_attn(
        dispatch(q, key), dispatch(k, key, role="kv"),
        dispatch(v, key, role="kv"), key,
    )
    out2 = undispatch(od, key)
    err = float(jnp.linalg.norm(
        (out2 - out1).astype(jnp.float32)
    ) / jnp.linalg.norm(out1.astype(jnp.float32)))
    print(f"cp=8 matches single-device: rel err {err:.2e}")

    # 4. FA-style call with per-query sink logits (layout 'ssh')
    B = 2
    qb = jnp.asarray(rng.standard_normal((B, 128, H, D)), jnp.bfloat16)
    kb = jnp.asarray(rng.standard_normal((B, 128, H, D)), jnp.bfloat16)
    vb = jnp.asarray(rng.standard_normal((B, 128, H, D)), jnp.bfloat16)
    sink = jnp.asarray(rng.standard_normal((B, 128, 4, H)), jnp.float32)
    out3 = fa3_func_with_sink(
        qb, kb, vb, sink=sink, sink_layout="ssh",
        causal=True, window_size=(64, 0),
    )
    print("fa3_func_with_sink(ssh):", out3.shape)

    # 5. the varlen front-end does the compile for you: cu_seqlens +
    # window + global tokens in one call (ref api/functools.py:335 —
    # global keys obey the leakage rule: query i sees at most
    # min(G, i + right + 1) of them)
    key_v = magi_attn_varlen_key(
        [0, S // 2, S], causal=False,
        window_size=(48, 0), global_window_size=8,
        mesh=mesh, chunk_size=64,
    )
    od, _ = calc_attn(
        dispatch(q, key_v), dispatch(k, key_v, role="kv"),
        dispatch(v, key_v, role="kv"), key_v,
    )
    print("varlen window+global out:", undispatch(od, key_v).shape)

    # 6. cross-shaped windows: q and k ranges may differ (chunked-prefill
    # style — the window rides the END-aligned diagonal; queries above
    # the end-aligned square are invalid and dropped, ref :216-225)
    cq, ck, ct = infer_attn_mask_from_sliding_window(
        AttnRanges.from_ranges([[0, S]]),
        AttnRanges.from_ranges([[0, S // 2]]),
        [AttnMaskType.FULL], window_size=(32, 8),
    )
    kc = jnp.asarray(rng.standard_normal((S // 2, H, D)), jnp.bfloat16)
    vc = jnp.asarray(rng.standard_normal((S // 2, H, D)), jnp.bfloat16)
    out4, _ = flex_flash_attn_func(
        q, kc, vc, cq, ck,
        np.asarray([t.to_int_type() for t in ct], np.int32),
    )
    print(f"cross-shaped window (sq={S}, sk={S // 2}): {len(cq)} slices, "
          f"out {out4.shape}")


if __name__ == "__main__":
    main()
