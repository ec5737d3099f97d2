"""Silicon probe for the serving path: paged-KV decode latency/throughput.

First perf evidence for the paged-attention kernel (kernels/paged_kv.py —
the TPU counterpart of the reference's kernel/cutedsl/paged_kv.py): decode
one token against paged contexts of 256 / 4k / 8k / 32k, slope-timed,
reporting per-token attention latency and the implied tokens/s for the
attention component. Appends to ``benchmarks/history/decode_probe.csv``.

Every row carries its BAR (r4 verdict Weak #7 — a number with no
comparison point cannot be judged):

- ``roofline_ms``: decode attention is HBM-bound — each token must read
  the whole kv cache once (ctx * hk * d * 2 tensors * 2 B) — so the
  floor is bytes / (819 GB/s * 0.8 streaming efficiency). A paged
  kernel within ~2-3x of this floor is healthy; 100x off means launch
  overhead or a gather pathology, not "slow attention".
- ``naive_ms_per_token``: the same decode step over a CONTIGUOUS kv
  buffer through plain XLA ops (einsum + softmax) — what a user gets
  with no paged kernel at all. The paged path must not lose to it.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import jax.numpy as jnp
import numpy as np

from magiattention_tpu.benchmarking.bench import (
    do_bench_scan_slope,
    measuring_device,
)
from magiattention_tpu.benchmarking.perf_report import append_row
from magiattention_tpu.kernels.paged_kv import (
    PagedKVCache, append_kv, assign_pages, paged_attn,
)

HQ, HK, D = 16, 8, 128
PAGE = 128


def probe(ctx_len: int) -> None:
    rng = np.random.default_rng(0)
    n_pages = ctx_len // PAGE + 2
    cache = PagedKVCache.create(
        num_pages=n_pages, page_size=PAGE, n_kv_heads=HK, head_dim=D,
        max_seqs=1, max_pages_per_seq=n_pages, dtype=jnp.bfloat16,
    )
    cache = assign_pages(cache, 0, np.arange(n_pages, dtype=np.int32))
    k_ctx = jnp.asarray(rng.standard_normal((ctx_len, HK, D)), jnp.bfloat16)
    v_ctx = jnp.asarray(rng.standard_normal((ctx_len, HK, D)), jnp.bfloat16)
    cache = append_kv(cache, 0, k_ctx, v_ctx)

    q1 = jnp.asarray(rng.standard_normal((1, HQ, D)), jnp.bfloat16)

    def decode_attn(q):
        o, _ = paged_attn(q, cache, seq_id=0, q_start=ctx_len - 1,
                          max_pages=n_pages)
        return o.astype(jnp.bfloat16)

    # HBM roofline floor: one full kv-cache read per decoded token
    kv_bytes = ctx_len * HK * D * 2 * 2
    roofline_ms = kv_bytes / (819e9 * 0.8) * 1e3

    # naive bar: contiguous kv, plain XLA attention (GQA via reshape)
    scale = float(D) ** -0.5

    def naive_attn(q):
        qg = q.reshape(1, HK, HQ // HK, D).astype(jnp.float32)
        kf = k_ctx.astype(jnp.float32)
        vf = v_ctx.astype(jnp.float32)
        logits = jnp.einsum("bhgd,shd->bhgs", qg, kf) * scale
        p = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bhgs,shd->bhgd", p, vf)
        return o.reshape(1, HQ, D).astype(jnp.bfloat16)

    ms = do_bench_scan_slope(decode_attn, q1, verbose=True)
    try:
        naive_ms = do_bench_scan_slope(naive_attn, q1, verbose=True)
    except Exception as e:  # noqa: BLE001 — bar loss must not cost the row
        print(f"naive bar FAIL: {type(e).__name__}: {str(e)[:120]}",
              flush=True)
        naive_ms = float("nan")
    toks = 1e3 / ms
    print(
        f"ctx={ctx_len}: decode attn {ms:.3f} ms/token "
        f"({toks:,.0f} tok/s attention-side) | naive {naive_ms:.3f} ms "
        f"| HBM roofline {roofline_ms:.4f} ms "
        f"(paged at {roofline_ms / ms:.1%} of floor)",
        flush=True,
    )
    append_row("decode_probe", {
        "ctx": ctx_len, "ms_per_token": round(ms, 4),
        "tok_per_s_attn": round(toks, 1), "page_size": PAGE,
        "hq": HQ, "hk": HK, "d": D,
        "naive_ms_per_token": round(naive_ms, 4),
        "roofline_ms": round(roofline_ms, 5),
        "pct_of_roofline": round(roofline_ms / ms * 100, 2),
    })


def main() -> int:
    print("device:", measuring_device("tpu_decode_probe"), flush=True)
    for ctx in (256, 4096, 8192, 32768):
        probe(ctx)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
