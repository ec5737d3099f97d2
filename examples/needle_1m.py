"""Needle-in-a-haystack retrieval through NSA's gather-free selection.

Plants a needle — ``l_slc`` rows whose keys align with a probe direction
and whose values carry a distinctive payload — at an arbitrary
block-aligned position in a long haystack of noise, then asks the final
query block to find it. NSA's compressed scores make the needle block
dominate the per-(kv-head, q-block) top-k, and the gather-free
block-sparse kernel (kernels/block_sparse.py) streams just
``top_k * l_slc`` KV rows per query block through its prefetched index
table — at the full 1M-token shape the slc branch reads ~0.01% of the
KV a dense pass would, and never materializes a gathered copy.

The retrieval metric is the cosine between the probe queries' output and
the needle payload: near 1 when the needle is planted, near 0 for the
pure-noise control haystack.

    python examples/needle_1m.py --smoke     # CPU-interpret, 2k tokens
    python examples/needle_1m.py             # the 1M-token shape (TPU)
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny CPU-interpret configuration (the make nsa-needle-smoke "
             "target): 2k tokens, f32, interpreted Pallas",
    )
    ap.add_argument(
        "--seq", type=int, default=None,
        help="override the token count (default: 2048 smoke, 1M full)",
    )
    args = ap.parse_args()

    if args.smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from magiattention_tpu.kernels import registry
    from magiattention_tpu.kernels.block_sparse import modeled_slc_bytes
    from magiattention_tpu.parallel.nsa import init_nsa_params, nsa_attn

    if args.smoke:
        S, hq, hk, dh = args.seq or 2048, 2, 1, 64
        dtype = jnp.float32
    else:
        S, hq, hk, dh = args.seq or (1 << 20), 4, 2, 128
        dtype = jnp.bfloat16
    # the aligned geometry (l_slc == l_cmp == d_stride) takes nsa_attn's
    # p_slc = p_cmp fast path: selection scores index the exact blocks the
    # slc branch then streams, which keeps the retrieval metric crisp
    l_cmp, l_slc, d_stride, bq, top_k = 64, 64, 64, 16, 2
    assert S % d_stride == 0 and S % bq == 0
    g = hq // hk
    n_qb = S // bq

    rng = np.random.default_rng(0)
    probe = rng.standard_normal(dh).astype(np.float32)
    probe /= np.linalg.norm(probe)
    payload = rng.standard_normal(dh).astype(np.float32)
    payload /= np.linalg.norm(payload)
    needle_at = (S // 3 // l_slc) * l_slc  # block-aligned, mid-haystack

    def build_kv(plant: bool):
        k = 0.1 * rng.standard_normal((S, hk, dh)).astype(np.float32)
        v = 0.1 * rng.standard_normal((S, hk, dh)).astype(np.float32)
        if plant:
            k[needle_at: needle_at + l_slc] = 8.0 * probe
            v[needle_at: needle_at + l_slc] = payload
        return jnp.asarray(k, dtype), jnp.asarray(v, dtype)

    q_np = 0.1 * rng.standard_normal((S, hq, dh)).astype(np.float32)
    q_np[S - bq:] = 4.0 * probe  # the final q block asks for the needle
    q = jnp.asarray(q_np, dtype)

    params = init_nsa_params(jax.random.PRNGKey(0), dh, l_cmp)
    # retrieval demo: a mean-pooling compressor (so the compressed needle
    # key stays aligned with the probe instead of being scrambled by a
    # random-init MLP) and the gate parked on the slc branch
    # (sigmoid(+/-4)) — the weighting NSA's training converges to for
    # lookup queries
    params["w_cmp_k"] = jnp.full((l_cmp,), 1.0 / l_cmp, jnp.float32)
    params["w_cmp_v"] = jnp.full((l_cmp,), 1.0 / l_cmp, jnp.float32)
    params["w_gate"] = jnp.zeros_like(params["w_gate"])
    params["b_gate"] = jnp.asarray([-4.0, 4.0, -4.0], jnp.float32)

    backend = registry.nsa_slc_backend(
        key=(hk, g, n_qb, top_k, l_slc, d_stride)
    )
    b = modeled_slc_bytes(
        hk=hk, n_qb=n_qb, top_k=top_k, block_len=l_slc, d_stride=d_stride,
        block_size_q=bq, g=g, d=dh, dv=dh,
        itemsize=jnp.dtype(dtype).itemsize,
    )
    dense_bytes = hk * n_qb * S * 2 * dh * jnp.dtype(dtype).itemsize
    print(f"tokens={S} heads={hq}/{hk} dh={dh} dtype={jnp.dtype(dtype).name}")
    print(f"slc backend: {backend}")
    print(
        f"slc KV bytes/step: streamed={b['streamed_bytes'] / 1e6:.1f} MB "
        f"(gathered would move {b['gathered_bytes'] / 1e6:.1f} MB, dense "
        f"{dense_bytes / 1e9:.1f} GB — {dense_bytes / b['streamed_bytes']:.0f}x)"
    )

    run = jax.jit(lambda q, k, v: nsa_attn(
        q, k, v, params, [0, S], l_cmp=l_cmp, l_slc=l_slc,
        d_stride=d_stride, block_size_q=bq, slc_top_k=top_k,
        window=(64, 0),
    ))

    def retrieval_score(plant: bool) -> float:
        k, v = build_kv(plant)
        t0 = time.perf_counter()
        out = np.asarray(run(q, k, v), np.float32)
        dt = time.perf_counter() - t0
        probe_out = out[S - bq:].reshape(-1, dh)
        cos = float(np.mean(
            (probe_out @ payload)
            / (np.linalg.norm(probe_out, axis=-1) + 1e-9)
        ))
        tag = "needle " if plant else "control"
        print(f"{tag}: cosine(out, payload) = {cos:+.3f}  ({dt:.2f}s)")
        return cos

    hit = retrieval_score(plant=True)
    miss = retrieval_score(plant=False)
    ok = hit > 0.8 and abs(miss) < 0.3
    print("RETRIEVED" if ok else "FAILED: needle not separable from noise")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
