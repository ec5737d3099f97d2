"""Autoregressive decoding with the paged KV cache (ref: the inference
capability behind kernel/cutedsl/paged_kv.py).

Greedy-decodes from the flagship Llama model using page-table KV storage:
prefill fills the cache in one chunk, then each decode step appends one
token's K/V and attends via `paged_attn` — same FFA kernel, page-gathered
KV, O(pages) memory instead of max-seqlen rectangles.

    python examples/generate_paged.py --steps 16
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--page-size", type=int, default=16)
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np

    from magiattention_tpu.kernels.paged_kv import (
        PagedKVCache,
        append_kv,
        assign_pages,
        paged_attn,
    )
    from magiattention_tpu.models import LlamaConfig, init_params
    from magiattention_tpu.models.llama import _rms_norm, _rope

    cfg = LlamaConfig(
        vocab_size=256, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=32, ffn_hidden=256, dtype="float32",
    )
    params = init_params(cfg, jax.random.key(0))
    dt = cfg.jdtype

    max_len = args.prompt_len + args.steps
    pages_per_seq = -(-max_len // args.page_size)
    caches = [
        PagedKVCache.create(
            num_pages=2 * pages_per_seq, page_size=args.page_size,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            max_seqs=1, max_pages_per_seq=pages_per_seq, dtype=dt,
        )
        for _ in range(cfg.n_layers)
    ]
    rng = np.random.default_rng(7)
    for i in range(cfg.n_layers):
        # non-contiguous allocation on purpose: pages need not be ordered
        ids = rng.permutation(2 * pages_per_seq)[:pages_per_seq]
        caches[i] = assign_pages(caches[i], 0, ids)

    def block(x, lyr, pos, li, q_start):
        """One transformer block over t rows at positions pos; attends the
        paged cache (which must already contain rows [0, q_start+t))."""
        h = _rms_norm(x, lyr["attn_norm"], cfg.norm_eps)
        q = (h @ lyr["wq"].astype(dt)).reshape(-1, cfg.n_heads, cfg.head_dim)
        q = _rope(q, pos, cfg.rope_theta)
        out, _ = paged_attn(
            q, caches[li], 0, q_start=q_start, max_pages=pages_per_seq
        )
        x = x + out.reshape(-1, cfg.n_heads * cfg.head_dim) @ lyr["wo"].astype(dt)
        h = _rms_norm(x, lyr["mlp_norm"], cfg.norm_eps)
        gate = jax.nn.silu(h @ lyr["w_gate"].astype(dt))
        return x + (gate * (h @ lyr["w_up"].astype(dt))) @ lyr["w_down"].astype(dt)

    def append_layer_kv(x, lyr, pos, li):
        h = _rms_norm(x, lyr["attn_norm"], cfg.norm_eps)
        k = (h @ lyr["wk"].astype(dt)).reshape(-1, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ lyr["wv"].astype(dt)).reshape(-1, cfg.n_kv_heads, cfg.head_dim)
        k = _rope(k, pos, cfg.rope_theta)
        caches[li] = append_kv(caches[li], 0, k, v)

    def forward_chunk(tokens, q_start):
        """Prefill or decode chunk: append each layer's K/V then attend."""
        pos = q_start + jnp.arange(tokens.shape[0], dtype=jnp.int32)
        x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        for li, lyr in enumerate(params["layers"]):
            append_layer_kv(x, lyr, pos, li)
            x = block(x, lyr, pos, li, q_start)
        x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
        return (x @ params["lm_head"].astype(dt)).astype(jnp.float32)

    prompt = rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32)
    logits = forward_chunk(jnp.asarray(prompt), 0)
    next_tok = int(jnp.argmax(logits[-1]))
    generated = [next_tok]
    print(f"prefill {args.prompt_len} tokens -> first token {next_tok}")

    for step in range(args.steps - 1):
        t = jnp.asarray([generated[-1]], dtype=jnp.int32)
        logits = forward_chunk(t, args.prompt_len + step)
        generated.append(int(jnp.argmax(logits[-1])))

    print("generated:", generated)
    # consistency check: cache length == prompt + generated-1 appended rows
    assert int(caches[0].lengths[0]) == args.prompt_len + args.steps - 1
    print("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
