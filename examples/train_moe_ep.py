"""MoE training with expert parallelism over the CP mesh.

The reference delegates MoE/EP to Megatron (ref examples/megatron/README.md);
here it is native: a Mixtral-style decoder whose expert FFNs are sharded
over the same mesh axis as the sequence (expert-parallel group == data/cp
group), token slots riding two ``lax.all_to_all``s per MoE layer while
attention runs through the CP engine on the dispatched layout.

Run (no TPU needed — virtual CPU mesh):

    python examples/train_moe_ep.py --devices 4 --steps 10
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--seqlen", type=int, default=512)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--top-k", type=int, default=2)
    args = ap.parse_args()

    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    from jax.sharding import Mesh

    from magiattention_tpu.api import magi_attn_flex_key
    from magiattention_tpu.models import (
        MoEConfig,
        init_moe_params,
        moe_train_step,
        shard_moe_params,
    )

    cfg = MoEConfig(
        vocab_size=512, dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=32, ffn_hidden=256, dtype="float32",
        n_experts=args.experts, top_k=args.top_k,
    )
    S = args.seqlen
    mesh = Mesh(
        np.array(jax.devices()[: args.devices]), axis_names=("cp",)
    )
    # varlen block-causal: two documents
    key = magi_attn_flex_key(
        [[0, S // 2], [S // 2, S]], [[0, S // 2], [S // 2, S]], [1, 1],
        S, S, mesh=mesh, chunk_size=max(S // (8 * args.devices), 16),
    )
    params = init_moe_params(cfg, jax.random.key(0))
    params = shard_moe_params(params, mesh, dp_axis="cp", ep_axis="cp")

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, S).astype(np.int32)
    labels = np.concatenate([tokens[1:], [-1]]).astype(np.int32)

    print(
        f"MoE: {cfg.n_experts} experts (top-{cfg.top_k}) sharded over "
        f"ep={args.devices}; {S} tokens CP-dispatched over the same axis"
    )
    for step in range(args.steps):
        params, loss = moe_train_step(
            params, cfg, tokens, labels, key, "cp", lr=5e-3
        )
        print(f"step {step}: loss {float(loss):.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
