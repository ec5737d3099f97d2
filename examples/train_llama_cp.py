"""End-to-end CP training example (ref: examples/torch_native/).

Trains the flagship Llama model on a varlen block-causal mask over a cp
(optionally cp x tp) mesh, with ZeRO-style parameter sharding — the TPU
equivalent of the reference's FSDP2 `fully_shard` + MagiAttention example.

Run (no TPU needed — virtual CPU mesh):

    python examples/train_llama_cp.py --devices 4 --steps 10
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
    ap.add_argument("--tp", type=int, default=1, help="tensor-parallel size")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--seqlen", type=int, default=512)
    ap.add_argument("--tpu", action="store_true",
                    help="run on the attached TPUs (kernels compiled) "
                         "instead of the virtual CPU mesh")
    args = ap.parse_args()

    if not args.tpu:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={args.devices}"
            ).strip()

    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")

    import numpy as np
    from jax.sharding import Mesh

    from magiattention_tpu.api import magi_attn_flex_key
    from magiattention_tpu.models import LlamaConfig, init_params, train_step
    from magiattention_tpu.models.llama import shard_params

    devs = jax.devices()[: args.devices]
    cp = args.devices // args.tp
    if args.tp > 1:
        mesh = Mesh(
            np.array(devs).reshape(cp, args.tp), axis_names=("cp", "tp")
        )
        head_axis = "tp"
    else:
        mesh = Mesh(np.array(devs), axis_names=("cp",))
        head_axis = None

    cfg = LlamaConfig(
        vocab_size=1024, dim=256, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=64, ffn_hidden=512,
    )
    S = args.seqlen
    # two packed documents, block-causal
    key = magi_attn_flex_key(
        [[0, S // 2], [S // 2, S]],
        [[0, S // 2], [S // 2, S]],
        ["causal", "causal"],
        S, S, mesh=mesh, cp_axis="cp", head_axis=head_axis,
    )

    params = init_params(cfg, jax.random.key(0))
    params = shard_params(
        params, mesh, "cp", tp_axis="tp" if args.tp > 1 else None
    )

    rng = np.random.default_rng(0)
    for step in range(args.steps):
        tokens = rng.integers(0, cfg.vocab_size, S).astype(np.int32)
        labels = np.concatenate([tokens[1:], [-1]]).astype(np.int32)
        params, loss = train_step(params, cfg, tokens, labels, key)
        print(f"step {step:3d}  loss {float(loss):.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
