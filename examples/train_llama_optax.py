"""Optax (AdamW) CP trainer with dense-parity check.

The TPU counterpart of the reference's examples/torch_native +
examples/transformers integrations (convergence-parity evidence): trains the
Llama model with MagiAttention context parallelism and, optionally, a
replicated dense-attention twin from the same init to verify the loss curves
track each other.

Run (no TPU needed — virtual CPU mesh):

    python examples/train_llama_optax.py --devices 4 --steps 10 --parity
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
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--parity", action="store_true",
                    help="also train a dense-attention twin and compare")
    ap.add_argument("--tpu", action="store_true",
                    help="run on the attached TPU instead of a CPU mesh")
    ap.add_argument("--save-dir", default=None,
                    help="orbax checkpoint dir: resume if present, save at "
                         "the end (the reference delegates checkpointing to "
                         "the host framework; here it is orbax)")
    args = ap.parse_args()

    import jax

    if not args.tpu:
        # the virtual CPU mesh: kernels run in the Pallas interpreter
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={args.devices}"
            ).strip()
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from magiattention_tpu.api import magi_attn_flex_key
    from magiattention_tpu.common.enum import AttnMaskType
    from magiattention_tpu.common.mask import AttnMask
    from magiattention_tpu.common.ranges import AttnRanges
    from magiattention_tpu.models import LlamaConfig, init_params
    from magiattention_tpu.models.llama import (
        make_optax_train_step,
        make_optax_train_step_dense,
        shard_params,
    )

    S = args.seqlen
    cfg = LlamaConfig(
        vocab_size=512, dim=256, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=64, ffn_hidden=512, dtype="float32",
    )
    qr = [[0, S // 2], [S // 2, S]]
    kr = [[0, S // 2], [S // 2, S]]
    tm = [1, 1]  # two causal documents

    mesh = Mesh(
        np.array(jax.devices()[: args.devices]), axis_names=("cp",)
    )
    key = magi_attn_flex_key(
        qr, kr, tm, S, S, mesh=mesh, cp_axis="cp", chunk_size=max(S // 32, 16)
    )

    optimizer = optax.adamw(args.lr)
    params = init_params(cfg, jax.random.key(0))

    ckptr = None
    if args.save_dir:
        import orbax.checkpoint as ocp

        ckptr = ocp.StandardCheckpointer()
        ckpt_path = Path(args.save_dir).resolve() / "params"
        if ckpt_path.exists():
            params = ckptr.restore(ckpt_path, params)
            print(f"resumed params from {ckpt_path}")

    params_dense = jax.tree.map(jnp.copy, params) if args.parity else None
    params = shard_params(params, mesh, "cp")
    step = make_optax_train_step(cfg, key, optimizer)
    opt_state = optimizer.init(params)

    if args.parity:
        mask = AttnMask.from_ranges(
            AttnRanges.from_ranges(qr), AttnRanges.from_ranges(kr),
            [AttnMaskType.from_int_type(t) for t in tm],
            total_seqlen_q=S, total_seqlen_k=S,
        ).mask_array
        step_dense = make_optax_train_step_dense(cfg, mask, optimizer)
        opt_dense = optimizer.init(params_dense)

    rng = np.random.default_rng(0)
    for i in range(args.steps):
        tokens = rng.integers(0, cfg.vocab_size, S).astype(np.int32)
        labels = np.concatenate([tokens[1:], [-1]]).astype(np.int32)
        tokens, labels = jnp.asarray(tokens), jnp.asarray(labels)
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        line = f"step {i:3d}  cp_loss {float(loss):.4f}"
        if args.parity:
            params_dense, opt_dense, loss_d = step_dense(
                params_dense, opt_dense, tokens, labels
            )
            line += (
                f"  dense_loss {float(loss_d):.4f}"
                f"  |diff| {abs(float(loss) - float(loss_d)):.2e}"
            )
        print(line, flush=True)
    if ckptr is not None:
        ckpt_path = Path(args.save_dir).resolve() / "params"
        ckptr.save(ckpt_path, params, force=True)
        ckptr.wait_until_finished()
        print(f"saved params to {ckpt_path}")
    print("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
