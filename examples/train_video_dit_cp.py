"""Video DiT (Magi-1-style) flow-matching trainer on spatiotemporal CP.

The reference's flagship workload is the Magi-1 autoregressive video
diffusion transformer (ref README.md:54-56), trained with the
varlen-block-causal spatiotemporal mask (bench config 4). This example
trains the compact TPU-native DiT (models/video_dit.py) through
``magi_attn_flex_key -> dispatch -> calc_attn`` over that mask, with AdamW
and an optional dense twin for convergence parity.

Run (no TPU needed — virtual CPU mesh):

    python examples/train_video_dit_cp.py --devices 8 --steps 10 --parity
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--frames", type=int, default=8)
    ap.add_argument("--tokens-per-frame", type=int, default=256)
    ap.add_argument("--window-frames", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--parity", action="store_true",
                    help="also train a dense-attention twin and compare")
    ap.add_argument("--remat", action="store_true",
                    help="per-layer jax.checkpoint (long-context memory)")
    ap.add_argument("--tpu", action="store_true",
                    help="run on the attached TPU instead of a CPU mesh")
    args = ap.parse_args()

    import jax

    if not args.tpu:
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

    from magiattention_tpu.models import video_dit

    cfg = video_dit.VideoDiTConfig(
        num_frames=args.frames,
        tokens_per_frame=args.tokens_per_frame,
        window_frames=args.window_frames,
        dtype="float32" if not args.tpu else "bfloat16",
        remat=args.remat,
    )
    devs = jax.devices()[: args.devices]
    mesh = Mesh(np.array(devs), axis_names=("cp",))
    key = video_dit.make_video_attn_key(cfg, mesh, "cp")
    print(
        f"video DiT: {cfg.num_frames} frames x {cfg.tokens_per_frame} tokens"
        f" = seqlen {cfg.seqlen}, window {cfg.window_frames} frames,"
        f" cp={len(devs)}"
    )

    params = video_dit.init_params(cfg, jax.random.PRNGKey(0))
    params = video_dit.shard_params(params, mesh, axis="cp")
    opt = optax.adamw(args.lr)
    step = video_dit.make_optax_train_step(cfg, key, opt)
    opt_state = opt.init(params)

    if args.parity:
        mask = video_dit.dense_video_mask(cfg)
        p_dn = jax.tree.map(jnp.copy, params)
        s_dn = opt.init(p_dn)
        step_dn = video_dit.make_optax_train_step_dense(cfg, mask, opt)

    rng = np.random.default_rng(0)
    for i in range(args.steps):
        clean = jnp.asarray(
            rng.standard_normal((cfg.seqlen, cfg.in_dim)), jnp.float32
        )
        noise = jnp.asarray(
            rng.standard_normal((cfg.seqlen, cfg.in_dim)), jnp.float32
        )
        t = jnp.float32(rng.uniform(0.02, 0.98))
        params, opt_state, loss = step(params, opt_state, clean, noise, t)
        line = f"step {i:3d}  loss {float(loss):.6f}"
        if args.parity:
            p_dn, s_dn, loss_dn = step_dn(p_dn, s_dn, clean, noise, t)
            line += (
                f"  dense {float(loss_dn):.6f}"
                f"  |diff| {abs(float(loss) - float(loss_dn)):.2e}"
            )
        print(line)

    print("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
