"""Overlap-degree benchmark (VERDICT r1 weak item 5: "overlap is asserted,
not demonstrated").

Times the CP forward (+backward) at overlap degree 0 (blocking merged
kernel), 1, and 2 on the mesh, and writes a markdown row set to stdout.
Timing uses chained dispatch (each iteration depends on the previous one) so
cached-execution tricks can't fake it.

On the virtual CPU mesh the collectives are memcpys, so the numbers measure
plan/kernel-launch structure only (recorded in docs/overlap_results.md); on
a multi-chip TPU slice the same script measures true comm/compute overlap.

    python benchmarks/overlap_bench.py --devices 8 --seqlen 4096 --cpu
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--seqlen", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--backward", action="store_true")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    if args.cpu:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={args.devices}"
            ).strip()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from magiattention_tpu.api import calc_attn, dispatch, magi_attn_flex_key
    from magiattention_tpu.config import DistAttnConfig, OverlapConfig

    S, HQ, HK, D = args.seqlen, args.heads, args.kv_heads, args.head_dim
    n = args.devices
    dtype = jnp.float32 if args.cpu else jnp.bfloat16
    rng = np.random.default_rng(0)
    q0 = jnp.asarray(rng.standard_normal((S, HQ, D)), dtype)
    k0 = jnp.asarray(rng.standard_normal((S, HK, D)), dtype)
    v0 = jnp.asarray(rng.standard_normal((S, HK, D)), dtype)
    w = jnp.asarray(rng.standard_normal((S, HQ, D)), dtype)
    mesh = Mesh(np.array(jax.devices()[:n]), axis_names=("cp",))

    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} device_kind={dev.device_kind!r} "
          f"count={n}" + ("  (CPU mesh: these are not device times)"
                          if dev.platform == "cpu" else ""))
    print(f"| degree | fwd ms | {'fwd+bwd ms |' if args.backward else ''}")
    print(f"|---|---|{'---|' if args.backward else ''}")

    for degree in (0, 1, 2):
        if degree == 0:
            cfg = DistAttnConfig(overlap_config=OverlapConfig(enable=False))
        else:
            cfg = DistAttnConfig(
                overlap_config=OverlapConfig(enable=True, degree=degree)
            )
        key = magi_attn_flex_key(
            [[0, S]], [[0, S]], [1], S, S, mesh=mesh, cp_axis="cp",
            dist_attn_config=cfg,
        )

        def fwd_step(q):
            qd = dispatch(q, key)
            kd = dispatch(k0, key, role="kv")
            vd = dispatch(v0, key, role="kv")
            od, _ = calc_attn(qd, kd, vd, key)
            return od

        @jax.jit
        def chain_fwd(q):
            qd = fwd_step(q)
            # feed output back as next q (chained dependence)
            from magiattention_tpu.api import undispatch

            return undispatch(od := qd, key)

        def timeit(f, x, iters):
            y = jax.block_until_ready(f(x))  # compile
            t0 = time.perf_counter()
            for _ in range(iters):
                y = f(y)
            jax.block_until_ready(y)
            return (time.perf_counter() - t0) / iters * 1e3

        fwd_ms = timeit(chain_fwd, q0, args.iters)

        row = f"| {degree} | {fwd_ms:8.2f} |"
        if args.backward:
            def loss(q):
                qd = dispatch(q, key)
                kd = dispatch(k0, key, role="kv")
                vd = dispatch(v0, key, role="kv")
                od, _ = calc_attn(qd, kd, vd, key)
                wd = dispatch(w, key)
                return jnp.sum(od.astype(jnp.float32) * wd.astype(jnp.float32))

            g = jax.grad(loss)

            @jax.jit
            def chain_bwd(q):
                return (q + 1e-3 * g(q).astype(q.dtype)).astype(q.dtype)

            bwd_ms = timeit(chain_bwd, q0, args.iters)
            row += f" {bwd_ms:8.2f} |"
        print(row, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
