"""Distributed-attention benchmark runner (ref: exps/dist_attn/run_benchmark.py).

Compares MagiAttention-TPU CP against the in-repo baselines (Ulysses, Ring,
USP, LoongTrain, HybridCP, AllGather) on the same mask and mesh, reporting
TFLOP/s/chip with the reference's FLOP counting (4*mask_area*d*hq fwd).

On a real TPU slice this gives the distributed-benchmark parity numbers
(cp_benchmark.md:384-404); on the virtual CPU mesh it serves as a
correctness + relative-cost smoke (interpret-mode kernels, not meaningful
for absolute throughput).

    python benchmarks/dist_attn_bench.py --devices 8 --seqlen 4096 --cpu
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--seqlen", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--mask", choices=["full", "causal"], default="causal")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument(
        "--impls",
        default="magi,ulysses,ring,allgather,usp,loongtrain,hybrid",
    )
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

    from magiattention_tpu.benchmarking.bench import do_bench
    from magiattention_tpu.meta.container.slice import band_area

    S, HQ, HK, D = args.seqlen, args.heads, args.kv_heads, args.head_dim
    n = args.devices
    dtype = jnp.float32 if args.cpu else jnp.bfloat16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((S, HQ, D)), dtype)
    k = jnp.asarray(rng.standard_normal((S, HK, D)), dtype)
    v = jnp.asarray(rng.standard_normal((S, HK, D)), dtype)
    causal = args.mask == "causal"
    qr = np.array([[0, S]], np.int32)
    tm = np.array([1 if causal else 0], np.int32)
    area = band_area(0, S, 0, S, -(1 << 30), 0 if causal else (1 << 30))
    flops = 4 * area * D * HQ

    devs = np.array(jax.devices()[:n])
    mesh1d = Mesh(devs, axis_names=("cp",))
    results = {}

    def record(name, fn):
        out = jax.jit(fn)
        ms = do_bench(lambda: out(q, k, v), warmup=1, rep=5)[0]
        results[name] = round(flops / (ms * 1e-3) / 1e12 / n, 4)

    impls = set(args.impls.split(","))

    if "magi" in impls:
        from magiattention_tpu.api import (
            calc_attn, dispatch, magi_attn_flex_key, undispatch,
        )

        key = magi_attn_flex_key(
            qr.tolist(), qr.tolist(), tm.tolist(), S, S,
            mesh=mesh1d, cp_axis="cp",
        )

        def magi(q, k, v):
            qd = dispatch(q, key)
            kd = dispatch(k, key, role="kv")
            vd = dispatch(v, key, role="kv")
            od, _ = calc_attn(qd, kd, vd, key)
            return undispatch(od, key)

        record("magi", magi)

    if "ulysses" in impls:
        from magiattention_tpu.parallel.ulysses import ulysses_attn

        record("ulysses", lambda q, k, v: ulysses_attn(
            q, k, v, qr, qr, tm, mesh1d)[0])

    if "ring" in impls:
        from magiattention_tpu.parallel.ring import (
            ring_attn, ring_attn_allgather, ring_dispatch, ring_undispatch,
        )

        def ring_f(q, k, v):
            od, _ = ring_attn(
                ring_dispatch(q, n), ring_dispatch(k, n),
                ring_dispatch(v, n), qr, qr, tm, mesh1d,
            )
            return ring_undispatch(od, n)

        record("ring", ring_f)

        def ring_ag_f(q, k, v):
            od, _ = ring_attn_allgather(
                ring_dispatch(q, n), ring_dispatch(k, n),
                ring_dispatch(v, n), qr, qr, tm, mesh1d,
            )
            return ring_undispatch(od, n)

        record("ring_allgather", ring_ag_f)

    if "allgather" in impls:
        from magiattention_tpu.parallel.hybrid import allgather_attn

        record("allgather", lambda q, k, v: allgather_attn(
            q, k, v, qr, qr, tm, mesh1d)[0])

    if "usp" in impls:
        from magiattention_tpu.parallel.usp import usp_attn

        mesh_usp = Mesh(devs.reshape(n // 2, 2), axis_names=("rp", "sp"))
        record("usp", lambda q, k, v: usp_attn(
            q, k, v, qr, qr, tm, mesh_usp)[0])

    if "loongtrain" in impls:
        from magiattention_tpu.parallel.loongtrain import loongtrain_attn
        from magiattention_tpu.parallel.ring import (
            ring_dispatch, ring_undispatch,
        )

        mesh_lt = Mesh(
            devs.reshape(n // 2, 2), axis_names=("rp_out", "rp_in")
        )

        def lt_f(q, k, v):
            od, _ = loongtrain_attn(
                ring_dispatch(q, n), ring_dispatch(k, n),
                ring_dispatch(v, n), qr, qr, tm, mesh_lt,
            )
            return ring_undispatch(od, n)

        record("loongtrain", lt_f)

    if "hybrid" in impls:
        from magiattention_tpu.parallel.hybrid import hybrid_cp_attn

        mesh_h = Mesh(
            devs.reshape(n // 2, 2), axis_names=("cp_inter", "cp_intra")
        )
        record("hybrid", lambda q, k, v: hybrid_cp_attn(
            q, k, v, qr, qr, tm, mesh_h)[0])

    print(json.dumps({
        "config": {
            "devices": n, "seqlen": S, "heads": HQ, "kv_heads": HK,
            "head_dim": D, "mask": args.mask,
            "unit": "TFLOP/s/chip",
            # a CPU mesh checks the plumbing; its rates are not a chip's
            "device": {
                "platform": jax.devices()[0].platform,
                "kind": jax.devices()[0].device_kind,
                "count": n,
            },
        },
        "results": results,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
