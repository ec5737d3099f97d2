"""FFA kernel benchmark grid (ref: docs/source/blog/cp_benchmark.md:82-96).

The reference's kernel-bench coverage: 6 masks (full, causal, varlen full,
varlen causal, sliding-window causal, Magi-1 video block causal), seqlen
sweep, fwd and fwd+bwd, TFLOP/s with FLOPs = 4 * mask_area * d * hq (bwd
2.5x). Chained-scan slope timing. Measures the TPU and stops without one.

    python benchmarks/kernel_bench.py --seqlens 4096,8192 --dtype bf16
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def build_mask(name: str, s: int):
    """Returns (q_ranges, k_ranges, type_map, area)."""
    import numpy as np

    from magiattention_tpu.common.enum import AttnMaskType
    from magiattention_tpu.common.mask import AttnMask
    from magiattention_tpu.common.ranges import AttnRanges

    if name == "full":
        qr, kr, tm = [[0, s]], [[0, s]], [0]
    elif name == "causal":
        qr, kr, tm = [[0, s]], [[0, s]], [1]
    elif name in ("varlen_full", "varlen_causal"):
        t = 0 if name == "varlen_full" else 1
        bounds = [0, s // 8, s // 3, s // 2, (3 * s) // 4, s]
        qr = [[a, b] for a, b in zip(bounds[:-1], bounds[1:])]
        kr = qr
        tm = [t] * len(qr)
    elif name == "sw_causal":
        from magiattention_tpu.api.functools import (
            infer_attn_mask_from_sliding_window,
        )

        q = AttnRanges.from_ranges([[0, s]])
        qo, ko, to = infer_attn_mask_from_sliding_window(
            q, q, [AttnMaskType.CAUSAL], window_size=(s // 8, 0),
            sink_size=64,
        )
        qr = [[r.start, r.end] for r in qo]
        kr = [[r.start, r.end] for r in ko]
        tm = [t.to_int_type() for t in to]
    elif name == "video":
        from magiattention_tpu.utils.sparse_utils import (
            block_mask_to_ranges, make_video_block_mask,
        )

        frames = 8
        per_frame = s // frames
        block = max(min(per_frame // 2, 1024), 16)
        bm = make_video_block_mask(frames, per_frame // block, 2)
        qo, ko, to = block_mask_to_ranges(bm, block, block)
        qr = [[r.start, r.end] for r in qo]
        kr = [[r.start, r.end] for r in ko]
        tm = [t.to_int_type() for t in to]
    else:
        raise ValueError(name)

    area = AttnMask.from_ranges(
        AttnRanges.from_ranges(qr), AttnRanges.from_ranges(kr),
        [AttnMaskType.from_int_type(t) for t in tm],
        total_seqlen_q=s, total_seqlen_k=s,
    ).area
    return (
        np.array(qr, np.int32), np.array(kr, np.int32),
        np.array(tm, np.int32), area,
    )


MASKS = ["full", "causal", "varlen_full", "varlen_causal", "sw_causal",
         "video"]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqlens", default="4096")
    ap.add_argument("--masks", default=",".join(MASKS))
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "fp32"])
    ap.add_argument("--backward", action="store_true")
    ap.add_argument(
        "--auto-tile", action="store_true",
        help="run with MAGI_ATTENTION_FFA_AUTO_TILE=1 (per-mask tile "
        "policy) — rows are tagged tiling=auto for the A/B vs env defaults",
    )
    ap.add_argument(
        "--dkv-pack", default="env", choices=["env", "on", "off"],
        help="force MAGI_ATTENTION_FFA_GQA_PACK_DKV for the GQA-packed "
        "dkv backward A/B; 'env' leaves the flag alone (default: packed)",
    )
    ap.add_argument(
        "--bwd-sweep", action="store_true",
        help="also append backward rows to history/bwd_override_sweep.csv "
        "tagged (tiling, dkv_pack) — the backward A/B record",
    )
    args = ap.parse_args()

    if args.auto_tile:
        os.environ["MAGI_ATTENTION_FFA_AUTO_TILE"] = "1"
    if args.dkv_pack != "env":
        os.environ["MAGI_ATTENTION_FFA_GQA_PACK_DKV"] = (
            "1" if args.dkv_pack == "on" else "0"
        )
    # effective state (flag defaults ON), so rows are tagged correctly
    # even under --dkv-pack env with the variable pre-set by the caller
    dkv_pack_tag = (
        "on" if os.environ.get("MAGI_ATTENTION_FFA_GQA_PACK_DKV", "1")
        == "1" else "off"
    )

    import jax
    import jax.numpy as jnp
    import numpy as np

    from magiattention_tpu.benchmarking.bench import (
        do_bench_scan_slope,
        make_consume_all_grads_kv_body,
        make_fwd_kv_body,
        measuring_device,
    )
    from magiattention_tpu.benchmarking.perf_report import (
        HW_FWD_BWD_RATIO,
        MEASURED_CEILING_TFLOPS,
        append_row,
        credible_floor_ms,
        history_report,
    )
    from magiattention_tpu.kernels.ffa import ffa_attn

    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    HQ, HK, D = args.heads, args.kv_heads, args.head_dim
    dev = measuring_device("kernel_bench")
    peak = dev["peak_tflops"]
    print(json.dumps({"device": dev}), flush=True)

    def scan_time(body, init, flops=None, reps=2):
        # slope timing cancels the fixed per-launch cost. flops sets the
        # physical floor: a slope implying > 1.05x the chip ceiling is an
        # under-cancelled pair and falls back to the long-scan upper bound
        floor = None if flops is None else credible_floor_ms(flops)
        return do_bench_scan_slope(
            body, init, reps=reps, verbose=True, min_credible_ms=floor
        )

    rows = []
    rng = np.random.default_rng(0)
    for s in (int(x) for x in args.seqlens.split(",")):
        q0 = jnp.asarray(rng.standard_normal((s, HQ, D)), dtype)
        k = jnp.asarray(rng.standard_normal((s, HK, D)), dtype)
        v = jnp.asarray(rng.standard_normal((s, HK, D)), dtype)
        w = jnp.asarray(rng.standard_normal((s, HQ, D)), dtype)
        for name in args.masks.split(","):
            try:
                qr, kr, tm, area = build_mask(name, s)
                flops = 4 * area * D * HQ

                # k/v/w ride the scan carry (jit arguments): closed-over
                # jax.Arrays lower as HLO constants, and at 131k rows that
                # is ~1 GB copied into the executable
                fwd_body = make_fwd_kv_body(
                    lambda qq, kk, vv, qr=qr, kr=kr, tm=tm:
                        ffa_attn(qq, kk, vv, qr, kr, tm)[0],
                    dtype,
                )
                dt = scan_time(fwd_body, (q0, k, v), flops=flops)
                row = {
                    "mask": name, "seqlen": s,
                    "fwd_ms": round(dt, 3),
                    "fwd_tflops": round(flops / (dt * 1e-3) / 1e12, 2),
                    "fwd_mfu": round(flops / (dt * 1e-3) / 1e12 / peak, 4),
                }
                if row["fwd_tflops"] > MEASURED_CEILING_TFLOPS:
                    # even the long-scan upper bound is unphysical; flag
                    # per PHASE so a bad fwd doesn't bar the row's valid
                    # fwdbwd columns from setting report baselines
                    row["suspect_fwd"] = 1
                if args.backward:
                    def loss(qq, kk, vv, ww, qr=qr, kr=kr, tm=tm):
                        o, _ = ffa_attn(qq, kk, vv, qr, kr, tm)
                        return jnp.sum(
                            o.astype(jnp.float32) * ww.astype(jnp.float32)
                        )

                    g = jax.grad(loss, argnums=(0, 1, 2))
                    bwd_body = make_consume_all_grads_kv_body(g, dtype)
                    # the floor and the suspect check use EXECUTED flops
                    # (4.5x fwd = 3.5x reference * HW ratio): the hardware
                    # runs 4.5x fwd matmul work, so a reference-convention
                    # floor would sit ~29% below the physical bound.
                    # Reported rates stay in reference convention (3.5x).
                    flops_hw = flops * 3.5 * HW_FWD_BWD_RATIO
                    dtb = scan_time(bwd_body, (q0, k, v, w),
                                    flops=flops_hw)
                    if (flops_hw / (dtb * 1e-3) / 1e12
                            > MEASURED_CEILING_TFLOPS):
                        row["suspect_fwdbwd"] = 1
                    row["fwdbwd_ms"] = round(dtb, 3)
                    row["fwdbwd_tflops"] = round(
                        flops * 3.5 / (dtb * 1e-3) / 1e12, 2
                    )
                    # hardware matmul convention (bwd = 3.5x fwd on TPU)
                    row["fwdbwd_mfu"] = round(
                        row["fwdbwd_tflops"] / peak, 4
                    )
                    row["fwdbwd_mfu_hw"] = round(
                        row["fwdbwd_tflops"] * HW_FWD_BWD_RATIO / peak, 4
                    )
                rows.append(row)
                print(json.dumps(row), flush=True)
                append_row("kernel_grid", {
                    "mask": name, "seqlen": s, "dtype": args.dtype,
                    "device_kind": dev["kind"],
                    "tiling": "auto" if args.auto_tile else "env",
                    "dkv_pack": dkv_pack_tag,
                    **{kk: vv for kk, vv in row.items()
                       if kk not in ("mask", "seqlen")},
                })
                if args.bwd_sweep and "fwdbwd_ms" in row:
                    append_row("bwd_override_sweep", {
                        "mask": name, "seqlen": s,
                        "dtype": args.dtype,
                        "device_kind": dev["kind"],
                        "tiling": "auto" if args.auto_tile else "env",
                        "dkv_pack": dkv_pack_tag,
                        **{kk: vv for kk, vv in row.items()
                           if kk.startswith(("fwdbwd", "suspect"))},
                    })
            except Exception as e:  # noqa: BLE001
                print(json.dumps({
                    "mask": name, "seqlen": s,
                    "error": f"{type(e).__name__}: {e}"[:160],
                }), flush=True)
    report = history_report(
        "kernel_grid", ["mask", "seqlen", "dtype"], "fwd_tflops"
    )
    if report:
        print(report, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
