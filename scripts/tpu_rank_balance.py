"""Silicon evidence for the load-balancing pillar: per-rank FFA kernel time.

The dispatch solver's claim is that every CP rank gets equal attention-area
workload (ref magi_attention/meta/solver/dispatch_solver.py). Multi-chip
hardware isn't available here, so this measures it on ONE chip for BASELINE
config 3 (262144 causal, CP=8):

- In the real SPMD runtime every rank runs the SAME padded grid
  (max-W over ranks), so per-rank kernel cost is equalized by construction
  and the interesting quantities are (a) the spread between the unpadded
  extreme ranks — the *true* work imbalance the solver left behind — and
  (b) the padding tax: padded-grid time vs the heaviest rank's unpadded
  time (what the max-W padding costs the fleet).
- Measures: unpadded min-W rank, unpadded max-W rank, padded grid.
  3 executables x 2 scan lengths; the persistent cache makes later
  runs cheap.

Appends to ``benchmarks/history/rank_balance.csv``.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import jax.numpy as jnp
import numpy as np

from magiattention_tpu.benchmarking.bench import (
    do_bench_scan_slope,
    make_fwd_kv_body,
    measuring_device,
)
from magiattention_tpu.benchmarking.perf_report import (
    append_row,
    credible_floor_ms,
)
from magiattention_tpu.common.enum import AttnMaskType
from magiattention_tpu.common.ranges import AttnRanges
from magiattention_tpu.kernels.ffa import (
    FFAParams, ffa_attn_with_plan, plan_arrays,
)
from magiattention_tpu.kernels.ffa_plan import build_ffa_plan, pad_plan
from magiattention_tpu.meta import (
    make_attn_meta_from_dispatch_meta,
    make_dispatch_meta_from_qk_ranges,
)

S, CP = 262144, 8
HQ, HK, D = 16, 8, 128
BQ, BK = 512, 512


def _time_plan(plan, w, wt, q, k, v, shard, sk_len, label):
    params = FFAParams(
        num_work=w, num_work_t=wt,
        num_q_tiles=plan.num_q_tiles, num_k_tiles=plan.num_k_tiles,
        block_q=BQ, block_k=BK, softmax_scale=D ** -0.5, softcap=0.0,
        group=HQ // HK, interpret=False,
    )
    arrays = plan_arrays(plan)

    # k/v ride the carry (jit arguments): a closed-over jax.Array lowers
    # as an HLO constant, and the 262k kv here is ~1 GB of constants
    # copied into the executable
    fwd = make_fwd_kv_body(
        lambda qq, kk, vv: ffa_attn_with_plan(qq, kk, vv, arrays, params)[0],
        jnp.bfloat16,
    )
    # credibility floor from the EXACT hardware work: every counted work
    # tile runs full (bq, bk) matmuls on the MXU regardless of banding,
    # so 4*W*bq*bk*D*hq is the true executed-flop count
    floor = credible_floor_ms(4.0 * w * BQ * BK * D * HQ)
    ms = do_bench_scan_slope(
        fwd, (q, k, v), verbose=True, min_credible_ms=floor
    )
    print(f"{label}: {ms:8.3f} ms (W={w})", flush=True)
    append_row("rank_balance", {
        "probe": label, "ms": round(ms, 4), "w": w,
        "shard": shard, "sk": sk_len, "block_q": BQ, "block_k": BK,
        **({"suspect": 1} if ms < floor else {}),
    })
    return ms


def _config_causal():
    return (
        "causal262k",
        AttnRanges.from_ranges([[0, S]]),
        AttnRanges.from_ranges([[0, S]]),
        [AttnMaskType.CAUSAL], S,
    )


def _config_video():
    """BASELINE config 4's heterogeneous mask: per-chunk areas are uneven
    (window widths differ across frames), so the balance here is earned by
    the dispatch solver, not by symmetry."""
    from magiattention_tpu.utils.sparse_utils import (
        block_mask_to_ranges, make_video_block_mask,
    )

    sv, block, frames = 131072, 512, 16
    bm = make_video_block_mask(frames, sv // frames // block, 2)
    qr, kr, tm = block_mask_to_ranges(bm, block, block)
    return "video131k", qr, kr, tm, sv


def _run_config(name, qr, kr, tm, s) -> None:
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        qr, kr, tm, s, s, 2048, CP,
    )
    cmm, km = make_attn_meta_from_dispatch_meta(bucket, mq)
    shard = km.shard_len
    sk_len = (km.kv_shard_len or shard) + sum(km.recv_len_per_stage)

    plans = [
        build_ffa_plan(a.q_ranges, a.k_ranges, a.d_lo, a.d_hi,
                       shard, sk_len, BQ, BK)
        for a in km.merged_args
    ]
    w_real = [p.num_work for p in plans]
    w_pad = max(w_real)
    wt_pad = max(p.num_work_t for p in plans)
    r_min = int(np.argmin(w_real))
    r_max = int(np.argmax(w_real))
    spread_planned = w_pad / max(1, min(w_real))
    print(
        f"[{name}] shard={shard} sk={sk_len} per-rank W={w_real} "
        f"(planned spread {spread_planned:.3f})",
        flush=True,
    )

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((shard, HQ, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((sk_len, HK, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((sk_len, HK, D)), jnp.bfloat16)

    ms_min = _time_plan(
        plans[r_min], w_real[r_min], plans[r_min].num_work_t,
        q, k, v, shard, sk_len, f"{name}_rank{r_min}_minW",
    )
    if r_max != r_min:
        ms_max = _time_plan(
            plans[r_max], w_real[r_max], plans[r_max].num_work_t,
            q, k, v, shard, sk_len, f"{name}_rank{r_max}_maxW",
        )
    else:
        ms_max = ms_min  # solver equalized W exactly — nothing to re-time
    padded = pad_plan(plans[r_min], w_pad, wt_pad)
    ms_pad = _time_plan(
        padded, w_pad, wt_pad, q, k, v, shard, sk_len, f"{name}_padded",
    )

    print(
        f"[{name}] measured imbalance (unpadded max/min): "
        f"{ms_max / ms_min:.3f}  planned W spread: {spread_planned:.3f}  "
        f"padding tax: {ms_pad / ms_max:.3f}",
        flush=True,
    )
    append_row("rank_balance", {
        "probe": f"{name}_summary",
        "imbalance": round(ms_max / ms_min, 4),
        "pad_tax": round(ms_pad / ms_max, 4),
        "planned_spread": round(spread_planned, 4),
        "shard": shard, "sk": sk_len, "block_q": BQ, "block_k": BK,
    })


def main() -> int:
    print("device:", measuring_device("tpu_rank_balance"), flush=True)
    _run_config(*_config_causal())
    _run_config(*_config_video())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
