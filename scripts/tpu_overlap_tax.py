"""Single-chip kernel-side overlap tax (VERDICT r2 weak item 2).

The multi-stage CP path trades ONE merged FFA kernel for a host kernel +
one kernel per stage with an lse merge — the comm overlap it buys is only
a win if this kernel-side tax is small. A single chip cannot run real CP
stages, but it can measure exactly that tax: the same causal workload
computed as 1 / 2 / 3 k-partitioned kernels through the identical
_multi_ffa machinery the CP runtime uses. Chained-scan slope timing.
Results land in benchmarks/history/overlap_tax.csv and
docs/overlap_results.md.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import jax.numpy as jnp
import numpy as np

from magiattention_tpu.benchmarking.bench import (
    do_bench_scan_slope,
    measuring_device,
)
from magiattention_tpu.benchmarking.perf_report import append_row
from magiattention_tpu.functional.dist_attn import _multi_ffa
from magiattention_tpu.kernels.ffa import default_blocks
from magiattention_tpu.kernels.mask_utils import BAND_INF
from magiattention_tpu.parallel._utils import (
    baseline_params, block_plan, clip_to_segs, stack_step_plans,
)

def main():
    dev = measuring_device("tpu_overlap_tax")
    PEAK = dev["peak_tflops"]
    print("device:", dev, flush=True)
    S, HQ, HK, D = 4096, 16, 8, 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((S, HQ, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((S, HK, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((S, HK, D)), jnp.bfloat16)
    qr = np.array([[0, S]], np.int32)
    kr = np.array([[0, S]], np.int32)
    lo = np.array([-BAND_INF], np.int32)
    hi = np.array([0], np.int32)  # causal
    area = S * (S + 1) // 2
    flops = 4 * area * D * HQ

    base_ms = None
    for parts in (1, 2, 3):
        cuts = np.linspace(0, S, parts + 1).astype(int)
        plans, ks, vs = [], [], []
        bq, bk = default_blocks(S, S)
        for p in range(parts):
            k0, k1 = int(cuts[p]), int(cuts[p + 1])
            sl = clip_to_segs(qr, kr, lo, hi, [(0, S, 0)], [(k0, k1, 0)])
            plans.append(block_plan(sl, S, k1 - k0, bq, bk))
            ks.append(k[k0:k1])
            vs.append(v[k0:k1])
        stacked, w, wt = stack_step_plans([plans])
        # per-part params: k lengths differ, so each part gets its own
        params_list = tuple(
            baseline_params(plans[p], w, wt, bq, bk, D ** -0.5, HQ, HK)
            for p in range(parts)
        )
        arrays_list = tuple(
            tuple(a[p] for a in stacked[0]) for p in range(parts)
        )

        def body(qc):
            out, _, _ = _multi_ffa(
                qc, tuple(ks), tuple(vs), arrays_list, params_list
            )
            return out.astype(jnp.bfloat16)

        ms = do_bench_scan_slope(body, q, reps=2, verbose=True)
        tf = flops / (ms * 1e-3) / 1e12
        tax = 0.0 if base_ms is None else (ms - base_ms) / base_ms * 100
        if base_ms is None:
            base_ms = ms
        print(
            f"parts={parts}: {ms:.3f} ms {tf:.1f} TF/s "
            f"({tf/PEAK*100:.1f}%) kernel-side tax {tax:+.1f}%",
            flush=True,
        )
        append_row("overlap_tax", {
            "backend": jax.default_backend(), "device_kind": dev["kind"],
            "parts": parts, "fwd_ms": round(ms, 3),
            "fwd_tflops": round(tf, 2), "tax_pct": round(tax, 1),
        })


if __name__ == "__main__":
    main()
