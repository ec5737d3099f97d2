"""Falsifiable roofline model for every queued benchmark config.

Zero-silicon perf predictions (r4 verdict Next #2): for each config this
prints mask-area FLOPs, modeled HBM traffic, the VMEM working set per
tile, and a predicted ms / MFU band — so the FIRST slope-timed chip
datum distinguishes kernel-bound from overhead-bound instantly, and any
number outside its band falsifies the stated assumption instead of
spawning a new hypothesis.

Model (all assumptions explicit, each one checkable against a trace):

- Compute floor: ``t_mxu = flops_hw / (PEAK * AMBIENT)``. PEAK = 197
  TFLOP/s (v5e bf16); AMBIENT is derived from the shared measured
  ceiling (perf_report.MEASURED_CEILING_TFLOPS = 208, the slope-timed
  mm4096 rate from benchmarks/history/true_rate.csv — the chip delivers
  ~105.6% of nominal). flops_hw counts
  the kernels actually launched: fwd = 4·area·d·hq; fwd+bwd = 4.5x fwd
  (separate q-major dq and k-major dkv passes re-run the score matmul,
  perf_report.HW_FWD_BWD_RATIO).
- Memory floor: ``t_hbm = bytes / (HBM_BW * BW_EFF)``. HBM_BW = 819
  GB/s (v5e). BW_EFF = 0.8 assumed for large sequential tile reads.
  Traffic is counted from the tile plan (exact work-item counts W, W_t
  from the plan builder): per fwd work item the kernel reads one q tile
  and one k+v tile pair per q head (GQA pack off — today's default);
  out/lse write once per (head, q tile). Backward adds the dq pass
  (q/k/v/do reads per work item, fp32 dq writes) and the dkv pass
  (k/v reads per transposed work item per KV head, q/do reads per GQA
  group member, fp32 dk/dv writes).
- Prediction: ``floor = max(t_mxu, t_hbm)`` is the best case; real
  flash-family kernels land at 50-90% of their floor (softmax lanes,
  pipeline bubbles), so the predicted band is
  ``[floor / 0.9, floor / 0.5]``. A measurement FASTER than floor/1.0
  falsifies the traffic model; slower than floor/0.4 indicates a
  non-kernel overhead (e.g. a fixed per-launch cost folded into a short
  scan; chip_calibration.csv implied_fixed_launch_ms).

The causal-vs-full corollary: both masks have the SAME predicted
TFLOP/s within a few percent (rates are area-normalized; only totals
differ), so the recorded 9.92 (causal) vs 26.9 (full) TF/s spread at
seq 4096 CANNOT be a kernel property — this script fits the implied
per-step fixed overhead from that pair and cross-checks it against the
independently calibrated launch cost.

Usage::

    python benchmarks/roofline.py              # quick configs
    python benchmarks/roofline.py --config5    # + the 1M rank shard
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from magiattention_tpu.benchmarking.perf_report import (  # noqa: E402
    DEVICE_PEAKS,
    MEASURED_CEILING_TFLOPS,
    PEAK_TFLOPS,
)

PEAK = PEAK_TFLOPS * 1e12
# ambient derate/uprate vs nominal, derived from the ONE shared measured
# ceiling (true_rate.csv mm4096 slope 207.98 TF/s ≈ 105.6% of nominal —
# superseding the earlier 0.957 from chip_calibration.csv):
# anchoring the compute floor to calibrated silicon means a genuine
# measurement at the chip's real matmul rate is never classified
# unphysical.
AMBIENT = MEASURED_CEILING_TFLOPS * 1e12 / PEAK
HBM_BW = DEVICE_PEAKS["TPU v5 lite"]["hbm_gbps"] * 1e9
BW_EFF = 0.8             # sequential tile streams
HW_FWD_BWD = 4.5         # hardware matmul multiple of fwd for fwd+bwd
EFF_BAND = (0.9, 0.5)    # kernel efficiency vs floor: band edges
BF16, FP32 = 2, 4


def model(name, qr, kr, tm, area, sq, sk, hq, hk, d, bq, bk):
    """Roofline rows for one config: fwd and fwd+bwd."""
    from magiattention_tpu.kernels.ffa_plan import get_ffa_plan
    from magiattention_tpu.kernels.mask_utils import types_to_bands

    lo, hi = types_to_bands(qr, kr, tm)
    plan = get_ffa_plan(qr, kr, lo, hi, sq, sk, bq, bk)
    return model_banded(name, plan, area, sq, sk, hq, hk, d, bq, bk)


def overhead_cross_check(rows):
    """Confront the two recorded pre-slope seq-4096 numbers (causal 9.92,
    full 26.87 TF/s, both len-6 scans on 2026-07-30) with the model.

    A common (kernel rate, fixed per-step overhead) pair would have to
    satisfy both rows; solving the two equations gives a NEGATIVE rate —
    physically impossible — so at least one row is an artifact. The
    per-row implied overheads quantify it: causal's is consistent with
    the calibrated 168.6 ms launch cost / 6 scan steps; full's is half
    that. Conclusion (printed): the pre-slope pair cannot be interpreted
    at all; only slope-timed rows are admissible evidence, and under
    slope timing the predicted causal/full ratio is ~1.0."""
    d, hq = 128, 16
    s = 4096
    lines = []
    for mask, tf_meas in (("causal", 9.92), ("full", 26.87)):
        area = s * (s + 1) // 2 if mask == "causal" else s * s
        fl = 4 * area * d * hq * 3.5
        t_meas = fl / (tf_meas * 1e12) * 1e3
        band = next(r for r in rows
                    if r["config"] == f"grid_{mask}_4096"
                    and r["phase"] == "fwdbwd")
        lines.append(
            f"  {mask}@{tf_meas} TF/s: measured {t_meas:.1f} ms/step vs "
            f"modeled kernel {band['ms_lo']:.1f}-{band['ms_hi']:.1f} ms "
            f"-> implied fixed overhead "
            f"{t_meas - band['ms_hi']:.1f}-{t_meas - band['ms_lo']:.1f} ms"
        )
    return lines


def quick_configs():
    from benchmarks.kernel_bench import build_mask

    cfgs = []
    # the bench.py headline shape
    s = 8192
    qr = np.array([[0, s]], np.int32)
    kr = np.array([[0, s]], np.int32)
    tm = np.array([1], np.int32)
    cfgs.append(("headline_8192_causal", qr, kr, tm,
                 s * (s + 1) // 2, s, s, 16, 8, 128, 512, 512))
    # the 6-mask kernel grid at its default seqlen
    for mask in ("full", "causal", "varlen_full", "varlen_causal",
                 "sw_causal", "video"):
        s = 4096
        qr, kr, tm, area = build_mask(mask, s)
        cfgs.append((f"grid_{mask}_4096", qr, kr, tm, area,
                     s, s, 16, 8, 128, 512, 512))
    # BASELINE config 4: video at the bench.py secondary shape + full 131k
    for s in (16384, 131072):
        qr, kr, tm, area = build_mask("video", s)
        cfgs.append((f"video_{s}", qr, kr, tm, area,
                     s, s, 16, 8, 128, 512, 512))
    return cfgs


def config5_rows():
    """The 1M-token cp=32 max-area rank shard (heavy: real solver run)."""
    from magiattention_tpu.common.enum import AttnMaskType
    from magiattention_tpu.common.ranges import AttnRanges
    from magiattention_tpu.meta import (
        make_attn_meta_from_dispatch_meta, make_dispatch_meta_from_qk_ranges,
    )
    from scripts.tpu_config5_shard import band_area

    sp, cpn = 1 << 20, 32
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        AttnRanges.from_ranges([[0, sp]]), AttnRanges.from_ranges([[0, sp]]),
        [AttnMaskType.CAUSAL], sp, sp, sp // 512, cpn,
    )
    _, calc = make_attn_meta_from_dispatch_meta(bucket, mq)
    sq = calc.shard_len
    sk = calc.kv_shard_len + sum(calc.recv_len_per_stage)
    areas = [band_area(a.q_ranges, a.k_ranges, a.d_lo, a.d_hi)
             for a in calc.merged_args]
    r = int(np.argmax(areas))
    a = calc.merged_args[r]
    from magiattention_tpu.kernels.ffa_plan import get_ffa_plan

    qr = np.asarray(a.q_ranges, np.int32)
    kr = np.asarray(a.k_ranges, np.int32)
    lo = np.asarray(a.d_lo, np.int32)
    hi = np.asarray(a.d_hi, np.int32)
    plan = get_ffa_plan(qr, kr, lo, hi, sq, sk, 512, 512)
    return model_banded(
        "config5_rank_shard", plan, areas[r], sq, sk, 32, 8, 128, 512, 512
    )


def model_banded(name, plan, area, sq, sk, hq, hk, d, bq, bk):
    """model() for a prebuilt plan (avoids re-deriving bands)."""
    w, wt = plan.num_work, plan.num_work_t
    nqt, nkt = plan.num_q_tiles, plan.num_k_tiles
    group = hq // hk
    flops_fwd = 4 * area * d * hq
    q_reads = w * bq * d * BF16 * hq
    kv_reads = w * 2 * bk * d * BF16 * hq
    out_writes = nqt * bq * (d * FP32 + FP32) * hq
    bytes_fwd = q_reads + kv_reads + out_writes
    dq_reads = w * (2 * bq * d + 2 * bk * d) * BF16 * hq \
        + w * 2 * bq * FP32 * hq
    dq_writes = nqt * bq * d * FP32 * hq
    dkv_reads = wt * 2 * bk * d * BF16 * hk \
        + wt * group * (2 * bq * d * BF16 + 2 * bq * FP32) * hk
    dkv_writes = nkt * 2 * bk * d * FP32 * hk
    bytes_fwdbwd = bytes_fwd + dq_reads + dq_writes + dkv_reads + dkv_writes
    vmem = (bq * d * BF16 + 2 * bk * d * BF16 + bq * d * FP32
            + 3 * bq * FP32 + (bq + bk) * 2 * 4)
    rows = []
    for phase, flops_rep, flops_hw, byts in (
        ("fwd", flops_fwd, flops_fwd, bytes_fwd),
        ("fwdbwd", flops_fwd * 3.5, flops_fwd * HW_FWD_BWD, bytes_fwdbwd),
    ):
        t_mxu = flops_hw / (PEAK * AMBIENT)
        t_hbm = byts / (HBM_BW * BW_EFF)
        floor = max(t_mxu, t_hbm)
        rows.append({
            "config": name, "phase": phase, "sq": sq, "sk": sk,
            "bq": bq, "bk": bk, "W": w, "Wt": wt, "area": area,
            "gbytes": byts / 1e9, "vmem_kb": vmem / 1024,
            "bound": "mxu" if t_mxu >= t_hbm else "hbm",
            "floor_ms": floor * 1e3,
            "ms_lo": floor * 1e3 / EFF_BAND[0],
            "ms_hi": floor * 1e3 / EFF_BAND[1],
            "tf_hi": flops_rep / (floor / EFF_BAND[0]) / 1e12,
            "tf_lo": flops_rep / (floor / EFF_BAND[1]) / 1e12,
            "mfu_hi": flops_rep / (floor / EFF_BAND[0]) / PEAK,
            "mfu_lo": flops_rep / (floor / EFF_BAND[1]) / PEAK,
        })
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config5", action="store_true",
                    help="include the 1M rank shard (runs the real solver)")
    args = ap.parse_args()

    rows = []
    for cfg in quick_configs():
        rows.extend(model(*cfg))
    if args.config5:
        rows.extend(config5_rows())

    hdr = (f"{'config':<24} {'phase':<7} {'W':>6} {'GB':>7} "
           f"{'VMEMkB':>7} {'bnd':>3} {'floor_ms':>9} "
           f"{'ms band':>17} {'TF/s band':>13} {'MFU band':>13}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        print(f"{r['config']:<24} {r['phase']:<7} {r['W']:>6} "
              f"{r['gbytes']:>7.2f} {r['vmem_kb']:>7.0f} {r['bound']:>3} "
              f"{r['floor_ms']:>9.2f} "
              f"{r['ms_lo']:>8.2f}-{r['ms_hi']:<8.2f} "
              f"{r['tf_lo']:>5.0f}-{r['tf_hi']:<7.0f} "
              f"{r['mfu_lo']:>5.2f}-{r['mfu_hi']:<7.2f}")

    full = next(r for r in rows
                if r["config"] == "grid_full_4096" and r["phase"] == "fwdbwd")
    caus = next(r for r in rows
                if r["config"] == "grid_causal_4096"
                and r["phase"] == "fwdbwd")
    ratio = (caus["tf_hi"] / full["tf_hi"], caus["tf_lo"] / full["tf_lo"])
    print(f"\npredicted causal/full TFLOP/s ratio at 4096: "
          f"{min(ratio):.2f}-{max(ratio):.2f} (rates are area-normalized)")
    print("pre-slope 9.92-vs-26.87 anomaly vs this model:")
    for line in overhead_cross_check(rows):
        print(line)
    print("  no common (rate, overhead) pair fits both rows (the joint "
          "solve gives a negative rate) -> at least one row is an "
          "artifact; calibrated launch cost 168.6 ms / 6-step scan = "
          "28.1 ms/step (chip_calibration.csv). Only slope-timed rows "
          "are admissible; under slope timing expect ratio ~1.0.")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
