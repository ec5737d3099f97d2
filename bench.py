"""Benchmark: FFA Pallas kernel fwd+bwd throughput on one TPU chip.

    python bench.py            # the headline line
    python bench.py --sparse-suite | --bwd-suite | --nsa-suite | --dcn-suite

Prints ONE JSON line: {"metric", "value", "unit", "device", ...}. One
process; it exits non-zero when JAX shows no TPU, every number it prints
was just measured on the device the line names, and a phase that raises
fails the run. (The cell benchmark of ROADMAP A0 replaces this file; until
then it keeps the one kernel-level number the repo has history for.)

Metric: attention TFLOP/s for bf16 causal self-attention, seq=8192, hq=16,
hk=8 (GQA), d=128, fwd+bwd (FLOPs = 4*area*d*hq fwd + 2.5x bwd, the
reference's counting — docs/source/blog/cp_benchmark.md:35-58), slope-timed
over two scan lengths so fixed launch cost cancels.

vs_baseline: achieved MFU divided by 0.5 — the reference's headline claim is
"FFA has MFU comparable to FA3" (README.md:69) and FA3-class kernels sit
around 50% MFU on their native hardware, so 1.0 means FA3-class efficiency
on this chip. The peak is looked up by ``device_kind``
(benchmarking/perf_report.DEVICE_PEAKS); an unknown device is an error.
"""

import json
import os
import sys

HEADLINE_SEQ = 8192
HEADLINE_METRIC = f"ffa_causal_fwd_bwd_seq{HEADLINE_SEQ}_bf16"


def _emit(obj) -> int:
    print(json.dumps(obj))
    return 0


def run_headline() -> int:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from magiattention_tpu.benchmarking.bench import (
        do_bench_scan_slope,
        make_consume_all_grads_body,
        measuring_device,
    )
    from magiattention_tpu.benchmarking.perf_report import (
        HW_FWD_BWD_RATIO as hw_ratio,
        append_row,
    )
    from magiattention_tpu.kernels.ffa import ffa_attn

    dev = measuring_device("bench.py")
    peak = dev.pop("peak_tflops")
    backend = jax.default_backend()
    S, HQ, HK, D = HEADLINE_SEQ, 16, 8, 128
    dtype = jnp.bfloat16
    block_q = int(os.environ.get("MAGI_BENCH_BLOCK_Q", "512"))
    block_k = int(os.environ.get("MAGI_BENCH_BLOCK_K", "512"))

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((S, HQ, D)), dtype=dtype)
    k = jnp.asarray(rng.standard_normal((S, HK, D)), dtype=dtype)
    v = jnp.asarray(rng.standard_normal((S, HK, D)), dtype=dtype)
    w = jnp.asarray(rng.standard_normal((S, HQ, D)), dtype=dtype)
    qr = np.array([[0, S]], dtype=np.int32)
    kr = np.array([[0, S]], dtype=np.int32)
    tm = np.array([1], dtype=np.int32)  # causal

    def loss(q, k, v):
        o, _ = ffa_attn(q, k, v, qr, kr, tm, block_q=block_q, block_k=block_k)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))

    grad = jax.grad(loss, argnums=(0, 1, 2))
    body = make_consume_all_grads_body(lambda q: grad(q, k, v), dtype)
    area = S * (S + 1) // 2
    flops = 4 * area * D * HQ * 3.5  # fwd + 2.5x bwd
    # seq-8192 steps are ~4x the 4096 cost; (8, 32) keeps the slope pair
    # short while still cancelling the fixed launch cost
    dt_ms = do_bench_scan_slope(body, q, lengths=(8, 32), reps=2)
    tflops = round(flops / (dt_ms * 1e-3) / 1e12, 2)
    mfu = tflops / peak

    # the chip's own matmul rate, same timing method, same moment: kernel
    # quality is reported against both the published peak and this
    n = 4096
    a_mm = jnp.asarray(np.random.default_rng(1).standard_normal((n, n)), dtype)
    mm_ms = do_bench_scan_slope(
        lambda x: (x @ a_mm).astype(dtype), a_mm, reps=3
    )
    chip_matmul_tf = round(2 * n**3 / (mm_ms * 1e-3) / 1e12, 2)

    # dual MFU conventions (docs/performance.md): "mfu" uses the reference's
    # counting (bwd = 2.5x fwd) for comparability; "mfu_hw" counts the
    # matmul work the TPU actually executes (bwd = 3.5x fwd: separate dq +
    # dkv passes) — the honest hardware-utilization number
    result = {
        "metric": HEADLINE_METRIC,
        "value": tflops,
        "unit": "TFLOP/s",
        "vs_baseline": round(mfu / 0.5, 3),
        "device": dev,
        "backend": backend,
        "timing_mode": "scan_slope",
        "peak_tflops": peak,
        "mfu": round(mfu, 4),
        "mfu_hw": round(mfu * hw_ratio, 4),
        "block_q": block_q,
        "block_k": block_k,
        "chip_matmul_tflops": chip_matmul_tf,
        # like-for-like: the ceiling is a measured matmul rate, so the
        # numerator uses executed matmul work (bwd = 3.5x fwd)
        "pct_ceiling_hw": round(tflops * hw_ratio / chip_matmul_tf, 3),
    }

    # comm-plan quality (host-side planning, a count not a time): wire rows
    # per payload row for the BASELINE config-3 shape (causal cp=8), per
    # wire tier — the zero-redundant-communication pillar quantified
    from magiattention_tpu.common.enum import AttnMaskType
    from magiattention_tpu.common.ranges import AttnRanges
    from magiattention_tpu.meta import (
        make_attn_meta_from_dispatch_meta,
        make_dispatch_meta_from_qk_ranges,
    )

    SP, CPN = 1 << 15, 8
    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        AttnRanges.from_ranges([[0, SP]]),
        AttnRanges.from_ranges([[0, SP]]),
        [AttnMaskType.CAUSAL], SP, SP, SP // 256, CPN,
    )
    cmm, _ = make_attn_meta_from_dispatch_meta(bucket, mq)
    payload = sum(s.payload_rows() for s in cmm.kv_stages)
    for tier, key in (("a2a", "a2a"), ("ppermute", "pp"),
                      ("ragged", "ragged")):
        result[f"wire_ratio_{key}"] = round(
            sum(s.wire_rows(tier) for s in cmm.kv_stages) / payload, 3
        )

    # secondary: Magi-1 spatiotemporal video block mask (BASELINE config 4)
    # — FLOPs counted by true mask area, the sparse-mask headline
    from magiattention_tpu.utils.sparse_utils import (
        block_mask_to_ranges, make_video_block_mask,
    )

    SV, frames, block = 16384, 8, 512
    bm = make_video_block_mask(frames, SV // frames // block, 2)
    qr_v, kr_v, tm_v = block_mask_to_ranges(bm, block, block)
    qr_vn = np.array([[r.start, r.end] for r in qr_v], np.int32)
    kr_vn = np.array([[r.start, r.end] for r in kr_v], np.int32)
    tm_vn = np.array([t.to_int_type() for t in tm_v], np.int32)
    qv = jnp.asarray(rng.standard_normal((SV, HQ, D)), dtype)
    kv_ = jnp.asarray(rng.standard_normal((SV, HK, D)), dtype)
    vv = jnp.asarray(rng.standard_normal((SV, HK, D)), dtype)

    def vbody(qv):
        o, _ = ffa_attn(qv, kv_, vv, qr_vn, kr_vn, tm_vn,
                        block_q=block_q, block_k=block_k)
        return o.astype(dtype)

    v_ms = do_bench_scan_slope(vbody, qv, reps=2)
    v_area = int(bm.sum()) * block * block
    v_tflops = 4 * v_area * D * HQ / (v_ms * 1e-3) / 1e12
    result["video_tflops_fwd"] = round(v_tflops, 2)
    result["video_mfu_fwd"] = round(v_tflops / peak, 4)

    append_row("bench_headline", {
        "metric": result["metric"], "backend": backend,
        "device_kind": dev["kind"],
        "block_q": block_q, "block_k": block_k, "tflops": tflops,
        "mfu": result["mfu"], "mfu_hw": result["mfu_hw"],
        "timing_mode": "scan_slope",
    })
    append_row("bench_video", {
        "backend": backend, "device_kind": dev["kind"],
        "tflops_fwd": result["video_tflops_fwd"],
        "mfu_fwd": result["video_mfu_fwd"],
    })
    return _emit(result)


# ---------------------------------------------------------------------------
# --sparse-suite: padded-vs-band accounting + TF/s per mask family
# ---------------------------------------------------------------------------


def _sparse_families(seq: int) -> dict:
    """name -> (qr, kr, d_lo, d_hi): the mask families the sparse suite
    reports on — dense anchors plus the fragmented shapes the extent
    clamp / mixed dispatch rescue (same generators as the kernel-audit
    fragmented corpus)."""
    import numpy as np

    from magiattention_tpu.analysis.kernel_check import _fragmented_masks
    from magiattention_tpu.kernels.mask_utils import types_to_bands

    qr = np.asarray([[0, seq]], np.int32)

    def band(tm):
        lo, hi = types_to_bands(qr, qr, np.asarray([tm], np.int32))
        return qr, qr.copy(), lo, hi

    fams = {
        "full": band(0),
        "causal": band(1),
        "sliding_window": (
            qr, qr.copy(),
            np.asarray([-256], np.int32), np.asarray([0], np.int32),
        ),
    }
    fams.update(_fragmented_masks(seq))
    h = seq // 2
    q2 = np.asarray([[0, h], [h, seq], [h, seq]], np.int32)
    k2 = np.asarray([[0, h], [0, h // 2], [h, seq]], np.int32)
    lo2, hi2 = types_to_bands(q2, k2, np.asarray([1, 0, 1], np.int32))
    fams["shared_prefix_causal"] = (q2, k2, lo2, hi2)
    return fams


def run_sparse_suite() -> int:
    """Per-mask-family plan accounting + fwd TF/s on the TPU.

    Emits one JSON line: for each family the padded/band ratio the
    un-clamped grid would execute, the post-clamp executed/band ratio from
    the plan's live extents, and — when a TPU is attached — measured fwd
    TFLOP/s with FLOPs counted by true band area. Without a TPU the line
    carries the counts only (a count needs no device; a time does). Rows
    land in the committed perf history (benchmarks/history/bench_sparse)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from magiattention_tpu.kernels.ffa import default_blocks, ffa_attn
    from magiattention_tpu.kernels.ffa_plan import (
        get_ffa_plan,
        plan_extent_stats,
    )
    from magiattention_tpu import telemetry
    from magiattention_tpu.kernels.tile_policy import slice_cover_ratios

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    device_kind = jax.devices()[0].device_kind
    seq = 16384 if on_tpu else 2048
    HQ, HK, D = (16, 8, 128) if on_tpu else (4, 2, 128)
    dtype = jnp.bfloat16
    bq, bk = default_blocks(seq, seq)

    rows = []
    for name, (qr, kr, lo, hi) in _sparse_families(seq).items():
        plan = get_ffa_plan(qr, kr, lo, hi, seq, seq, bq, bk)
        stats = plan_extent_stats(plan)
        band = telemetry.band_area(qr, kr, lo, hi)
        ratios = slice_cover_ratios(qr, kr, lo, hi, bq, bk)
        row = {
            "family": name,
            "seq": seq,
            "block_q": bq,
            "block_k": bk,
            "band_elems": int(band),
            "padded_elems": stats["padded_elems"],
            "executed_elems": stats["executed_elems"],
            "padded_band_ratio": round(stats["padded_elems"] / band, 3)
            if band else None,
            "executed_band_ratio": round(stats["executed_elems"] / band, 3)
            if band else None,
            "worst_slice_cover": round(float(ratios.max()), 3)
            if len(ratios) else None,
        }
        if on_tpu:
            from magiattention_tpu.benchmarking.bench import (
                do_bench_scan_slope,
            )

            rng = np.random.default_rng(0)
            q = jnp.asarray(rng.standard_normal((seq, HQ, D)), dtype)
            k = jnp.asarray(rng.standard_normal((seq, HK, D)), dtype)
            v = jnp.asarray(rng.standard_normal((seq, HK, D)), dtype)

            def body(q):
                o, _ = ffa_attn(q, k, v, qr, kr, d_lo=lo, d_hi=hi)
                return o.astype(dtype)

            ms = do_bench_scan_slope(body, q, reps=2)
            row["tflops_fwd"] = round(
                4 * band * D * HQ / (ms * 1e-3) / 1e12, 2
            )
        rows.append(row)

    from magiattention_tpu.benchmarking.perf_report import append_row

    for row in rows:
        append_row("bench_sparse", {
            "backend": backend, "device_kind": device_kind, **row})
    return _emit(
        {
            "metric": "ffa_sparse_suite",
            "backend": backend,
            "device_kind": device_kind,
            "families": rows,
        }
    )


# ---------------------------------------------------------------------------
# --bwd-suite: split-vs-fused backward A/B (MAGI_ATTENTION_BACKEND_FFA_BWD)
# ---------------------------------------------------------------------------


def _bwd_families(seq: int) -> dict:
    """name -> (qr, kr, tmap): the fwd+bwd A/B mask families. varlen packs
    three causal documents of uneven length — the fragmented plan whose
    partial q-tiles exercise the QVF/QVL revisit flags hardest."""
    import numpy as np

    one = np.asarray([[0, seq]], np.int32)
    a, b = seq // 4, 5 * seq // 8
    vr = np.asarray([[0, a], [a, b], [b, seq]], np.int32)
    return {
        "causal": (one, one.copy(), np.asarray([1], np.int32)),
        "full": (one, one.copy(), np.asarray([0], np.int32)),
        "varlen": (vr, vr.copy(), np.asarray([1, 1, 1], np.int32)),
    }


def run_bwd_suite() -> int:
    """Slope-timed split-vs-fused backward A/B per mask family and seqlen.

    Each (family, seq) runs the SAME fwd+bwd grad body under
    MAGI_ATTENTION_BACKEND_FFA_BWD=split (dq + dkv passes) and =fused (one
    pass), with the credibility floor computed from each mode's OWN
    executed matmul work (fwd 2 tile matmuls + bwd 7 split / 5 fused —
    a fused slope beating the 5-matmul physics is an under-cancelled
    pair, not a win). Rows append to benchmarks/history/bench_bwd.csv.
    A timing suite: it needs the TPU and stops without one."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from magiattention_tpu import telemetry
    from magiattention_tpu.benchmarking.bench import (
        do_bench_scan_slope,
        make_consume_all_grads_body,
        measuring_device,
    )
    from magiattention_tpu.benchmarking.perf_report import (
        append_row,
        credible_floor_ms,
    )
    from magiattention_tpu.kernels.ffa import (
        FFAParams,
        _should_interpret,
        default_blocks,
        ffa_attn,
        resolved_bwd_mode,
    )
    from magiattention_tpu.kernels.ffa_plan import _cached_plan, get_ffa_plan
    from magiattention_tpu.kernels.mask_utils import types_to_bands

    dev = measuring_device("bench.py --bwd-suite")
    backend = jax.default_backend()
    seqs = (4096, 8192, 16384)
    HQ, HK, D = 16, 8, 128
    dtype = jnp.bfloat16

    # per-tile-matmul flops = 2 * band * d * hq (each of fwd's 2 matmuls
    # contributes 4*band*d*hq / 2); bwd executes 7 (split) or 5 (fused)
    BWD_MATMULS = {"split": 7, "fused": 5}

    rows = []
    for seq in seqs:
        bq, bk = default_blocks(seq, seq)
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((seq, HQ, D)), dtype)
        k = jnp.asarray(rng.standard_normal((seq, HK, D)), dtype)
        v = jnp.asarray(rng.standard_normal((seq, HK, D)), dtype)
        w = jnp.asarray(rng.standard_normal((seq, HQ, D)), jnp.float32)
        for name, (qr, kr, tm) in _bwd_families(seq).items():
            lo, hi = types_to_bands(qr, kr, tm)
            band = telemetry.band_area(qr, kr, lo, hi)
            plan = get_ffa_plan(qr, kr, lo, hi, seq, seq, bq, bk)
            prm = FFAParams(
                num_work=plan.num_work, num_work_t=plan.num_work_t,
                num_q_tiles=plan.num_q_tiles,
                num_k_tiles=plan.num_k_tiles, block_q=bq, block_k=bk,
                softmax_scale=float(D) ** -0.5, softcap=0.0,
                group=HQ // HK, interpret=_should_interpret(),
                min_revisit_distance=plan.min_revisit_distance,
            )
            auto_mode = resolved_bwd_mode(
                prm, plan.num_q_tiles * bq, D, D,
                jnp.dtype(dtype).itemsize,
            )

            def make_grad_body():
                def loss(q, k, v):
                    o, _ = ffa_attn(q, k, v, qr, kr, tm,
                                    block_q=bq, block_k=bk)
                    return jnp.sum(o.astype(jnp.float32) * w)

                grad = jax.grad(loss, argnums=(0, 1, 2))
                return make_consume_all_grads_body(
                    lambda q: grad(q, k, v), dtype
                )

            pair = {}
            for mode in ("split", "fused"):
                saved = os.environ.get("MAGI_ATTENTION_BACKEND_FFA_BWD")
                os.environ["MAGI_ATTENTION_BACKEND_FFA_BWD"] = mode
                _cached_plan.cache_clear()
                row = {
                    "family": name, "seq": seq, "mode": mode,
                    "auto_mode": auto_mode, "backend": backend,
                    "device_kind": dev["kind"],
                    "block_q": bq, "block_k": bk,
                    "band_elems": int(band),
                }
                # executed matmul flops for THIS mode's floor
                exec_flops = (
                    2 * band * D * HQ * (2 + BWD_MATMULS[mode])
                )
                try:
                    floor = credible_floor_ms(exec_flops)
                    ms = do_bench_scan_slope(
                        make_grad_body(), q, lengths=(8, 32),
                        reps=2, min_credible_ms=floor,
                    )
                    row["floor_ms"] = round(floor, 3)
                    row["timing_mode"] = "scan_slope"
                    # reference-convention fwd+bwd rate (fwd + 2.5x bwd)
                    row["ms"] = round(ms, 3)
                    row["tflops_ref"] = round(
                        4 * band * D * HQ * 3.5 / (ms * 1e-3) / 1e12, 3
                    )
                    pair[mode] = ms
                finally:
                    if saved is None:
                        os.environ.pop(
                            "MAGI_ATTENTION_BACKEND_FFA_BWD", None
                        )
                    else:
                        os.environ["MAGI_ATTENTION_BACKEND_FFA_BWD"] = saved
                    _cached_plan.cache_clear()
                rows.append(row)
            rows[-1]["fused_speedup"] = round(
                pair["split"] / pair["fused"], 3
            )

    for row in rows:
        append_row("bench_bwd", row)
    return _emit({"metric": "ffa_bwd_suite", "backend": backend,
                  "device": dev, "rows": rows})


# ---------------------------------------------------------------------------
# --nsa-suite: gathered vs gather-free NSA slc branch A/B
# ---------------------------------------------------------------------------


def _nsa_families(seq: int) -> dict:
    """name -> cu_seqlens: the NSA A/B layouts. single_doc is the long-
    context anchor; block_sparse_pretrain packs uneven causal documents
    (the block-sparse pretraining mask family — segment boundaries force
    per-segment block layouts and segment-masked top-k); many_docs packs
    eight short documents (worst-case selection-table churn). All
    boundaries stay on the d_stride grid so the gather-free kernel is
    feasible for every family."""
    a, b = seq // 4, 5 * seq // 8
    return {
        "single_doc": [0, seq],
        "block_sparse_pretrain": [0, a, b, seq],
        "many_docs": [seq * i // 8 for i in range(9)],
    }


def run_nsa_suite() -> int:
    """Gathered vs gather-free NSA selected-block attention A/B.

    Each (family, seq) runs the SAME nsa_attn forward under
    MAGI_ATTENTION_BACKEND_NSA_SLC=gathered_dense and =block_sparse_pallas
    (the pin bypasses the registry memo, so the flip takes effect per
    call). Rows carry the modeled HBM story from modeled_slc_bytes —
    streamed_bytes (what the kernel moves) vs gathered_bytes (stream +
    materialized top-k copy) — alongside measured wall time, with the
    credibility floor computed from the slc branch's own executed matmul
    flops (4 * S * top_k * l_slc * D * HQ: a slope beating that physics
    is an under-cancelled pair, not a win). Rows append to
    benchmarks/history/bench_nsa.csv. A timing suite: it needs the TPU
    and stops without one."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from magiattention_tpu.benchmarking.bench import (
        do_bench_scan_slope,
        measuring_device,
    )
    from magiattention_tpu.benchmarking.perf_report import (
        append_row,
        credible_floor_ms,
    )
    from magiattention_tpu.kernels.block_sparse import modeled_slc_bytes
    from magiattention_tpu.parallel.nsa import init_nsa_params, nsa_attn

    dev = measuring_device("bench.py --nsa-suite")
    backend = jax.default_backend()
    seqs = (8192, 32768)
    HQ, HK, D = 16, 8, 128
    L_CMP, L_SLC, D_STRIDE, BQ = 32, 64, 32, 16
    TOP_K = 8
    WINDOW = (128, 0)
    dtype = jnp.bfloat16

    PINS = (
        ("gathered_dense", "gathered_dense"),
        ("gather_free", "block_sparse_pallas"),
    )

    rows = []
    for seq in seqs:
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((seq, HQ, D)), dtype)
        k = jnp.asarray(rng.standard_normal((seq, HK, D)), dtype)
        v = jnp.asarray(rng.standard_normal((seq, HK, D)), dtype)
        params = init_nsa_params(jax.random.PRNGKey(0), D, L_CMP)
        n_qb = seq // BQ
        slc_bytes = modeled_slc_bytes(
            hk=HK, n_qb=n_qb, top_k=TOP_K, block_len=L_SLC,
            d_stride=D_STRIDE, block_size_q=BQ, g=HQ // HK, d=D, dv=D,
            itemsize=jnp.dtype(dtype).itemsize,
        )
        for name, cu in _nsa_families(seq).items():
            pair = {}
            for mode, pin in PINS:
                saved = os.environ.get("MAGI_ATTENTION_BACKEND_NSA_SLC")
                os.environ["MAGI_ATTENTION_BACKEND_NSA_SLC"] = pin
                row = {
                    "family": name, "seq": seq, "mode": mode,
                    "backend": backend, "device_kind": dev["kind"],
                    "top_k": TOP_K, "l_slc": L_SLC,
                    "d_stride": D_STRIDE,
                    "slc_streamed_bytes": slc_bytes["streamed_bytes"],
                    "slc_gathered_bytes": slc_bytes["gathered_bytes"],
                }
                # slc-branch executed matmul flops: the floor for THIS A/B
                exec_flops = 4 * seq * TOP_K * L_SLC * D * HQ
                try:
                    def body(q):
                        return nsa_attn(
                            q, k, v, params, cu, l_cmp=L_CMP, l_slc=L_SLC,
                            d_stride=D_STRIDE, block_size_q=BQ,
                            slc_top_k=TOP_K, window=WINDOW,
                        ).astype(dtype)

                    floor = credible_floor_ms(exec_flops)
                    ms = do_bench_scan_slope(
                        body, q, lengths=(8, 32), reps=2,
                        min_credible_ms=floor,
                    )
                    row["floor_ms"] = round(floor, 3)
                    row["timing_mode"] = "scan_slope"
                    row["ms"] = round(ms, 3)
                    pair[mode] = ms
                finally:
                    if saved is None:
                        os.environ.pop(
                            "MAGI_ATTENTION_BACKEND_NSA_SLC", None
                        )
                    else:
                        os.environ["MAGI_ATTENTION_BACKEND_NSA_SLC"] = saved
                rows.append(row)
            rows[-1]["gather_free_speedup"] = round(
                pair["gathered_dense"] / pair["gather_free"], 3
            )

    for row in rows:
        append_row("bench_nsa", row)
    return _emit({"metric": "nsa_suite", "backend": backend,
                  "device": dev, "rows": rows})


# ---------------------------------------------------------------------------
# --dcn-suite: flat vs two-level (DCN x ICI) comm-plan A/B (CPU-safe)
# ---------------------------------------------------------------------------


def run_dcn_suite() -> int:
    """Host-side A/B of flat vs two-level comm plans per mask and mesh.

    Entirely plan-level — row counts and modeled makespans, no device
    time — so the suite runs identically on any host: for each
    (mask, n_outer x n_inner)
    it solves both ways and reports the flat cross-node row volume, the
    two-level post-dedup DCN rows (must never exceed the flat prediction),
    the dedup ratio, and the modeled makespans under the flat
    (pipeline_makespan) vs two-tier (two_level_makespan) cost models.
    Rows append to benchmarks/history/bench_dcn.csv."""
    import jax

    from magiattention_tpu.common.enum import AttnMaskType
    from magiattention_tpu.common.ranges import AttnRanges
    from magiattention_tpu.config import DistAttnConfig, OverlapConfig
    from magiattention_tpu.meta import (
        make_attn_meta_from_dispatch_meta,
        make_dispatch_meta_from_qk_ranges,
    )
    from magiattention_tpu.meta.solver.overlap_solver import (
        OverlapStageCost,
        pipeline_makespan,
        two_level_makespan,
    )

    seq, chunk = 4096, 256
    M = AttnMaskType
    h = seq // 2
    families = {
        "causal": ([[0, seq]], [[0, seq]], [M.CAUSAL]),
        "shared_prefix": (
            [[0, seq], [512, seq]], [[0, 512], [512, seq]],
            [M.FULL, M.CAUSAL],
        ),
        "varlen_block_causal": (
            [[0, h], [h, seq]], [[0, h], [h, seq]], [M.CAUSAL, M.CAUSAL],
        ),
    }
    # one kv row of k + v at bf16, serving-ish head geometry
    hk, d = 8, 128
    row_bytes = 2 * hk * d * 2
    dcn_per_row = 8.0

    rows = []
    for name, (qr_l, kr_l, tm) in families.items():
        qr = AttnRanges.from_ranges(qr_l)
        kr = AttnRanges.from_ranges(kr_l)
        for n_outer, n_inner in ((2, 4), (4, 2)):
            cp = n_outer * n_inner
            cfg = DistAttnConfig(overlap_config=OverlapConfig(degree=2))
            mq, mkv, bucket = make_dispatch_meta_from_qk_ranges(
                qr, kr, list(tm), seq, seq, chunk, cp, cfg.dispatch_config
            )
            cmm, calc = make_attn_meta_from_dispatch_meta(
                bucket, mq, cfg, dispatch_meta_kv=mkv,
                mesh_shape=(n_outer, n_inner),
            )
            flat_dcn = dcn = 0
            costs = []
            for st, s in enumerate(cmm.kv_stages):
                flat_dcn += sum(
                    s.transfer_table[dst][src].total_seqlen
                    for dst in range(cp)
                    for src in range(cp)
                    if dst // n_inner != src // n_inner
                )
                dcn += s.hier_plan.dcn_rows()
                per_rank_recv = [int(x) for x in s.recv_len]
                per_rank_area = [
                    int(a.area())
                    for a in calc.remote_args_per_stage[st]
                ]
                costs.append(OverlapStageCost(
                    comm_cost=float(max(per_rank_recv, default=0)),
                    calc_cost=float(
                        max(per_rank_area, default=0) / chunk
                    ),
                    dcn_cost=(
                        s.hier_plan.dcn_rows() / cp * dcn_per_row
                    ),
                ))
            host_calc = max(
                (int(a.area()) for a in calc.host_args), default=0
            ) / chunk
            row = {
                "mask": name,
                "mesh": f"{n_outer}x{n_inner}",
                "seq": seq,
                "stages": len(cmm.kv_stages),
                "flat_dcn_rows": int(flat_dcn),
                "dcn_rows": int(dcn),
                "dcn_bytes": int(dcn) * row_bytes,
                "flat_dcn_bytes": int(flat_dcn) * row_bytes,
                "dcn_dedup_ratio": round(flat_dcn / dcn, 3) if dcn else 1.0,
                # acceptance: post-dedup DCN volume never exceeds the
                # flat plan's cross-node volume
                "dcn_ok": bool(dcn <= flat_dcn),
                "flat_makespan": round(pipeline_makespan(costs, host_calc), 1),
                "two_level_makespan": round(
                    two_level_makespan(costs, host_calc), 1
                ),
            }
            rows.append(row)

    from magiattention_tpu.benchmarking.perf_report import append_row

    for row in rows:
        append_row("bench_dcn", row)
    return _emit({
        "metric": "dcn_suite",
        "backend": jax.default_backend(),
        "ok": all(r["dcn_ok"] for r in rows),
        "rows": rows,
    })


if __name__ == "__main__":
    if "--sparse-suite" in sys.argv:
        sys.exit(run_sparse_suite())
    if "--bwd-suite" in sys.argv:
        sys.exit(run_bwd_suite())
    if "--nsa-suite" in sys.argv:
        sys.exit(run_nsa_suite())
    if "--dcn-suite" in sys.argv:
        sys.exit(run_dcn_suite())
    sys.exit(run_headline())
