"""Slope-timed (launch-overhead-free) chip ceiling + FFA kernel rates.

A fixed cost per executable launch once made every length-6-scan
measurement (a 10 TF/s headline, a "34 TF/s chip ceiling")
overhead-dominated, not kernel-dominated. All probes here use
:func:`do_bench_scan_slope` (two trip counts, slope cancels the fixed cost)
and append to ``benchmarks/history/true_rate.csv``.

Measures: bf16 matmul ceiling (the honest MFU denominator), FFA fwd and
fwd+bwd at the bench shape across tilings, splash_attention on the SAME
shapes — the GQA headline shape (hq16/hk8, via the MQA kernel vmapped
over kv heads) AND equal heads — and the bundled ``flash_attention`` A/B
on the identical dense-causal problem. Both splash ratios are the TPU
analogue of the reference's "FFA comparable to FA3" claim
(/root/reference/README.md:69); target FFA >= 0.9x splash.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import jax.numpy as jnp
import numpy as np

from magiattention_tpu.benchmarking.bench import (  # noqa: E402
    do_bench_scan_slope,
    make_consume_all_grads_body,
    measuring_device,
)
from magiattention_tpu.benchmarking.perf_report import (  # noqa: E402
    HW_FWD_BWD_RATIO,
    append_row,
)

PEAK = None  # bf16 peak of the attached device, looked up in main()
LENGTHS = (24, 96)


def record(probe, ms, flops, *, lengths, extra=None):
    """Append one slope-timed row. ``lengths`` is REQUIRED and must be the
    scan trip counts the measurement actually used (ffa probes use
    ATT_LENGTHS, mm probes LENGTHS) — fit_tile_overhead.py keys its shape
    guard on len_short, so a mismatched stamp silently disqualifies the
    row; requiring it keeps future call sites from inheriting a wrong
    default. ``extra`` merges additional columns (e.g. the splash
    ``BlockSizes`` config a row was measured with)."""
    tf = flops / (ms * 1e-3) / 1e12
    print(f"{probe}: {ms:.3f} ms {tf:.1f} TF/s ({tf/PEAK*100:.1f}% of nominal)",
          flush=True)
    append_row("true_rate", {
        "probe": probe, "ms": round(ms, 4), "tflops": round(tf, 2),
        "pct_of_nominal": round(tf / PEAK * 100, 1),
        "len_short": lengths[0], "len_long": lengths[1],
        **(extra or {}),
    })
    return tf


def _splash_candidates(s):
    """BlockSizes sweep for the splash baseline. FFA runs its tuned
    512/512 tiling, so timing splash at library defaults (128 everywhere)
    under-states the bar (r5 verdict weak #2); each candidate sets fwd AND
    bwd blocks so the fwdbwd probe of the winner is covered too. Returns
    [(label, BlockSizes)] — 'default' first so a window that dies mid-sweep
    still produced the historical baseline config."""
    from jax.experimental.pallas.ops.tpu import splash_attention as _sp

    BS = _sp.splash_attention_kernel.BlockSizes
    cands = [("default", BS.get_default())]
    for bq, bkv in ((256, 512), (512, 512), (512, 1024)):
        if bq > s or bkv > s:
            continue
        cands.append((
            f"bq{bq}_bkv{bkv}",
            BS(block_q=bq, block_kv=bkv, block_kv_compute=bkv,
               block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv,
               block_q_dq=bq, block_kv_dq=bkv),
        ))
    return cands


def main():
    global PEAK
    dev = measuring_device("tpu_true_rate")
    PEAK = dev["peak_tflops"]
    print("device:", dev, flush=True)
    rng = np.random.default_rng(0)

    # Ordered so a run cut short still yields the decisive numbers: the
    # minimal set (ceiling matmul -> headline-tiling FFA -> bundled A/B)
    # runs before any sweep extras, and every probe appends to the CSV the
    # moment it completes.

    # -- 1. matmul ceiling (slope) ---------------------------------------
    # mm8192 (usually the higher rate) runs in the sweep extras; each mm
    # probe re-appends the running-max ceiling row so the CSV's last
    # 'ceiling' entry is the window's best measurement.
    ceiling = 0.0

    def mm_probe(n):
        nonlocal ceiling
        a = jnp.asarray(rng.standard_normal((n, n)), jnp.bfloat16)
        try:
            ms = do_bench_scan_slope(
                lambda x: (x @ a).astype(jnp.bfloat16), a,
                lengths=LENGTHS, verbose=True,
            )
            ceiling = max(
                ceiling, record(f"mm{n}", ms, 2 * n**3, lengths=LENGTHS)
            )
        except Exception as e:
            print(f"mm{n}: FAIL {type(e).__name__}: {str(e)[:160]}",
                  flush=True)
        if ceiling:
            append_row("true_rate", {
                "probe": "ceiling", "ms": 0.0, "tflops": round(ceiling, 2),
                "pct_of_nominal": round(ceiling / PEAK * 100, 1),
                "len_short": LENGTHS[0], "len_long": LENGTHS[1],
            })

    mm_probe(4096)

    # -- 2. FFA on the bench shape (slope), headline tiling first --------
    from magiattention_tpu.kernels.ffa import ffa_attn

    S, HQ, HK, D = 8192, 16, 8, 128
    # per-step ~4x the 4096 cost; slope still cancels
    ATT_LENGTHS = (8, 32)
    area = S * (S + 1) // 2
    fwd_flops = 4 * area * D * HQ
    qs = jnp.asarray(rng.standard_normal((S, HQ, D)), jnp.bfloat16)
    ks = jnp.asarray(rng.standard_normal((S, HK, D)), jnp.bfloat16)
    vs = jnp.asarray(rng.standard_normal((S, HK, D)), jnp.bfloat16)
    ws = jnp.asarray(rng.standard_normal((S, HQ, D)), jnp.bfloat16)
    qr = np.array([[0, S]], np.int32)
    kr = np.array([[0, S]], np.int32)
    tm = np.array([1], np.int32)

    def run_ffa_tiling(bq, bk):
        """fwd + fwd/bwd slope probes of one tiling (ONE body definition
        for headline and sweep so their numbers can't desynchronize)."""

        def ffa_fwd(q):
            return ffa_attn(
                q, ks, vs, qr, kr, tm, block_q=bq, block_k=bk
            )[0].astype(jnp.bfloat16)

        def ffa_loss(q, k, v):
            o, _ = ffa_attn(q, k, v, qr, kr, tm, block_q=bq, block_k=bk)
            return jnp.sum(o.astype(jnp.float32) * ws.astype(jnp.float32))

        try:
            ms = do_bench_scan_slope(ffa_fwd, qs, lengths=ATT_LENGTHS, verbose=True)
            record(f"ffa_fwd_bq{bq}_bk{bk}", ms, fwd_flops, lengths=ATT_LENGTHS)
            g = jax.grad(ffa_loss, argnums=(0, 1, 2))
            step = make_consume_all_grads_body(
                lambda q: g(q, ks, vs), jnp.bfloat16
            )
            msb = do_bench_scan_slope(step, qs, lengths=ATT_LENGTHS, verbose=True)
            record(f"ffa_fwdbwd_bq{bq}_bk{bk}", msb, fwd_flops * 3.5,
                   lengths=ATT_LENGTHS)
            record(f"ffa_fwdbwd_hw_bq{bq}_bk{bk}", msb,
                   fwd_flops * 3.5 * HW_FWD_BWD_RATIO, lengths=ATT_LENGTHS)
        except Exception as e:
            print(f"ffa bq{bq} bk{bk}: FAIL {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)

    run_ffa_tiling(512, 512)

    # -- 2b. splash on the SAME GQA shape (hq16/hk8) -----------------------
    # The kernel-quality bar must compare identical workloads (r4 verdict
    # Weak #2): splash serves GQA natively through its MQA kernel vmapped
    # over kv heads — q (hk, g, S, D), kv (hk, S, D) — so kv HBM traffic
    # matches FFA's GQA layout. Ratio of record: ffa_fwd_bq512_bk512 /
    # splash_gqa_fwd (and the fwdbwd pair).
    try:
        from jax.experimental.pallas.ops.tpu import splash_attention as _sp

        GRP = HQ // HK
        gqa_mask = _sp.MultiHeadMask(
            [_sp.CausalMask((S, S)) for _ in range(GRP)]
        )
        qg = jnp.asarray(
            rng.standard_normal((HK, GRP, S, D)), jnp.bfloat16
        )
        kg = jnp.asarray(rng.standard_normal((HK, S, D)), jnp.bfloat16)
        vg = jnp.asarray(rng.standard_normal((HK, S, D)), jnp.bfloat16)
        wg = jnp.asarray(
            rng.standard_normal((HK, GRP, S, D)), jnp.bfloat16
        )

        # BlockSizes sweep — the ratio of record must bar FFA against the
        # best splash config, not the library default
        best_label, best_kernel, best_ms = None, None, float("inf")
        for label, bs in _splash_candidates(S):
            try:
                kern = jax.vmap(
                    _sp.splash_attention_kernel.make_splash_mqa_single_device(
                        gqa_mask, block_sizes=bs
                    )
                )

                def splash_gqa_fwd(q, kern=kern):
                    return kern(q, kg, vg).astype(jnp.bfloat16)

                ms = do_bench_scan_slope(splash_gqa_fwd, qg,
                                         lengths=ATT_LENGTHS, verbose=True)
                record(f"splash_gqa_fwd_{label}", ms, fwd_flops,
                       lengths=ATT_LENGTHS,
                       extra={"splash_config": label})
                if ms < best_ms:
                    best_label, best_kernel, best_ms = label, kern, ms
            except Exception as e:
                print(f"splash gqa {label}: FAIL {type(e).__name__}: "
                      f"{str(e)[:200]}", flush=True)
        if best_kernel is not None:
            # canonical probe names carry the winner (ratio tooling keys
            # on them); splash_config records WHICH config won
            record("splash_gqa_fwd", best_ms, fwd_flops,
                   lengths=ATT_LENGTHS,
                   extra={"splash_config": best_label})

            def splash_gqa_loss(q, k, v):
                o = best_kernel(q, k, v)
                return jnp.sum(
                    o.astype(jnp.float32) * wg.astype(jnp.float32)
                )

            g = jax.grad(splash_gqa_loss, argnums=(0, 1, 2))
            step = make_consume_all_grads_body(
                lambda q: g(q, kg, vg), jnp.bfloat16
            )
            msb = do_bench_scan_slope(step, qg, lengths=ATT_LENGTHS,
                                      verbose=True)
            record("splash_gqa_fwdbwd", msb, fwd_flops * 3.5,
                   lengths=ATT_LENGTHS,
                   extra={"splash_config": best_label})
    except Exception as e:
        print(f"splash gqa: FAIL {type(e).__name__}: {str(e)[:200]}",
              flush=True)

    # -- 3. A/B vs bundled flash_attention (slope, equal heads) ----------
    H = HQ
    ab_flops = 4 * area * D * H
    # equal-heads FFA for a like-for-like vs bundled (GQA off)
    ksf = jnp.asarray(rng.standard_normal((S, H, D)), jnp.bfloat16)
    vsf = jnp.asarray(rng.standard_normal((S, H, D)), jnp.bfloat16)

    def ffa_fwd_eq(q):
        return ffa_attn(
            q, ksf, vsf, qr, kr, tm, block_q=512, block_k=512
        )[0].astype(jnp.bfloat16)

    def ffa_loss_eq(q, k, v):
        o, _ = ffa_attn(q, k, v, qr, kr, tm, block_q=512, block_k=512)
        return jnp.sum(o.astype(jnp.float32) * ws.astype(jnp.float32))

    try:
        ms = do_bench_scan_slope(ffa_fwd_eq, qs, lengths=ATT_LENGTHS, verbose=True)
        record("ffa_fwd_eqheads_bq512_bk512", ms, ab_flops, lengths=ATT_LENGTHS)
        # fwd+bwd too, so the splash_fwdbwd ratio is same-shape as well
        g = jax.grad(ffa_loss_eq, argnums=(0, 1, 2))
        step = make_consume_all_grads_body(
            lambda q: g(q, ksf, vsf), jnp.bfloat16
        )
        msb = do_bench_scan_slope(step, qs, lengths=ATT_LENGTHS, verbose=True)
        record("ffa_fwdbwd_eqheads_bq512_bk512", msb, ab_flops * 3.5,
               lengths=ATT_LENGTHS)
    except Exception as e:
        print(f"ffa eqheads: FAIL {type(e).__name__}: {str(e)[:200]}",
              flush=True)

    # bundled kernel (guarded: its absence must not cost the probes above)
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention,
        )
    except Exception as e:
        print(f"bundled flash unavailable: {e}", flush=True)
        flash_attention = None
    if flash_attention is not None:
        qb = jnp.asarray(rng.standard_normal((1, H, S, D)), jnp.bfloat16)
        kb = jnp.asarray(rng.standard_normal((1, H, S, D)), jnp.bfloat16)
        vb = jnp.asarray(rng.standard_normal((1, H, S, D)), jnp.bfloat16)
        wb = jnp.asarray(rng.standard_normal((1, H, S, D)), jnp.bfloat16)

        def bundled_fwd(q):
            return flash_attention(q, kb, vb, causal=True).astype(jnp.bfloat16)

        def bundled_loss(q, k, v):
            o = flash_attention(q, k, v, causal=True)
            return jnp.sum(o.astype(jnp.float32) * wb.astype(jnp.float32))

        try:
            ms = do_bench_scan_slope(bundled_fwd, qb, lengths=ATT_LENGTHS,
                                     verbose=True)
            record("bundled_fwd", ms, ab_flops, lengths=ATT_LENGTHS)
            g = jax.grad(bundled_loss, argnums=(0, 1, 2))
            step = make_consume_all_grads_body(
                lambda q: g(q, kb, vb), jnp.bfloat16
            )
            msb = do_bench_scan_slope(step, qb, lengths=ATT_LENGTHS, verbose=True)
            record("bundled_fwdbwd", msb, ab_flops * 3.5, lengths=ATT_LENGTHS)
        except Exception as e:
            print(f"bundled: FAIL {type(e).__name__}: {str(e)[:200]}",
                  flush=True)

    # -- 3b. splash_attention bar (the production TPU kernel, equal heads)
    try:
        from jax.experimental.pallas.ops.tpu import splash_attention as _sp

        sp_mask = _sp.MultiHeadMask(
            [_sp.CausalMask((S, S)) for _ in range(H)]
        )
        qsp = jnp.asarray(rng.standard_normal((H, S, D)), jnp.bfloat16)
        ksp = jnp.asarray(rng.standard_normal((H, S, D)), jnp.bfloat16)
        vsp = jnp.asarray(rng.standard_normal((H, S, D)), jnp.bfloat16)
        wsp = jnp.asarray(rng.standard_normal((H, S, D)), jnp.bfloat16)

        best_label, best_kernel, best_ms = None, None, float("inf")
        for label, bs in _splash_candidates(S):
            try:
                kern = (
                    _sp.splash_attention_kernel.make_splash_mha_single_device(
                        sp_mask, block_sizes=bs
                    )
                )

                def splash_fwd(q, kern=kern):
                    return kern(q, ksp, vsp).astype(jnp.bfloat16)

                ms = do_bench_scan_slope(splash_fwd, qsp,
                                         lengths=ATT_LENGTHS, verbose=True)
                record(f"splash_fwd_{label}", ms, ab_flops,
                       lengths=ATT_LENGTHS,
                       extra={"splash_config": label})
                if ms < best_ms:
                    best_label, best_kernel, best_ms = label, kern, ms
            except Exception as e:
                print(f"splash {label}: FAIL {type(e).__name__}: "
                      f"{str(e)[:200]}", flush=True)
        if best_kernel is not None:
            record("splash_fwd", best_ms, ab_flops, lengths=ATT_LENGTHS,
                   extra={"splash_config": best_label})

            def splash_loss(q, k, v):
                o = best_kernel(q, k, v)
                return jnp.sum(
                    o.astype(jnp.float32) * wsp.astype(jnp.float32)
                )

            g = jax.grad(splash_loss, argnums=(0, 1, 2))
            step = make_consume_all_grads_body(
                lambda q: g(q, ksp, vsp), jnp.bfloat16
            )
            msb = do_bench_scan_slope(step, qsp, lengths=ATT_LENGTHS,
                                      verbose=True)
            record("splash_fwdbwd", msb, ab_flops * 3.5,
                   lengths=ATT_LENGTHS,
                   extra={"splash_config": best_label})
    except Exception as e:
        print(f"splash: FAIL {type(e).__name__}: {str(e)[:200]}", flush=True)

    # -- 4. sweep extras (only reached when the window survived the
    # decisive set): alternative tilings, GQA-packed fwd, mm8192 ---------
    for bq, bk in [(256, 512), (512, 1024), (1024, 1024)]:
        run_ffa_tiling(bq, bk)

    # GQA-packed A/Bs: fwd pack (MAGI_ATTENTION_FFA_GQA_PACK, grid (hk, W)
    # — k/v HBM traffic /g) and dq pack (MAGI_ATTENTION_FFA_GQA_PACK_DQ,
    # same idea for the dq backward). Env read at trace time, so set it
    # around body construction only.
    prev_pack = os.environ.get("MAGI_ATTENTION_FFA_GQA_PACK")
    os.environ["MAGI_ATTENTION_FFA_GQA_PACK"] = "1"
    try:
        for bq, bk in [(512, 512), (1024, 512)]:
            def ffa_fwd_p(q, bq=bq, bk=bk):
                return ffa_attn(
                    q, ks, vs, qr, kr, tm, block_q=bq, block_k=bk
                )[0].astype(jnp.bfloat16)

            try:
                ms = do_bench_scan_slope(
                    ffa_fwd_p, qs, lengths=ATT_LENGTHS, verbose=True
                )
                record(f"ffa_fwd_gqapack_bq{bq}_bk{bk}", ms, fwd_flops,
                       lengths=ATT_LENGTHS)
            except Exception as e:
                print(f"gqapack bq{bq} bk{bk}: FAIL {type(e).__name__}: "
                      f"{str(e)[:200]}", flush=True)
    finally:
        if prev_pack is None:
            os.environ.pop("MAGI_ATTENTION_FFA_GQA_PACK", None)
        else:
            os.environ["MAGI_ATTENTION_FFA_GQA_PACK"] = prev_pack

    prev_pack_dq = os.environ.get("MAGI_ATTENTION_FFA_GQA_PACK_DQ")
    os.environ["MAGI_ATTENTION_FFA_GQA_PACK_DQ"] = "1"
    try:
        def ffa_loss_pdq(q, k, v):
            o, _ = ffa_attn(q, k, v, qr, kr, tm, block_q=512, block_k=512)
            return jnp.sum(o.astype(jnp.float32) * ws.astype(jnp.float32))

        try:
            g = jax.grad(ffa_loss_pdq, argnums=(0, 1, 2))
            step = make_consume_all_grads_body(
                lambda q: g(q, ks, vs), jnp.bfloat16
            )
            msb = do_bench_scan_slope(step, qs, lengths=ATT_LENGTHS, verbose=True)
            record("ffa_fwdbwd_gqapackdq_bq512_bk512", msb, fwd_flops * 3.5,
                   lengths=ATT_LENGTHS)
        except Exception as e:
            print(f"gqapack_dq: FAIL {type(e).__name__}: {str(e)[:200]}",
                  flush=True)
    finally:
        if prev_pack_dq is None:
            os.environ.pop("MAGI_ATTENTION_FFA_GQA_PACK_DQ", None)
        else:
            os.environ["MAGI_ATTENTION_FFA_GQA_PACK_DQ"] = prev_pack_dq

    mm_probe(8192)


if __name__ == "__main__":
    main()
