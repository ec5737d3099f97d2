"""Chip practical-peak calibration + FFA vs bundled-kernel A/B.

A short scan of a bare 4096^3 bf16 XLA matmul once read 34 TFLOP/s, 17% of
the nominal peak, because a fixed per-launch cost was divided by six steps.
This script measures

1. the practical matmul ceiling across sizes/batching (the honest MFU
   denominator for this chip), and
2. the bundled `jax.experimental.pallas.ops.tpu.flash_attention` on the
   exact bench shape, timed identically to our FFA kernel — the direct
   answer to "does a reference-quality Pallas kernel go faster here?"

Appends to benchmarks/history/{chip_calibration,ab_flash}.csv.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import jax.numpy as jnp
import numpy as np

from magiattention_tpu.benchmarking.bench import (  # noqa: E402
    do_bench_scan_verbose as scan_time,
    make_consume_all_grads_body,
    measuring_device,
)
from magiattention_tpu.benchmarking.perf_report import (  # noqa: E402
    HW_FWD_BWD_RATIO,
    append_row,
)

PEAK = None  # bf16 peak of the attached device, looked up in main()


def main():
    global PEAK
    dev = measuring_device("tpu_calibrate")
    PEAK = dev["peak_tflops"]
    print("device:", dev, flush=True)
    rng = np.random.default_rng(0)
    best_ceiling = 0.0

    # -- 0. fixed-overhead probe ------------------------------------------
    # Every launch carries a constant cost that a length-6 scan divides by
    # only 6. Time the same matmul at several scan lengths:
    # if per-step ms falls as length grows, the short-scan numbers are
    # overhead-dominated and the TRUE kernel time is the long-scan slope.
    n = 4096
    a = jnp.asarray(rng.standard_normal((n, n)), jnp.bfloat16)
    per_step = {}
    for length in (6, 24, 96):
        try:
            dt = scan_time(
                lambda x: (x @ a).astype(jnp.bfloat16), a, length=length, reps=3
            )
            per_step[length] = dt
            tf = 2 * n**3 / (dt * 1e-3) / 1e12
            print(f"overhead-probe len={length}: {dt:.3f} ms/step {tf:.1f} TF/s", flush=True)
            append_row("chip_calibration", {
                "probe": f"mm4096_len{length}", "ms": round(dt, 3),
                "tflops": round(tf, 2), "pct_of_nominal": round(tf / PEAK * 100, 1),
            })
        except Exception as e:
            print(f"overhead-probe len={length}: FAIL {type(e).__name__}", flush=True)
    if 6 in per_step and 96 in per_step:
        # fixed ms per executable launch implied by the two lengths
        fixed = (per_step[6] - per_step[96]) * 6 * 96 / (96 - 6)
        print(f"implied fixed overhead per launch: {fixed:.1f} ms", flush=True)
        append_row("chip_calibration", {
            "probe": "implied_fixed_launch_ms", "ms": round(fixed, 2),
            "tflops": 0.0, "pct_of_nominal": 0.0,
        })

    # -- 1. matmul ceiling sweep ------------------------------------------
    for tag, shape_fn, flops in [
        ("mm2048", lambda: (2048, 2048), 2 * 2048**3),
        ("mm4096", lambda: (4096, 4096), 2 * 4096**3),
        ("mm8192", lambda: (8192, 8192), 2 * 8192**3),
        ("bmm8x4096", lambda: (8, 4096, 4096), 8 * 2 * 4096**3),
    ]:
        shape = shape_fn()
        a = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        try:
            dt = scan_time(lambda x: (x @ a).astype(jnp.bfloat16), a, length=6, reps=3)
            tf = flops / (dt * 1e-3) / 1e12
            best_ceiling = max(best_ceiling, tf)
            print(f"{tag}: {dt:.3f} ms {tf:.1f} TF/s ({tf/PEAK*100:.1f}% of {PEAK})", flush=True)
            append_row("chip_calibration", {
                "probe": tag, "ms": round(dt, 3), "tflops": round(tf, 2),
                "pct_of_nominal": round(tf / PEAK * 100, 1),
            })
        except Exception as e:
            print(f"{tag}: FAIL {type(e).__name__}: {str(e)[:160]}", flush=True)
    print(f"practical ceiling: {best_ceiling:.1f} TF/s", flush=True)

    # -- 2. bundled flash_attention vs our FFA, same shape ----------------
    # dense causal, equal heads (the bundled kernel has no GQA): the kernel-
    # efficiency A/B. FLOPs by causal area, identical for both.
    from magiattention_tpu.kernels.ffa import ffa_attn

    S, H, D = 4096, 16, 128
    area = S * (S + 1) // 2
    fwd_flops = 4 * area * D * H
    qb = jnp.asarray(rng.standard_normal((1, H, S, D)), jnp.bfloat16)
    kb = jnp.asarray(rng.standard_normal((1, H, S, D)), jnp.bfloat16)
    vb = jnp.asarray(rng.standard_normal((1, H, S, D)), jnp.bfloat16)
    wb = jnp.asarray(rng.standard_normal((1, H, S, D)), jnp.bfloat16)

    def run_ab(tag, fwd_fn, grad_fn, init):
        try:
            dt = scan_time(fwd_fn, init, length=6, reps=2)
            tf = fwd_flops / (dt * 1e-3) / 1e12
            dtb = scan_time(grad_fn, init, length=6, reps=2)
            tfb = fwd_flops * 3.5 / (dtb * 1e-3) / 1e12
            ceil = best_ceiling or PEAK
            # ceiling pct must compare like with like: the ceiling is a
            # measured matmul rate, so the fwd+bwd numerator uses the
            # executed-matmul-work convention (bwd = 3.5x fwd), not the
            # reference's 2.5x accounting
            tfb_hw = tfb * HW_FWD_BWD_RATIO
            print(
                f"{tag}: fwd {dt:.3f} ms {tf:.1f} TF/s ({tf/ceil*100:.0f}% of ceiling) | "
                f"fwd+bwd {dtb:.3f} ms {tfb:.1f} TF/s (hw {tfb_hw/ceil*100:.0f}%)",
                flush=True,
            )
            append_row("ab_flash", {
                "kernel": tag, "fwd_ms": round(dt, 3), "fwd_tflops": round(tf, 2),
                "fwdbwd_ms": round(dtb, 3), "fwdbwd_tflops": round(tfb, 2),
                "ceiling_tflops": round(ceil, 2),
                "fwd_pct_ceiling": round(tf / ceil * 100, 1),
                "fwdbwd_pct_ceiling_hw": round(tfb_hw / ceil * 100, 1),
            })
        except Exception as e:
            print(f"{tag}: FAIL {type(e).__name__}: {str(e)[:300]}", flush=True)

    # our FFA on the dense-causal problem FIRST (seq-major layout, H==HK):
    # it must be measured even if the bundled-kernel module is missing
    qs = jnp.asarray(rng.standard_normal((S, H, D)), jnp.bfloat16)
    ks = jnp.asarray(rng.standard_normal((S, H, D)), jnp.bfloat16)
    vs = jnp.asarray(rng.standard_normal((S, H, D)), jnp.bfloat16)
    ws = jnp.asarray(rng.standard_normal((S, H, D)), jnp.bfloat16)
    qr = np.array([[0, S]], np.int32)
    kr = np.array([[0, S]], np.int32)
    tm = np.array([1], np.int32)

    for bq, bk in [(256, 512), (512, 512)]:
        def ffa_fwd(q, bq=bq, bk=bk):
            return ffa_attn(q, ks, vs, qr, kr, tm, block_q=bq, block_k=bk)[0].astype(jnp.bfloat16)

        def ffa_loss(q, k, v, bq=bq, bk=bk):
            o, _ = ffa_attn(q, k, v, qr, kr, tm, block_q=bq, block_k=bk)
            return jnp.sum(o.astype(jnp.float32) * ws.astype(jnp.float32))

        ffa_g = jax.grad(ffa_loss, argnums=(0, 1, 2))
        ffa_step = make_consume_all_grads_body(
            lambda q, g=ffa_g: g(q, ks, vs), jnp.bfloat16
        )
        run_ab(f"ffa_bq{bq}_bk{bk}", ffa_fwd, ffa_step, qs)

    # bundled kernel (guarded: jax.experimental churns — its absence must
    # not cost the FFA measurements above or abort a scarce chip window)
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            BlockSizes,
            flash_attention,
        )
    except Exception as e:
        print(f"bundled flash_attention unavailable: {type(e).__name__}: "
              f"{str(e)[:160]}", flush=True)
        return

    def bundled_fwd(q):
        return flash_attention(q, kb, vb, causal=True).astype(jnp.bfloat16)

    def bundled_loss(q, k, v):
        o = flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) * wb.astype(jnp.float32))

    bundled_g = jax.grad(bundled_loss, argnums=(0, 1, 2))
    bundled_step = make_consume_all_grads_body(
        lambda q: bundled_g(q, kb, vb), jnp.bfloat16
    )
    run_ab("bundled_flash", bundled_fwd, bundled_step, qb)

    # bundled kernel with our winning block sizes, for tile parity
    try:
        bs = BlockSizes(
            block_q=512, block_k_major=512, block_k=512, block_b=1,
            block_q_major_dkv=512, block_k_major_dkv=512, block_k_dkv=512,
            block_q_dkv=512, block_k_major_dq=512, block_k_dq=512,
            block_q_dq=512,
        )

        def bundled_fwd_b(q):
            return flash_attention(q, kb, vb, causal=True, block_sizes=bs).astype(jnp.bfloat16)

        def bundled_loss_b(q, k, v):
            o = flash_attention(q, k, v, causal=True, block_sizes=bs)
            return jnp.sum(o.astype(jnp.float32) * wb.astype(jnp.float32))

        bundled_gb = jax.grad(bundled_loss_b, argnums=(0, 1, 2))
        bundled_step_b = make_consume_all_grads_body(
            lambda q: bundled_gb(q, kb, vb), jnp.bfloat16
        )
        run_ab("bundled_flash_b512", bundled_fwd_b, bundled_step_b, qb)
    except Exception as e:
        print(f"bundled_flash_b512: skip {type(e).__name__}: {str(e)[:160]}", flush=True)


if __name__ == "__main__":
    main()
