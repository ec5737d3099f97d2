"""Chip calibration + FFA block sweep with data-dependent chained timing.

Everything here is a lax.scan whose carry feeds iteration i+1, timed at two
trip counts so the fixed per-launch cost cancels.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import jax.numpy as jnp
import numpy as np

PEAK = None  # bf16 peak of the attached device, looked up in main()

from magiattention_tpu.benchmarking.perf_report import (  # noqa: E402
    HW_FWD_BWD_RATIO as HW_RATIO,
    append_row,
)


from magiattention_tpu.benchmarking.bench import (  # noqa: E402
    do_bench_scan_slope,
    make_consume_all_grads_body,
    measuring_device,
)


def scan_time(body, init):
    # slope timing cancels the fixed per-launch cost; verbose keeps
    # compile wall-clock visible so a timeout is diagnosable
    return do_bench_scan_slope(body, init, reps=2, verbose=True)


def main():
    global PEAK
    dev = measuring_device("tpu_perf_probe")
    PEAK = dev["peak_tflops"]
    print("device:", dev, flush=True)
    rng = np.random.default_rng(0)

    n = 4096
    a = jnp.asarray(rng.standard_normal((n, n)), jnp.bfloat16)
    dt = scan_time(lambda x: (x @ a).astype(jnp.bfloat16), a)
    tf = 2 * n**3 / (dt * 1e-3) / 1e12
    print(f"matmul {n}: {dt:.3f} ms {tf:.1f} TFLOP/s ({tf/PEAK*100:.1f}% of {PEAK})", flush=True)

    from magiattention_tpu.kernels.ffa import ffa_attn

    S, HQ, HK, D = 4096, 16, 8, 128
    area = S * (S + 1) // 2
    q0 = jnp.asarray(rng.standard_normal((S, HQ, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((S, HK, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((S, HK, D)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((S, HQ, D)), jnp.bfloat16)
    qr = np.array([[0, S]], np.int32)
    kr = np.array([[0, S]], np.int32)
    tm = np.array([1], np.int32)

    def time_fwd(bq, bk):
        dt = scan_time(
            lambda q: ffa_attn(q, k, v, qr, kr, tm, block_q=bq,
                               block_k=bk)[0].astype(jnp.bfloat16),
            q0,
        )
        return dt, 4 * area * D * HQ / (dt * 1e-3) / 1e12

    def time_fwd_bwd(bq, bk):
        def loss(q, k, v):
            o, _ = ffa_attn(q, k, v, qr, kr, tm, block_q=bq, block_k=bk)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))

        g = jax.grad(loss, argnums=(0, 1, 2))
        body = make_consume_all_grads_body(
            lambda q: g(q, k, v), jnp.bfloat16
        )
        dtb = scan_time(body, q0)
        return dtb, 4 * area * D * HQ * 3.5 / (dtb * 1e-3) / 1e12

    for bq, bk in [(256, 512), (512, 512), (512, 1024), (1024, 512),
                   (1024, 1024), (512, 2048), (1024, 2048), (2048, 512)]:
        try:
            dt, tf = time_fwd(bq, bk)
            dtb, tfb = time_fwd_bwd(bq, bk)
            print(
                f"ffa bq={bq} bk={bk}: fwd {dt:.3f} ms {tf:.1f} TF/s "
                f"({tf/PEAK*100:.1f}%) | fwd+bwd {dtb:.3f} ms {tfb:.1f} TF/s "
                f"({tfb/PEAK*100:.1f}%, hw {tfb*HW_RATIO/PEAK*100:.1f}%)",
                flush=True,
            )
            append_row("block_sweep", {
                "block_q": bq, "block_k": bk,
                "fwd_ms": round(dt, 3), "fwd_tflops": round(tf, 2),
                "fwdbwd_ms": round(dtb, 3), "fwdbwd_tflops": round(tfb, 2),
                "fwdbwd_mfu": round(tfb / PEAK, 4),
                "fwdbwd_mfu_hw": round(tfb * HW_RATIO / PEAK, 4),
            })
        except Exception as e:
            print(f"ffa bq={bq} bk={bk}: FAIL {type(e).__name__}: {str(e)[:200]}", flush=True)

    # backward-specific tile overrides (fwd pinned at 512x1024): the dq and
    # dkv kernels have different VMEM/compute profiles, so their best tiles
    # can differ from fwd's (MAGI_ATTENTION_FFA_BLOCK_*_D{Q,KV})
    bq, bk = 512, 1024
    names = (
        "MAGI_ATTENTION_FFA_BLOCK_Q_DQ", "MAGI_ATTENTION_FFA_BLOCK_K_DQ",
        "MAGI_ATTENTION_FFA_BLOCK_Q_DKV", "MAGI_ATTENTION_FFA_BLOCK_K_DKV",
    )
    for dq_blk, dkv_blk in [
        ((256, 1024), None),
        ((1024, 512), None),
        (None, (256, 1024)),
        (None, (1024, 512)),
        ((1024, 512), (1024, 512)),
    ]:
        vals = (dq_blk or (None, None)) + (dkv_blk or (None, None))
        for key, val in zip(names, vals):
            if val:
                os.environ[key] = str(val)
            else:
                os.environ.pop(key, None)
        try:
            dtb, tfb = time_fwd_bwd(bq, bk)
            print(
                f"ffa bwd-override dq={dq_blk} dkv={dkv_blk}: fwd+bwd "
                f"{dtb:.3f} ms {tfb:.1f} TF/s ({tfb/PEAK*100:.1f}%)",
                flush=True,
            )
            append_row("bwd_override_sweep", {
                "dq_blocks": str(dq_blk), "dkv_blocks": str(dkv_blk),
                "fwdbwd_ms": round(dtb, 3), "fwdbwd_tflops": round(tfb, 2),
                "fwdbwd_mfu": round(tfb / PEAK, 4),
            })
        except Exception as e:
            print(f"ffa bwd-override dq={dq_blk} dkv={dkv_blk}: FAIL "
                  f"{type(e).__name__}: {str(e)[:200]}", flush=True)
    for key in names:
        os.environ.pop(key, None)


if __name__ == "__main__":
    main()
