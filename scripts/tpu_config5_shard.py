"""Silicon slice of BASELINE config 5 (Llama-3-8B CP=32, seq=1M, fwd+bwd).

Multi-chip hardware is unavailable here, but the per-rank program of the
1M-token cp=32 plan — a 32k q-shard attending its host+remote kv rows — is a
single-chip kernel. This script builds the REAL plan (same solver path the
sanity-checked 1M test uses, tests/test_support/test_scale_numeric.py), picks
the maximum-area rank, and runs its merged FFA program fwd+bwd on silicon
with slope timing, recording TFLOP/s against the rank's true band area —
the kernel-side half of the north-star claim (BASELINE.md config 5).

HBM guard: the full kv buffer of a 1M causal rank shard does not fit one
chip once the fp32 dkv outputs and head-major transposes are counted, so
the kv rows stream in k-chunks — exactly the distributed-flash schedule
(_multi_ffa, functional/dist_attn.py): per-chunk kernels + the exact lse
merge (functional/utils.py lse_weighted_reduce, whose contract is pinned
by tests/test_functional/test_lse_contract.py). Band clipping to a chunk
is exact, each kv row lands in exactly one chunk, and every chunk runs —
so the row covers 100% of the rank's workload (r4 verdict Weak #5: the
old largest-prefix clip covered 62% and proved nothing about the full
program). Reported ms = sum of slope-timed chunk kernels + the measured
merge/delta epilogue.

Appends to benchmarks/history/config5_shard.csv.
``MAGI_CONFIG5_HBM_GB`` overrides the budget (smoke: force chunking on
small shapes). Chunk-split exactness + the merge identity are pinned by
tests/test_support/test_config5_chunking.py.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import jax.numpy as jnp
import numpy as np

from magiattention_tpu.benchmarking.bench import (  # noqa: E402
    do_bench_scan_slope,
    make_consume_all_grads_kv_body,
    make_fwd_kv_body,
    measuring_device,
)
from magiattention_tpu.benchmarking.perf_report import (  # noqa: E402
    HW_FWD_BWD_RATIO,
    append_row,
    credible_floor_ms,
)

SP = int(os.environ.get("MAGI_CONFIG5_SP", 1 << 20))
CPN = int(os.environ.get("MAGI_CONFIG5_CP", 32))
HQ, HK, D = 32, 8, 128  # Llama-3-8B attention geometry
# leave headroom out of 16 GB for XLA scratch
HBM_BUDGET = int(float(os.environ.get("MAGI_CONFIG5_HBM_GB", 11)) * 2**30)


def split_kv_chunks(qr_np, kr_np, lo_np, hi_np, sk_full, step_k):
    """Split band slices into kv chunks of ``step_k`` rows.

    Returns ``[(c0, c1, qr, kr(shifted), lo(shifted), hi(shifted)), ...]``.
    Clipping a band slice to a k interval is exact (per-row bounds
    intersect), every kv row lands in exactly one chunk, and the summed
    chunk areas equal the original area — pinned by
    tests/test_support/test_config5_chunking.py, which also checks the
    streamed partials lse-merge to the whole-kv kernel output."""
    bounds = list(range(0, sk_full, step_k)) + [sk_full]
    bounds = sorted(set(min(b, sk_full) for b in bounds))
    chunks = []
    for c0, c1 in zip(bounds[:-1], bounds[1:]):
        keep = (kr_np[:, 1] > c0) & (kr_np[:, 0] < c1)
        kr_c = np.clip(kr_np[keep], c0, c1) - c0
        chunks.append((
            c0, c1, qr_np[keep], kr_c, lo_np[keep] - c0, hi_np[keep] - c0,
        ))
    return chunks


def band_area(qr, kr, lo, hi) -> int:
    """Exact unmasked area of band slices.

    Delegates to the closed-form O(1)-per-slice ``band_area_batch``
    (meta/container/slice.py) — the 1M-rank configs carry tens of
    thousands of slices per rank, and a per-slice Python row loop here
    costs minutes of a minutes-long chip window."""
    from magiattention_tpu.meta.container.slice import band_area_batch

    qr = np.asarray(qr, np.int64).reshape(-1, 2)
    kr = np.asarray(kr, np.int64).reshape(-1, 2)
    if qr.size == 0:
        return 0
    return int(band_area_batch(
        qr[:, 0], qr[:, 1], kr[:, 0], kr[:, 1],
        np.asarray(lo, np.int64), np.asarray(hi, np.int64),
    ).sum())


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _solver_cache_key() -> str:
    """Hash of the planner-relevant sources: a stale cached plan must
    never be measured after a solver change. Covers everything the plan
    transitively depends on: the solver/meta layer, common structures,
    the ctypes backend AND its C++ source, kernels/ (BAND_INF and the
    band encoding feed the cached d_lo/d_hi), and config.py."""
    import hashlib
    from pathlib import Path

    pkg = Path(_REPO_ROOT) / "magiattention_tpu"
    h = hashlib.md5()
    for sub in ("meta", "common", "csrc_backend", "kernels", "env"):
        for p in sorted((pkg / sub).rglob("*.py")):
            h.update(p.read_bytes())
    h.update((pkg / "config.py").read_bytes())
    for p in sorted((Path(_REPO_ROOT) / "csrc").rglob("*.cpp")):
        h.update(p.read_bytes())
    return h.hexdigest()[:12]


def _max_rank_slices():
    """(sq, sk_full, rank, qr, kr, lo, hi, area, min_area) for the
    max-area rank — cached on disk so a chip window never spends its
    minutes re-running the 1M host solver (the plan is deterministic in
    (SP, CPN, solver sources))."""
    cache_dir = os.path.join(_REPO_ROOT, ".tpu_logs")
    os.makedirs(cache_dir, exist_ok=True)
    cache = os.path.join(
        cache_dir, f"config5_plan_{SP}_{CPN}_{_solver_cache_key()}.npz"
    )
    if os.path.exists(cache):
        try:
            z = np.load(cache)
            out = (int(z["sq"]), int(z["sk_full"]), int(z["rank"]),
                   z["qr"], z["kr"], z["lo"], z["hi"],
                   int(z["area"]), int(z["min_area"]))
            print(f"solver plan cache hit: {cache}", flush=True)
            return out
        except Exception as e:  # truncated/corrupt: re-solve, re-write
            print(f"solver plan cache unreadable ({e!r}) — re-solving",
                  flush=True)

    from magiattention_tpu.common.enum import AttnMaskType
    from magiattention_tpu.common.ranges import AttnRanges
    from magiattention_tpu.meta import (
        make_attn_meta_from_dispatch_meta,
        make_dispatch_meta_from_qk_ranges,
    )

    mq, _, bucket = make_dispatch_meta_from_qk_ranges(
        AttnRanges.from_ranges([[0, SP]]),
        AttnRanges.from_ranges([[0, SP]]),
        [AttnMaskType.CAUSAL], SP, SP, SP // 512, CPN,
    )
    _, calc = make_attn_meta_from_dispatch_meta(bucket, mq)
    sq = calc.shard_len
    sk_full = calc.kv_shard_len + sum(calc.recv_len_per_stage)
    areas = [band_area(a.q_ranges, a.k_ranges, a.d_lo, a.d_hi)
             for a in calc.merged_args]
    r = int(np.argmax(areas))
    a = calc.merged_args[r]
    out = (sq, sk_full, r,
           np.asarray(a.q_ranges, np.int32),
           np.asarray(a.k_ranges, np.int32),
           np.asarray(a.d_lo, np.int64),
           np.asarray(a.d_hi, np.int64),
           int(areas[r]), int(min(areas)))
    # atomic publish: a killed run must never leave a truncated file at
    # the final path (the key would still match and poison every window)
    tmp = cache + f".tmp.{os.getpid()}"
    np.savez_compressed(
        tmp, sq=sq, sk_full=sk_full, rank=r, qr=out[3], kr=out[4],
        lo=out[5], hi=out[6], area=out[7], min_area=out[8],
    )
    os.replace(tmp + ".npz" if os.path.exists(tmp + ".npz") else tmp,
               cache)
    print(f"solver plan cached: {cache}", flush=True)
    return out


def main() -> int:
    dev = measuring_device("tpu_config5_shard")
    PEAK = dev["peak_tflops"]
    print("device:", dev, flush=True)

    from magiattention_tpu.kernels.ffa import (
        FFAParams, _should_interpret, default_blocks, ffa_attn_with_plan,
        plan_arrays,
    )
    from magiattention_tpu.kernels.ffa_plan import get_ffa_plan

    (sq, sk_full, r, qr_np, kr_np, lo_np, hi_np,
     area_max, area_min) = _max_rank_slices()
    print(f"rank {r}: sq={sq} sk={sk_full} area={area_max:.3e} "
          f"(min-rank area {area_min:.3e})", flush=True)

    # HBM estimate: q/do/out bf16 + k/v bf16 (+head-major copies) + fp32
    # dq/dk/dv outputs + lse/delta
    def mem_bytes(sk):
        q_side = sq * HQ * D * 2 * 4        # q, do, out, dq(fp32 ~ 2x bf16)
        kv_side = sk * HK * D * 2 * 2 * 2   # k, v + head-major copies
        dkv = sk * HK * D * 4 * 2           # fp32 dk + dv
        return q_side + kv_side + dkv

    # chunked-kv streaming: smallest chunk count whose per-chunk buffers
    # fit the budget. Every kv row lands in exactly one chunk -> coverage
    # is 1.0 by construction; per-chunk bands are exact clips.
    n_chunks = 1
    while mem_bytes(-(-sk_full // n_chunks)) > HBM_BUDGET:
        n_chunks += 1
        if n_chunks > 64:
            raise SystemExit(
                "HBM budget too small for the q-side buffers alone — "
                "raise MAGI_CONFIG5_HBM_GB"
            )
    per = -(-sk_full // n_chunks)
    step_k = max(128, -(-per // 128) * 128) if n_chunks > 1 else sk_full
    chunks = split_kv_chunks(qr_np, kr_np, lo_np, hi_np, sk_full, step_k)
    chunk_areas = [band_area(q_, k_, lo_, hi_)
                   for _, _, q_, k_, lo_, hi_ in chunks]
    area = int(sum(chunk_areas))
    assert area == area_max, (area, area_max)  # clipping must be exact
    print(f"kv streaming: {n_chunks} chunk(s) of <= {step_k} rows "
          f"(full-rank coverage by construction)", flush=True)

    if "--plan-only" in sys.argv:
        print(f"plan-only: area={area:.3e} chunks={n_chunks} "
              f"slices={[len(c[2]) for c in chunks]} ok", flush=True)
        return 0

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((sq, HQ, D)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((sq, HQ, D)), jnp.bfloat16)
    fwd_flops = 4 * area * D * HQ

    ms_fwd_total = 0.0
    ms_fwdbwd_total = 0.0
    suspect_fwd = suspect_bwd = False
    outs, lses = [], []
    for ci, (c0, c1, qr_c, kr_c, lo_c, hi_c) in enumerate(chunks):
        sk_c = c1 - c0
        bq, bk = default_blocks(sq, sk_c)
        plan = get_ffa_plan(qr_c, kr_c, lo_c, hi_c, sq, sk_c, bq, bk)
        params = FFAParams(
            num_work=plan.num_work, num_work_t=plan.num_work_t,
            num_q_tiles=plan.num_q_tiles, num_k_tiles=plan.num_k_tiles,
            block_q=bq, block_k=bk, softmax_scale=float(D) ** -0.5,
            softcap=0.0, group=HQ // HK, interpret=_should_interpret(),
        )
        arrays = tuple(jnp.asarray(x) for x in plan_arrays(plan))
        crng = np.random.default_rng(1000 + ci)
        k = jnp.asarray(crng.standard_normal((sk_c, HK, D)), jnp.bfloat16)
        v = jnp.asarray(crng.standard_normal((sk_c, HK, D)), jnp.bfloat16)

        # k/v/w must ride the scan CARRY (jit arguments), never a closure:
        # a closed-over jax.Array lowers as an HLO constant, and this
        # loop's kv chunks total ~2 GB of constants copied into the
        # executable; the ~268 MB cotangent seed w gets the same route
        def fwd(qc, kc, vc, arrays=arrays, params=params):
            o, lse = ffa_attn_with_plan(qc, kc, vc, arrays, params)
            return o.astype(jnp.bfloat16), lse

        chunk_flops = 4 * chunk_areas[ci] * D * HQ
        ms = do_bench_scan_slope(
            make_fwd_kv_body(lambda qc, kc, vc: fwd(qc, kc, vc)[0],
                             jnp.bfloat16),
            (q, k, v), lengths=(4, 12),
            min_credible_ms=credible_floor_ms(chunk_flops),
        )
        if ms < credible_floor_ms(chunk_flops):
            suspect_fwd = True  # even the long-scan bound is unphysical
        ms_fwd_total += ms
        o_c, lse_c = jax.jit(fwd)(q, k, v)
        outs.append(np.asarray(o_c, np.float32))
        lses.append(np.asarray(lse_c, np.float32))

        def loss(qc, kc, vc, ww, arrays=arrays, params=params):
            # per-chunk grad: identical kernel launches and shapes as the
            # final-lse distributed-flash backward (_multi_ffa_bwd runs
            # the same dq/dkv kernels per part), so the timing transfers
            o, _ = ffa_attn_with_plan(qc, kc, vc, arrays, params)
            return jnp.sum(o.astype(jnp.float32) * ww.astype(jnp.float32))

        g = jax.grad(loss, argnums=(0, 1, 2))
        step = make_consume_all_grads_kv_body(g, jnp.bfloat16)
        # floor in EXECUTED flops (4.5x fwd = 3.5x reference *
        # HW_FWD_BWD_RATIO): the hardware runs 4.5x fwd matmul work for
        # fwd+bwd, so a 3.5x-based floor is ~29% looser than physical.
        # Reported rates stay in reference convention.
        chunk_flops_hw = chunk_flops * 3.5 * HW_FWD_BWD_RATIO
        msb = do_bench_scan_slope(
            step, (q, k, v, w), lengths=(3, 9),
            min_credible_ms=credible_floor_ms(chunk_flops_hw),
        )
        if msb < credible_floor_ms(chunk_flops_hw):
            suspect_bwd = True
        ms_fwdbwd_total += msb
        tf_c = 4 * chunk_areas[ci] * D * HQ / (ms * 1e-3) / 1e12
        print(f"  chunk {ci} [{c0}:{c1}): fwd {ms:.1f} ms {tf_c:.1f} TF/s"
              f", fwd+bwd {msb:.1f} ms", flush=True)

    # merge/delta epilogue: the exact lse merge of the streamed partials
    # + the backward's delta rowsum — measured, not assumed negligible
    from magiattention_tpu.functional.utils import lse_weighted_reduce

    ost = jnp.asarray(np.stack(outs))
    lst = jnp.asarray(np.stack(lses))

    def epilogue(carry):
        # carry-invariant body (scan requires it) that CONSUMES out, lse
        # and delta — the 1e-30 dependence is the repo's anti-DCE idiom
        # (make_consume_all_grads_body): without it XLA dead-code-
        # eliminates the delta rowsum and lse from the timed program.
        # lst/w ride the carry for the same no-captured-constants reason
        # as the chunk bodies above.
        ost, lst, wc = carry
        out, lse = lse_weighted_reduce(ost, lst)
        delta = jnp.sum(
            out.astype(jnp.float32) * wc.astype(jnp.float32), axis=-1
        )
        touch = (jnp.sum(lse) + jnp.sum(delta)) * 1e-30
        return (
            ost + (out.astype(jnp.float32) + touch)[None] * 1e-30, lst, wc
        )

    ms_merge = do_bench_scan_slope(epilogue, (ost, lst, w), lengths=(4, 12))
    print(f"  merge/delta epilogue: {ms_merge:.2f} ms", flush=True)

    ms_fwd_total += ms_merge
    ms_fwdbwd_total += ms_merge
    tf_fwd = fwd_flops / (ms_fwd_total * 1e-3) / 1e12
    print(f"config5 rank-shard fwd (100% coverage): {ms_fwd_total:.1f} ms "
          f"{tf_fwd:.1f} TF/s ({tf_fwd/PEAK*100:.1f}% nominal)", flush=True)
    append_row("config5_shard", {
        "phase": "fwd", "rank": r, "sq": sq, "sk": sk_full,
        "area_frac": 1.0, "n_chunks": n_chunks,
        "ms": round(ms_fwd_total, 2), "tflops": round(tf_fwd, 2),
        "pct_nominal": round(tf_fwd / PEAK * 100, 1),
        # rows are single-phase, so the whole-row taint is the right form
        **({"suspect": 1} if suspect_fwd else {}),
    })
    tf = fwd_flops * 3.5 / (ms_fwdbwd_total * 1e-3) / 1e12
    print(f"config5 rank-shard fwd+bwd (100% coverage): "
          f"{ms_fwdbwd_total:.1f} ms {tf:.1f} TF/s "
          f"({tf/PEAK*100:.1f}% nominal)", flush=True)
    append_row("config5_shard", {
        "phase": "fwdbwd", "rank": r, "sq": sq, "sk": sk_full,
        "area_frac": 1.0, "n_chunks": n_chunks,
        "ms": round(ms_fwdbwd_total, 2), "tflops": round(tf, 2),
        "pct_nominal": round(tf / PEAK * 100, 1),
        **({"suspect": 1} if suspect_bwd else {}),
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
