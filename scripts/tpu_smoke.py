"""Kernel census on the chip: one compile-and-compare case per
``pallas_call`` site of the package.

    python scripts/tpu_smoke.py

The sites are the union of the three ``PALLAS_CONTRACTS`` tables
(``kernels/ffa.py`` 9, ``kernels/paged_decode.py`` 3,
``kernels/block_sparse.py`` 2); a site without a case here fails the run.
Each case calls ONE site's wrapper directly — no selection flag decides what
runs — under Mosaic on the attached TPU, and compares with a dense fp32
reference computed at ``highest`` matmul precision. Per site the census
records *compiled* (with its error against the reference) or *refused*
(with the compiler's message). Every case runs even when an earlier one
failed; the exit code is non-zero if any site was refused or disagreed.

Without a TPU it exits 1 before compiling anything. ``--rehearse-cpu`` runs
the same cases in the Pallas interpreter to debug the script itself; it
proves nothing about the compiler and says so.
"""

from __future__ import annotations

import json
import os
import sys
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# bf16 inputs, fp32 accumulation, fp32 `highest` reference: the bounds and
# their derivation are chip_smoke.py's
from chip_smoke import TOL_ATTN_REL as TOL_REL, TOL_LSE_ABS  # noqa: E402


def _rel(got, ref) -> float:
    from magiattention_tpu.testing.precision import rel_norm_err

    return rel_norm_err(np.asarray(got, np.float32),
                        np.asarray(ref, np.float32))


def _lse_abs(got, ref) -> float:
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    both_inf = np.isneginf(got) & np.isneginf(ref)  # empty slots / rows
    with np.errstate(invalid="ignore"):
        return float(np.max(np.where(both_inf, 0.0, np.abs(got - ref))))


# ---------------------------------------------------------------------------
# FFA: 9 sites on the chip_smoke shape class (g = 4, d = 128, bf16, packed
# varlen block-causal, default blocks)
# ---------------------------------------------------------------------------


def ffa_cases() -> dict:
    from magiattention_tpu.common.enum import AttnMaskType
    from magiattention_tpu.common.mask import AttnMask
    from magiattention_tpu.common.ranges import AttnRanges
    from magiattention_tpu.kernels import ffa
    from magiattention_tpu.kernels.ffa_plan import get_ffa_plan
    from magiattention_tpu.kernels.mask_utils import types_to_bands
    from magiattention_tpu.testing.ref_attn import ref_attn

    S, HQ, HK, D = 2048, 8, 2, 128
    cu = [0, 700, 1500, S]
    docs = [[a, b] for a, b in zip(cu[:-1], cu[1:])]
    qr = np.asarray(docs, np.int32)
    tm = np.ones(len(docs), np.int32)  # causal
    rng = np.random.default_rng(0)
    q, k, v, do = (
        jnp.asarray(rng.standard_normal((S, h, D)), jnp.bfloat16)
        for h in (HQ, HK, HK, HQ)
    )
    mask = AttnMask.from_ranges(
        AttnRanges.from_ranges(docs), AttnRanges.from_ranges(docs),
        [AttnMaskType.CAUSAL] * len(docs), total_seqlen_q=S, total_seqlen_k=S,
    ).mask_array

    def ref_loss(q, k, v):
        out, lse = ref_attn(q, k, v, mask)
        return jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32)), (
            out, lse)

    with jax.default_matmul_precision("highest"):
        (_, (ro, rlse)), (rdq, rdk, rdv) = jax.jit(jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)

    d_lo, d_hi = types_to_bands(qr, qr, tm)
    bq, bk = ffa.default_blocks(S, S)
    plan = get_ffa_plan(qr, qr, d_lo, d_hi, S, S, bq, bk)
    arrays = ffa.plan_arrays(plan)
    prm = ffa.FFAParams(
        num_work=plan.num_work, num_work_t=plan.num_work_t,
        num_q_tiles=plan.num_q_tiles, num_k_tiles=plan.num_k_tiles,
        block_q=bq, block_k=bk, softmax_scale=float(D) ** -0.5, softcap=0.0,
        group=HQ // HK, interpret=ffa._should_interpret(),
        min_revisit_distance=plan.min_revisit_distance,
    )
    hm = lambda x: x.transpose(1, 0, 2)  # noqa: E731  (S, h, d) -> (h, S, d)
    q_t, k_t, v_t, do_t, out_t = map(hm, (q, k, v, do, ro))
    lse_t = rlse.T
    delta_t = jnp.sum(
        out_t.astype(jnp.float32) * do_t.astype(jnp.float32), axis=-1)
    bwd_in = (q_t, k_t, v_t, do_t, lse_t, delta_t)
    q_major, k_major = arrays[0:3], arrays[3:6]

    def fwd(wrapper):
        out, lse, _ = jax.jit(partial(wrapper, prm))(*q_major, q_t, k_t, v_t)
        return {"out": (_rel(hm(out), ro), TOL_REL),
                "lse": (_lse_abs(lse.T, rlse), TOL_LSE_ABS)}

    def dq(wrapper):
        got = jax.jit(partial(wrapper, prm))(*q_major, *bwd_in)
        return {"dq": (_rel(hm(got), rdq), TOL_REL)}

    def dkv(wrapper):
        gk, gv = jax.jit(partial(wrapper, prm))(*k_major, *bwd_in)
        return {"dk": (_rel(hm(gk), rdk), TOL_REL),
                "dv": (_rel(hm(gv), rdv), TOL_REL)}

    def fused(wrapper):
        gq, gk, gv = jax.jit(partial(wrapper, prm))(*k_major, *bwd_in)
        return {"dq": (_rel(hm(gq), rdq), TOL_REL),
                "dk": (_rel(hm(gk), rdk), TOL_REL),
                "dv": (_rel(hm(gv), rdv), TOL_REL)}

    def delta():
        got = jax.jit(
            lambda o, d_: ffa._ffa_delta_pallas(o, d_, bq, prm.interpret)
        )(out_t, do_t)
        return {"delta": (_rel(got, delta_t), 1e-5)}  # fp32 row sums

    shape = f"S={S} hq={HQ} hk={HK} d={D} bf16 bq={bq} bk={bk}"
    return {
        "_fwd_kernel": (shape, partial(fwd, ffa._ffa_fwd_pallas)),
        "_fwd_kernel_gqa": (shape, partial(fwd, ffa._ffa_fwd_pallas_gqa)),
        "_bwd_dq_kernel": (shape, partial(dq, ffa._ffa_bwd_dq_pallas)),
        "_bwd_dq_kernel_gqa": (
            shape, partial(dq, ffa._ffa_bwd_dq_pallas_gqa)),
        "_bwd_dkv_kernel": (shape, partial(dkv, ffa._ffa_bwd_dkv_pallas)),
        "_bwd_dkv_kernel_gqa": (
            shape, partial(dkv, ffa._ffa_bwd_dkv_pallas_gqa)),
        "_bwd_fused_kernel": (
            shape, partial(fused, ffa._ffa_bwd_fused_pallas)),
        "_bwd_fused_kernel_gqa": (
            shape, partial(fused, ffa._ffa_bwd_fused_pallas_gqa)),
        "_delta_kernel": (shape, delta),
    }


# ---------------------------------------------------------------------------
# paged decode: 3 sites, each at the serving default page size (16 rows,
# below the 128-lane tile) and at a lane-aligned 128-row page
# ---------------------------------------------------------------------------


def _decode_fixture(ps: int, int8: bool, spec_k: int = 1):
    """A ragged batch (an empty slot, a page-boundary length) written
    straight into pages; returns (cache, q, dense reference out / lse)."""
    from magiattention_tpu.kernels.paged_kv import PagedKVCache

    HQ, HK, D = 8, 2, 128
    lens = [5 + spec_k, 0, 2 * ps, ps + 9]
    max_pages = 3
    num_pages = 16
    rng = np.random.default_rng(1)
    k_pages = np.zeros((num_pages, ps, HK, D), np.float32)
    v_pages = np.zeros_like(k_pages)
    table = np.full((len(lens), max_pages), -1, np.int32)
    free = list(rng.permutation(num_pages))
    nat = []
    for s, n in enumerate(lens):
        kn = rng.standard_normal((n, HK, D)).astype(np.float32)
        vn = rng.standard_normal((n, HK, D)).astype(np.float32)
        for p in range(-(-n // ps)):
            page = free.pop()
            table[s, p] = page
            rows = slice(p * ps, min((p + 1) * ps, n))
            k_pages[page, : rows.stop - rows.start] = kn[rows]
            v_pages[page, : rows.stop - rows.start] = vn[rows]
        nat.append((kn, vn))
    k_scales = v_scales = None
    if int8:
        def quant(pages):
            scale = np.abs(pages).max(axis=(1, 3)) / 127.0  # (pages, hk)
            safe = np.where(scale > 0, scale, 1.0)
            codes = np.clip(np.round(pages / safe[:, None, :, None]),
                            -127, 127)
            return codes.astype(np.int8), scale.astype(np.float32)

        k_codes, k_scales = quant(k_pages)
        v_codes, v_scales = quant(v_pages)
        deq_k = k_codes.astype(np.float32) * k_scales[:, None, :, None]
        deq_v = v_codes.astype(np.float32) * v_scales[:, None, :, None]
        k_dev, v_dev = jnp.asarray(k_codes), jnp.asarray(v_codes)
    else:
        k_dev = jnp.asarray(k_pages, jnp.bfloat16)
        v_dev = jnp.asarray(v_pages, jnp.bfloat16)
        deq_k = np.asarray(k_dev, np.float32)
        deq_v = np.asarray(v_dev, np.float32)
    cache = PagedKVCache(
        k_dev, v_dev, jnp.asarray(table), jnp.asarray(lens, jnp.int32),
        None if k_scales is None else jnp.asarray(k_scales),
        None if v_scales is None else jnp.asarray(v_scales),
    )
    q = jnp.asarray(
        rng.standard_normal((len(lens), spec_k, HQ, D)), jnp.bfloat16)
    # dense reference in float64 from what the pages really hold
    g = HQ // HK
    ref_out = np.zeros((len(lens), spec_k, HQ, D))
    ref_lse = np.full((len(lens), spec_k, HQ), -np.inf)
    qf = np.asarray(q, np.float64)
    for s, n in enumerate(lens):
        if n == 0:
            continue
        pages = table[s, : -(-n // ps)]
        kk = deq_k[pages].reshape(-1, HK, D)[:n].astype(np.float64)
        vv = deq_v[pages].reshape(-1, HK, D)[:n].astype(np.float64)
        for t in range(spec_k):
            vis = n - spec_k + t + 1  # draft row t sees its causal prefix
            for h in range(HQ):
                logit = kk[:vis, h // g] @ qf[s, t, h] * D ** -0.5
                m = logit.max()
                p = np.exp(logit - m)
                ref_lse[s, t, h] = m + np.log(p.sum())
                ref_out[s, t, h] = (p / p.sum()) @ vv[:vis, h // g]
    return cache, q, ref_out, ref_lse


def decode_cases() -> dict:
    from magiattention_tpu.kernels import paged_decode as pd

    def run(fn, ps, int8, spec_k):
        cache, q, ro, rl = _decode_fixture(ps, int8, spec_k)
        if spec_k == 1 and fn is not pd.paged_decode_attn_spec:
            out, lse = jax.jit(fn)(q[:, 0], cache)
            out, lse = out[:, None], lse[:, None]
        else:
            out, lse = jax.jit(fn)(q, cache)
        return {"out": (_rel(out, ro), TOL_REL),
                "lse": (_lse_abs(lse, rl), TOL_LSE_ABS)}

    cases = {}
    for ps in (16, 128):
        tag = f"page_size={ps} hq=8 hk=2 d=128"
        cases[f"_paged_decode_kernel@ps{ps}"] = (
            tag + " bf16", partial(run, pd.paged_decode_attn, ps, False, 1))
        cases[f"_paged_decode_spec_kernel@ps{ps}"] = (
            tag + " bf16 spec_k=4",
            partial(run, pd.paged_decode_attn_spec, ps, False, 4))
        cases[f"_paged_decode_int8_kernel@ps{ps}"] = (
            tag + " int8 kv",
            partial(run, pd.paged_decode_attn_int8, ps, True, 1))
    return cases


# ---------------------------------------------------------------------------
# block-sparse (NSA selected-block branch): 2 sites at the NSA defaults
# ---------------------------------------------------------------------------


def block_sparse_cases() -> dict:
    from magiattention_tpu.kernels.block_sparse import block_sparse_attn

    S, HK, G, D = 1024, 2, 4, 128
    L_SLC, D_STRIDE, BQ, TOP_K = 64, 32, 16, 8
    HQ = HK * G
    rng = np.random.default_rng(2)
    starts = np.arange(0, S - L_SLC + 1, D_STRIDE, dtype=np.int32)
    n_blocks, n_qb = len(starts), S // BQ
    idx = np.stack([
        rng.choice(n_blocks, size=TOP_K, replace=False)
        for _ in range(HK * n_qb)
    ]).reshape(HK, n_qb, TOP_K).astype(np.int32)
    q, k, v, do = (
        jnp.asarray(rng.standard_normal((S, h, D)), jnp.bfloat16)
        for h in (HQ, HK, HK, HQ)
    )
    scale = D ** -0.5

    def gathered(q_, k_, v_):
        """take_along_axis + dense softmax over the same index table."""
        q_, k_, v_ = (x.astype(jnp.float32) for x in (q_, k_, v_))
        kb = jnp.stack([k_[s: s + L_SLC] for s in starts])
        vb = jnp.stack([v_[s: s + L_SLC] for s in starts])
        sel = lambda b: jnp.take_along_axis(  # noqa: E731
            b.transpose(2, 0, 1, 3)[:, None], idx[..., None, None], axis=2
        ).reshape(HK, n_qb, TOP_K * L_SLC, D)
        qb = q_.reshape(n_qb, BQ, HK, G, D)
        s_ = jnp.einsum("bqhgd,hbld->hbgql", qb, sel(kb)) * scale
        p = jax.nn.softmax(s_, axis=-1)
        return jnp.einsum("hbgql,hbld->bqhgd", p, sel(vb)).reshape(S, HQ, D)

    def kernel(q_, k_, v_):
        out, _ = block_sparse_attn(
            q_, k_, v_, jnp.asarray(idx), starts, block_len=L_SLC,
            d_stride=D_STRIDE, block_size_q=BQ, softmax_scale=scale,
        )
        return out

    w = do.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        ro = jax.jit(gathered)(q, k, v)
        rgrads = jax.jit(jax.grad(
            lambda *a: jnp.sum(gathered(*a) * w), argnums=(0, 1, 2)
        ))(q, k, v)

    def fwd():
        return {"out": (_rel(jax.jit(kernel)(q, k, v), ro), TOL_REL)}

    def bwd():
        grads = jax.jit(jax.grad(
            lambda *a: jnp.sum(kernel(*a).astype(jnp.float32) * w),
            argnums=(0, 1, 2),
        ))(q, k, v)
        return {n: (_rel(g_, r), TOL_REL)
                for n, g_, r in zip(("dq", "dk", "dv"), grads, rgrads)}

    shape = (f"S={S} hq={HQ} hk={HK} d={D} bf16 l_slc={L_SLC} "
             f"d_stride={D_STRIDE} bq={BQ} top_k={TOP_K}")
    return {
        "_bsp_fwd_kernel": (shape, fwd),
        # reached through jax.grad, so it also needs _bsp_fwd_kernel
        "_bsp_bwd_kernel": (shape + " (via grad: needs _bsp_fwd_kernel)",
                            bwd),
    }


def _declared_sites() -> set[str]:
    from magiattention_tpu.kernels import block_sparse, ffa, paged_decode

    return {
        name
        for mod in (ffa, paged_decode, block_sparse)
        for name in mod.PALLAS_CONTRACTS
    }


def main(argv: list[str]) -> int:
    rehearse = argv == ["--rehearse-cpu"]
    if argv and not rehearse:
        sys.exit("usage: tpu_smoke.py [--rehearse-cpu]")
    backend = jax.default_backend()
    dev = jax.devices()[0]
    tag = "[cpu rehearsal: interpreter, not the compiler] " if rehearse else ""
    print(f"{tag}device: platform={dev.platform} device_kind="
          f"{dev.device_kind!r} count={len(jax.devices())}", flush=True)
    if backend != ("cpu" if rehearse else "tpu"):
        print(f"tpu_smoke: jax.default_backend()={backend!r}, not a TPU — "
              "the census is of the TPU compiler; exiting 1",
              file=sys.stderr)
        return 1
    if not rehearse:
        from magiattention_tpu.utils.compile_cache import (
            enable_persistent_cache,
        )

        enable_persistent_cache()

    cases: dict = {}
    for build in (ffa_cases, decode_cases, block_sparse_cases):
        cases.update(build())
    missing = _declared_sites() - {name.split("@")[0] for name in cases}
    if missing:
        sys.exit(f"tpu_smoke: no census case for pallas_call site(s) "
                 f"{sorted(missing)} declared in PALLAS_CONTRACTS")

    rows = []
    for name, (shape, run) in cases.items():
        row = {"site": name, "shape": shape}
        # the one try/except of this script: the census exists to record
        # what the compiler says about EVERY site, so a refusal is a row
        try:
            errs = run()
        except Exception as e:  # noqa: BLE001
            row.update(status="refused",
                       message=f"{type(e).__name__}: {e}"[:1500])
        else:
            row["errors"] = {k: {"err": e, "tol": t} for k, (e, t)
                             in errs.items()}
            agree = all(e <= t for e, t in errs.values())
            ran = "interpreted" if rehearse else "compiled"
            row["status"] = ran if agree else ran + ", DISAGREES"
        rows.append(row)
        detail = row.get("message") or ", ".join(
            f"{k}={v['err']:.2e}" for k, v in row["errors"].items())
        print(f"{tag}{row['status']:>20}  {name:<34} {detail[:300]}",
              flush=True)

    bad = [r["site"] for r in rows if "status" not in r
           or r["status"] not in ("compiled", "interpreted")]
    print(f"{tag}census: {len(rows) - len(bad)}/{len(rows)} ran and agree; "
          f"not ok: {bad}", flush=True)
    if not rehearse:
        # the compiler's full messages are too long for the end of stdout
        out_dir = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "kernel_census.json"), "w") as f:
            json.dump({"device": {"platform": dev.platform,
                                  "kind": dev.device_kind,
                                  "count": len(jax.devices())},
                       "rows": rows}, f, indent=1)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
