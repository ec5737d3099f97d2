"""Chip smoke: the context-parallel training path on the TPU, end to end.

    python chip_smoke.py

One process, no arguments, run from the root of a checkout (no git, no
network needed). On every TPU JAX shows it (cp = 1 on one chip, cp = 4 on a
four-chip host; tokens per chip constant) it

1. checks ``undispatch(calc_attn(dispatch(q), dispatch(k), dispatch(v)))``
   and its gradients at 32 q / 8 kv heads, head_dim 128, bf16, under a packed
   varlen block-causal mask, against ``testing.ref_attn`` at ``highest``
   matmul precision;
2. checks the Llama train loss and logits through ``llama.loss_fn`` /
   ``llama.forward`` against the dense twin ``loss_fn_dense`` on the same
   parameters and tokens;
3. takes ``TRAIN_STEPS`` ``llama.train_step`` steps at Llama-3-8B widths
   (depth and vocabulary cut, every cut printed) on 8192 tokens per chip.

It exits non-zero within seconds when JAX's default backend is not ``tpu``,
and refuses to start under any variable that could hide the device
(interpret mode, fallbacks, retries, backend pins, fault injection,
telemetry). No phase is wrapped in try/except: a phase that fails fails the
run. The line before the last, ``report: {...}``, carries the sizes, every
check's error against its tolerance, the kernels, plans and compile times.
The last line of stdout is the result, one JSON object with exactly these
keys, ``"ok": true`` only when every check passed::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``python chip_smoke.py --rehearse-cpu [N]`` is a toy-size rehearsal of the
same code on N virtual CPU devices (default 4) with the kernels interpreted.
Every line it prints is marked ``[cpu rehearsal]`` and it never prints the
pass line.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

# variables that select interpret mode, a fallback, a retry, another
# backend or injected faults, and telemetry, whose stage timers and records
# add host work to every step: none may be set for a run that claims the
# device
FORBIDDEN_ENV = (
    "MAGI_ATTENTION_PALLAS_INTERPRET",
    "MAGI_ATTENTION_FALLBACK",
    "MAGI_ATTENTION_STEP_RETRIES",
    "MAGI_ATTENTION_KERNEL_BACKEND",
    "MAGI_ATTENTION_FAULT_INJECT",
    "MAGI_ATTENTION_TELEMETRY",
)

SEED = 0
TRAIN_STEPS = 3
N_DOCS = 5  # packed documents per batch, unequal seeded lengths

# Llama-3-8B widths (BASELINE.md config 5; models/convert.py
# config_from_hf): nothing about a layer's width is cut.
FULL = dict(
    dim=4096, n_heads=32, n_kv_heads=8, head_dim=128, ffn_hidden=14336,
    rope_theta=500000.0, n_layers=4, vocab_size=128256 // 8,
    tokens_per_chip=8192, attn_ref_tokens_per_chip=8192,
    model_ref_tokens_per_chip=1024,
)
CUTS = (
    "n_layers 32 -> 4 (one layer is 218M parameters = 0.87 GB of fp32 "
    "masters; all 32 are 28 GB, a chip holds 16)",
    "vocab_size 128256 -> 16032 (one chip's share of an 8-chip "
    "vocabulary-parallel deployment; the full embedding + head with "
    "gradients is 8.4 GB and its fp32 logits at 8k tokens 4.2 GB)",
)
# the same code at sizes the CPU interpreter finishes in minutes; g = 4 and
# head_dim 128 are kept so the same kernel variants are selected
TOY = dict(
    dim=256, n_heads=4, n_kv_heads=1, head_dim=128, ffn_hidden=512,
    rope_theta=500000.0, n_layers=2, vocab_size=512,
    tokens_per_chip=512, attn_ref_tokens_per_chip=256,
    model_ref_tokens_per_chip=256,
)

# Tolerances. Inputs are bf16: 8 significand bits, unit roundoff 2^-8 =
# 3.9e-3, about 1.7e-3 rms per rounding; every kernel matmul accumulates in
# fp32. Against an fp32 `highest` reference on the SAME bf16 inputs the
# kernel's own roundings are q * scale back to bf16, p to bf16 before p@v,
# ds to bf16 before ds@k and ds^T@q, and the bf16 result — a few
# independent 1.7e-3 roundings, i.e. a relative Frobenius error of 2e-3 to
# 4e-3. Accumulating in bf16 instead of fp32 would add one rounding per
# partial sum of a 128-long (q.k) or 512-long (p@v tile) contraction:
# sqrt(128) * 1.7e-3 = 1.9e-2 at the least. The bound sits between the two.
TOL_ATTN_REL = 8e-3  # rel_norm_err of out, dq, dk, dv
# The largest error of one element of dq, dk or dv over the tensor's largest
# element, the number a wrong tile shows in: a few of the roundings above on
# one element, 4 to 5 sigma over 1e7 elements. The chip read 4.1e-3 to
# 4.5e-3 on one chip and 3.9e-3 to 6.8e-3 on four (my chip runs, PR 30); a
# dq window accumulated on another tile's buffer read 0.25 (the one-pass
# backward before PR 30, PERF.md §6). The bound sits between the two.
TOL_GRAD_WORST_REL = 2.5e-2
# lse is fp32. Rounding q * scale to bf16 perturbs a logit by 0.088 *
# 1.7e-3 * sqrt(128) = 1.7e-3 rms; lse is a softmax-weighted mean of those
# errors, and on rows with few keys it IS one of them, so its maximum over
# 1e5 rows is about 4.5 sigma = 8e-3. bf16 accumulation would make the
# logit error 1.9e-2 rms, 8e-2 at the maximum.
TOL_LSE_ABS = 1.5e-2
# The CP model and its dense twin run the same bf16 network and differ only
# inside attention (bf16 kernel vs fp32 `highest`). That difference re-rolls
# every later bf16 rounding of the residual stream — 20 to 40 of them over
# a few layers, 1.7e-3 * sqrt(20..40) = 0.8e-2..1.1e-2 — so the logits bound
# cannot separate precisions (check (a) does); it catches wiring: a wrong
# position id, permutation, label shift or mask is an error of order 1.
TOL_LOGITS_REL = 2e-2
# A token's loss moves by its logit error, 1.1e-2 with either sign; the mean
# over the >= 1024 tokens of the check is 1.1e-2 / 32 = 3.4e-4, against a
# loss of ln(vocab) ~ 10 that is 3.4e-5 relative. Six sigma. An attention
# path an order less precise would land beyond it.
TOL_LOSS_REL = 2e-4


def _refuse_hidden_device_env() -> None:
    bad = [k for k in FORBIDDEN_ENV if k in os.environ]
    if bad:
        sys.exit(
            "chip_smoke: refusing to start with "
            + ", ".join(f"{k}={os.environ[k]!r}" for k in bad)
            + " set: each can hide the device behind an interpreter or a "
            "fallback, or adds host work to the steps. Unset and re-run."
        )


def _cache_entries(path: str) -> int:
    """Files in the compile cache; a directory named by
    JAX_COMPILATION_CACHE_DIR need not exist until JAX first writes to it."""
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def _parse_args(argv: list[str]) -> int | None:
    """None for the chip run; the virtual device count for a rehearsal."""
    if not argv:
        return None
    count = argv[1] if len(argv) == 2 else "4"
    if argv[0] != "--rehearse-cpu" or len(argv) > 2 or not (
        count.isdigit() and int(count) >= 1
    ):
        sys.exit("usage: chip_smoke.py [--rehearse-cpu [N_DEVICES >= 1]]")
    return int(count)


def main(argv: list[str]) -> int:
    rehearse = _parse_args(argv)
    _refuse_hidden_device_env()
    if importlib.util.find_spec("magiattention_tpu") is None:
        sys.exit(
            "chip_smoke: the magiattention_tpu package is not importable: "
            "run this script from the root of a checkout, not on its own."
        )
    tag = "[cpu rehearsal] " if rehearse else ""

    def say(msg: str) -> None:
        print(tag + msg, flush=True)

    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={rehearse}"
        ).strip()

    t_start = time.perf_counter()
    from importlib.metadata import version

    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    say(
        f"device: platform={device['platform']} "
        f"device_kind={device['kind']!r} count={device['count']} | "
        f"jax {version('jax')} jaxlib {version('jaxlib')} "
        f"libtpu {version('libtpu')}"
    )
    want = "cpu" if rehearse else "tpu"
    if jax.default_backend() != want or any(
        d.platform != want for d in devices
    ):
        print(
            f"chip_smoke: no TPU: jax.default_backend()="
            f"{jax.default_backend()!r}, devices={devices}. This script "
            "proves the program on the chip and does not run elsewhere "
            "(--rehearse-cpu is the toy CPU rehearsal).",
            file=sys.stderr,
        )
        return 1

    # seconds XLA spent compiling (or loading from the persistent cache)
    # each program, and how many came from the cache: the cold / warm
    # compile report of two consecutive runs against one cache directory
    compile_s: list[float] = []
    cache_hits: list[str] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None
    )
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_hits.append(event)
        if event == "/jax/compilation_cache/cache_hits" else None
    )

    cache = None
    if not rehearse:
        # chip runs only: XLA:CPU executables cached on one machine can
        # fault on another, and the rehearsal compiles in seconds anyway
        from magiattention_tpu.utils.compile_cache import (
            enable_persistent_cache,
        )

        cache = {"dir": enable_persistent_cache()}
        cache["entries_before"] = _cache_entries(cache["dir"])
        say(f"compile cache: {cache['dir']} "
            f"({cache['entries_before']} entries)")

    result = run(TOY if rehearse else FULL, devices, say)
    result["compile"] = {
        "programs": len(compile_s),
        "compile_or_load_s": round(sum(compile_s), 1),
        "persistent_cache_hits": len(cache_hits),
        # what a 2 s cache floor would leave to every later process
        "programs_under_2s": sum(t < 2 for t in compile_s),
        "under_2s_total_s": round(sum(t for t in compile_s if t < 2), 1),
    }
    say(f"compile: {result['compile']}")
    if cache:
        cache["entries_after"] = _cache_entries(cache["dir"])
        result["compile_cache"] = cache
    result["wall_s"] = round(time.perf_counter() - t_start, 1)
    return _emit(result, device, bool(rehearse), say)


def _emit(result: dict, device: dict, rehearse: bool, say) -> int:
    """Print the report line and, on a chip run, the result line; return the
    exit code. The result line is the LAST line of stdout and holds exactly
    ``ok`` and ``device`` (``platform``, ``kind``, ``count``): whoever runs
    the smoke parses that line and nothing else."""
    failed = [k for k, c in result["checks"].items() if not c["ok"]]
    say("report: " + json.dumps(
        {"device": device, "failed_checks": failed, **result}))
    if rehearse:
        # never the result line: a rehearsal proves nothing about the chip
        say("rehearsal " + ("FAILED: " + ", ".join(failed) if failed
                            else "finished; no result line on the cpu"))
        return 1 if failed else 0
    print(json.dumps({"ok": not failed, "device": device}), flush=True)
    return 1 if failed else 0


def _check(checks: dict, name: str, err: float, tol: float, say) -> None:
    ok = bool(err <= tol)  # NaN compares False
    checks[name] = {"err": float(err), "tol": tol, "ok": ok}
    say(f"  check {name}: err={err:.3e} tol={tol:.1e} "
        f"{'ok' if ok else 'FAILED'}")


def _flag(checks: dict, name: str, ok: bool, detail: str, say) -> None:
    checks[name] = {"ok": bool(ok), "detail": detail}
    say(f"  check {name}: {detail} {'ok' if ok else 'FAILED'}")


def _packed_docs(total: int, rng) -> list[int]:
    """cu_seqlens of N_DOCS documents of unequal, unaligned lengths."""
    import numpy as np

    while True:
        cuts = np.sort(rng.choice(np.arange(1, total), N_DOCS - 1, False))
        cu = [0, *cuts.tolist(), total]
        lens = np.diff(cu)
        if len(set(lens.tolist())) == N_DOCS and lens.min() >= total // 64:
            return cu


def _pallas_kernels(closed_jaxpr) -> dict[str, bool]:
    """{kernel body name: interpret flag} of every pallas_call in a traced
    program — what the compiled step really contains."""
    found: dict[str, bool] = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["jaxpr"].debug_info.func_name
                found[name] = bool(eqn.params["interpret"]) or found.get(
                    name, False
                )
            for p in eqn.params.values():
                for sub in p if isinstance(p, (tuple, list)) else (p,):
                    inner = getattr(sub, "jaxpr", sub)
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(closed_jaxpr.jaxpr)
    return found


def _distinct_devices(x) -> int:
    return len({s.device for s in x.addressable_shards})


def run(size: dict, devices, say) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from magiattention_tpu.models import llama
    from magiattention_tpu.api import (
        calc_attn, dispatch, magi_attn_flex_key, undispatch,
    )
    from magiattention_tpu.api.functools import (
        infer_attn_mask_from_cu_seqlens,
    )
    from magiattention_tpu.api.magi_attn_interface import _mgr
    from magiattention_tpu.common.mask import AttnMask
    from magiattention_tpu.csrc_backend.build import host_backend
    from magiattention_tpu.kernels import registry
    from magiattention_tpu.kernels.ffa import _should_interpret
    from magiattention_tpu.resilience.fallback import resilience_event_counts
    from magiattention_tpu.testing.precision import rel_norm_err
    from magiattention_tpu.testing.ref_attn import ref_attn

    cp = len(devices)
    mesh = Mesh(np.asarray(devices), ("cp",))
    rng = np.random.default_rng(SEED)
    hq, hk, d = size["n_heads"], size["n_kv_heads"], size["head_dim"]
    on_cpu = devices[0].platform == "cpu"
    checks: dict = {}
    info: dict = {"cp": cp, "host_planner": host_backend()}
    say(f"mesh: cp={cp} over {[str(dv) for dv in devices]}; host planner: "
        f"{info['host_planner']}")

    def varlen_key(total: int, dense_mask: bool = False):
        cu = _packed_docs(total, rng)
        qr, kr, types = infer_attn_mask_from_cu_seqlens(cu, cu, True)
        key = magi_attn_flex_key(
            qr, kr, types, total, total, mesh=mesh, cp_axis="cp"
        )
        mask = AttnMask.from_ranges(
            qr, kr, types, total_seqlen_q=total, total_seqlen_k=total
        ).mask_array if dense_mask else None
        return key, cu, mask

    def plan_info(key) -> dict:
        mgr = _mgr(key)
        return {
            "overlap_degree": mgr.comm_meta.overlap_degree,
            # the tier each stage EXECUTES, as the runtime reports it
            "stage_lowering": [
                d["lowering_executed"] for d in mgr._stage_telemetry_dicts()
            ],
            "chunk_size": key.chunk_size,
        }

    row_sharded = NamedSharding(mesh, P("cp"))

    # -- (a) attention through the public API vs ref_attn -----------------
    total_a = size["attn_ref_tokens_per_chip"] * cp
    key_a, cu_a, _ = varlen_key(total_a)
    info["attn_check"] = {"tokens": total_a, "cu_seqlens": cu_a,
                          **plan_info(key_a)}
    say(f"(a) attention: {total_a} tokens, docs {np.diff(cu_a).tolist()}, "
        f"hq={hq} hk={hk} d={d} bf16, plan {plan_info(key_a)}")
    q, k, v, do = (
        jax.device_put(
            jnp.asarray(rng.standard_normal((total_a, h, d)), jnp.bfloat16),
            row_sharded,
        )
        for h in (hq, hk, hk, hq)
    )

    def attn(q, k, v):
        out_d, meta = calc_attn(
            dispatch(q, key_a), dispatch(k, key_a, "kv"),
            dispatch(v, key_a, "kv"), key_a,
        )
        return undispatch(out_d, key_a), undispatch(meta.lse, key_a)

    def attn_loss(q, k, v):
        out, lse = attn(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32)), (
            out, lse)

    attn_grad = jax.jit(
        jax.value_and_grad(attn_loss, argnums=(0, 1, 2), has_aux=True)
    )
    kernels_a = _pallas_kernels(attn_grad.trace(q, k, v).jaxpr)
    t0 = time.perf_counter()
    (_, (out, lse)), grads = jax.block_until_ready(attn_grad(q, k, v))
    say(f"  fwd+bwd first call (compile + run): "
        f"{time.perf_counter() - t0:.1f} s; kernels {sorted(kernels_a)}")

    # eager route: the same three calls outside jit, op by op — plan and
    # index arrays are born on device 0 here and must still shard
    q_d = dispatch(q, key_a)
    out_e, _ = calc_attn(
        q_d, dispatch(k, key_a, "kv"), dispatch(v, key_a, "kv"), key_a
    )
    out_e = undispatch(out_e, key_a)
    _flag(checks, "dispatch_shards_on_every_device",
          _distinct_devices(q_d) == cp,
          f"dispatched q on {_distinct_devices(q_d)}/{cp} devices", say)

    # the backward this check ran, as the program resolved it from the
    # shapes (pin > rule), and the bodies traced into it
    info["attn_check"]["bwd"] = {
        "mode": registry.last_choice("ffa_bwd"),
        "bodies": sorted(b for b in kernels_a if "_bwd_" in b),
    }

    # the reference holds (L, L) fp32 logits: one document and one q head
    # at a time, on one device (a packed document attends to itself only)
    dev0 = devices[0]

    @jax.jit
    def ref_grad(q, k, v, do):
        def f(q, k, v):
            out, lse = ref_attn(q, k, v, np.tri(q.shape[0], dtype=bool))
            return jnp.sum(out.astype(jnp.float32) * do), (out, lse)

        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)

    g = hq // hk
    ro, rl, rdq = (np.zeros((total_a, hq, *t), np.float32)
                   for t in ((d,), (), (d,)))
    rdk, rdv = (np.zeros((total_a, hk, d), np.float32) for _ in range(2))
    host = [np.asarray(jax.device_get(x)) for x in (q, k, v, do)]
    host[3] = host[3].astype(np.float32)
    with jax.default_matmul_precision("highest"):
        for s0, s1 in zip(cu_a[:-1], cu_a[1:]):
            for h in range(hq):
                j = h // g
                # sliced on the host: an eager slice would compile a
                # program per document and head
                (_, (o1, l1)), (gq, gk, gv) = ref_grad(*(
                    jax.device_put(x[s0:s1, i:i + 1], dev0)
                    for x, i in zip(host, (h, j, j, h))))
                ro[s0:s1, h], rl[s0:s1, h] = (
                    np.asarray(o1[:, 0], np.float32), np.asarray(l1[:, 0]))
                rdq[s0:s1, h] = np.asarray(gq[:, 0], np.float32)
                rdk[s0:s1, j] += np.asarray(gk[:, 0], np.float32)
                rdv[s0:s1, j] += np.asarray(gv[:, 0], np.float32)
    f32 = lambda x: np.asarray(jax.device_get(x), np.float32)  # noqa: E731
    _check(checks, "attn_out", rel_norm_err(f32(out), f32(ro)),
           TOL_ATTN_REL, say)
    _check(checks, "attn_out_eager", rel_norm_err(f32(out_e), f32(ro)),
           TOL_ATTN_REL, say)
    _check(checks, "attn_lse", float(np.max(np.abs(f32(lse) - f32(rl)))),
           TOL_LSE_ABS, say)
    for name, got, ref in zip(("dq", "dk", "dv"), grads, (rdq, rdk, rdv)):
        _check(checks, f"attn_{name}", rel_norm_err(f32(got), f32(ref)),
               TOL_ATTN_REL, say)
        _check(checks, f"attn_{name}_worst",
               float(np.abs(f32(got) - ref).max() / np.abs(ref).max()),
               TOL_GRAD_WORST_REL, say)
    info["attn_check"]["bwd"]["worst_rel_err"] = {
        name: checks[f"attn_{name}_worst"]["err"]
        for name in ("dq", "dk", "dv")}
    say(f"  backward of (a): {info['attn_check']['bwd']}")
    del q, k, v, do, out, lse, grads, out_e, q_d

    # -- (b) the model: CP loss and logits vs the dense twin --------------
    cfg = llama.LlamaConfig(
        vocab_size=size["vocab_size"], dim=size["dim"],
        n_layers=size["n_layers"], n_heads=hq, n_kv_heads=hk, head_dim=d,
        ffn_hidden=size["ffn_hidden"], rope_theta=size["rope_theta"],
        dtype="bfloat16", remat=True,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(SEED))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    info["model"] = {
        "config": {k: getattr(cfg, k) for k in (
            "dim", "n_heads", "n_kv_heads", "head_dim", "ffn_hidden",
            "rope_theta", "n_layers", "vocab_size", "dtype", "remat")},
        "cuts": [] if on_cpu else list(CUTS),
        "params": n_params, "param_bytes": param_bytes,
    }
    say(f"(b) model: {info['model']['config']}")
    for cut in info["model"]["cuts"]:
        say(f"  cut: {cut}")
    say(f"  parameters: {n_params / 1e6:.1f} M, {param_bytes / 2**30:.2f} "
        f"GiB fp32 masters")
    params_dev0 = jax.device_put(params, dev0) if cp > 1 else params
    params = llama.shard_params(params, mesh, "cp")
    wq = params["layers"][0]["wq"]
    _flag(checks, "params_shards_on_every_device",
          _distinct_devices(wq) == cp and _distinct_devices(params["embed"])
          == cp, f"wq on {_distinct_devices(wq)}/{cp} devices, "
          f"shard {wq.addressable_shards[0].data.shape}", say)

    def batch(total: int, cu):
        toks = rng.integers(0, cfg.vocab_size, total, dtype=np.int32)
        labels = np.roll(toks, -1)
        labels[np.asarray(cu[1:]) - 1] = -1  # no target across documents
        return jnp.asarray(toks), jnp.asarray(labels)

    total_b = size["model_ref_tokens_per_chip"] * cp
    key_b, cu_b, mask_b = varlen_key(total_b, dense_mask=True)
    toks_b, labels_b = batch(total_b, cu_b)
    info["model_check"] = {"tokens": total_b, "cu_seqlens": cu_b,
                           **plan_info(key_b)}
    say(f"  {total_b} tokens, docs {np.diff(cu_b).tolist()}")

    @jax.jit
    def cp_loss_logits(params, toks, labels):
        logits = llama.forward(params, cfg, toks, key_b)
        loss = llama.masked_ce(logits, dispatch(labels, key_b))
        return loss, undispatch(logits, key_b)

    loss_cp, logits_cp = jax.device_get(
        cp_loss_logits(params, toks_b, labels_b))

    @jax.jit
    def dense_loss_logits(params, toks, labels):
        mask = jnp.asarray(mask_b)
        return (llama.loss_fn_dense(params, cfg, toks, labels, mask),
                llama.forward_dense(params, cfg, toks, mask))

    with jax.default_matmul_precision("highest"):
        loss_dn, logits_dn = jax.device_get(dense_loss_logits(
            params_dev0, *(jax.device_put(x, dev0)
                           for x in (toks_b, labels_b))))
    del params_dev0
    say(f"  step-0 loss: cp {float(loss_cp):.6f} dense {float(loss_dn):.6f}")
    _check(checks, "model_loss",
           abs(float(loss_cp) - float(loss_dn)) / abs(float(loss_dn)),
           TOL_LOSS_REL, say)
    _check(checks, "model_logits", rel_norm_err(logits_cp, logits_dn),
           TOL_LOGITS_REL, say)

    # -- train steps at full tokens per chip -------------------------------
    total_t = size["tokens_per_chip"] * cp
    key_t, cu_t, _ = varlen_key(total_t)
    toks_t, labels_t = batch(total_t, cu_t)
    info["train"] = {"tokens": total_t, "tokens_per_chip": total_t // cp,
                     "cu_seqlens": cu_t, **plan_info(key_t)}
    say(f"train: {total_t} tokens ({total_t // cp}/chip), docs "
        f"{np.diff(cu_t).tolist()}, plan {plan_info(key_t)}")
    kernels_t = _pallas_kernels(
        llama.train_step.trace(params, cfg, toks_t, labels_t, key_t).jaxpr
    )
    wq_before = np.asarray(jax.device_get(wq[:8, :8]))
    losses, step_s = [], []
    for step in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        params, loss = llama.train_step(params, cfg, toks_t, labels_t, key_t)
        jax.block_until_ready((params, loss))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
        say(f"  step {step}: loss {losses[-1]:.6f} wall {step_s[-1]:.2f} s"
            + (" (includes compile)" if step == 0 else ""))
    wq_after = np.asarray(jax.device_get(params["layers"][0]["wq"][:8, :8]))
    info["train"].update(losses=losses, step_wall_s=step_s)
    _flag(checks, "train_losses_finite",
          bool(np.all(np.isfinite(losses))) and len(losses) == TRAIN_STEPS,
          f"{TRAIN_STEPS} steps, losses {losses}", say)
    _flag(checks, "train_params_updated",
          bool(np.all(np.isfinite(wq_after)))
          and not np.array_equal(wq_before, wq_after),
          "wq changed and is finite", say)
    mem = [dv.memory_stats() for dv in devices]
    if all(m is not None for m in mem):
        info["peak_bytes_in_use"] = [m["peak_bytes_in_use"] for m in mem]
        say("peak_bytes_in_use per device: "
            + ", ".join(f"{b / 2**30:.2f} GiB"
                        for b in info["peak_bytes_in_use"]))

    # -- what ran ---------------------------------------------------------
    kernels = {**kernels_a, **kernels_t}
    info["kernels"] = {
        "pallas_bodies": sorted(kernels),
        "ffa_bwd_mode": registry.last_choice("ffa_bwd"),
        "gqa_pack_variant": {
            kind: registry.gqa_pack_variant(kind)
            for kind in ("fwd", "dq", "dkv")
        },
        "calc_attn_backend": registry.last_choice("calc_attn"),
    }
    say(f"kernels traced into the programs: {info['kernels']}")
    _flag(checks, "kernels_compiled_not_interpreted",
          (not _should_interpret() and not any(kernels.values()))
          or on_cpu,
          f"_should_interpret()={_should_interpret()}, interpreted bodies "
          f"{[k for k, i in kernels.items() if i]}", say)
    _flag(checks, "calc_attn_backend_ffa",
          registry.last_choice("calc_attn") == "ffa"
          and any(k.startswith("_fwd_kernel") for k in kernels_t),
          f"backend {registry.last_choice('calc_attn')}", say)
    events = resilience_event_counts()
    _flag(checks, "no_resilience_events", not events, f"events {events}",
          say)
    return {"sizes": {k: size[k] for k in (
        "tokens_per_chip", "attn_ref_tokens_per_chip",
        "model_ref_tokens_per_chip")}, "checks": checks, **info}


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
