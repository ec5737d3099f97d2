"""Run one cell of the benchmark and print its result line.

    python -m cellbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run, from the root of a checkout. It refuses to
start under a variable that could hide the device, exits non-zero within
seconds (and prints no result) when JAX shows no TPU or fewer chips than the
cell asks for, builds the cell from ``--seed``, warms up (set-up is the time
from the moment JAX shows the devices to the first measured step), runs whole
train steps one at a time until ``--seconds`` have passed, reads the peak memory,
checks the program against the plain reference, and prints as the last line
of stdout one JSON object::

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

(``breakdown`` with ``--trace 1``; ``checks`` is every number the comparison
read beside its limit, and the same are the last lines of stderr.)

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the run sets ``MAGI_ATTENTION_PROFILE_MODE=1``, profiles
``TRACED_STEPS`` further steps and reports the per-layer metrics instead.
The line before the last, ``report: {...}``, carries everything else.

``--rehearse-cpu N`` runs the same code at toy widths on N virtual CPU
devices with interpreted kernels. Every line it prints is marked ``[cpu
rehearsal]`` and it never prints a result line.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()  # the process's start, near enough

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

# Variables that select interpret mode, a fallback, a retry, another
# backend, injected faults, or the telemetry store whose persisted policies
# feed backend selection (chip_smoke.py's list): none may be set.
FORBIDDEN_ENV = (
    "MAGI_ATTENTION_PALLAS_INTERPRET",
    "MAGI_ATTENTION_FALLBACK",
    "MAGI_ATTENTION_STEP_RETRIES",
    "MAGI_ATTENTION_KERNEL_BACKEND",
    "MAGI_ATTENTION_FAULT_INJECT",
    "MAGI_ATTENTION_TELEMETRY",
)
HOST_SPANS = ("step_dispatch", "loss_readback", "batch_handover")
WARMUP_STEPS = 2
TRACED_STEPS = 3
REHEARSAL_STEPS = 2
REHEARSAL_TOKENS_PER_CHIP = 512
REHEARSAL_CHECK_TOKENS_PER_CHIP = 256
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def parse_args(argv: list[str]) -> argparse.Namespace:
    from cellbench.manifest import ROOT

    ap = argparse.ArgumentParser(prog="python -m cellbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearse-cpu", type=int, default=0, metavar="N_DEVICES",
        help="toy-width rehearsal on N virtual CPU devices; no result line")
    ap.add_argument(
        "--root", default=ROOT,
        help="directory holding BENCHMARK.json and cellbench/ data files")
    return ap.parse_args(argv)


def refuse_hidden_device_env() -> None:
    bad = [k for k in FORBIDDEN_ENV if k in os.environ]
    if bad:
        sys.exit(
            "cellbench: refusing to start with "
            + ", ".join(f"{k}={os.environ[k]!r}" for k in bad)
            + " set: each can hide the device behind an interpreter, a "
            "fallback or a persisted policy. Unset and re-run.")


def result_line(
    correct: bool, attempted: int, failed: int, metrics: dict, units: dict,
    device: dict, breakdown: dict | None, checks: dict,
) -> str:
    """The one line the driver reads: the contract's keys, every value as
    measured, and last ``checks``: each number the comparison read beside
    its limit."""
    out = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {
            name: {"value": value, "unit": units[name]}
            for name, value in metrics.items()
        },
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {
        name: {"err": c["err"], "limit": c["tol"]}
        for name, c in checks.items()}
    return json.dumps(out)


def peak_hbm_bytes(devices) -> tuple[int | None, list]:
    """Peak HBM held on the fullest device, and every device's stats. On a
    TPU the scratch of the running program (XLA's temp buffers) is not in
    ``peak_bytes_in_use`` but in ``peak_bytes_reserved``; it is not free
    either (``largest_free_block_bytes`` = limit - in use - reserved), and
    it is what an activation-heavy step runs out of. The peak is their sum;
    both repeat exactly from run to run. None where the backend reports no
    statistics (the CPU)."""
    stats = [d.memory_stats() for d in devices]
    held = [
        s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
        for s in stats if s
    ]
    return (max(held) if held else None), stats


def events_since(before: dict, now: dict) -> dict:
    """The resilience events counted between two readings of the program's
    per-process counters. ``correct`` judges the run, not the process: a
    test worker's earlier tests may have left counts behind (on the chip a
    process starts at zero, so the two are the same there)."""
    return {k: n - before.get(k, 0) for k, n in now.items()
            if n != before.get(k, 0)}


def cell_sizes(cell, family, rehearse: int) -> tuple[dict, int, int | None, int]:
    """``(configuration, tokens, window, tokens of the check)`` as run: the
    cell's own, or for a rehearsal the family's toy widths with the tokens
    and the window shrunk in proportion (``make_mask`` shrinks the length
    law with the tokens)."""
    cfg, traffic = dict(cell.config), cell.traffic
    window = cfg.get("sliding_window") if traffic["window"] == "config" else (
        traffic["window"])
    if not rehearse:
        return cfg, traffic["tokens"], window, (
            traffic["check_tokens_per_chip"] * cell.chips)
    tokens = REHEARSAL_TOKENS_PER_CHIP * cell.chips
    cfg.update(family.TOY)
    if window is not None:
        window = max(1, window * tokens // traffic["tokens"])
    return cfg, tokens, window, REHEARSAL_CHECK_TOKENS_PER_CHIP * cell.chips


def measure_steps(step, params, batches, stop, compiles: list) -> dict:
    """Run ``step`` one at a time, as a training loop that logs its loss
    does, until ``stop(steps done, seconds since the first began)``."""
    import jax

    step_ms, losses, failed = [], [], 0
    t_first = time.perf_counter()
    while True:
        toks, labels = batches[len(step_ms) % len(batches)]
        seen = len(compiles)
        t0 = time.perf_counter()
        params, loss = step(params, toks, labels)
        jax.block_until_ready((params, loss))
        t1 = time.perf_counter()
        losses.append(float(loss))
        step_ms.append((t1 - t0) * 1e3)
        failed += not math.isfinite(losses[-1]) or len(compiles) > seen
        if stop(len(step_ms), t1 - t_first):
            return {"params": params, "step_ms": step_ms, "losses": losses,
                    "failed": failed, "elapsed_s": t1 - t_first}


def traced_steps(step, params, batches, trace_dir: str) -> dict:
    """``TRACED_STEPS`` steps under ``jax.profiler``, the host's part of
    each in a span of its own; returns the parameters, the steps' host
    times and the path of the ``.xplane.pb``."""
    import glob

    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the spans below are all we need
    step_ms = []
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        for i in range(TRACED_STEPS):
            with jax.profiler.TraceAnnotation("batch_handover"):
                toks, labels = batches[i % len(batches)]
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("step_dispatch"):
                params, loss = step(params, toks, labels)
            with jax.profiler.TraceAnnotation("loss_readback"):
                jax.block_until_ready((params, loss))
                float(loss)
            step_ms.append((time.perf_counter() - t0) * 1e3)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb, found {found}")
    return {"params": params, "step_ms": step_ms, "xplane": found[0]}


def reference_check(
    family, mcfg, cfg: dict, spec, params, batch, mesh, devices, say
) -> dict:
    """Compare what the family's check program returns for ``spec`` with
    the family's plain reference on the same parameters and tokens, name by
    name as the family's ``CHECKS`` says."""
    import jax
    import jax.numpy as jnp

    from cellbench import reference

    key = family.make_key(spec, mesh)
    toks, labels = (jnp.asarray(x) for x in batch)
    t0 = time.perf_counter()
    got = jax.device_get(family.check_program(mcfg, key)(params, toks, labels))
    t1 = time.perf_counter()
    one = devices[0]
    ref = jax.device_get(family.reference(
        jax.device_put(params, one), cfg, jax.device_put(toks, one),
        jax.device_put(labels, one), spec))
    t2 = time.perf_counter()
    checks = reference.compare(
        got, ref, family.CHECKS, targets=int((batch[1] >= 0).sum()))
    for name, c in checks.items():
        say(f"  check {name}: err={c['err']:.3e} tol={c['tol']:.1e} "
            f"{'ok' if c['ok'] else 'FAILED'}")
    say(f"  check took {t1 - t0:.1f} s (program) + {t2 - t1:.1f} s "
        f"(reference) at {spec.tokens} tokens")
    return checks


def main(argv: list[str]) -> int:
    args = parse_args(argv)
    rehearse = args.rehearse_cpu
    refuse_hidden_device_env()
    if importlib.util.find_spec("magiattention_tpu") is None:
        sys.exit(
            "cellbench: the magiattention_tpu package is not importable: "
            "run from the root of a checkout, not beside the benchmark alone.")
    tag = "[cpu rehearsal] " if rehearse else ""

    def say(msg: str) -> None:
        print(f"{tag}[{time.perf_counter() - _T_START:6.1f} s] {msg}",
              flush=True)

    from cellbench import manifest

    cell = manifest.load_cell(args.root, args.workload)
    if rehearse and "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={rehearse}").strip()
    if args.trace:
        # read per trace by utils/profiling.py; not a runtime-key variable
        os.environ["MAGI_ATTENTION_PROFILE_MODE"] = "1"

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"device: {device} | workload {cell.name} seed {args.seed} "
        f"seconds {args.seconds} trace {args.trace}")
    want = "cpu" if rehearse else "tpu"
    if jax.default_backend() != want or len(devices) < cell.chips:
        print(
            f"cellbench: {cell.name} needs {cell.chips} {want} device(s); "
            f"jax.default_backend()={jax.default_backend()!r}, devices="
            f"{devices}. The benchmark measures on the chip and does not "
            "run elsewhere (--rehearse-cpu N is the toy CPU rehearsal).",
            file=sys.stderr)
        return 1
    devices = devices[: cell.chips]
    # Set-up is counted from here. Before it lie the imports of JAX and the
    # start of the TPU runtime, 10 to 20 s that vary from run to run with
    # nothing the program or the benchmark does (reported apart as
    # device_start_s); everything the program does, its import too, is after.
    t_device = time.perf_counter()

    compiles: list[float] = []
    cache_hits: list[str] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(secs)
        if event == COMPILE_EVENT else None)
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_hits.append(event)
        if event == CACHE_HIT_EVENT else None)
    if not rehearse:
        from magiattention_tpu.utils.compile_cache import (
            enable_persistent_cache,
        )

        say(f"compile cache: {enable_persistent_cache()}")
        if args.trace:
            # keep the scope names: by default the cache key ignores them
            # and a program cached by an untraced run would be loaded
            jax.config.update(
                "jax_compilation_cache_include_metadata_in_key", True)

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from cellbench import flops, metrics_read, peaks, trace_reduce, traffic_gen

    # -- build the cell from the seed --------------------------------------
    family = manifest.load_family(args.root, cell.config["family"])
    events_before = family.what_ran()["resilience_events"]
    generate = manifest.load_generator(args.root, cell.traffic["generator"])
    traffic = cell.traffic
    cfg, tokens, window, check_tokens = cell_sizes(cell, family, rehearse)
    spec = traffic_gen.make_mask(traffic, tokens, window, args.seed, generate)
    mesh = Mesh(np.asarray(devices), ("cp",))
    mcfg = family.model_config(cfg)
    params = family.init_params(mcfg, mesh, args.seed)
    jax.block_until_ready(params)
    say("parameters are on the device(s)")
    key, plan_ms = family.timed_plan(spec, mesh)
    replicated = NamedSharding(mesh, P())
    batches = [
        tuple(jax.device_put(jnp.asarray(x), replicated) for x in batch)
        for batch in traffic_gen.token_batches(
            spec, cfg["vocab_size"], args.seed, traffic["batches"])
    ]
    plan = family.plan_facts(key, flops.rows_area(spec))
    say(f"cell: family {cfg['family']}, {spec.tokens} tokens, "
        f"{len(spec.cu_seqlens) - 1} documents, window {spec.window}, "
        f"band area {flops.band_area(spec)}; plan {plan_ms:.1f} ms, "
        f"{plan['slices']} slices, chunk {plan['chunk_size']}, overlap "
        f"degree {plan['overlap_degree']}, stages {plan['stage_lowering']}")

    def step(params, toks, labels):
        return family.train_step(params, mcfg, toks, labels, key)

    kernels = family.pallas_kernels(jax.make_jaxpr(step)(params, *batches[0]))
    say(f"step traced: kernels {sorted(kernels)}")

    # -- warm up: every shape the window uses ------------------------------
    warm = measure_steps(
        step, params, batches, lambda n, _: n >= WARMUP_STEPS, compiles)
    params = warm["params"]
    setup = {
        "programs": len(compiles), "compile_or_load_s": sum(compiles),
        "cache_hits": len(cache_hits),
    }
    setup_s = time.perf_counter() - t_device
    say(f"set-up {setup_s:.1f} s: {setup}; warm-up steps "
        f"{[round(x) for x in warm['step_ms']]} ms")

    # -- the measured window ------------------------------------------------
    window_stop = (
        (lambda n, _: n >= REHEARSAL_STEPS) if rehearse
        else (lambda _, elapsed: elapsed >= args.seconds))
    run = measure_steps(step, params, batches, window_stop, compiles)
    params = run["params"]
    peak_bytes, stats = peak_hbm_bytes(devices)
    steps = len(run["step_ms"])
    say(f"window: {steps} steps in {run['elapsed_s']:.2f} s, losses "
        f"{run['losses'][0]:.4f} .. {run['losses'][-1]:.4f}, failed "
        f"{run['failed']}")

    facts = {**plan, **setup, "plan_ms": plan_ms, "step_ms": run["step_ms"],
             "device_start_s": t_device - _T_START}
    reduction = None
    if args.trace:
        trace_dir = os.path.join(manifest.ROOT, ".cellbench_trace", cell.name)
        traced = traced_steps(step, params, batches, trace_dir)
        params = traced["params"]
        facts["traced_step_ms"] = traced["step_ms"]
        events = trace_reduce.load_xplane(traced["xplane"], HOST_SPANS)
        if events.devices or not rehearse:  # the CPU has no device plane
            reduction = trace_reduce.reduce_trace(
                events, trace_reduce.load_classes(args.root), TRACED_STEPS)
        say(f"traced {TRACED_STEPS} steps: "
            f"{[round(x) for x in traced['step_ms']]} ms, {traced['xplane']}")

    # -- correctness, outside the window ------------------------------------
    check_spec = traffic_gen.make_mask(
        traffic, check_tokens, window, args.seed, generate)
    checks = reference_check(
        family, mcfg, cfg, check_spec, params,
        traffic_gen.token_batches(
            check_spec, cfg["vocab_size"], args.seed, 1)[0],
        mesh, devices, say)
    ran = family.what_ran()
    ran["resilience_events"] = events_since(
        events_before, ran["resilience_events"])
    interpreted = [k for k, i in kernels.items() if i]
    flags = {
        "reference": all(c["ok"] for c in checks.values()),
        "no_resilience_event": not ran["resilience_events"],
        "kernels_compiled": bool(kernels) and (
            bool(rehearse)
            or not (interpreted or ran["should_interpret"])),
        "backend_ffa": ran["calc_attn_backend"] == "ffa" and any(
            k.startswith("_fwd_kernel") for k in kernels),
    }
    correct = all(flags.values())

    # -- metrics -------------------------------------------------------------
    entries = cell.per_layer if args.trace else cell.end_to_end
    units = {m["name"]: m["unit"] for m in entries}
    breakdown = None
    if args.trace:
        ctx = metrics_read.Context(
            cell=cell, family=family, config=cfg, spec=spec,
            # a rehearsal only exercises the readers; it prints no result
            peaks=peaks.peaks_for(
                "TPU v5 lite" if rehearse else device["kind"]),
            facts=facts, trace=reduction)
        values = {
            m["name"]: metrics_read.read_metric(args.root, m["name"], ctx)
            for m in entries}
        if reduction is not None:
            device.update(
                busy_s=reduction.busy_s(), window_s=reduction.window_s)
            breakdown = {
                "device_ops": reduction.top_ops(10),
                "idle_gaps": reduction.idle_by_host_span(10),
            }
    else:
        values = {
            "tokens_per_s": spec.tokens * steps / run["elapsed_s"],
            "peak_hbm_gib": None if peak_bytes is None
            else peak_bytes / 2**30,
            "setup_s": setup_s,
        }
    metrics = {k: v for k, v in values.items() if v is not None and k in units}
    device["memory_peak_bytes"] = peak_bytes

    say("report: " + json.dumps({
        "workload": cell.name, "seed": args.seed, "flags": flags,
        "checks": checks, "kernels": sorted(kernels), "what_ran": ran,
        "plan": {k: v for k, v in plan.items() if k != "rank_rows"},
        "setup": setup, "steps": steps, "elapsed_s": run["elapsed_s"],
        "step_ms": run["step_ms"], "losses": run["losses"],
        "traced_step_ms": facts.get("traced_step_ms"),
        "memory_stats": stats,
        "metrics": metrics, "breakdown": breakdown,
        "wall_s": time.perf_counter() - _T_START,
    }))
    if rehearse:
        say("rehearsal " + ("finished; no result line on the cpu" if correct
                            else f"FAILED: {flags}"))
        return 0 if correct else 1
    print(result_line(correct, steps, run["failed"], metrics, units, device,
                      breakdown, checks), flush=True)
    for name, c in checks.items():
        print(f"check {name}: err {c['err']!r} limit {c['tol']!r}",
              file=sys.stderr)
    print("flags: " + " ".join(f"{k}={v}" for k, v in flags.items()),
          file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
