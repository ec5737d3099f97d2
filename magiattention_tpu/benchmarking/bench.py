"""Perf harness (ref: magi_attention/benchmarking/bench.py:47-1378).

Triton-style ``do_bench`` / ``perf_report`` re-designed for JAX/TPU: no CUDA
graphs or events — functions are jitted once and timing brackets
``block_until_ready`` with host perf counters (the dispatch overhead is
amortized over ``rep`` launches, or cancelled by the two-length scan slope).

Everything that measures starts at :func:`measuring_device`: a time, a rate
or a utilization is a statement about the TPU it names, so without one the
harness stops instead of timing the CPU under a device metric's name.
"""

from __future__ import annotations

import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import numpy as np


def measuring_device(what: str) -> dict:
    """The device gate of every measuring script: returns
    ``{"platform", "kind", "count", "peak_tflops"}`` for the TPUs JAX shows
    (peak looked up by ``device_kind``; an unknown kind raises) and turns
    the persistent compile cache on. Without a TPU it exits non-zero —
    a CPU run can check results and count work, never time a device."""
    from ..utils.compile_cache import enable_persistent_cache
    from .perf_report import peak_tflops

    devices = jax.devices()
    if jax.default_backend() != "tpu" or any(
        d.platform != "tpu" for d in devices
    ):
        raise SystemExit(
            f"{what}: no TPU (jax.default_backend()="
            f"{jax.default_backend()!r}, devices={devices}); this script "
            "measures the chip and does not run elsewhere"
        )
    enable_persistent_cache()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "peak_tflops": peak_tflops(devices[0].device_kind),
    }


def _echo(msg: str) -> None:
    """Benchmark-table output channel. The harness's tables and timing
    lines ARE its product (chip-run logs consume them), so they must
    not be gated behind MAGI_ATTENTION_LOG_LEVEL like library logging."""
    sys.stdout.write(msg + "\n")
    sys.stdout.flush()


def do_bench(
    fn: Callable[[], Any],
    warmup: int = 3,
    rep: int = 20,
    quantiles: Sequence[float] = (0.5, 0.2, 0.8),
) -> list[float]:
    """Time fn() in milliseconds; returns the requested quantiles."""
    for _ in range(warmup):
        out = fn()
    jax.block_until_ready(out)
    times = []
    for _ in range(rep):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) * 1e3)
    return [float(np.quantile(times, q)) for q in quantiles]


def do_bench_flops(
    fn: Callable[[], Any], flops: float, **kwargs
) -> tuple[float, float]:
    """(median ms, TFLOP/s)."""
    ms = do_bench(fn, **kwargs)[0]
    return ms, flops / (ms * 1e-3) / 1e12


def do_bench_mem(
    fn: Callable[[], Any], bytes_moved: float, **kwargs
) -> tuple[float, float]:
    """(median ms, GB/s)."""
    ms = do_bench(fn, **kwargs)[0]
    return ms, bytes_moved / (ms * 1e-3) / 1e9


def _make_scan_runner(
    body: Callable[[Any], Any], carry0: Any, length: int
) -> Callable[[], float]:
    """Compile + warm a ``length``-step chained scan of ``body``; returns a
    closure that executes it once and returns total wall SECONDS. The one
    place the timing mechanics live: the carried data dependence keeps every
    step in the program, and the trailing value fetch means the result was
    really produced before the clock stops."""
    import jax.numpy as jnp

    @jax.jit
    def run(c):
        def f(c, _):
            return body(c), None

        c, _ = jax.lax.scan(f, c, None, length=length)
        return c

    out = run(carry0)  # compile + warm
    jax.block_until_ready(out)

    def time_once() -> float:
        t0 = time.perf_counter()
        o = run(carry0)
        jax.block_until_ready(o)
        # consume one value on the host inside the timed region
        jnp.asarray(jax.tree_util.tree_leaves(o)[0]).ravel()[0].item()
        return time.perf_counter() - t0

    return time_once


def do_bench_scan(
    body: Callable[[Any], Any],
    carry0: Any,
    length: int = 8,
    reps: int = 3,
) -> float:
    """Per-iteration ms of ``body`` chained ``length`` times inside ONE jit
    via ``lax.scan``: per-dispatch host overhead amortizes over the scan.
    ``body`` must map
    carry -> carry of identical shape/dtype."""
    time_once = _make_scan_runner(body, carry0, length)
    return min(time_once() for _ in range(reps)) / length * 1e3


def do_bench_scan_slope(
    body: Callable[[Any], Any],
    carry0: Any,
    lengths: tuple[int, int] = (24, 96),
    reps: int = 3,
    verbose: bool = False,
    min_credible_ms: float | None = None,
) -> float:
    """Overhead-robust per-iteration ms of ``body``.

    Every executable launch carries a FIXED cost (host dispatch, program
    load, pipeline fill) that a single-scan timing folds into the per-step
    number; how large it is on the attached chip is not measured yet, which
    is why the slope is kept: it is right whatever the cost is.

    This helper times the SAME scanned body at two trip counts and
    returns the slope (T_long - T_short) / (L_long - L_short): the fixed
    launch cost appears in both totals and cancels exactly. Per-step cost
    must be trip-count-independent (it is: identical program, every step
    data-dependent on the last) for the slope to equal the true kernel
    time.

    A device measurement: off the TPU it raises. A harness that wants to
    exercise its plumbing on the CPU calls :func:`do_bench_scan` itself and
    labels the number as a CPU number.

    ``min_credible_ms``: physical floor on the per-step time (the caller
    knows its flop count and the chip ceiling; the slope does not). A
    slope BELOW the floor is an under-cancelled pair (observed 2026-08-01:
    250 TF/s reported on a 197 TF/s chip) and triggers the same fallback
    as the noise guard — the long-scan per-step time, a true upper bound.
    """
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            "do_bench_scan_slope times a TPU; jax.default_backend() is "
            f"{jax.default_backend()!r}"
        )
    short, long_ = lengths
    assert long_ > short
    t0 = time.perf_counter()

    run_short = _make_scan_runner(body, carry0, short)
    run_long = _make_scan_runner(body, carry0, long_)
    # PAIRED reps: each rep times short and long back-to-back so both see
    # the same host conditions, then contributes its own slope; the
    # median rejects a rep whose overhead drifted mid-pair. (Independent
    # best-of-reps runs would subtract overhead samples from different
    # moments — a 50 ms drift over the 72-step delta fakes ~0.7 ms/step.)
    slopes = []
    t_long_best = float("inf")
    for _ in range(max(reps, 2)):
        ts = run_short() * 1e3
        tl = run_long() * 1e3
        t_long_best = min(t_long_best, tl / long_)
        slopes.append((tl - ts) / (long_ - short))
    slope = float(np.median(slopes))
    ok = 0.0 < slope <= t_long_best
    floor_hit = (
        ok and min_credible_ms is not None and slope < min_credible_ms
    )
    if floor_hit:
        ok = False
    if verbose:
        if floor_hit:
            from .perf_report import MEASURED_CEILING_TFLOPS

            # the floor is anchored at the measured chip ceiling, so the
            # implied rate scales as floor/slope
            implied_tf = MEASURED_CEILING_TFLOPS * min_credible_ms / slope
        guard = "" if ok else (
            f" -> CREDIBILITY FLOOR ({min_credible_ms:.3f} ms): slope "
            f"implies a rate above the chip ceiling "
            f"({implied_tf:.0f} TF/s > {MEASURED_CEILING_TFLOPS:.0f}) — "
            f"under-cancelled pair, fallback to "
            f"len{long_} upper bound {t_long_best:.3f}"
            if floor_hit else
            f" -> NOISE GUARD: fallback to len{long_} upper bound "
            f"{t_long_best:.3f}"
        )
        _echo(
            f"  [slope timing incl compile {time.perf_counter()-t0:.0f}s: "
            f"per-rep slopes {[round(s, 3) for s in slopes]} ms/step"
            + guard
        )
    # noise guard: non-positive slope (long ran FASTER than short) or slope
    # above the long-scan per-step time (negative implied overhead) means
    # the pair medians are still contaminated; the long-scan per-step time
    # is a true upper bound on the kernel time.
    if not ok:
        return t_long_best
    return slope


def do_bench_scan_verbose(body, carry0, length=8, reps=3):
    """:func:`do_bench_scan` + a one-line wall-clock print (chip-run
    scripts want compile time visible in their logs)."""
    t0 = time.perf_counter()
    ms = do_bench_scan(body, carry0, length=length, reps=reps)
    _echo(f"  [total incl compile {time.perf_counter()-t0:.0f}s]")
    return ms


def make_consume_all_grads_body(grad_fn, dtype):
    """Timing body ``q -> q`` that consumes ALL of (dq, dk, dv).

    Load-bearing anti-DCE measurement logic: dk/dv come from a separate
    pallas_call that XLA dead-code-eliminates when unused, silently
    dropping ~60% of the backward from the measured program (caught on
    silicon when fwd+bwd timed faster than fwd alone). Every fwd+bwd
    timing harness must build its body through this helper or its
    sibling `make_consume_all_grads_kv_body` — use THIS one only when
    the closed-over operands are small (closure capture lowers them as
    HLO constants); at >~100 MB switch to the kv/carry variant.

    ``grad_fn(q) -> (dq, dk, dv)``; dk/dv enter the carry as a 1e-30-scaled
    scalar — numerically invisible, but a real data dependence XLA cannot
    fold away (mul-by-zero would be simplifiable; 1e-30 is not).
    """
    import jax.numpy as jnp

    def body(q):
        dq, dk, dv = grad_fn(q)
        touch = (jnp.sum(dk) + jnp.sum(dv)) * 1e-30
        return (q + 1e-3 * dq.astype(dtype) + touch.astype(dtype)).astype(dtype)

    return body


def make_consume_all_grads_kv_body(grad_fn, dtype):
    """`make_consume_all_grads_body` variant whose carry is ``(q, k, v)``.

    A jitted body that merely *closes over* a jax.Array lowers it as an
    HLO constant; at GB scale (config 5: 2.15 GB of captured kv chunks)
    that constant is copied into the executable and its compile request.
    Carrying k/v through the scan makes them jit ARGUMENTS — zero
    per-step cost (XLA aliases unmodified carry leaves) and a
    constant-free executable. Same anti-DCE contract as the q-only
    helper: ``grad_fn(q, k, v, *aux) -> (dq, dk, dv)``, all three
    consumed; any further carry leaves (e.g. a large cotangent seed w)
    ride through unchanged so they too stay arguments.
    """
    import jax.numpy as jnp

    def body(carry):
        q, k, v, *aux = carry
        dq, dk, dv = grad_fn(q, k, v, *aux)
        touch = (jnp.sum(dk) + jnp.sum(dv)) * 1e-30
        qn = (
            q + 1e-3 * dq.astype(dtype) + touch.astype(dtype)
        ).astype(dtype)
        return (qn, k, v, *aux)

    return body


def make_fwd_kv_body(fwd_fn, dtype):
    """Forward-only timing body with a ``(q, k, v, *aux)`` carry.

    Same no-captured-constants rationale as
    `make_consume_all_grads_kv_body`: ``fwd_fn(q, k, v, *aux) -> out``
    (out must be q-shaped) is called with every operand as a scan-carry
    leaf so GB-scale k/v lower as jit arguments, and the out->q chain
    provides the step-to-step data dependence.
    """

    def body(carry):
        q, k, v, *aux = carry
        return (fwd_fn(q, k, v, *aux).astype(dtype), k, v, *aux)

    return body


@dataclass
class Benchmark:
    """Declarative sweep spec (ref Benchmark/Mark :372)."""

    x_names: list[str]
    x_vals: list[Any]
    line_arg: str
    line_vals: list[Any]
    line_names: list[str]
    ylabel: str = "TFLOP/s"
    plot_name: str = "bench"
    args: dict[str, Any] = field(default_factory=dict)


def perf_report(benchmark: Benchmark):
    """Decorator: fn(**point) -> float; run() sweeps and returns rows."""

    def wrap(fn):
        def run(print_data: bool = True, save_path: str | None = None):
            rows = []
            for xv in benchmark.x_vals:
                row = {benchmark.x_names[0]: xv}
                for lv, ln in zip(benchmark.line_vals, benchmark.line_names):
                    kwargs = dict(benchmark.args)
                    kwargs[benchmark.x_names[0]] = xv
                    kwargs[benchmark.line_arg] = lv
                    try:
                        row[ln] = fn(**kwargs)
                    except Exception as e:  # noqa: BLE001
                        row[ln] = float("nan")
                        row[f"{ln}_error"] = type(e).__name__
                rows.append(row)
            if print_data:
                _print_table(rows)
            if save_path:
                _save_csv(rows, save_path)
            return rows

        fn.run = run
        return fn

    return wrap


def _print_table(rows: list[dict]) -> None:
    if not rows:
        return
    keys = list(rows[0].keys())
    widths = [max(len(str(k)), 12) for k in keys]
    _echo("  ".join(str(k).ljust(w) for k, w in zip(keys, widths)))
    for row in rows:
        _echo(
            "  ".join(
                (f"{row.get(k, ''):.2f}" if isinstance(row.get(k), float)
                 else str(row.get(k, ""))).ljust(w)
                for k, w in zip(keys, widths)
            )
        )


def _save_csv(rows: list[dict], path: str) -> None:
    import csv

    with open(path, "w", newline="") as f:
        writer = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
        writer.writeheader()
        writer.writerows(rows)
