"""Degradation chains (docs/resilience.md): keep the step alive when a
kernel or plan fails.

Three documented chains, all gated by ``MAGI_ATTENTION_FALLBACK=1``:

1. **Kernel ladder** (:func:`run_calc_attn`): when the FFA path raises —
   an injected ``kernel_lowering`` fault, a Pallas lowering error, or an
   XLA RESOURCE_EXHAUSTED — the runtime rebuilds its plans one rung down
   the tile ladder (:func:`tile_ladder`, derived from
   ``kernels/tile_policy.CANDIDATES``) and retries; when every rung fails
   it pins the runtime to the reference ``kernels/sdpa_online.py`` dense
   path. Degradation is sticky: later steps keep the surviving rung (or
   the reference backend) instead of re-failing every step.
2. **Planner fallback** (``dist_attn_runtime_mgr.py``): a dynamic
   (qo-comm) plan solve that raises falls back to the static solver plan.
3. **Bounded build retry** (``DistAttnRuntimeDict``): a runtime build that
   raises is retried once; a build that still fails propagates its typed
   error and is never cached.

Every hop emits a ``resilience`` telemetry record (action="fallback" /
"retry") so ``scripts/telemetry_report.py`` shows exactly how degraded a
run was. With ``MAGI_ATTENTION_FALLBACK`` unset, failures propagate
unchanged — and when no resilience flag at all is set, the guarded entry
points are never reached (functional/dist_attn.py gates on
``env/resilience.is_resilience_active``).
"""

from __future__ import annotations

from collections import Counter

from .. import telemetry
from ..env import resilience as env_resilience
from .errors import FallbackExhaustedError, InjectedFault
from .guards import check_outputs
from .inject import should_fire

# bounded retry budget for runtime/plan builds (attempts = 1 + RETRIES)
PLAN_BUILD_RETRIES = 1

# the final rung of the kernel ladder: the reference dense path. Kept as
# a module constant for compatibility; _descend_ladder consults the
# backend registry's calc_attn ladder, whose lowest-ranked rung is this.
REFERENCE_BACKEND = "sdpa_online"


def reference_backend() -> str:
    """Last rung of the registry's ``calc_attn`` ladder — the backend the
    kernel fallback chain pins when every tile rung has failed."""
    from ..kernels import registry as _registry

    rungs = _registry.ladder("calc_attn")
    return rungs[-1] if rungs else REFERENCE_BACKEND


def kernel_failure_types() -> tuple[type[BaseException], ...]:
    """Exception types the kernel ladder treats as recoverable: injected
    faults plus the runtime/lowering errors XLA and Pallas raise."""
    import jax

    return (InjectedFault, jax.errors.JaxRuntimeError)


# plain per-process event counts, maintained whether or not telemetry is
# on (telemetry.inc is a no-op when it is off): chip_smoke.py fails the run
# if any resilience action fired on a path that should never degrade
_EVENT_COUNTS: Counter[str] = Counter()


def resilience_event_counts() -> dict[str, int]:
    """``{"<action>@<site>": n}`` for every resilience action this process
    has recorded; empty when nothing descended, retried or recovered."""
    return dict(_EVENT_COUNTS)


def record_resilience_event(action: str, site: str, **extra) -> None:
    """One telemetry record + counter per resilience action."""
    _EVENT_COUNTS[f"{action}@{site}"] += 1
    telemetry.inc(f"resilience.{action}")
    telemetry.record_event("resilience", action=action, site=site, **extra)


def tile_ladder(bq: int, bk: int) -> list[tuple[int, int]]:
    """Descending retry rungs below the current (bq, bk): every
    ``tile_policy.CANDIDATES`` entry with strictly smaller padded area,
    largest first — each rung shrinks the kernel's VMEM residency, the
    resource whose exhaustion the ladder exists to survive."""
    from ..kernels.tile_policy import CANDIDATES

    area = bq * bk
    rungs = sorted(
        {c for c in CANDIDATES if c[0] * c[1] < area},
        key=lambda c: (-(c[0] * c[1]), -c[0]),
    )
    return rungs


def _corrupt_output(out):
    """The nan_output injection payload: poison one element so the
    numeric guards have something real to catch."""
    return out.at[(0,) * out.ndim].set(float("nan"))


def run_calc_attn(runtime, q, k, v, return_max_logits: bool = False):
    """Guarded execution of one ``calc_attn`` step (both CP runtimes).

    Only reached when a resilience flag is set; the fast path in
    ``functional/dist_attn.py`` bypasses this function entirely. With
    ``MAGI_ATTENTION_STEP_RETRIES`` > 0 the step watchdog governs instead:
    bounded retry through backend rungs with numeric quarantine
    (resilience/watchdog.py); otherwise behavior is exactly the
    pre-watchdog chain below.
    """
    if env_resilience.step_retries() > 0:
        from .watchdog import run_with_watchdog

        return run_with_watchdog(runtime, q, k, v, return_max_logits)
    stage = f"{type(runtime).__name__}.calc_attn"
    failures = kernel_failure_types()
    try:
        result = runtime._calc_attn_impl(q, k, v, return_max_logits)
    except failures as e:
        if not env_resilience.is_fallback_enable():
            raise
        result = _descend_ladder(
            runtime, q, k, v, return_max_logits, first_err=e,
            failures=failures,
        )
    if should_fire("nan_output"):
        result = (_corrupt_output(result[0]), *result[1:])
    check_outputs(stage, result[0], result[1])
    return result


def _descend_ladder(runtime, q, k, v, return_max_logits, first_err,
                    failures):
    """Retry down the tile ladder, then the reference dense path."""
    bq = getattr(runtime, "_bq", None)
    bk = getattr(runtime, "_bk", None)
    record_resilience_event(
        "fallback", "kernel_lowering", action_detail="ladder_start",
        blocks=[bq, bk], error=type(first_err).__name__,
    )
    if bq is not None:
        # pin the ladder's choice: the deferred auto-tile policy must not
        # overwrite a rung's plans on the retry
        runtime._auto_tile_pending = False
        runtime._tile_source = "pin"  # a rung is an explicit tile
        for hop, (rung_bq, rung_bk) in enumerate(tile_ladder(bq, bk)):
            try:
                runtime._build_plans(rung_bq, rung_bk)
                result = runtime._calc_attn_impl(
                    q, k, v, return_max_logits
                )
            except failures:
                record_resilience_event(
                    "fallback", "kernel_lowering",
                    action_detail="ladder_hop_failed", hop=hop,
                    blocks=[rung_bq, rung_bk],
                )
                continue
            record_resilience_event(
                "recovered", "kernel_lowering",
                action_detail="ladder_hop", hop=hop,
                blocks=[rung_bq, rung_bk],
            )
            return result
    # last rung: the reference dense path (kernels/sdpa_online.py)
    reference = reference_backend()
    runtime._backend_override = reference
    try:
        result = runtime._calc_attn_impl(q, k, v, return_max_logits)
    except Exception as e:
        runtime._backend_override = None
        raise FallbackExhaustedError(
            "kernel fallback chain exhausted: tile ladder and the "
            f"{reference} reference path all failed"
        ) from (first_err if isinstance(e, failures) else e)
    record_resilience_event(
        "recovered", "kernel_lowering", action_detail="reference_backend",
        backend=reference,
    )
    return result
