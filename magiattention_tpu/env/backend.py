"""Backend-registry pins and the telemetry store's directory.

The unified backend registry (kernels/registry.py) resolves every
attention-backend decision as pin > the call site's rule over shapes. This
module owns the *pin* layer: typed getters that read one
``MAGI_ATTENTION_BACKEND_*`` key per decision (``MAGI_ATTENTION_KERNEL_BACKEND``
for ``calc_attn``) and return an explicit backend name, or ``None`` for
"no pin, let the rule decide". The three getters whose pin once had an
alias first refuse a removed key (env/general.py:REMOVED_ENV_KEYS).

``MAGI_ATTENTION_STORE_DIR`` places the persistent telemetry store
(telemetry/store.py), which is active exactly when telemetry is on.
"""

from __future__ import annotations

import os

from .general import _get_str, refuse_removed_keys


def kernel_backend_pin() -> str | None:
    """The MAGI_ATTENTION_KERNEL_BACKEND value as a registry pin: the
    explicit value when set, None when unset (general.kernel_backend()
    folds the default 'ffa' in — here the registry's rule supplies it)."""
    val = os.environ.get("MAGI_ATTENTION_KERNEL_BACKEND")
    return val.lower() if val else None


def ffa_bwd_pin() -> str | None:
    """Pin for the split-vs-fused FFA backward
    (MAGI_ATTENTION_BACKEND_FFA_BWD): 'fused' | 'split' | None.

    A 'fused' pin is still subject to the call site's feasibility guards
    (VMEM residency, meta layout)."""
    refuse_removed_keys()
    val = _get_str("MAGI_ATTENTION_BACKEND_FFA_BWD", "").lower()
    return val if val in ("fused", "split") else None


def mixed_blocks_pin() -> str | None:
    """Pin for mixed-granularity dispatch
    (MAGI_ATTENTION_BACKEND_MIXED_BLOCKS): 'mixed' | 'single' | None.

    'mixed' skips the profitability gate but still degrades to single
    when the mask yields a trivial partition."""
    refuse_removed_keys()
    val = _get_str("MAGI_ATTENTION_BACKEND_MIXED_BLOCKS", "").lower()
    return val if val in ("mixed", "single") else None


def serve_decode_pin() -> str | None:
    """Pin for the serve decode rung (MAGI_ATTENTION_BACKEND_SERVE_DECODE):
    'paged_decode_sharded' | 'paged_decode_spec' | 'paged_decode_int8' |
    'paged_decode' | 'gather_ffa' | 'dense' | None.

    The resilience ladder still descends from the pinned rung on kernel
    failure, and a pin remains subject to the call site's feasibility
    guards (shard divisibility, cache dtype, 1-row vs multi-row step) — an
    infeasible pin starts at the first feasible rung below it."""
    refuse_removed_keys()
    val = _get_str("MAGI_ATTENTION_BACKEND_SERVE_DECODE", "").lower()
    if val in (
        "paged_decode_sharded",
        "paged_decode_spec",
        "paged_decode_int8",
        "paged_decode",
        "gather_ffa",
        "dense",
    ):
        return val
    return None


def nsa_slc_pin() -> str | None:
    """Pin for the NSA selected-block branch
    (MAGI_ATTENTION_BACKEND_NSA_SLC): 'block_sparse_pallas' |
    'gathered_dense' | None."""
    val = _get_str("MAGI_ATTENTION_BACKEND_NSA_SLC", "").lower()
    if val in ("block_sparse_pallas", "gathered_dense"):
        return val
    return None


def store_dir() -> str:
    """Directory of the persistent telemetry store (history JSONL files +
    compacted store.json). Empty default = '<telemetry_dir>/store'."""
    return _get_str("MAGI_ATTENTION_STORE_DIR", "")
