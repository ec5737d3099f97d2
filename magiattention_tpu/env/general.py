"""General runtime toggles (ref: magi_attention/env/general.py:56-287).

Flag names keep the ``MAGI_ATTENTION_`` prefix for drop-in familiarity with the
reference; values are read lazily on each call so tests can monkeypatch
``os.environ``.
"""

from __future__ import annotations

import os


def _get_bool(name: str, default: bool = False) -> bool:
    return os.environ.get(name, "1" if default else "0") == "1"


def _get_int(name: str, default: int) -> int:
    return int(os.environ.get(name, str(default)))


def _get_float(name: str, default: float) -> float:
    return float(os.environ.get(name, str(default)))


def _get_str(name: str, default: str) -> str:
    return os.environ.get(name, default)


def log_level() -> str:
    return _get_str("MAGI_ATTENTION_LOG_LEVEL", "WARNING").upper()


def is_sanity_check_enable() -> bool:
    """Expensive invariant checks throughout solver/comm planning."""
    return _get_bool("MAGI_ATTENTION_SANITY_CHECK")


def is_verify_plans_enable() -> bool:
    """Run the static plan verifier (analysis/verifier.py R1-R5) at
    plan-build time and raise PlanVerificationError on error-severity
    violations. Plan-time only — never on the step hot path."""
    return _get_bool("MAGI_ATTENTION_VERIFY_PLANS")


def kernel_backend() -> str:
    """Attention kernel backend: ffa | sdpa | sdpa_online."""
    return _get_str("MAGI_ATTENTION_KERNEL_BACKEND", "ffa").lower()


def precision() -> str:
    """Precision override for attention compute: default | fp32 | bf16."""
    return _get_str("MAGI_ATTENTION_PRECISION", "default").lower()


def is_profile_mode_enable() -> bool:
    """Wrap hot-path functions in profiler scopes (utils/profiling.py
    instrument_scope — the ref nvtx.instrument_nvtx analogue, nvtx.py:81)."""
    return _get_bool("MAGI_ATTENTION_PROFILE_MODE")


def is_telemetry_enable() -> bool:
    """Record runtime telemetry (telemetry/ registry): dispatch balance,
    per-stage comm volumes, plan/step timings, cache stats — exported as
    JSONL. Off by default: zero overhead on the hot path, same contract as
    MAGI_ATTENTION_PROFILE_MODE."""
    return _get_bool("MAGI_ATTENTION_TELEMETRY")


def telemetry_dir() -> str:
    """Directory for telemetry JSONL files (one per writer,
    ``magiattention-<host>-<pid>-<token>.jsonl``); read by
    telemetry/registry.py."""
    return _get_str("MAGI_ATTENTION_TELEMETRY_DIR", "telemetry")


def is_range_merge_enable() -> bool:
    """Merge band-compatible adjacent slices before kernel planning
    (kernels/ffa_plan.py build_ffa_plan -> mask_utils.merge_band_slices;
    the ref merges at its kernel entry, functional/flex_flash_attn.py:87)."""
    return _get_bool("MAGI_ATTENTION_RANGE_MERGE", default=True)


def runtime_dict_size() -> int:
    """LRU capacity of the per-mesh runtime cache."""
    return _get_int("MAGI_ATTENTION_RUNTIME_DICT_SIZE", 100)


def is_plan_cache_enable() -> bool:
    """Solved-plan cache one level below the traced-runtime LRU
    (dist_attn_runtime_mgr.py): repeated mask signatures skip the solver
    entirely; a miss still seeds the next incremental re-solve. Reuse never
    changes which plan is produced for a signature, so (like
    MAGI_ATTENTION_VERIFY_PLANS) this is not a runtime-cache-key flag."""
    return _get_bool("MAGI_ATTENTION_PLAN_CACHE", default=True)


def plan_cache_size() -> int:
    """LRU capacity of the solved-plan cache (entries = mask signatures)."""
    return _get_int("MAGI_ATTENTION_PLAN_CACHE_SIZE", 100)


def is_plan_store_enable() -> bool:
    """On-disk tier of the solved-plan cache (meta/plan_store.py): plans
    persist across processes and restarts in a shared directory, keyed by
    the mask signature digest. Like MAGI_ATTENTION_PLAN_CACHE, reuse is
    byte-exact (every load is checksum-verified and re-verified by R1-R5),
    so this is not a runtime-cache-key flag."""
    return _get_bool("MAGI_ATTENTION_PLAN_STORE")


def plan_store_dir() -> str:
    """Directory of the on-disk plan store (shared across processes)."""
    return _get_str("MAGI_ATTENTION_PLAN_STORE_DIR", "plan_store")


def is_incremental_solve_enable() -> bool:
    """Dynamic-solver incremental re-solve: diff the mask's rectangles
    against the previous solve's state and re-run the assignment algorithm
    only on changed rectangles (meta/solver/dynamic_attn_solver.py). May
    produce a different (equally verified) plan than a cold solve, so it IS
    part of the runtime cache key."""
    return _get_bool("MAGI_ATTENTION_INCREMENTAL_SOLVE", default=True)


def min_chunks_per_rank() -> int:
    """Lower bound on dispatch chunks per rank when auto-deriving chunk_size
    (api/magi_attn_interface.py _auto_chunk_size; ref env/general.py:263 —
    default there is 8, here 4: TPU plans favor fewer, larger chunks)."""
    return _get_int("MAGI_ATTENTION_MIN_CHUNKS_PER_RANK", 4)


def is_cpp_backend_enable() -> bool:
    """Use the C++ host backend for ranges / solver hot loops when built."""
    return _get_bool("MAGI_ATTENTION_CPP_BACKEND", default=True)


def is_interpret_mode_enable() -> bool:
    """Force Pallas kernels into interpreter mode (CPU testing)."""
    return _get_bool("MAGI_ATTENTION_PALLAS_INTERPRET")


def jit_cache_dir() -> str:
    """On-disk cache for the native (C) host backend's build artifacts
    (csrc_backend/build.py)."""
    return _get_str(
        "MAGI_ATTENTION_JIT_CACHE_DIR",
        os.path.join(os.path.expanduser("~"), ".cache", "magiattention_tpu"),
    )


def jax_compilation_cache_dir() -> str:
    """JAX persistent compilation cache directory (utils/compile_cache.py);
    empty = caller's default. Not a MAGI_ key — it is JAX's own knob,
    surfaced here so key ownership stays in env/."""
    return _get_str("JAX_COMPILATION_CACHE_DIR", "")


class scoped_env:
    """Temporarily set/del environment variables, restoring on exit — the
    ONE sanctioned ``os.environ`` mutation point outside process startup
    (lint rule MAGI-L001 allows env/ only). Values of ``None`` unset the
    key. Used by testing/flag_generator.with_flags and test fixtures."""

    def __init__(self, overrides: dict[str, str | None]) -> None:
        self._overrides = dict(overrides)
        self._saved: dict[str, str | None] = {}

    def __enter__(self) -> "scoped_env":
        for key, val in self._overrides.items():
            self._saved[key] = os.environ.get(key)
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = str(val)
        return self

    def __exit__(self, *exc) -> None:
        for key, old in self._saved.items():
            if old is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = old


# flags that change numerics / planning output and therefore must be part of
# every runtime cache key (ref: env/ffa.py:125 ENV_KEYS_AFFECTING_COMPILATION)
ENV_KEYS_AFFECTING_RUNTIME: tuple[str, ...] = (
    "MAGI_ATTENTION_KERNEL_BACKEND",
    "MAGI_ATTENTION_PRECISION",
    "MAGI_ATTENTION_RANGE_MERGE",
    # HP reduce changes the traced collective program (wire dtype)
    "MAGI_ATTENTION_FWD_HIGH_PRECISION_REDUCE",
    "MAGI_ATTENTION_BWD_HIGH_PRECISION_REDUCE",
    "MAGI_ATTENTION_MIN_CHUNKS_PER_RANK",
    "MAGI_ATTENTION_CPP_BACKEND",
    "MAGI_ATTENTION_PALLAS_INTERPRET",
    "MAGI_ATTENTION_QO_COMM",
    "MAGI_ATTENTION_HIERARCHICAL_COMM",
    # incremental re-solve can legitimately pick a different (verified)
    # assignment than a cold solve (MAGI_ATTENTION_PLAN_CACHE only reuses
    # identical plans — excluded, same precedent as VERIFY_PLANS)
    "MAGI_ATTENTION_INCREMENTAL_SOLVE",
    "MAGI_ATTENTION_FFA_BLOCK_Q",
    "MAGI_ATTENTION_FFA_BLOCK_K",
    "MAGI_ATTENTION_FFA_BLOCK_Q_DQ",
    "MAGI_ATTENTION_FFA_BLOCK_K_DQ",
    "MAGI_ATTENTION_FFA_BLOCK_Q_DKV",
    "MAGI_ATTENTION_FFA_BLOCK_K_DKV",
    "MAGI_ATTENTION_FFA_GQA_PACK",
    "MAGI_ATTENTION_FFA_GQA_PACK_DQ",
    "MAGI_ATTENTION_FFA_GQA_PACK_DKV",
    "MAGI_ATTENTION_FFA_AUTO_TILE",
    # extent clamping changes the lowered kernel bodies
    "MAGI_ATTENTION_FFA_EXTENT_CLAMP",
    # registry pins (env/backend.py) select traced kernels directly: fused
    # vs split backward, which plans/kernels a mask dispatches to, the NSA
    # branch — cached runtimes must not be shared across flips of them
    "MAGI_ATTENTION_BACKEND_FFA_BWD",
    "MAGI_ATTENTION_BACKEND_MIXED_BLOCKS",
    "MAGI_ATTENTION_BACKEND_NSA_SLC",
    # wire-tier selection changes the traced collective program
    "MAGI_ATTENTION_RAGGED_GRPCOLL",
    "MAGI_ATTENTION_SPLIT_ALIGNMENT",
    # resilience: injection/fallback change which plans/kernels actually
    # run, so cached runtimes must not be shared across flag flips
    # (MAGI_ATTENTION_NUMERIC_GUARD is a read-only check — excluded)
    "MAGI_ATTENTION_FAULT_INJECT",
    "MAGI_ATTENTION_FALLBACK",
)


# keys that left the package -> what to set instead. The docs once told
# users to set them, and a pin that is silently ignored changes which
# kernel a caller traces, so a removed key found in the environment is
# refused by name.
REMOVED_ENV_KEYS: dict[str, str] = {
    "MAGI_ATTENTION_FFA_FUSED_BWD":
        "MAGI_ATTENTION_BACKEND_FFA_BWD=fused|split (was 1|0)",
    "MAGI_ATTENTION_FFA_MIXED_BLOCKS":
        "MAGI_ATTENTION_BACKEND_MIXED_BLOCKS=mixed|single (was 1|0)",
    "MAGI_ATTENTION_SERVE_DECODE_KERNEL":
        "MAGI_ATTENTION_BACKEND_SERVE_DECODE=paged_decode|gather_ffa "
        "(was 1|0)",
    "MAGI_ATTENTION_BACKEND_STORE":
        "nothing: the store is active exactly when MAGI_ATTENTION_TELEMETRY "
        "is, and no choice reads it",
    "MAGI_ATTENTION_DRIFT_THRESHOLD":
        "nothing: the drift layer is gone",
    "MAGI_ATTENTION_CALIBRATION":
        "nothing: OVERHEAD_ELEMS and DCN_PER_ROW are constants",
}


def refuse_removed_keys() -> None:
    """Raise ValueError for a REMOVED_ENV_KEYS key set in the environment."""
    for key, instead in REMOVED_ENV_KEYS.items():
        if key in os.environ:
            raise ValueError(
                f"{key} was removed; set {instead} "
                "(docs/env_variables.md)"
            )


def snapshot_env() -> tuple[tuple[str, str | None], ...]:
    """Hashable snapshot of every behavior-affecting flag."""
    refuse_removed_keys()
    return tuple((k, os.environ.get(k)) for k in ENV_KEYS_AFFECTING_RUNTIME)
