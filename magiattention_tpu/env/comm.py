"""Communication toggles (ref: magi_attention/env/comm.py:33-172)."""

from __future__ import annotations

from .general import _get_bool, _get_int


def is_hierarchical_comm_enable() -> bool:
    """2-level (DCN x ICI) group-collective planning."""
    return _get_bool("MAGI_ATTENTION_HIERARCHICAL_COMM")


def is_qo_comm_enable() -> bool:
    """Move q/o/do instead of (only) kv — enables the dynamic solver."""
    return _get_bool("MAGI_ATTENTION_QO_COMM")


def is_fwd_high_precision_reduce_enable() -> bool:
    """Return partial out across ranks in fp32 instead of the compute dtype.

    Applies to the qo-comm (dynamic) runtime, where partial outputs travel
    back to their owner rank for the lse merge
    (functional/dynamic_dist_attn.py _dyn_fwd_impl). Doubles that wire
    volume for better merge precision. The static (kv-comm) runtime never
    sends partial out, so this is a no-op there — same as the reference
    (_reduce_partial_out_lse is qo-comm-only, dist_attn.py:1979).

    Default ``0``, matching the reference (env/comm.py:106).
    """
    return _get_bool("MAGI_ATTENTION_FWD_HIGH_PRECISION_REDUCE")


def is_bwd_high_precision_reduce_enable() -> bool:
    """Reduce partial dq/dk/dv across ranks in fp32 instead of the compute
    dtype (ref _reduce_partial_dkv, dist_attn.py:2123; default ``0`` matching
    env/comm.py:123). Doubles backward comm volume; removes the cp-way
    low-precision summation error.

    Consumed by functional/dist_attn.py (hp_group_cast_all fused custom-VJP wire) and
    functional/dynamic_dist_attn.py (_dyn_bwd partial dtype choice).
    """
    return _get_bool("MAGI_ATTENTION_BWD_HIGH_PRECISION_REDUCE")


def split_alignment() -> int:
    """Pad collective split sizes to a multiple of this (TPU lane alignment).

    Consumed as the default of ``GrpCollConfig.split_alignment`` (config.py);
    an explicit config value wins over the env.
    """
    return _get_int("MAGI_ATTENTION_SPLIT_ALIGNMENT", 128)


def is_plan_broadcast_enable() -> bool:
    """Solve-once-broadcast tier of the plan control plane
    (meta/plan_broadcast.py): the leader host solves, every other host
    receives the serialized plan instead of cold-solving. Byte-exact reuse
    (every received plan is checksum- and R1-R5-verified), so — like
    MAGI_ATTENTION_PLAN_CACHE / PLAN_STORE — not a runtime-cache-key flag."""
    return _get_bool("MAGI_ATTENTION_PLAN_BROADCAST")


def plan_broadcast_transport() -> str:
    """Broadcast transport: ``auto`` (multihost when jax.process_count()>1,
    else the filesystem transport when a dir is set) | ``multihost``
    (jax.experimental.multihost_utils) | ``file`` (shared-directory
    publish/poll — single-host tests, or meshes without a jax distributed
    client)."""
    from .general import _get_str

    return _get_str("MAGI_ATTENTION_PLAN_BROADCAST_TRANSPORT", "auto").lower()


def plan_broadcast_dir() -> str:
    """Shared directory for the ``file`` broadcast transport."""
    from .general import _get_str

    return _get_str("MAGI_ATTENTION_PLAN_BROADCAST_DIR", "plan_broadcast")


def plan_broadcast_role() -> str:
    """Role override for the broadcast tier: ``auto`` (leader iff
    jax.process_index()==0) | ``leader`` | ``follower``. The override
    exists for tests and for meshes where host 0 is not the solver."""
    from .general import _get_str

    return _get_str("MAGI_ATTENTION_PLAN_BROADCAST_ROLE", "auto").lower()


def plan_broadcast_retries() -> int:
    """Receive attempts after the first before the broadcast tier gives up
    and degrades to a local cold solve."""
    return _get_int("MAGI_ATTENTION_PLAN_BROADCAST_RETRIES", 3)


def plan_broadcast_backoff_ms() -> int:
    """Initial retry backoff (doubles per attempt, capped by the deadline)."""
    return _get_int("MAGI_ATTENTION_PLAN_BROADCAST_BACKOFF_MS", 50)


def plan_broadcast_deadline_ms() -> int:
    """Hard wall-clock budget for one broadcast receive, all retries
    included; exhaustion is a recorded degradation, never a raise."""
    return _get_int("MAGI_ATTENTION_PLAN_BROADCAST_DEADLINE_MS", 5000)


def is_ragged_grpcoll_enable() -> bool:
    """Use ``jax.lax.ragged_all_to_all`` for GroupCast — true per-pair split
    sizes, zero padding on the wire (the TPU counterpart of the reference's
    native grpcoll kernel tier, csrc/comm/grpcoll/).

    ``MAGI_ATTENTION_RAGGED_GRPCOLL`` = ``1`` / ``0`` decides outright;
    unset (``auto``) the tier is on exactly when the devices the plan will
    run on are TPUs — ``jax.default_backend()``, the platform every Mesh
    over ``jax.devices()`` is built from (XLA:CPU does not implement the
    op). The executed tier of each stage is reported by the runtime
    (``DistAttnRuntime._cast_kinds``; INFO log ``comm plan stage``)."""
    import os

    v = os.environ.get("MAGI_ATTENTION_RAGGED_GRPCOLL", "auto").lower()
    if v in ("1", "true", "on"):
        return True
    if v in ("0", "false", "off"):
        return False
    import jax

    return jax.default_backend() == "tpu"
