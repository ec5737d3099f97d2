"""Primary user API (ref: magi_attention/api/magi_attn_interface.py).

Same call surface as the reference — ``magi_attn_flex_key`` /
``magi_attn_varlen_key`` plan a distributed mask and return a hashable key;
``dispatch`` / ``calc_attn`` / ``undispatch`` execute against the cached
runtime. Differences are TPU-native: a ``jax.sharding.Mesh`` (+ cp axis name)
replaces the process group, and all ops are traceable jit-compatible
functions over sharded global arrays.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import Mesh

from ..common.enum import AttnMaskType
from ..common.forward_meta import AttnForwardMeta
from ..common.range import RangeError
from ..common.ranges import AttnRanges
from ..config import DistAttnConfig
from ..dist_attn_runtime_mgr import (
    DistAttnRuntimeDict,
    DistAttnRuntimeKey,
    DistAttnRuntimeMgr,
    _mesh_signature,
)
from ..env import snapshot_env
from ..env import general as env_general
from ..telemetry import health as telemetry_health
from .functools import infer_attn_mask_from_cu_seqlens


def _check_no_overlapping_slices(q_ranges, k_ranges, mask_ints) -> None:
    """Sanity invariant: slice coverage must be disjoint — overlapping
    (q, k) coverage is double-counted by the kernel's online softmax (the
    bug class fixed in the sliding-window+sink compiler). Pairwise band
    geometry, gated behind MAGI_ATTENTION_SANITY_CHECK."""
    import numpy as np

    from ..kernels.mask_utils import types_to_bands

    n = len(q_ranges)
    if n > 4096:  # keep the check O(n^2)-affordable
        return
    qr = np.array([[r.start, r.end] for r in q_ranges], np.int64)
    kr = np.array([[r.start, r.end] for r in k_ranges], np.int64)
    lo, hi = types_to_bands(
        qr.astype(np.int32), kr.astype(np.int32),
        np.asarray(mask_ints, np.int32),
    )
    lo = lo.astype(np.int64)
    hi = hi.astype(np.int64)
    for i in range(n):
        for j in range(i + 1, n):
            q0 = max(qr[i, 0], qr[j, 0])
            q1 = min(qr[i, 1], qr[j, 1])
            k0 = max(kr[i, 0], kr[j, 0])
            k1 = min(kr[i, 1], kr[j, 1])
            if q0 >= q1 or k0 >= k1:
                continue
            d_lo = max(lo[i], lo[j], k0 - (q1 - 1))
            d_hi = min(hi[i], hi[j], (k1 - 1) - q0)
            if d_lo <= d_hi:
                raise ValueError(
                    f"slices {i} and {j} overlap on q[{q0},{q1}) x "
                    f"k[{k0},{k1}) (band [{d_lo},{d_hi}]): overlapping "
                    "coverage double-counts in the softmax — make the "
                    "slice set disjoint"
                )

_runtime_dict = DistAttnRuntimeDict()
_most_recent_key: DistAttnRuntimeKey | None = None


def _auto_chunk_size(
    total_seqlen: int, cp_size: int, uneven_shard: bool = False
) -> int:
    """Pick the largest chunk <= 512 giving every rank >=
    ``MAGI_ATTENTION_MIN_CHUNKS_PER_RANK`` chunks (ref :644-655
    auto-derivation from env.general.min_chunks_per_rank). Uneven shard only
    needs ``chunk_size | total_seqlen``; even shard additionally needs the
    chunk count divisible by cp_size."""
    shard = total_seqlen // cp_size
    min_chunks = max(1, env_general.min_chunks_per_rank())
    target = min(512, max(1, shard // min_chunks))
    for cs in range(target, 0, -1):
        if uneven_shard:
            if total_seqlen % cs == 0:
                return cs
        elif total_seqlen % (cs * cp_size) == 0:
            return cs
    return 1


def _validate_mask_inputs(
    q_ranges: AttnRanges,
    k_ranges: AttnRanges,
    mask_ints: tuple[int, ...],
    total_seqlen_q: int,
    total_seqlen_k: int,
) -> None:
    """Always-on key-entry validation, shared by BOTH public key entries
    (the reference asserts these at its key entry,
    api/magi_attn_interface.py:442ff). A count mismatch would otherwise
    zip-TRUNCATE silently downstream (common/mask.py, api/functools.py) —
    wrong results, no error."""
    if not (len(q_ranges) == len(k_ranges) == len(mask_ints)):
        raise ValueError(
            f"q_ranges ({len(q_ranges)}), k_ranges ({len(k_ranges)}) and "
            f"attn_mask_type ({len(mask_ints)}) must have the same length"
        )
    if q_ranges.end > total_seqlen_q:
        bad = max(q_ranges, key=lambda r: r.end)
        raise RangeError(
            f"q range {bad} reaches {q_ranges.end} > total_seqlen_q "
            f"{total_seqlen_q}"
        )
    if k_ranges.end > total_seqlen_k:
        bad = max(k_ranges, key=lambda r: r.end)
        raise RangeError(
            f"k range {bad} reaches {k_ranges.end} > total_seqlen_k "
            f"{total_seqlen_k}"
        )


def magi_attn_flex_key(
    q_ranges: AttnRanges | Sequence[Sequence[int]],
    k_ranges: AttnRanges | Sequence[Sequence[int]],
    attn_mask_type: Sequence[AttnMaskType | str | int],
    total_seqlen_q: int,
    total_seqlen_k: int,
    *,
    mesh: Mesh,
    cp_axis: str = "cp",
    head_axis: str | None = None,
    chunk_size: int | None = None,
    dist_attn_config: DistAttnConfig | None = None,
    label: str | None = None,
) -> DistAttnRuntimeKey:
    """Plan a flexible-mask distributed attention; returns the runtime key.

    ``head_axis`` (optional) names a mesh axis to tensor-parallel-shard the
    head dimension over — attention runs TP x CP in one shard_map.

    ``label`` (optional) is a short name for the mask, for a model that
    attends under several keys a step (``"full"`` here, ``"window"`` on the
    key :func:`make_varlen_key_for_new_mask_after_dispatch` makes of it):
    it follows the kernel bodies' names in a device trace
    (``magi_fwd_kernel_window``) and keys the registry's record of the
    calls' tiles and backward mode (``registry.labelled_choices``). It
    changes no plan; a key without one names everything as before.

    The mask is ``(q_ranges, k_ranges, attn_mask_type)`` slice metadata in
    global coordinates (ref :442). ``total_seqlen_q`` must be pre-padded to
    divide ``cp_size * chunk_size`` (see :func:`compute_pad_size`).
    """
    global _most_recent_key
    if not isinstance(q_ranges, AttnRanges):
        q_ranges = AttnRanges.from_ranges(q_ranges)
    if not isinstance(k_ranges, AttnRanges):
        k_ranges = AttnRanges.from_ranges(k_ranges)
    mask_ints = tuple(
        AttnMaskType.normalize(t).to_int_type() for t in attn_mask_type
    )
    _validate_mask_inputs(
        q_ranges, k_ranges, mask_ints, total_seqlen_q, total_seqlen_k
    )
    if env_general.is_sanity_check_enable():
        _check_no_overlapping_slices(q_ranges, k_ranges, mask_ints)
    if isinstance(cp_axis, (tuple, list)):
        # 2D (dcn, ici) cp mesh — hierarchical comm capable
        cp_axis = tuple(cp_axis)
        cp_size = 1
        for ax in cp_axis:
            cp_size *= mesh.shape[ax]
    else:
        cp_size = mesh.shape[cp_axis]
    if chunk_size is None:
        uneven = bool(
            dist_attn_config
            and dist_attn_config.dispatch_config.uneven_shard
        )
        chunk_size = (
            dist_attn_config.dispatch_config.chunk_size
            if dist_attn_config and dist_attn_config.dispatch_config.chunk_size
            else _auto_chunk_size(total_seqlen_q, cp_size, uneven)
        )
    config = dist_attn_config or DistAttnConfig()

    key = DistAttnRuntimeKey(
        q_ranges=tuple(q_ranges.to_naive_ranges()),
        k_ranges=tuple(k_ranges.to_naive_ranges()),
        attn_mask_type=mask_ints,
        total_seqlen_q=total_seqlen_q,
        total_seqlen_k=total_seqlen_k,
        chunk_size=chunk_size,
        cp_size=cp_size,
        cp_axis=cp_axis,
        head_axis=head_axis,
        mesh_sig=_mesh_signature(mesh),
        config=config,
        env_snapshot=snapshot_env(),
        # straggler-aware elastic dispatch: the active capacity vector
        # rides the key, so the plan re-solves exactly when it changes
        # (None when detection is off or every rank is healthy)
        capacities=telemetry_health.active_capacities(cp_size),
        label=label,
    )
    _runtime_dict.get_or_create(key, mesh)
    _most_recent_key = key
    return key


def magi_attn_varlen_key(
    cu_seqlens_q: Sequence[int],
    cu_seqlens_k: Sequence[int] | None = None,
    *,
    causal: bool = False,
    window_size: tuple[int, int] = (-1, -1),
    global_window_size: int = 0,
    mesh: Mesh,
    cp_axis: str = "cp",
    head_axis: str | None = None,
    chunk_size: int | None = None,
    dist_attn_config: DistAttnConfig | None = None,
    label: str | None = None,
) -> DistAttnRuntimeKey:
    """Varlen (cu_seqlens) convenience wrapper (ref :160; causal defaults
    False, matching the reference and the re-key variant). ``window_size``
    / ``global_window_size`` compile per-segment sliding windows with
    global (sink) tokens (ref :169,317)."""
    q_ranges, k_ranges, types = infer_attn_mask_from_cu_seqlens(
        cu_seqlens_q, cu_seqlens_k, causal,
        window_size=window_size, global_window_size=global_window_size,
    )
    return magi_attn_flex_key(
        q_ranges,
        k_ranges,
        types,
        total_seqlen_q=q_ranges.end,
        total_seqlen_k=k_ranges.end,
        mesh=mesh,
        cp_axis=cp_axis,
        head_axis=head_axis,
        chunk_size=chunk_size,
        dist_attn_config=dist_attn_config,
        label=label,
    )


def make_flex_key_for_new_mask_after_dispatch(
    q_ranges,
    k_ranges,
    attn_mask_type,
    key_for_dispatch: DistAttnRuntimeKey,
    dist_attn_config: DistAttnConfig | None = None,
    label: str | None = None,
) -> DistAttnRuntimeKey:
    """New mask, same dispatch solution (ref :1320); ``label`` as in
    :func:`magi_attn_flex_key`, and not inherited from ``key_for_dispatch``.

    For hybrid-attn models applying several masks in one pass: one mask is
    chosen for dispatch (load balance + comm optimization follow it); the
    others reuse its chunk->rank assignment with freshly-solved comm/calc
    plans. No balance guarantee for the extra masks (ref WARNING).
    """
    global _most_recent_key
    mgr0 = _mgr(key_for_dispatch)
    if not isinstance(q_ranges, AttnRanges):
        q_ranges = AttnRanges.from_ranges(q_ranges)
    if not isinstance(k_ranges, AttnRanges):
        k_ranges = AttnRanges.from_ranges(k_ranges)
    mask_ints = tuple(
        AttnMaskType.normalize(t).to_int_type() for t in attn_mask_type
    )
    old = key_for_dispatch
    # same rule set as magi_attn_flex_key — the re-keyed mask must fit the
    # layout planned by key_for_dispatch
    _validate_mask_inputs(
        q_ranges, k_ranges, mask_ints,
        old.total_seqlen_q, old.total_seqlen_k,
    )
    key = DistAttnRuntimeKey(
        q_ranges=tuple(q_ranges.to_naive_ranges()),
        k_ranges=tuple(k_ranges.to_naive_ranges()),
        attn_mask_type=mask_ints,
        total_seqlen_q=old.total_seqlen_q,
        total_seqlen_k=old.total_seqlen_k,
        chunk_size=old.chunk_size,
        cp_size=old.cp_size,
        cp_axis=old.cp_axis,
        head_axis=old.head_axis,
        mesh_sig=old.mesh_sig,
        config=dist_attn_config or old.config,
        env_snapshot=snapshot_env(),
        fixed_partitions=tuple(
            tuple(p) for p in mgr0.dispatch_meta_q.partitions
        ),
        # the pinned partitions already embody the dispatch key's capacity
        # weighting; carry the vector so the signature stays consistent
        capacities=old.capacities,
        label=label,
    )
    _runtime_dict.get_or_create(key, mgr0.mesh)
    _most_recent_key = key
    return key


def make_varlen_key_for_new_mask_after_dispatch(
    cu_seqlens_q,
    cu_seqlens_k,
    key_for_dispatch: DistAttnRuntimeKey,
    causal: bool = False,
    window_size: tuple[int, int] = (-1, -1),
    global_window_size: int = 0,
    dist_attn_config: DistAttnConfig | None = None,
    label: str | None = None,
) -> DistAttnRuntimeKey:
    """Varlen convenience form of re-keying (ref :1172) — ONE compile
    path with :func:`magi_attn_varlen_key`, so a model created with
    windows + global sinks re-keys to the identical mask. A model whose
    layers attend under two masks a step (window layers and full layers,
    ``models/hybrid.py``) plans the full mask, which owns the dispatch, and
    makes the window key of it here: tensors dispatched under either key
    are in the same layout."""
    q_ranges, k_ranges, types = infer_attn_mask_from_cu_seqlens(
        cu_seqlens_q, cu_seqlens_k, causal,
        window_size=window_size, global_window_size=global_window_size,
    )
    return make_flex_key_for_new_mask_after_dispatch(
        q_ranges, k_ranges, types, key_for_dispatch, dist_attn_config,
        label=label,
    )


def _mgr(key: DistAttnRuntimeKey) -> DistAttnRuntimeMgr:
    mgr = _runtime_dict.get(key)
    if mgr is None:
        raise KeyError(
            "unknown DistAttnRuntimeKey — create it with magi_attn_flex_key "
            "in this process first"
        )
    return mgr


def dispatch(
    x: jax.Array, key: DistAttnRuntimeKey, role: str = "qo"
) -> jax.Array:
    """Global natural-order tensor -> dispatched cp-sharded layout (ref :892).

    In a model, dispatch what is per token and narrow (token ids, labels,
    other per-token integers) and let everything ``dim`` wide be born
    dispatched: embed ``dispatch(tokens, key)``, as
    ``models.llama.embed_dispatched`` does. Embedding first and dispatching
    the activations builds all ``total_seqlen`` rows on every chip and, with
    a vocabulary-sharded table at cp 4, all-reduces ``[S, dim]`` every step."""
    mgr = _mgr(key)
    return mgr.dispatch_qo(x) if role == "qo" else mgr.dispatch_kv(x)


def undispatch(
    x: jax.Array, key: DistAttnRuntimeKey, role: str = "qo"
) -> jax.Array:
    """Dispatched layout -> global natural order (ref :929)."""
    mgr = _mgr(key)
    return mgr.undispatch_qo(x) if role == "qo" else mgr.undispatch_kv(x)


def calc_attn(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    key: DistAttnRuntimeKey,
    return_max_logits: bool = False,
) -> tuple[jax.Array, AttnForwardMeta]:
    """Distributed attention over dispatched q/k/v (ref :1046).

    With ``return_max_logits``, ``meta.max_logits`` is the per-head max
    logit [hq] all-reduced MAX across cp (ref dist_attn.py:550)."""
    res = _mgr(key).calc_attn(q, k, v, return_max_logits=return_max_logits)
    if return_max_logits:
        out, lse, ml = res
        return out, AttnForwardMeta(lse=lse, max_logits=ml)
    out, lse = res
    return out, AttnForwardMeta(lse=lse)


def roll(
    x: jax.Array, key: DistAttnRuntimeKey, shifts: int = 1
) -> jax.Array:
    """Global roll on dispatched tensors (for MTP label shift, ref :965)."""
    return _mgr(key).roll(x, shifts)


def roll_simple(
    x: jax.Array, key: DistAttnRuntimeKey, shifts: int = 1
) -> jax.Array:
    """Alias of :func:`roll` under the reference's ``roll_simple`` name
    (the batched-P2P vs isend/irecv distinction is a CUDA stream concern;
    on TPU both lower to the same segment-ppermute program). NOTE the
    TPU-native argument order ``(x, key, shifts)`` — the reference takes
    ``(x, shift, dim, key)``; see docs/migration.md."""
    return roll(x, key, shifts)


def magi_attn_flex_dispatch(
    x: jax.Array,
    q_ranges,
    k_ranges,
    attn_mask_type,
    total_seqlen_q: int,
    total_seqlen_k: int,
    **key_kwargs,
) -> tuple[jax.Array, DistAttnRuntimeKey]:
    """Key + dispatch in one call: returns ``(local_x, key)`` (the ref
    :730 combo under its name — NOT signature-identical: mesh/cp_axis/
    chunk_size arrive as keywords and the torch-only num_heads/head_dim/
    pad_size/cp_group params don't exist here; see docs/migration.md. New
    code should call :func:`magi_attn_flex_key` then :func:`dispatch`)."""
    key = magi_attn_flex_key(
        q_ranges, k_ranges, attn_mask_type,
        total_seqlen_q, total_seqlen_k, **key_kwargs,
    )
    return dispatch(x, key), key


def magi_attn_varlen_dispatch(
    x: jax.Array,
    cu_seqlens_q,
    cu_seqlens_k=None,
    **key_kwargs,
) -> tuple[jax.Array, DistAttnRuntimeKey]:
    """Key + dispatch for cu_seqlens masks: returns ``(local_x, key)``
    (the ref api :307 combo under its name — keyword-style args as in
    :func:`magi_attn_varlen_key`, not the torch signature; see
    docs/migration.md)."""
    key = magi_attn_varlen_key(cu_seqlens_q, cu_seqlens_k, **key_kwargs)
    return dispatch(x, key), key


def get_position_ids(key: DistAttnRuntimeKey) -> jax.Array:
    """Global position of each dispatched row (for RoPE etc., ref :1117)."""
    return _mgr(key).get_position_ids()


def get_document_starts(key: DistAttnRuntimeKey) -> jax.Array:
    """For each dispatched row, the global row of its document's first token
    (``int32``), in the same order as :func:`get_position_ids`: rank-major,
    each rank's chunks in the order its plan holds them, so at cp 1 natural
    order. ``get_position_ids(key) - get_document_starts(key)`` is a token's
    position inside its document; a layer that carries state along a
    document (a recurrence, a causal convolution) resets where the value
    changes. The documents are the key's own: its slices' q and k ranges,
    merged where they overlap."""
    return _mgr(key).get_document_starts()


def same_dispatch(key_a: DistAttnRuntimeKey, key_b: DistAttnRuntimeKey) -> bool:
    """Whether the two keys lay a sequence out alike: a tensor dispatched
    under one is in place for ``calc_attn`` under the other. True of a key
    and one made of it by ``make_*_key_for_new_mask_after_dispatch``."""
    import numpy as np

    a, b = _mgr(key_a).dispatch_meta_q, _mgr(key_b).dispatch_meta_q
    return np.array_equal(a.position_ids, b.position_ids)


def get_mesh(key: DistAttnRuntimeKey):
    """The ``jax.sharding.Mesh`` the key's runtime was planned for (model
    code composing further parallelism — e.g. expert-parallel shard_maps —
    needs the mesh back from the key)."""
    return _mgr(key).mesh


def get_most_recent_key() -> DistAttnRuntimeKey | None:
    return _most_recent_key


def init_dist_attn_runtime_key(
    q_ranges: AttnRanges | Sequence[Sequence[int]],
    k_ranges: AttnRanges | Sequence[Sequence[int]],
    attn_mask_type: Sequence[AttnMaskType | str | int],
    total_seqlen_q: int,
    total_seqlen_k: int,
    chunk_size: int,
    *,
    mesh: Mesh,
    cp_axis: str = "cp",
    head_axis: str | None = None,
    pad_size: int = 0,
    dist_attn_config: DistAttnConfig | None = None,
) -> DistAttnRuntimeKey:
    """Reference-named runtime-key init (ref dist_attn_runtime_mgr.py:486).

    Thin adapter over :func:`magi_attn_flex_key` for migration parity:
    ``pad_size > 0`` applies :func:`~..api.functools.apply_padding` to the
    mask first (the reference keys on pad_size; here padding is part of the
    mask itself). The reference's ``num_heads_q/num_heads_kv/head_dim``
    parameters do not exist here: JAX traces tensor shapes per call, so
    head geometry never needs to be declared at planning time.
    """
    if not isinstance(q_ranges, AttnRanges):
        q_ranges = AttnRanges.from_ranges(q_ranges)
    if not isinstance(k_ranges, AttnRanges):
        k_ranges = AttnRanges.from_ranges(k_ranges)
    mask_types = [AttnMaskType.normalize(t) for t in attn_mask_type]
    if pad_size > 0:
        from .functools import apply_padding

        q_ranges, k_ranges, mask_types = apply_padding(
            q_ranges, k_ranges, mask_types, total_seqlen_q, pad_size
        )
        total_seqlen_q += pad_size
        total_seqlen_k += pad_size
    return magi_attn_flex_key(
        q_ranges, k_ranges, mask_types, total_seqlen_q, total_seqlen_k,
        mesh=mesh, cp_axis=cp_axis, head_axis=head_axis,
        chunk_size=chunk_size, dist_attn_config=dist_attn_config,
    )


def init_dist_attn_runtime_mgr(
    q_ranges: AttnRanges | Sequence[Sequence[int]],
    k_ranges: AttnRanges | Sequence[Sequence[int]],
    attn_mask_type: Sequence[AttnMaskType | str | int],
    total_seqlen_q: int,
    total_seqlen_k: int,
    chunk_size: int,
    *,
    mesh: Mesh,
    cp_axis: str = "cp",
    head_axis: str | None = None,
    pad_size: int = 0,
    dist_attn_config: DistAttnConfig | None = None,
) -> "DistAttnRuntimeMgr":
    """Reference-named manager init (ref dist_attn_runtime_mgr.py:558):
    plans the mask and returns the manager itself (sharing the same LRU as
    the key-based API) for callers that want direct access to the metas."""
    key = init_dist_attn_runtime_key(
        q_ranges, k_ranges, attn_mask_type, total_seqlen_q, total_seqlen_k,
        chunk_size, mesh=mesh, cp_axis=cp_axis, head_axis=head_axis,
        pad_size=pad_size, dist_attn_config=dist_attn_config,
    )
    return _mgr(key)


def clear_cache() -> None:
    _runtime_dict.clear()
