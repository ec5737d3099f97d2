"""Unified attention-backend registry: ONE selection point for every
kernel-choice decision in the package.

A choice is **pin > the call site's rule over shapes**:

1. **pin** — an explicit env-derived choice (the env/backend.py getters
   read one ``MAGI_ATTENTION_BACKEND_*`` key per decision;
   ``MAGI_ATTENTION_KERNEL_BACKEND`` pins ``calc_attn``). A pin bypasses
   the memo, is re-read per call (tests flip env vars mid-process), and is
   subject only to the call site's *feasibility* guards (VMEM, plan meta
   layout).
2. **rule** — the call site's heuristic (a cost model over static shapes,
   or a constant), run at most once per key and memoized in-process. Each
   run counts in ``stats()["heuristic_calls"]``.

Telemetry is told of every choice (one ``backend_select`` record per
decision, key and choice) and is asked nothing: no file, no measured
history and no earlier run decides a kernel.

A call made for a labelled runtime key (``DistAttnRuntimeKey.label``: a
model that attends under two masks a step labels them, say ``window`` and
``full``) hands its label along: the choice is then also kept per label
(``last_choice(decision, label=...)``; ``labelled_choices(decision)`` has
every distinct choice a label saw with who made it) and the record carries
it. The rule and the memo do not see the label: the same
shapes get the same answer under any.

Rank-ordered backend registrations double as the resilience ladders:
``ladder("serve_decode")`` is the decode fallback order and
``ladder("calc_attn")[-1]`` is the reference rung the resilience module
descends to (resilience/fallback.py).

MAGI-L002: no clocks here. MAGI-L001: env access only through typed
getters.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Callable

from .. import telemetry
from ..env import backend as env_backend
from ..env import kernel as env_kernel
from ..utils.canonical import canonical_key


@dataclass(frozen=True)
class BackendChoice:
    name: str
    source: str  # "pin" | "heuristic", or what note_choice was given


def _memo_key(key: Any) -> Any:
    """Hashable form of a decision key. Dict keys (the calc_attn key)
    canonicalize to their sorted-JSON string."""
    try:
        hash(key)
        return key
    except TypeError:
        return canonical_key(key)


# decision -> [(rank, name, description)], rank order = ladder order
_BACKENDS: dict[str, list[tuple[int, str, str]]] = {}


def register_backend(
    decision: str, name: str, rank: int, description: str = ""
) -> None:
    """Register a backend for a decision. Rank orders the fallback ladder
    (0 = preferred / fastest, last = most conservative reference)."""
    entries = _BACKENDS.setdefault(decision, [])
    entries[:] = [e for e in entries if e[1] != name]
    entries.append((rank, name, description))
    entries.sort()


def decisions() -> tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


def backends_for(decision: str) -> tuple[str, ...]:
    return tuple(name for _, name, _ in _BACKENDS.get(decision, ()))


def ladder(decision: str, start: str | None = None) -> tuple[str, ...]:
    """The rank-ordered fallback ladder for a decision, optionally starting
    at ``start`` (an unknown start returns the full ladder)."""
    names = backends_for(decision)
    if start in names:
        return names[names.index(start):]
    return names


class BackendRegistry:
    """In-process resolution cache + tuning stats (one global instance)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._memo: dict[tuple[str, Any], BackendChoice] = {}
        self._last: dict[str, tuple[Any, str]] = {}
        # the last choice of a (decision, label or None), and every distinct
        # "choice (source)" made for a label, in order
        self._last_choice: dict[tuple[str, str | None], BackendChoice] = {}
        self._said_by_label: dict[tuple[str, str], list[str]] = {}
        self._announced: set[tuple[str, Any, str, str | None]] = set()
        self.stats: dict[str, int] = {
            "resolves": 0,
            "pins": 0,
            "memo_hits": 0,
            "heuristic_calls": 0,
        }

    def _settle(
        self, decision: str, key: Any, choice: BackendChoice,
        label: str | None, announce: bool = True,
    ) -> BackendChoice:
        """Keep ``choice`` as the decision's last (and its label's), and
        tell telemetry once per (decision, key, choice, label): selection
        provenance without per-step record spam."""
        with self._lock:
            self._last[decision] = (key, choice.name)
            self._last_choice[decision, None] = choice
            if label is not None:
                self._last_choice[decision, label] = choice
                said = self._said_by_label.setdefault((decision, label), [])
                if f"{choice.name} ({choice.source})" not in said:
                    said.append(f"{choice.name} ({choice.source})")
        if not (announce and telemetry.enabled()):
            return choice
        tag = (decision, _memo_key(key), choice.name, label)
        with self._lock:
            if tag in self._announced:
                return choice
            self._announced.add(tag)
        telemetry.record_event(
            "backend_select",
            decision=decision,
            key=list(key) if isinstance(key, tuple) else key,
            choice=choice.name,
            source=choice.source,
            **({} if label is None else {"label": label}),
        )
        return choice

    def resolve(
        self,
        decision: str,
        key: Any,
        heuristic: Callable[[], str],
        pin: str | None = None,
        label: str | None = None,
    ) -> BackendChoice:
        with self._lock:
            self.stats["resolves"] += 1
        if pin is not None:
            with self._lock:
                self.stats["pins"] += 1
            return self._settle(
                decision, key, BackendChoice(pin, "pin"), label)

        ck = (decision, _memo_key(key))
        with self._lock:
            hit = self._memo.get(ck)
            if hit is not None:
                self.stats["memo_hits"] += 1
        if hit is not None:  # announced when it was made
            return self._settle(
                decision, key, hit, label, announce=label is not None)

        choice = BackendChoice(heuristic(), "heuristic")
        with self._lock:
            self.stats["heuristic_calls"] += 1
            self._memo[ck] = choice
        return self._settle(decision, key, choice, label)

    def note(
        self, decision: str, key: Any, name: str, source: str,
        label: str | None = None,
    ) -> BackendChoice:
        """Record a choice the call site computed itself (a rule over
        shapes, nothing to resolve against): ``last_choice`` and one
        ``backend_select`` record, no memo."""
        return self._settle(
            decision, key, BackendChoice(name, source), label)

    def last(self, decision: str) -> tuple[Any, str] | None:
        with self._lock:
            return self._last.get(decision)

    def last_choice(
        self, decision: str, label: str | None = None
    ) -> BackendChoice | None:
        with self._lock:
            return self._last_choice.get((decision, label))

    def labelled(self, decision: str) -> dict[str, str]:
        with self._lock:
            return {label: "; ".join(said) for (d, label), said
                    in self._said_by_label.items() if d == decision}


_registry: BackendRegistry | None = None
_registry_lock = threading.Lock()


def get_registry() -> BackendRegistry:
    global _registry
    with _registry_lock:
        if _registry is None:
            _registry = BackendRegistry()
        return _registry


def reset_registry() -> None:
    """Drop the in-process resolution cache + stats (tests)."""
    global _registry
    with _registry_lock:
        _registry = None


def resolve(
    decision: str,
    key: Any,
    heuristic: Callable[[], str],
    pin: str | None = None,
    label: str | None = None,
) -> BackendChoice:
    return get_registry().resolve(
        decision, key, heuristic, pin=pin, label=label)


def note_choice(
    decision: str, key: Any, name: str, source: str,
    label: str | None = None,
) -> BackendChoice:
    return get_registry().note(decision, key, name, source, label=label)


def stats() -> dict[str, int]:
    return dict(get_registry().stats)


def last_choice(decision: str, label: str | None = None) -> str | None:
    """The decision's last choice in this process; with ``label`` the last
    one made for a runtime key of that label."""
    last = get_registry().last_choice(decision, label)
    return None if last is None else last.name


def last_source(decision: str, label: str | None = None) -> str | None:
    """Who made :func:`last_choice`'s choice: "pin", "heuristic", or what
    the call site said of its own rule ("shape_rule", "guard", ...)."""
    last = get_registry().last_choice(decision, label)
    return None if last is None else last.source


def labelled_choices(decision: str) -> dict[str, str]:
    """``{label: "choice (source)"}`` of the calls made for labelled runtime
    keys, every distinct one a label saw, in order, joined by ``"; "`` — a
    key called at two sizes (a model's step, then a smaller check program)
    can choose twice: ``"fwd256x512 dq256x512 dkv256x512g8 (table_guard);
    fwd128x512g8 dq128x512g8 dkv128x512g8 (shape_rule)"``. Empty where no
    key carries a label."""
    return get_registry().labelled(decision)


# -- call-site conveniences (the env reads kernel code used to do) ----------


def calc_attn_backend(key: Any = ()) -> str:
    """The attention backend for a runtime/step: explicit
    MAGI_ATTENTION_KERNEL_BACKEND pins it; otherwise 'ffa'."""
    return resolve(
        "calc_attn", key, lambda: "ffa",
        pin=env_backend.kernel_backend_pin(),
    ).name


def nsa_slc_backend(key: Any = ()) -> str:
    """The NSA selected-block branch for a shape: explicit
    MAGI_ATTENTION_BACKEND_NSA_SLC pins it; otherwise the gather-free
    kernel."""
    return resolve(
        "nsa_slc", key, lambda: "block_sparse_pallas",
        pin=env_backend.nsa_slc_pin(),
    ).name


def tiles_pinned() -> bool:
    """Explicit FFA block settings present (env FFA_BLOCK_Q/K): auto-tile
    and mixed dispatch must stand down — explicit settings always win."""
    return env_kernel.ffa_blocks_pinned()


def tiles_source(explicit: bool, auto_tile: bool) -> str:
    """Who chose a call's FFA tiles, for the ``ffa_tiles`` note: "pin"
    (tile arguments, or any FFA_BLOCK_* key, one pass's included),
    "auto_tile" (the policy), else "default" (``ffa.default_blocks``) —
    which the call then hands to ``tile_policy.group_block_q``, and that
    answers "default", "shape_rule" (``block_q`` moved to fit the group) or
    "table_guard" (it would have, and the plan's table does not fit)."""
    if explicit or tiles_pinned() or env_kernel.ffa_pass_blocks_pinned():
        return "pin"
    return "auto_tile" if auto_tile else "default"


def gqa_pack_flags() -> tuple[bool, bool, bool]:
    """The fwd / dq / dkv pack flags as they stand, read without a
    resolve: a cache key for what :func:`gqa_pack_variant` would say."""
    return (env_kernel.ffa_gqa_pack(), env_kernel.ffa_gqa_pack_dq(),
            env_kernel.ffa_gqa_pack_dkv())


def gqa_pack_variant(kind: str) -> str:
    """'gqa_packed' | 'plain' for the fwd / bwd-dq / bwd-dkv kernels. The
    pack flags (all on by default) are read as pins, so these decisions
    are always pinned; the call site's grouping and VMEM-residency guard
    (kernels/ffa.gqa_pack_admitted) still applies on top."""
    if kind == "fwd":
        flag = env_kernel.ffa_gqa_pack()
        decision = "ffa_fwd"
    elif kind == "dq":
        flag = env_kernel.ffa_gqa_pack_dq()
        decision = "ffa_bwd_dq"
    elif kind == "dkv":
        flag = env_kernel.ffa_gqa_pack_dkv()
        decision = "ffa_bwd_dkv"
    else:
        raise ValueError(f"unknown gqa pack kind: {kind!r}")
    return resolve(
        decision, (), lambda: "plain",
        pin="gqa_packed" if flag else "plain",
    ).name


def extent_clamp_enabled() -> bool:
    """Lowering variant of the FFA kernel bodies: extent-clamped chunked
    dots vs the legacy single-dot bodies."""
    return (
        resolve(
            "ffa_lowering", (), lambda: "clamped",
            pin="clamped" if env_kernel.ffa_extent_clamp() else "single_dot",
        ).name
        == "clamped"
    )


# -- backend registrations --------------------------------------------------

register_backend(
    "calc_attn", "ffa", 0, "Pallas flex-flash-attention (default)")
register_backend(
    "calc_attn", "sdpa", 1, "XLA dense reference")
register_backend(
    "calc_attn", "sdpa_online", 2,
    "streamed dense reference — resilience ladder's last rung")
register_backend(
    "ffa_fwd", "gqa_packed", 0, "grouped-head packed fwd kernel (default on)")
register_backend("ffa_fwd", "plain", 1, "per-head fwd kernel")
register_backend("ffa_bwd", "fused", 0, "one-pass fused dq/dk/dv")
register_backend(
    "ffa_bwd", "split", 1, "split dq + dkv passes — fused's fallback rung")
register_backend("ffa_bwd_dq", "gqa_packed", 0, "packed dq (default on)")
register_backend("ffa_bwd_dq", "plain", 1, "per-head dq kernel")
register_backend("ffa_bwd_dkv", "gqa_packed", 0, "packed dkv (default on)")
register_backend("ffa_bwd_dkv", "plain", 1, "per-head dkv kernel")
register_backend(
    "ffa_dispatch", "mixed", 0, "coarse+fine two-pass LSE-merged dispatch")
register_backend("ffa_dispatch", "single", 1, "one plan, one tiling")
register_backend(
    "ffa_lowering", "clamped", 0, "extent-clamped chunked-dot bodies")
register_backend(
    "ffa_lowering", "single_dot", 1, "legacy full-tile dot bodies")
register_backend(
    "serve_decode", "paged_decode_sharded", 0,
    "paged-decode kernel shard_mapped over kv heads (one launch per shard)")
register_backend(
    "serve_decode", "paged_decode_spec", 1,
    "multi-token speculative-verify kernel (spec_k draft rows per q tile)")
register_backend(
    "serve_decode", "paged_decode_int8", 2,
    "int8-KV paged-decode kernel (per-page scales, dequant in-kernel)")
register_backend(
    "serve_decode", "paged_decode", 3, "Pallas ragged paged-decode kernel")
register_backend(
    "serve_decode", "gather_ffa", 4, "per-slot gather+FFA reference")
register_backend(
    "serve_decode", "dense", 5, "dense jnp softmax — last resort")
register_backend(
    "ssd", "pallas_chunked", 0,
    "chunked state-space scan, Pallas forward and backward (kernels/ssd.py)")
register_backend(
    "moe_grouped", "pallas_grouped", 0,
    "rows sorted by expert, a Pallas grouped matmul over the live row "
    "tiles of the experts held, forward, d rows and dW "
    "(kernels/grouped_matmul.py; the row tile, tile_policy."
    "grouped_row_tile, is noted under moe_grouped_tiles; the row buffer, "
    "tile_policy.grouped_row_capacity, under moe_row_buffer)")
register_backend(
    "nsa_slc", "block_sparse_pallas", 0,
    "gather-free Pallas block-sparse slc kernel")
register_backend(
    "nsa_slc", "gathered_dense", 1,
    "take_along_axis + dense softmax reference")

# which env keys pin each decision — provenance for reports and
# docs/env_variables.md
PIN_KEYS: dict[str, tuple[str, ...]] = {
    "calc_attn": ("MAGI_ATTENTION_KERNEL_BACKEND",),
    "ffa_bwd": ("MAGI_ATTENTION_BACKEND_FFA_BWD",),
    "ffa_dispatch": ("MAGI_ATTENTION_BACKEND_MIXED_BLOCKS",),
    "serve_decode": ("MAGI_ATTENTION_BACKEND_SERVE_DECODE",),
    "ffa_fwd": ("MAGI_ATTENTION_FFA_GQA_PACK",),
    "ffa_bwd_dq": ("MAGI_ATTENTION_FFA_GQA_PACK_DQ",),
    "ffa_bwd_dkv": ("MAGI_ATTENTION_FFA_GQA_PACK_DKV",),
    "ffa_lowering": ("MAGI_ATTENTION_FFA_EXTENT_CLAMP",),
    # any FFA_BLOCK_* key is a "pin"; else the auto-tile policy, or
    # ffa.default_blocks, block_q by tile_policy.group_block_q (tiles_source)
    "ffa_tiles": (
        "MAGI_ATTENTION_FFA_BLOCK_Q", "MAGI_ATTENTION_FFA_BLOCK_K",
        "MAGI_ATTENTION_FFA_BLOCK_Q_DQ", "MAGI_ATTENTION_FFA_BLOCK_K_DQ",
        "MAGI_ATTENTION_FFA_BLOCK_Q_DKV", "MAGI_ATTENTION_FFA_BLOCK_K_DKV",
        "MAGI_ATTENTION_FFA_AUTO_TILE"),
    "nsa_slc": ("MAGI_ATTENTION_BACKEND_NSA_SLC",),
    # one backend each and no pin: the call site notes its choice
    "ssd": (),
    "ssd_heads": (),  # a rule, no key
    "moe_grouped": (),
    "moe_grouped_tiles": (),
    "moe_row_buffer": (),
}
