"""Runtime key + manager (ref: magi_attention/dist_attn_runtime_mgr.py:62,164).

``DistAttnRuntimeKey`` is a frozen hashable key over (mask metadata, mesh
signature, chunking, config, env-flag snapshot); ``DistAttnRuntimeMgr`` owns
the planning pipeline output (dispatch meta -> attn meta -> DistAttnRuntime)
and the dispatch/undispatch/calc_attn methods. Managers are memoized in an
LRU keyed by the runtime key — this is what caches traced/compiled plans
across steps.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Any

import jax
from jax.sharding import Mesh

from . import telemetry
from .common.enum import AttnMaskType
from .common.ranges import AttnRanges
from .config import DistAttnConfig
from .env import general as env_general
from .env import resilience as env_resilience
from .functional.dispatch import dispatch_func, undispatch_func
from .functional.dist_attn import DistAttnRuntime
from .meta import (
    make_attn_meta_from_dispatch_meta,
    make_dispatch_meta_from_qk_ranges,
)
from .meta import plan_broadcast, plan_io, plan_store


def _plan_build_retries() -> int:
    from .resilience.fallback import PLAN_BUILD_RETRIES

    return PLAN_BUILD_RETRIES


def _mesh_signature(mesh: Mesh) -> tuple:
    """Canonical mesh identity: per-axis (name, size) pairs + device ids.

    Pairing name with size (instead of separate name/shape tuples) keeps a
    flat cp=8 mesh and a 2x4 two-level (dcn, ici) mesh from ever colliding,
    and makes the axis-extent lookup for two-level planning unambiguous."""
    return (
        tuple(zip(mesh.axis_names, mesh.devices.shape)),
        tuple(d.id for d in mesh.devices.flat),
    )


def _mesh_shape_for(key: "DistAttnRuntimeKey", mesh: Mesh) -> tuple[int, int] | None:
    """(n_outer, n_inner) for two-level planning, or None on flat meshes.

    Two-level plans are built exactly when the runtime will execute them:
    tuple cp_axis + MAGI_ATTENTION_HIERARCHICAL_COMM=1 (both are part of
    the cache keys, so flat and two-level plans never mix)."""
    from .env import comm as env_comm

    if (
        isinstance(key.cp_axis, tuple)
        and env_comm.is_hierarchical_comm_enable()
    ):
        dcn_axis, ici_axis = key.cp_axis
        return (int(mesh.shape[dcn_axis]), int(mesh.shape[ici_axis]))
    return None


@dataclass(frozen=True)
class DistAttnRuntimeKey:
    """Hashable identity of one planned runtime (ref :62-121)."""

    q_ranges: tuple[tuple[int, int], ...]
    k_ranges: tuple[tuple[int, int], ...]
    attn_mask_type: tuple[int, ...]
    total_seqlen_q: int
    total_seqlen_k: int
    chunk_size: int
    cp_size: int
    cp_axis: str | tuple[str, str]
    head_axis: str | None
    mesh_sig: tuple
    config: DistAttnConfig
    env_snapshot: tuple
    # pinned chunk->rank assignment: set when re-keying a new mask after
    # dispatch (ref api :1172 make_*_key_for_new_mask_after_dispatch) so the
    # new mask reuses the old dispatch solution
    fixed_partitions: tuple[tuple[int, ...], ...] | None = None
    # per-rank capacity vector from straggler detection (telemetry/health):
    # None = uniform. A changed vector is a changed key, so the runtime
    # re-solves exactly when the vector changes and the plan control plane
    # caches/persists/broadcasts weighted plans like any other.
    capacities: tuple[float, ...] | None = None
    # a short name for the mask ("window", "full") where a model attends
    # under several keys a step: it rides in the kernels' scope names
    # (kernels/_named.py), in the registry's record of the calls' tiles and
    # backward mode and in the telemetry records, and changes no plan
    label: str | None = None


def _plan_signature(key: DistAttnRuntimeKey) -> tuple:
    """Everything the host-side solved plan depends on.

    The runtime key minus the parts that only affect traced execution:
    device ids (mesh_sig[1] — the same plan is valid on any device
    assignment of the same axis layout) and head_axis (TP sharding of the
    already-solved plan). The capacity vector is appended ONLY when
    non-uniform: uniform signatures stay byte-identical to builds without
    capacity support, so warm plan stores are never invalidated."""
    sig = (
        key.q_ranges,
        key.k_ranges,
        key.attn_mask_type,
        key.total_seqlen_q,
        key.total_seqlen_k,
        key.chunk_size,
        key.cp_size,
        key.cp_axis,
        key.mesh_sig[0],
        key.config,
        key.env_snapshot,
        key.fixed_partitions,
    )
    if key.capacities is not None:
        sig = sig + (("capacities", key.capacities),)
    return sig


def _mask_family(sig: tuple) -> tuple:
    """Signature minus the mask itself (q/k ranges + types): dynamic-mask
    steps of the same workload share a family, so a new signature can seed
    its incremental re-solve from the family's previous solve state."""
    return sig[3:]


class _PlanCache:
    """Mask-signature-keyed solved-plan LRU, one level below the runtime
    LRU (DistAttnRuntimeDict).

    The runtime LRU caches traced managers per full runtime key; this cache
    holds only the host-solved artifacts (dispatch metas + static attn
    metas / dynamic plan), so a repeated mask signature skips every solver
    pass even when the traced runtime was evicted or is keyed differently
    (e.g. same plan on a different device assignment). It also remembers
    each mask family's latest dynamic solve state to seed incremental
    re-solves on a miss. Reuse is exact — a hit returns the identical plan
    objects a cold solve produced — and every reusing manager still runs
    the R1-R5 verifier on its plan (MAGI_ATTENTION_VERIFY_PLANS=1)."""

    def __init__(self) -> None:
        self._d: OrderedDict[tuple, dict] = OrderedDict()
        self._prev_dyn: dict[tuple, Any] = {}
        self._hits = 0
        self._misses = 0

    def lookup(self, sig: tuple) -> dict | None:
        if sig in self._d:
            self._d.move_to_end(sig)
            self._hits += 1
            telemetry.inc("plan_solve.cache_hit")
            return self._d[sig]
        self._misses += 1
        telemetry.inc("plan_solve.cache_miss")
        return None

    def store(self, sig: tuple, entry: dict) -> None:
        self._d[sig] = entry
        self._d.move_to_end(sig)
        while len(self._d) > env_general.plan_cache_size():
            self._d.popitem(last=False)

    def prev_dyn_state(self, family: tuple):
        return self._prev_dyn.get(family)

    def set_dyn_state(self, family: tuple, state) -> None:
        if state is not None:
            self._prev_dyn[family] = state

    def get_stats(self) -> dict[str, int]:
        return {
            "hits": self._hits,
            "misses": self._misses,
            "size": len(self._d),
        }

    def clear(self) -> None:
        self._d.clear()
        self._prev_dyn.clear()
        self._hits = 0
        self._misses = 0


# module-level: plans outlive any one runtime dict (api/magi_attn_interface
# builds one DistAttnRuntimeDict; tests may build their own)
_PLAN_CACHE = _PlanCache()


# ---------------------------------------------------------------------------
# plan control plane: memory LRU -> disk store -> broadcast -> cold solve
# (docs/plan_control_plane.md). Every tier below memory is byte-serialized
# (meta/plan_io.py), so every loaded entry is integrity-checked at decode
# and re-verified by R1-R5/check_hier_plan before first use. Every failure
# on the way down the ladder is a recorded miss, never a crash — the cold
# solver is always reachable.
# ---------------------------------------------------------------------------


def _chaos_miss(site: str, err: Exception) -> None:
    """Recover-or-typed-raise for an InjectedFault at a control-plane site:
    with MAGI_ATTENTION_FALLBACK=1 the fault becomes a recorded miss, else
    it propagates typed (the standard chaos contract)."""
    if not env_resilience.is_fallback_enable():
        raise err
    from .resilience.fallback import record_resilience_event

    record_resilience_event(
        "fallback", getattr(err, "site", site),
        action_detail="cold_solve", error=type(err).__name__,
    )


def _verify_loaded_entry(entry: dict, key: DistAttnRuntimeKey) -> bool:
    """R1-R5 (+ check_hier_plan for two-level stages) over a disk/wire
    loaded entry — unconditional, unlike MAGI_ATTENTION_VERIFY_PLANS: a
    deserialized plan is only trusted after it verifies exactly like a
    cold-solved one. Any verifier error (or malformed entry) rejects the
    entry back to a miss."""
    from .analysis.verifier import verify_dynamic_plan, verify_plan

    align = key.config.grpcoll_config.split_alignment
    try:
        meta_q, meta_kv, bucket = entry["dispatch"]
        dynamic = entry.get("dynamic")
        if dynamic is not None and not verify_dynamic_plan(
            dynamic, split_alignment=align
        ).ok():
            return False
        static = entry.get("static")
        comm_meta, calc_meta = static if static is not None else (None, None)
        report = verify_plan(
            dispatch_meta=meta_q,
            bucket=bucket,
            comm_meta=comm_meta,
            calc_meta=calc_meta,
            dispatch_meta_kv=(meta_kv if meta_kv is not meta_q else None),
            split_alignment=align,
        )
        return report.ok()
    except Exception:
        return False


def _reject_loaded_entry(site: str, reason: str) -> None:
    from .resilience.fallback import record_resilience_event

    record_resilience_event("reject", site, reason=reason)


def _control_plane_lookup(
    sig: tuple, key: DistAttnRuntimeKey, entry: dict | None, source: str
) -> tuple[dict | None, str, dict, bool]:
    """Run the disk + broadcast tiers for one plan resolution.

    ``entry``/``source`` are the memory tier's result; returns the
    (possibly upgraded) ``(entry, source, telemetry_extra, exchanged)``,
    where ``exchanged`` records that this resolution's one collective
    broadcast exchange already happened (so ``_persist_entry`` must not
    publish again — hosts pair ``broadcast_one_to_all`` calls one-to-one,
    and a second leader-side exchange would desync every later pairing).
    Loaded entries are verified here; a broadcast-received entry is
    written through to the disk store so later processes warm-start
    locally."""
    env_sig = key.env_snapshot
    digest: str | None = None
    extra: dict = {}

    store = plan_store.get_store()
    if entry is None and store is not None:
        digest = plan_io.plan_signature_digest(sig)
        try:
            candidate, miss = store.read(digest, env_sig=env_sig)
        except Exception as e:
            from .resilience.errors import InjectedFault

            if not isinstance(e, InjectedFault):
                raise
            _chaos_miss("plan_cache_read", e)
            candidate, miss = None, None
        if candidate is not None:
            if _verify_loaded_entry(candidate, key):
                entry, source = candidate, "disk"
            else:
                _reject_loaded_entry("plan_cache_read", plan_store.MISS_VERIFY)

    transport = plan_broadcast.get_transport()
    if transport is None:
        return entry, source, extra, False
    leader = plan_broadcast.is_leader()
    multihost = isinstance(transport, plan_broadcast.MultihostTransport)
    if digest is None:
        digest = plan_io.plan_signature_digest(sig)
    if leader:
        exchanged = False
        if multihost and entry is not None:
            # the multihost transport is collective — the leader must
            # exchange on EVERY resolution (hits included) so follower
            # receive counts align. This publish IS the resolution's one
            # exchange: the returned flag makes any later _persist_entry
            # (e.g. a dynamic re-solve over a static-fallback hit) skip
            # its publish instead of exchanging a second time.
            exchanged = _persist_entry(sig, key, entry, store=None)
        elif (
            entry is not None
            and isinstance(transport, plan_broadcast.FileTransport)
            and not transport.published_ok(digest, env_sig)
        ):
            # warm leader, file transport: the published blob is missing
            # or corrupt (e.g. a crash raced the publish) — heal it so
            # followers stop burning the full retry path on this digest
            _persist_entry(sig, key, entry, store=None)
        return entry, source, extra, exchanged
    if entry is not None and not multihost:
        return entry, source, extra, False
    try:
        result = plan_broadcast.exchange_plan(digest, None)
    except Exception as e:
        from .resilience.errors import InjectedFault

        if not isinstance(e, InjectedFault):
            raise
        _chaos_miss("plan_broadcast", e)
        return entry, source, extra, False
    if result.attempts > 1:
        extra["attempts"] = result.attempts
        extra["backoff_ms"] = result.backoff_ms
    if entry is not None or result.blob is None:
        if result.blob is None:
            from .resilience.fallback import record_resilience_event

            record_resilience_event(
                "exhausted", "plan_broadcast",
                action_detail="cold_solve", attempts=result.attempts,
            )
        return entry, source, extra, False
    try:
        candidate = plan_io.decode_plan(
            result.blob, env_sig=env_sig, expect_digest=digest
        )
    except plan_io.PlanDecodeError as e:
        _reject_loaded_entry("plan_broadcast", type(e).__name__)
        return entry, source, extra, False
    if not _verify_loaded_entry(candidate, key):
        _reject_loaded_entry("plan_broadcast", plan_store.MISS_VERIFY)
        return entry, source, extra, False
    if store is not None:  # write-through: future processes warm-start
        store.write(digest, result.blob)
    return candidate, "broadcast", extra, False


def _persist_failure(
    site: str, err: Exception, collective_transport, digest: str
) -> None:
    """Failure tail of ``_persist_entry``: an InjectedFault follows the
    chaos contract (recover under fallback, typed raise without), any
    genuine error is a recorded degradation — persisting is write-through
    and must never cost the step. Either way, when a collective transport
    is mid-resolution (followers already blocked in their receive), the
    exchange is completed with a zero-length blob so their collective call
    pairs off and they degrade to a local cold solve instead of hanging."""
    from .resilience.errors import InjectedFault

    try:
        if isinstance(err, InjectedFault):
            _chaos_miss(site, err)
        else:
            from .resilience.fallback import record_resilience_event

            record_resilience_event(
                "fallback", site, action_detail="skip_persist",
                error=type(err).__name__,
            )
    finally:
        if collective_transport is not None:
            try:
                collective_transport.exchange(digest, b"")
            except Exception:
                pass


def _persist_entry(
    sig: tuple,
    key: DistAttnRuntimeKey,
    entry: dict,
    store: plan_store.PlanStore | None = ...,
    exchanged: bool = False,
) -> bool:
    """Write-through after a solve: serialize once, land in the disk
    store, and (as broadcast leader) publish to the other hosts — unless
    ``exchanged`` says this resolution's one collective exchange already
    happened. Never costs the step — every failure is a recorded
    degradation except the chaos contract's typed raise, and on a
    collective transport even the failure paths complete the exchange
    (zero-length blob) so followers never hang. Returns True when this
    call performed (or completed) the resolution's broadcast exchange."""
    if store is ...:
        store = plan_store.get_store()
    transport = plan_broadcast.get_transport()
    multihost = isinstance(transport, plan_broadcast.MultihostTransport)
    publish = (
        transport is not None
        and plan_broadcast.is_leader()
        and not exchanged
    )
    if store is None and not publish:
        return False
    digest = plan_io.plan_signature_digest(sig)
    wire_entry = {
        k: v for k, v in entry.items() if k in ("dispatch", "static", "dynamic")
    }
    try:
        blob = plan_io.encode_plan(
            wire_entry, env_sig=key.env_snapshot, sig_digest=digest
        )
    except Exception as e:
        _persist_failure(
            "plan_serialize", e,
            transport if (publish and multihost) else None, digest,
        )
        return publish and multihost
    if store is not None:
        store.write(digest, blob)
    if not publish:
        return False
    try:
        plan_broadcast.exchange_plan(digest, blob)
    except Exception as e:
        _persist_failure(
            "plan_broadcast", e, transport if multihost else None, digest
        )
    return True


class DistAttnRuntimeMgr:
    """Owns metas + runtime for one key (ref :164-483)."""

    def __init__(self, key: DistAttnRuntimeKey, mesh: Mesh) -> None:
        self.key = key
        self.mesh = mesh
        q_ranges = AttnRanges.from_ranges(key.q_ranges)
        k_ranges = AttnRanges.from_ranges(key.k_ranges)
        mask_types = [AttnMaskType.from_int_type(t) for t in key.attn_mask_type]

        cache_on = env_general.is_plan_cache_enable()
        sig = _plan_signature(key) if cache_on else None
        entry = _PLAN_CACHE.lookup(sig) if cache_on else None
        # where this manager's solved plan came from:
        # memory | disk | broadcast | cold (stamped on plan_solve telemetry)
        self.plan_source = "memory" if entry is not None else "cold"
        self._plan_meta: dict = {}
        # True once this resolution's single collective broadcast exchange
        # happened (leader publish-on-hit): later persists must not
        # exchange again or hosts pair collectives off-by-one
        bcast_exchanged = False
        if cache_on:
            fetched, src, extra, bcast_exchanged = _control_plane_lookup(
                sig, key, entry, self.plan_source
            )
            if entry is None and fetched is not None:
                entry = fetched
                self.plan_source = src
                self._plan_meta = extra
                # promote into the memory tier: the next resolution of this
                # signature is a plain LRU hit
                _PLAN_CACHE.store(sig, entry)

        if entry is not None:
            # solved-plan cache hit: the whole solver pipeline (dispatch +
            # attn plan) is skipped; verification below still runs
            self.dispatch_meta_q, self.dispatch_meta_kv, self.bucket = (
                entry["dispatch"]
            )
        else:
            self.dispatch_meta_q, self.dispatch_meta_kv, self.bucket = (
                make_dispatch_meta_from_qk_ranges(
                    q_ranges,
                    k_ranges,
                    mask_types,
                    key.total_seqlen_q,
                    key.total_seqlen_k,
                    key.chunk_size,
                    key.cp_size,
                    key.config.dispatch_config,
                    preset_partitions=(
                        [list(p) for p in key.fixed_partitions]
                        if key.fixed_partitions is not None
                        else None
                    ),
                    capacities=(
                        list(key.capacities)
                        if key.capacities is not None
                        else None
                    ),
                )
            )
        from .env import comm as env_comm

        if env_comm.is_qo_comm_enable():
            # dynamic (qo-comm) planner: q/o rows may move, overlap degree 1
            # (ref config.py:67-71)
            from .functional.dynamic_dist_attn import DynamicDistAttnRuntime
            from .meta._make_attn_meta import make_dynamic_attn_plan

            # the dynamic runtime supports neither TP head sharding nor
            # hierarchical comm yet — fail loudly instead of silently
            # dropping the requested config
            if key.head_axis is not None:
                raise NotImplementedError(
                    "MAGI_ATTENTION_QO_COMM=1 does not support head_axis "
                    "(TP head sharding) yet; unset one of the two"
                )
            if env_comm.is_hierarchical_comm_enable():
                raise NotImplementedError(
                    "MAGI_ATTENTION_QO_COMM=1 does not support "
                    "MAGI_ATTENTION_HIERARCHICAL_COMM=1 yet; unset one"
                )

            cached_plan = entry.get("dynamic") if entry is not None else None
            if cached_plan is not None:
                self.dynamic_plan = cached_plan
                if telemetry.enabled():
                    telemetry.record_event(
                        "plan_solve", planner="dynamic", event="cache_hit",
                        source=self.plan_source, incremental=False,
                        wall_ms=0.0, rows_resolved=0, **self._plan_meta,
                    )
                built_dynamic = True
            else:
                built_dynamic = False
                try:
                    self.dynamic_plan = make_dynamic_attn_plan(
                        q_ranges, k_ranges, mask_types,
                        self.dispatch_meta_q, key.config,
                        dispatch_meta_kv=self.dispatch_meta_kv,
                        prev_state=(
                            _PLAN_CACHE.prev_dyn_state(_mask_family(sig))
                            if cache_on
                            else None
                        ),
                    )
                except Exception as e:
                    # degradation chain 2 (docs/resilience.md): a failed
                    # dynamic solve falls back to the static solver plan —
                    # same mask, kv-comm execution instead of qo-comm
                    if not env_resilience.is_fallback_enable():
                        raise
                    from .resilience.fallback import record_resilience_event

                    record_resilience_event(
                        "fallback", "dynamic_plan_solve",
                        action_detail="static_plan", error=type(e).__name__,
                    )
                else:
                    built_dynamic = True
                    if cache_on:
                        new_entry = {
                            "dispatch": (
                                self.dispatch_meta_q,
                                self.dispatch_meta_kv,
                                self.bucket,
                            ),
                            "dynamic": self.dynamic_plan,
                        }
                        _PLAN_CACHE.store(sig, new_entry)
                        _PLAN_CACHE.set_dyn_state(
                            _mask_family(sig),
                            self.dynamic_plan.solver_state,
                        )
                        _persist_entry(
                            sig, key, new_entry, exchanged=bcast_exchanged
                        )
            if built_dynamic:
                self.comm_meta = self.calc_meta = None
                self.runtime = DynamicDistAttnRuntime(
                    plan=self.dynamic_plan, mesh=mesh, cp_axis=key.cp_axis
                )
                if telemetry.enabled():
                    p = self.dynamic_plan
                    telemetry.record_event(
                        "plan_build",
                        planner="dynamic",
                        cp_size=key.cp_size,
                        overlap_degree=1,
                        stages=[
                            {"name": name, **cast.telemetry_dict()}
                            for name, cast in (
                                ("q_cast", p.q_cast),
                                ("kv_cast", p.kv_cast),
                                ("ret", p.ret),
                            )
                        ],
                    )
                self._maybe_verify()
                return

        self.dynamic_plan = None
        cached_metas = entry.get("static") if entry is not None else None
        if cached_metas is not None:
            self.comm_meta, self.calc_meta = cached_metas
            if telemetry.enabled():
                telemetry.record_event(
                    "plan_solve", planner="static", event="cache_hit",
                    source=self.plan_source, incremental=False,
                    wall_ms=0.0, rows_resolved=0, **self._plan_meta,
                )
        else:
            self.comm_meta, self.calc_meta = make_attn_meta_from_dispatch_meta(
                self.bucket, self.dispatch_meta_q, key.config,
                dispatch_meta_kv=self.dispatch_meta_kv,
                mesh_shape=_mesh_shape_for(key, mesh),
            )
            if cache_on:
                new_entry = dict(entry) if entry is not None else {}
                new_entry["dispatch"] = (
                    self.dispatch_meta_q, self.dispatch_meta_kv, self.bucket
                )
                new_entry["static"] = (self.comm_meta, self.calc_meta)
                _PLAN_CACHE.store(sig, new_entry)
                _persist_entry(sig, key, new_entry, exchanged=bcast_exchanged)
        overlap_cfg = key.config.overlap_config
        self.runtime = DistAttnRuntime(
            comm_meta=self.comm_meta,
            calc_meta=self.calc_meta,
            mesh=mesh,
            cp_axis=key.cp_axis,
            head_axis=key.head_axis,
            # auto (overlap iff the solver produced >1 stage) when enabled,
            # forced single merged kernel when disabled
            use_overlap=None if overlap_cfg.enable else False,
            label=key.label,
        )
        self._record_comm_plan()
        self._maybe_verify()

    def _maybe_verify(self) -> None:
        """Opt-in static verification of the freshly built plan
        (MAGI_ATTENTION_VERIFY_PLANS=1, analysis/verifier.py): raises
        PlanVerificationError on error-severity violations so a malformed
        plan fails at build time instead of as a wrong loss inside
        shard_map."""
        from .analysis import maybe_verify_runtime

        maybe_verify_runtime(self)

    def _stage_telemetry_dicts(self) -> list[dict]:
        """Per-stage comm summaries with the EXECUTED lowering: the runtime
        may override the solver's portable choice with the backend-dependent
        ragged/hier tier — report what actually runs."""
        kinds = getattr(self.runtime, "_cast_kinds", None)
        names = {"pp": "ppermute", "a2a": "a2a", "ragged": "ragged",
                 "hier": "hier"}
        out = []
        for st, s in enumerate(self.comm_meta.kv_stages):
            executed = (
                names.get(kinds[st][0], kinds[st][0])
                if kinds and st < len(kinds)
                else s.lowering
            )
            out.append(
                {
                    "stage": st,
                    "xprof_scope": f"group_cast_stage{st}",
                    **s.telemetry_dict(executed=executed),
                }
            )
        return out

    def _record_comm_plan(self) -> None:
        """The init-time comm-plan dump (ref dist_attn_runtime_mgr.py:
        673-1033 meta dumps + comm_meta.py:86-155 send/recv token counts):
        per-stage payload rows, wire rows, padding ratio, chosen lowering —
        emitted to the telemetry registry when MAGI_ATTENTION_TELEMETRY=1
        and to the INFO log when enabled (one source of numbers for both)."""
        import logging

        logger = logging.getLogger("magiattention_tpu.runtime")
        log_on = logger.isEnabledFor(logging.INFO)
        if not (log_on or telemetry.enabled()):
            return
        stages = self._stage_telemetry_dicts()
        if telemetry.enabled():
            telemetry.record_event(
                "plan_build",
                planner="static",
                cp_size=self.key.cp_size,
                overlap_degree=self.comm_meta.overlap_degree,
                stages=stages,
            )
        if log_on:
            for d in stages:
                logger.info(
                    "comm plan stage %d/%d: executed=%s planned=%s "
                    "payload_rows=%d wire_rows=%d ratio=%.3f (a2a would be "
                    "%d) a_cap=%d r_max=%d per-rank send rows=%s recv "
                    "rows=%s",
                    d["stage"], len(stages), d["lowering_executed"],
                    d["lowering_planned"], d["payload_rows"], d["wire_rows"],
                    d["wire_ratio"], d["a2a_wire_rows"], d["a_cap"],
                    d["r_max"], d["send_rows_per_rank"],
                    d["recv_rows_per_rank"],
                )

    # -- ops ---------------------------------------------------------------

    def dispatch_qo(self, x: jax.Array) -> jax.Array:
        return dispatch_func(
            x, self.dispatch_meta_q.position_ids, self.mesh, self.key.cp_axis
        )

    def dispatch_kv(self, x: jax.Array) -> jax.Array:
        return dispatch_func(
            x, self.dispatch_meta_kv.position_ids, self.mesh, self.key.cp_axis
        )

    def undispatch_qo(self, x: jax.Array) -> jax.Array:
        return undispatch_func(
            x, self.dispatch_meta_q.unpermute_index, self.mesh, self.key.cp_axis
        )

    def undispatch_kv(self, x: jax.Array) -> jax.Array:
        return undispatch_func(
            x, self.dispatch_meta_kv.unpermute_index, self.mesh, self.key.cp_axis
        )

    def calc_attn(
        self,
        q: jax.Array,
        k: jax.Array,
        v: jax.Array,
        return_max_logits: bool = False,
    ):
        if env_general.precision() == "bf16":
            # precision override (ref dist_attn.py:3760-3786) — applied at
            # the manager chokepoint so every entry path honors it
            import jax.numpy as jnp

            q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
        return self.runtime.calc_attn(
            q, k, v, return_max_logits=return_max_logits
        )

    def roll(self, x: jax.Array, shifts: int) -> jax.Array:
        from .functional.roll import roll_func

        return roll_func(
            x, self.dispatch_meta_q, shifts, self.mesh, self.key.cp_axis
        )

    def get_position_ids(self) -> jax.Array:
        import jax.numpy as jnp

        return jnp.asarray(self.dispatch_meta_q.position_ids.reshape(-1))

    def get_document_starts(self) -> jax.Array:
        """The global row of the first token of each dispatched row's
        document, in the order of :meth:`get_position_ids` (rank-major, each
        rank's chunks in its plan's order, pad rows 0). A document is a run
        of rows that the mask ties together: the slices' q and k ranges,
        merged where they overlap, are the documents; a row no slice covers
        is a document of its own."""
        import jax.numpy as jnp
        import numpy as np

        key = self.key
        spans = sorted(
            (min(q[0], k[0]), max(q[1], k[1]))
            for q, k in zip(key.q_ranges, key.k_ranges))
        starts = np.arange(key.total_seqlen_q, dtype=np.int32)
        lo = hi = -1
        for s, e in spans + [(key.total_seqlen_q, key.total_seqlen_q)]:
            if s >= hi:  # the run that ended at ``hi`` is one document
                if hi > lo:
                    starts[lo:hi] = lo
                lo, hi = s, e
            else:
                hi = max(hi, e)
        pos = self.dispatch_meta_q.position_ids.reshape(-1)
        return jnp.asarray(starts[pos])

    def get_xattn_args(
        self,
        ref_xattn_q_ranges: AttnRanges,
        ref_xattn_k_ranges: AttnRanges,
        attn_mask_type=None,
    ) -> Any:
        """Cross-attention args for the dispatched q layout (ref :269-357).

        The dispatched q tensor is chunk-permuted; to cross-attend it
        against a NEW (replicated, undistributed) kv tensor, each global
        (q_range, k_range) pair must be re-expressed in local dispatched q
        coordinates. Only FULL masks are supported (ref asserts the same).

        Returns:
            The rank-stacked list of per-rank :class:`AttnArg` — this API
            is SPMD; the caller selects its shard inside shard_map.
        """
        from .common.enum import AttnMaskType as _MT
        from .kernels.mask_utils import BAND_INF
        from .meta.collection.calc_meta import AttnArg

        if len(ref_xattn_q_ranges) != len(ref_xattn_k_ranges):
            raise ValueError(
                f"q/k range count mismatch: {len(ref_xattn_q_ranges)} vs "
                f"{len(ref_xattn_k_ranges)}"
            )
        if attn_mask_type is not None:
            types = (
                attn_mask_type
                if isinstance(attn_mask_type, list)
                else [attn_mask_type] * len(ref_xattn_q_ranges)
            )
            assert all(
                _MT.normalize(t) == _MT.FULL for t in types
            ), "only FULL cross-attn masks supported (ref :293)"

        meta = self.dispatch_meta_q
        shard = meta.shard_seqlen
        sk = ref_xattn_k_ranges.end
        args = []
        for rank in range(meta.cp_size):
            own = meta.host_ranges_per_rank[rank]
            slices = []
            for qr, kr in zip(ref_xattn_q_ranges, ref_xattn_k_ranges):
                for piece in AttnRanges([qr]).find_overlap_ranges(own):
                    q_loc = own.make_range_local(piece)
                    slices.append(
                        (q_loc.start, q_loc.end, kr.start, kr.end,
                         -BAND_INF, BAND_INF)
                    )
            args.append(AttnArg.from_slices(slices, shard, sk))
        return args


class DistAttnRuntimeDict:
    """LRU cache of managers (ref :412; api/magi_attn_interface.py:64)."""

    def __init__(self, maxsize: int | None = None) -> None:
        self.maxsize = maxsize or env_general.runtime_dict_size()
        self._d: OrderedDict[DistAttnRuntimeKey, DistAttnRuntimeMgr] = OrderedDict()
        # plain int counters: always maintained (no timers / file I/O, so
        # the telemetry-off contract holds); exported via get_stats() and,
        # when MAGI_ATTENTION_TELEMETRY=1, mirrored into the registry
        self._hits = 0
        self._misses = 0
        self._evictions = 0

    def get_or_create(
        self, key: DistAttnRuntimeKey, mesh: Mesh
    ) -> DistAttnRuntimeMgr:
        if key in self._d:
            self._d.move_to_end(key)
            self._hits += 1
            telemetry.inc("runtime_cache.hit")
            return self._d[key]
        self._misses += 1
        telemetry.inc("runtime_cache.miss")
        with telemetry.stage_timer("runtime_mgr_init"):
            try:
                mgr = self._build_mgr(key, mesh)
            except Exception:
                # invariant: a build that raised must never leave an
                # entry behind — the next get_or_create must rebuild
                self._d.pop(key, None)
                raise
        self._d[key] = mgr
        while len(self._d) > self.maxsize:
            self._d.popitem(last=False)
            self._evictions += 1
            telemetry.inc("runtime_cache.evict")
        if telemetry.enabled():
            telemetry.record_event("runtime_cache", **self.get_stats())
        return mgr

    def _build_mgr(self, key: DistAttnRuntimeKey, mesh: Mesh):
        """One manager build, with the resilience layer's bounded retry
        (MAGI_ATTENTION_FALLBACK=1: one extra attempt — enough to absorb
        a transient plan-build failure, never an infinite loop). The
        manager class is resolved by NAME at call time so tests can
        monkeypatch the module global."""
        retries = (
            0 if not env_resilience.is_fallback_enable()
            else _plan_build_retries()
        )
        for attempt in range(retries + 1):
            try:
                mgr = DistAttnRuntimeMgr(key, mesh)
            except Exception as e:
                if attempt >= retries:
                    raise
                from .resilience.fallback import record_resilience_event

                record_resilience_event(
                    "retry", "plan_build", attempt=attempt + 1,
                    error=type(e).__name__,
                )
                continue
            if attempt:
                from .resilience.fallback import record_resilience_event

                record_resilience_event(
                    "recovered", "plan_build", attempt=attempt,
                )
            return mgr

    def get(self, key: DistAttnRuntimeKey) -> DistAttnRuntimeMgr | None:
        return self._d.get(key)

    def get_stats(self) -> dict[str, int]:
        """Cache behavior counters (the cache is keyed on mask + mesh +
        config + ENV_KEYS_AFFECTING_RUNTIME snapshot, so a surprise miss
        rate usually means env flags are churning between steps)."""
        return {
            "hits": self._hits,
            "misses": self._misses,
            "evictions": self._evictions,
            "size": len(self._d),
            "maxsize": self.maxsize,
        }

    def clear(self) -> None:
        self._d.clear()

    def __len__(self) -> int:
        return len(self._d)
