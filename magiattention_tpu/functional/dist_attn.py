"""The CP engine (ref: magi_attention/functional/dist_attn.py:142,3101).

``DistAttnRuntime`` turns the solver's host plans (CommMeta + CalcMeta) into a
single SPMD function over the CP mesh axis:

- no-overlap path (ref :3305): GroupCast all remote kv, concatenate with the
  local shard, run ONE merged FFA kernel. Simplest, fewest launches.
- multi-stage overlap path (ref :3195-3266): run the host kernel and one FFA
  per stage against that stage's receive buffer, lse-merging partials. The
  per-stage all_to_alls have no data dependence on earlier compute, so XLA's
  async collective scheduler hides stage i+1's communication under stage i's
  compute — replacing the reference's stream/event + KernelBarrier machinery.

Backward: jax AD. The kernel has a custom VJP (Pallas dq/dkv kernels); the
GroupCast gathers + all_to_all transpose to scatter-add + reverse all_to_all,
which IS GroupReduce — zero-redundant dkv reduction with no hand-written comm
(replacing _reduce_partial_dkv, ref :2123). The lse-merge transposes through
jnp autodiff (replacing _reduce_partial_out_lse, ref :1979).

SPMD note: per-rank metadata (slice lists, index arrays, FFA plans) is padded
to rank-uniform shapes and passed as sharded operands, so one traced program
serves every rank — the TPU answer to the reference's per-rank host code.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from ..comm.primitives import cast_rows, reduce_rows
from ..env import comm as env_comm
from ..env import general as env_general
from ..env import resilience as env_resilience
from ..kernels.ffa import (
    FFAParams,
    _bwd_plan_slices,
    ffa_bwd_pallas_dispatch,
    ffa_delta_pallas_dispatch,
    ffa_fwd_pallas_dispatch,
    _should_interpret,
    ffa_attn_with_plan,
    note_tiles,
    resolved_bwd_mode,
)
from ..kernels.ffa_plan import build_ffa_plan, pad_plan
from ..meta.collection.calc_meta import AttnArg, CalcMeta
from ..meta.collection.comm_meta import CommMeta
from ..utils.profiling import instrument_scope, profile_scope
from .utils import lse_weighted_reduce
from .. import telemetry


def _head_major(x: jax.Array, sp: int) -> jax.Array:
    """(s, h, d) -> (h, sp, d) padded to sp rows."""
    return jnp.pad(x, ((0, sp - x.shape[0]), (0, 0), (0, 0))).transpose(1, 0, 2)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _multi_ffa(q, ks, vs, arrays_list, params_list):
    """Merged multi-part FFA: part i attends q against (ks[i], vs[i]) with its
    own plan; partials are lse-merged into one (out, lse, max_logits).

    The VJP is the distributed-flash identity (ref dist_attn.py bwd loop
    :3561): each part's backward kernel runs against the FINAL merged lse and
    delta = rowsum(do * out_final), which makes per-part dq/dkv contributions
    exact — no gradient flows through the merge weights themselves.
    max_logits is the elementwise MAX over parts (ref reduce_max_logits,
    dist_attn.py:550); it is a non-differentiable auxiliary output.
    """
    out, lse, ml, _, _ = _multi_ffa_impl(q, ks, vs, arrays_list, params_list)
    return out, lse, ml


def _multi_ffa_impl(q, ks, vs, arrays_list, params_list):
    outs, lses = [], []
    ml = None
    for i, (k, v, arrs, prm) in enumerate(
        zip(ks, vs, arrays_list, params_list)
    ):
        sqp = prm.num_q_tiles * prm.block_q
        skp = prm.num_k_tiles * prm.block_k
        q_t = _head_major(q, sqp)
        # compute always in q's dtype: k/v parts may arrive fp32 from the
        # high-precision-reduce cast (hp_group_cast) so their cotangents
        # stay fp32 through the wire reduce
        k_t = _head_major(k.astype(q.dtype), skp)
        v_t = _head_major(v.astype(q.dtype), skp)
        with profile_scope(f"ffa_fwd_stage{i}"):
            out_t, lse_t, ml_p = ffa_fwd_pallas_dispatch(
                prm, *arrs[:3], q_t, k_t, v_t
            )
        outs.append(out_t.transpose(1, 0, 2)[: q.shape[0]])
        lses.append(lse_t.T[: q.shape[0]])
        ml = ml_p if ml is None else jnp.maximum(ml, ml_p)
    with profile_scope("lse_merge"):
        out, lse = lse_weighted_reduce(jnp.stack(outs), jnp.stack(lses))
    return out, lse, ml, outs, lses


def _multi_ffa_fwd(q, ks, vs, arrays_list, params_list):
    out, lse, ml, _, _ = _multi_ffa_impl(q, ks, vs, arrays_list, params_list)
    # residuals keep the PRIMAL-dtype parts: under HP reduce the remote
    # parts are fp32 (2x residual HBM — the flag's documented cost) so
    # their cotangents legally leave fp32 for the wire reduce
    return (out, lse, ml), (q, ks, vs, out, lse, arrays_list)


def _multi_ffa_bwd(params_list, res, cts):
    do, _, _ = cts  # lse/max_logits cotangents ignored (auxiliary outputs)
    q, ks, vs, out, lse, arrays_list = res
    sq = q.shape[0]
    # delta = rowsum(do ⊙ out) on the MXU-free VPU path (Pallas kernel),
    # computed once at part 0's tile geometry and shared by every part
    prm0 = params_list[0]
    sqp0 = prm0.num_q_tiles * prm0.block_q
    with profile_scope("ffa_bwd_delta"):
        delta = ffa_delta_pallas_dispatch(
            prm0, _head_major(out, sqp0), _head_major(do, sqp0)
        ).T[:sq]  # (sq, hq)

    dq_total = None
    dks, dvs = [], []
    for k, v, arrs, prm in zip(ks, vs, arrays_list, params_list):
        sqp = prm.num_q_tiles * prm.block_q
        skp = prm.num_k_tiles * prm.block_k
        q_t = _head_major(q, sqp)
        k_t = _head_major(k.astype(q.dtype), skp)
        v_t = _head_major(v.astype(q.dtype), skp)
        do_t = _head_major(do, sqp)
        # pad lse with -inf, delta with 0 for rows beyond sq
        lse_t = jnp.pad(
            lse, ((0, sqp - sq), (0, 0)), constant_values=float("-inf")
        ).T
        delta_t = jnp.pad(delta, ((0, sqp - sq), (0, 0))).T
        dq_arrs, dkv_arrs = _bwd_plan_slices(arrs)
        with profile_scope("ffa_bwd"):
            dq_t, dk_t, dv_t = ffa_bwd_pallas_dispatch(
                prm, dq_arrs, dkv_arrs, q_t, k_t, v_t, do_t, lse_t, delta_t
            )
        # dk/dv already per kv head (dkv kernel sums the GQA group); the
        # kernels emit fp32, so the casts are identity under HP reduce
        dq = dq_t.transpose(1, 0, 2)[:sq].astype(q.dtype)
        dq_total = dq if dq_total is None else dq_total + dq
        dks.append(dk_t.transpose(1, 0, 2)[: k.shape[0]].astype(k.dtype))
        dvs.append(dv_t.transpose(1, 0, 2)[: v.shape[0]].astype(v.dtype))
    return dq_total, tuple(dks), tuple(dvs), None


_multi_ffa.defvjp(_multi_ffa_fwd, _multi_ffa_bwd)


def _cast_any(x, ops, kind, axis_name):
    """cast_rows extended with the hierarchical tier
    (kind ``("hier", dcn_axis, ici_axis)``)."""
    if kind[0] == "hier":
        from ..comm.hier import hier_group_cast_rows

        return hier_group_cast_rows(
            x, ops[0], ops[1], ops[2], ops[3], kind[1], kind[2]
        )
    return cast_rows(x, ops, kind, axis_name)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def hp_group_cast(x, ops, kind, axis_name, shard_len, in_dtype):
    """GroupCast whose transpose (GroupReduce) runs in fp32 on the wire.

    Forward sends x in its own dtype (bf16 wire, unchanged) and upcasts the
    receive buffer to fp32; backward reduces the fp32 cotangent through the
    collective and casts to x's dtype only AFTER the cross-rank sum — the
    reference's high-precision partial-grad reduce (_reduce_partial_dkv,
    magi_attention/functional/dist_attn.py:2123, enabled by
    MAGI_ATTENTION_BACKWARD_HIGH_PRECISION_REDUCE). Doubles backward comm
    bytes; removes the cp-way low-precision summation error. XLA folds the
    fwd up/down-cast pair around the kernel's compute cast, so the fp32
    receive buffer never persists.
    """
    return _cast_any(x, ops, kind, axis_name).astype(jnp.float32)


def _hp_group_cast_fwd(x, ops, kind, axis_name, shard_len, in_dtype):
    return hp_group_cast(x, ops, kind, axis_name, shard_len, in_dtype), ops


def _hp_group_cast_bwd(kind, axis_name, shard_len, in_dtype, res, g):
    ops = res
    if kind[0] == "hier":
        # transpose via jax.vjp of the cast itself (same trick as the
        # ragged tier in reduce_rows) — no hand-maintained mirror plan
        zeros = jnp.zeros((shard_len, *g.shape[1:]), g.dtype)
        _, vjp_fn = jax.vjp(
            lambda z: _cast_any(z, ops, kind, axis_name), zeros
        )
        (red,) = vjp_fn(g)
    else:
        red = reduce_rows(g, ops, kind, axis_name, shard_len)
    return red.astype(in_dtype), None


hp_group_cast.defvjp(_hp_group_cast_fwd, _hp_group_cast_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def hp_group_cast_all(x, ops_list, kinds, axis_name, shard_len, in_dtype):
    """All stages of the GroupCast — local fp32 copy first, then one fp32
    receive buffer per stage — behind ONE custom VJP.

    Per-stage :func:`hp_group_cast` downcasts each reduced cotangent to the
    input dtype independently, so JAX's implicit cotangent accumulation
    still sums the (stages+1) dkv partials in bf16 — only approximately the
    reference's _reduce_partial_dkv, which keeps every partial fp32 and
    casts once (magi_attention/functional/dist_attn.py:2123; ADVICE r4).
    Spanning local shard + all stages here lets the backward reduce each
    stage's cotangent in fp32 on the wire, sum ALL partials (including the
    local shard's) in fp32, and cast to the input dtype exactly once.
    """
    parts = [x.astype(jnp.float32)]
    for ops, kind in zip(ops_list, kinds):
        parts.append(_cast_any(x, ops, kind, axis_name).astype(jnp.float32))
    return tuple(parts)


def _hp_all_fwd(x, ops_list, kinds, axis_name, shard_len, in_dtype):
    return (
        hp_group_cast_all(x, ops_list, kinds, axis_name, shard_len, in_dtype),
        ops_list,
    )


def _hp_all_bwd(kinds, axis_name, shard_len, in_dtype, res, g):
    ops_list = res
    total = g[0]  # local-shard cotangent, fp32 (part 0 is the fp32 upcast)
    for gi, ops, kind in zip(g[1:], ops_list, kinds):
        if kind[0] == "hier":
            # transpose via jax.vjp of the cast itself (same trick as the
            # ragged tier in reduce_rows) — no hand-maintained mirror plan
            zeros = jnp.zeros((shard_len, *gi.shape[1:]), gi.dtype)
            _, vjp_fn = jax.vjp(
                lambda z, o=ops, kk=kind: _cast_any(z, o, kk, axis_name),
                zeros,
            )
            (red,) = vjp_fn(gi)
        else:
            red = reduce_rows(gi, ops, kind, axis_name, shard_len)
        total = total + red
    return total.astype(in_dtype), None


hp_group_cast_all.defvjp(_hp_all_fwd, _hp_all_bwd)


def _ragged_arrays(s) -> tuple[jax.Array, ...]:
    """Whole-mesh arrays for the ragged_all_to_all GroupCast tier, derived
    from a stage's a2a plan (true per-pair sizes; the receive buffer lands
    directly in the solver's src-asc layout).

    Returns (send_row_idx (cp, send_cap), input_offsets (cp, cp),
    send_sizes (cp, cp), output_offsets (cp, cp), recv_sizes (cp, cp))."""
    counts = s.send_counts.astype(np.int64)  # [src][dst]
    cp = counts.shape[0]
    send_tot = counts.sum(axis=1)
    send_cap = max(int(send_tot.max()), 1)
    send_row_idx = np.zeros((cp, send_cap), dtype=np.int32)
    input_offsets = np.zeros((cp, cp), dtype=np.int32)
    for src in range(cp):
        off = 0
        for dst in range(cp):
            n = int(counts[src, dst])
            input_offsets[src, dst] = off
            if n:
                send_row_idx[src, off: off + n] = s.send_idx[src, dst, :n]
                off += n
    # [src][dst]: where src's segment lands at dst = sum of earlier sources
    output_offsets = (
        np.cumsum(counts, axis=0) - counts
    ).astype(np.int32)
    recv_sizes = counts.T.astype(np.int32)  # [dst][src]
    return (
        jnp.asarray(send_row_idx),
        jnp.asarray(input_offsets),
        jnp.asarray(s.send_counts.astype(np.int32)),
        jnp.asarray(output_offsets),
        jnp.asarray(recv_sizes),
    )


def _stack_plans(args: list[AttnArg], sq: int, sk: int, bq: int, bk: int,
                 policy_dq: tuple[int, int] | None = None,
                 policy_dkv: tuple[int, int] | None = None,
                 label: str | None = None):
    """Per-rank FFA plans -> rank-stacked arrays padded to a common size.

    Returns ``(stacked_arrays, dims)`` where dims feeds
    ``DistAttnRuntime._ffa_params``. When the env bwd-tile overrides
    (MAGI_ATTENTION_FFA_BLOCK_*_D{Q,KV}) — or the auto-tile policy's
    per-pass picks (``policy_dq``/``policy_dkv``; env wins) — are active
    and compatible with this plan group's padded geometry, the stack
    carries 12 arrays (fwd6 + dq3 + dkv3) and dims includes the FFAParams
    override fields — so the distributed runtimes honor the same tuning
    flags as single-device ``ffa_attn``.
    """
    from ..kernels.ffa import assemble_bwd_overrides

    def build_stack(blq: int, blk: int, fields: tuple[str, ...]):
        plans = [
            build_ffa_plan(
                a.q_ranges, a.k_ranges, a.d_lo, a.d_hi, sq, sk, blq, blk,
                label=label,
            )
            for a in args
        ]
        w = max(p.num_work for p in plans)
        wt = max(p.num_work_t for p in plans)
        padded = [pad_plan(p, w, wt) for p in plans]
        stacked = tuple(
            jnp.asarray(np.stack([getattr(p, f) for p in padded]))
            for f in fields
        )
        # the ranks run one program: the least distance binds them all
        dist = min(p.min_revisit_distance for p in plans)
        return (stacked, plans[0].num_q_tiles, plans[0].num_k_tiles, w, wt,
                dist)

    fwd_fields = ("work_qt", "work_kt", "meta", "work_qt_t", "work_kt_t",
                  "meta_t")
    stacked, nqt, nkt, w, wt, dist = build_stack(bq, bk, fwd_fields)

    def build_triple(blocks, kind):
        if kind == "dq":
            triple, _, _, w2, _, _ = build_stack(*blocks, fwd_fields[0:3])
            return triple, w2
        triple, _, _, _, wt2, dist2 = build_stack(*blocks, fwd_fields[3:6])
        return triple, wt2, dist2

    stacked, overrides = assemble_bwd_overrides(
        stacked, bq, bk, nqt, nkt, build_triple, dist,
        policy_dq=policy_dq, policy_dkv=policy_dkv,
    )
    return stacked, (nqt, nkt, w, wt, overrides)


class DeferredTilePolicy:
    """The CP runtimes' one lazy path from a call's data signature to its
    plans' tiles.

    A key declares no heads, so the plans are built at once at the tiles
    known then (an argument, an ``FFA_BLOCK_*`` key, else
    ``default_blocks``), and the first ``calc_attn`` — the first place that
    sees head dims, dtype and the GQA group — chooses again with them
    (:meth:`_ensure_plans`): the auto-tile policy, which must score the
    VMEM guard with the REAL head dims and dtype (r3 advisor finding) and
    so builds nothing before, else the subclass's rule over the group
    (:meth:`_group_tile`). Plans are rebuilt only when the tile chosen
    differs from the one built. Subclasses provide
    ``_build_plans(blk_q, blk_k)`` and ``_tile_geoms() -> (geoms, sq, sk)``.
    """

    def _init_tile_policy(self, block_q, block_k) -> None:
        from ..kernels import registry as kernel_registry

        self._plan_sig = None
        self._auto_tile_pending = False
        # set by the resilience ladder when the FFA path is abandoned for
        # the reference backend (resilience/fallback.py); wins over env
        self._backend_override: str | None = None
        # per-pass picks from the auto-tile policy, consumed by the
        # subclasses' _build_plans via _stack_plans (env overrides win)
        self._policy_bwd: tuple = (None, None)
        # telemetry signatures (computed lazily; mask sig is plan-stable)
        self._tel_mask_sig: str | None = None
        self._tel_env_sig: tuple | None = None
        if (
            block_q is None and block_k is None
            and not kernel_registry.tiles_pinned()
        ):
            from ..kernels.tile_policy import auto_tile_enabled

            self._auto_tile_pending = auto_tile_enabled()
        # who chose the tiles, for the registry's ``ffa_tiles`` note
        self._tile_source = kernel_registry.tiles_source(
            block_q is not None or block_k is not None,
            self._auto_tile_pending,
        )
        # the (blk_q, blk_k) and per-pass picks the plans were built for
        self._plans_built: tuple | None = None
        if not self._auto_tile_pending:
            self._rebuild_plans(block_q, block_k)

    def _rebuild_plans(self, blk_q, blk_k) -> None:
        """``_build_plans`` unless the plans stand built for this choice."""
        choice = (blk_q, blk_k, self._policy_bwd)
        if self._plans_built != choice:
            self._build_plans(blk_q, blk_k)
            self._plans_built = choice

    def _group_tile(self, d: int, dv: int, itemsize: int, group: int,
                    emit_max_logits: bool) -> tuple[int | None, str]:
        """``(block_q or None for the default, source)`` of an unpinned
        call; a runtime without a rule over the group keeps the default."""
        return None, "default"

    def _ensure_plans(self, d: int, dv: int, itemsize: int, group: int,
                      emit_max_logits: bool = False) -> None:
        """Choose tiles with the real data signature; rebuild on change.

        A key called at two signatures (two models of different group on
        one mask) holds the plans of the LAST one: each change rebuilds
        (tens of ms a plan group), and a program compiled before it keeps
        the arrays it was traced with."""
        if self._tile_source == "pin":
            return  # an argument, an env key or a resilience rung: as built
        sig = (d, dv, itemsize, group, emit_max_logits)
        if self._plan_sig == sig:
            return
        blk_q = blk_k = None
        if self._auto_tile_pending:
            from ..kernels.tile_policy import choose_blocks_per_pass_multi

            geoms, sq, sk = self._tile_geoms()
            pol_dq = pol_dkv = None
            try:
                (blk_q, blk_k), pol_dq, pol_dkv = choose_blocks_per_pass_multi(
                    geoms, sq, sk, d, dv, itemsize
                )
            except Exception as e:
                # a failed VMEM scoring pass must not kill the step: the
                # clamped defaults are always lowerable (docs/resilience.md)
                if not env_resilience.is_fallback_enable():
                    raise
                from ..resilience.fallback import record_resilience_event

                record_resilience_event(
                    "recovered", "vmem_check",
                    action_detail="default_blocks", error=type(e).__name__,
                )
            self._policy_bwd = (pol_dq, pol_dkv)
        else:
            blk_q, self._tile_source = self._group_tile(*sig)
        self._rebuild_plans(blk_q, blk_k)
        self._plan_sig = sig

    # -- signatures (registry, quarantine and run-history keys) ---------

    def _policy_key(self) -> dict:
        """The calc_attn decision key: mask-class signature x mesh x env
        snapshot — what the registry memoizes the backend under and the
        step watchdog quarantines a backend for."""
        return {
            "mask_sig": self._mask_signature(),
            "mesh_sig": self._mesh_signature(),
            "env_sig": self._env_signature(),
        }

    def _mask_signature(self) -> str:
        """Digest of the mask-class geometry (slice arrays + shard lens);
        plan-stable, so computed once per runtime."""
        sig = self._tel_mask_sig
        if sig is None:
            geoms, sq, sk = self._tile_geoms()
            h = hashlib.md5(repr((sq, sk, len(geoms))).encode())
            for g in geoms:
                for a in g:
                    h.update(np.ascontiguousarray(a).tobytes())
            sig = h.hexdigest()[:16]
            self._tel_mask_sig = sig
        return sig

    def _mesh_signature(self) -> str:
        return repr((
            tuple(sorted(self.mesh.shape.items())),
            self.cp_axis,
            getattr(self, "head_axis", None),
        ))

    def _env_signature(self) -> str:
        """Digest of the behavior-affecting env snapshot (memoized per
        snapshot value — flips mid-life re-key the decision)."""
        snap = env_general.snapshot_env()
        cached = self._tel_env_sig
        if cached is not None and cached[0] == snap:
            return cached[1]
        sig = hashlib.md5(repr(snap).encode()).hexdigest()[:16]
        self._tel_env_sig = (snap, sig)
        return sig

    @property
    def backend(self) -> str:
        """Kernel backend via the registry's ``calc_attn`` decision: an
        explicit MAGI_ATTENTION_KERNEL_BACKEND pins it, otherwise 'ffa'. A
        resilience-ladder override (sticky degradation to the reference
        path) wins over everything."""
        if self._backend_override is not None:
            return self._backend_override
        from ..kernels import registry as kernel_registry

        return kernel_registry.calc_attn_backend(self._policy_key())


@dataclass(eq=False)
class DistAttnRuntime(DeferredTilePolicy):
    """Compiled-plan holder for one (mask, mesh, config) combination."""

    comm_meta: CommMeta
    calc_meta: CalcMeta
    mesh: Mesh
    cp_axis: str | tuple[str, str]  # 2-tuple = 2D (dcn, ici) cp mesh
    softmax_scale: float | None = None
    softcap: float = 0.0
    block_q: int | None = None
    block_k: int | None = None
    use_overlap: bool | None = None  # None -> overlap iff >1 stage
    # tensor parallelism: shard the head dim over this mesh axis (composes
    # with cp — the reference delegates TP to the host framework, SURVEY
    # §2.8; on TPU the attention itself runs TP-sharded in the same
    # shard_map, no host framework needed)
    head_axis: str | None = None
    # the runtime key's label (DistAttnRuntimeKey.label): handed to every
    # FFA call's params and to the plans' telemetry records
    label: str | None = None

    def __post_init__(self) -> None:
        cm, km = self.comm_meta, self.calc_meta
        self.cp_size = len(km.host_args)
        kv_shard = km.kv_shard_len
        self.num_stages = len(cm.kv_stages)
        if self.use_overlap is None:
            self.use_overlap = self.num_stages > 1

        self._init_tile_policy(self.block_q, self.block_k)

        # comm arrays (host-planned, stacked over ranks)
        self._hier = (
            isinstance(self.cp_axis, tuple)
            and env_comm.is_hierarchical_comm_enable()
            and cm.kv_host_ranges is not None
        )
        if self._hier:
            # each stage runs the 2-phase (DCN x ICI) cast; the final
            # receive buffer is flat-identical (comm/hier.py), so CalcMeta
            # is untouched. Solver-built plans (s.hier_plan, emitted when
            # the solver knew the 2D mesh shape) are used directly — they
            # were cached and verified with the rest of the plan; stages
            # planned without a mesh shape are re-planned here from their
            # transfer tables (identical construction)
            from ..comm.hier import make_hier_group_cast_plan

            dcn_axis, ici_axis = self.cp_axis
            n_outer = self.mesh.shape[dcn_axis]
            n_inner = self.mesh.shape[ici_axis]
            self._hier_arrays = []
            for st, s in enumerate(cm.kv_stages):
                plan = s.hier_plan
                if (
                    plan is None
                    or plan.n_outer != n_outer
                    or plan.n_inner != n_inner
                ):
                    plan = make_hier_group_cast_plan(
                        s.transfer_table, cm.kv_host_ranges, n_outer,
                        n_inner, alignment=128, r_max=s.r_max,
                        shard_len=kv_shard,
                    )
                self._hier_arrays.append(tuple(
                    jnp.asarray(a) for a in (
                        plan.a_send_idx, plan.a_recv_sel,
                        plan.b_send_idx, plan.b_recv_sel,
                    )
                ))
        # unified per-stage cast operand tuples (flat/pp: 2 arrays; hier: 4)
        # + per-stage static lowering descriptors (host-chosen, cheapest
        # wire volume — see GroupCollectiveArg.lowering)
        if self._hier:
            self._cast_ops = self._hier_arrays
            self._cast_kinds = [("hier",)] * len(self._hier_arrays)
        else:
            # per-stage tier from the solver's AUTO choice (s.lowering);
            # the ragged tier only appears there when the backend supports
            # it (env_comm.is_ragged_grpcoll_enable at plan time)
            self._cast_ops = []
            self._cast_kinds = []
            for s in cm.kv_stages:
                if s.lowering == "ragged":
                    self._cast_ops.append(_ragged_arrays(s))
                    self._cast_kinds.append(("ragged", s.r_max))
                elif s.lowering == "ppermute":
                    self._cast_ops.append(
                        (jnp.asarray(s.pp_send_idx), jnp.asarray(s.pp_recv_sel))
                    )
                    self._cast_kinds.append(
                        ("pp", s.pp_deltas, s.pp_caps, self.cp_size)
                    )
                else:
                    self._cast_ops.append(
                        (jnp.asarray(s.send_idx), jnp.asarray(s.recv_sel))
                    )
                    self._cast_kinds.append(("a2a",))

        # merged slice arrays for the jnp (sdpa) backend path: (cp, N, 2)/(cp, N)
        n_max = max(a.num_slices for a in km.merged_args) or 1
        padded = [a.pad_to(n_max) for a in km.merged_args]
        self._merged_slices = tuple(
            jnp.asarray(np.stack([getattr(a, f) for a in padded]))
            for f in ("q_ranges", "k_ranges", "d_lo", "d_hi")
        )

    def _build_plans(self, blk_q, blk_k) -> None:
        """Stack the per-rank FFA plans for the chosen (or default) tiles.

        May run inside a jit trace (auto-tile defers to the first
        calc_attn), so the plan constants are forced concrete — caching
        trace-local tracers on ``self`` would leak them into later traces.
        """
        with jax.ensure_compile_time_eval():
            with telemetry.stage_timer("build_plans"):
                self._build_plans_impl(blk_q, blk_k)

    def _build_plans_impl(self, blk_q, blk_k) -> None:
        from ..kernels.ffa import default_blocks

        self._tel_plan_groups = None  # recomputed per plan build
        _, sq, sk = self._tile_geoms()
        bq, bk = default_blocks(sq, sk, blk_q, blk_k)
        self._bq, self._bk = bq, bk
        pol_dq, pol_dkv = getattr(self, "_policy_bwd", (None, None))

        # the merged (no-overlap) plan, then the host's and the stages':
        # stage geometries clamp bk; policy picks that don't divide a
        # stage's padded grid silently inherit (resolve gate)
        self._stage_arrays = []
        self._stage_dims = []
        for name, args, g_sq, g_sk, g_bk in self._plan_groups(bk):
            arrays, dims = _stack_plans(
                args, g_sq, g_sk, bq, g_bk,
                policy_dq=pol_dq, policy_dkv=pol_dkv, label=self.label,
            )
            if name == "merged":
                self._merged_arrays, self._merged_dims = arrays, dims
            elif name == "host":
                self._host_arrays, self._host_dims = arrays, dims
            else:
                self._stage_arrays.append(arrays)
                self._stage_dims.append(dims)
        if telemetry.enabled():
            self._plan_group_stats()

    def _plan_group_stats(self) -> list[dict]:
        """Padded-grid work accounting per executed kernel group, cached for
        the attn_step record (the per-plan ``ffa_plan`` records carry the
        same numbers at build; caching here lets every step report estimated
        vs executed work without re-walking the plans)."""
        km = self.calc_meta
        cp = self.cp_size

        def grp(name, dims, bq, bk):
            w = dims[2]  # rank-uniform padded work-item count
            return {
                "name": name, "block_q": bq, "block_k": bk, "num_work": w,
                "padded_elems": cp * w * bq * bk,
            }

        bq, bk = self._bq, self._bk
        if self.use_overlap:
            groups = [grp("host", self._host_dims, bq,
                          min(bk, _ceil_to(km.kv_shard_len, 128)))]
            for st, d in enumerate(self._stage_dims):
                rl = km.recv_len_per_stage[st]
                groups.append(
                    grp(f"stage{st}", d, bq, min(bk, _ceil_to(rl, 128)))
                )
        else:
            groups = [grp("merged", self._merged_dims, bq, bk)]
        self._tel_plan_groups = groups
        self._tel_band_elems = sum(
            telemetry.band_area(a.q_ranges, a.k_ranges, a.d_lo, a.d_hi)
            for a in km.merged_args
        )
        return groups

    def _attn_step_payload(self, q, k, v) -> dict:
        """One attention step's telemetry payload (callers gate on
        ``telemetry.enabled()``). Comm rows were planned dtype-blind; bytes
        resolve here where head dims and dtypes are known — k and v rows
        ride one fused collective, so a wire row carries both."""
        sq, hq, dh = q.shape
        _, hk, dv = v.shape
        row_bytes = hk * dh * k.dtype.itemsize + hk * dv * v.dtype.itemsize
        exec_map = {"pp": "ppermute", "a2a": "a2a", "ragged": "ragged",
                    "hier": "hier"}
        stages = []
        payload_total = wire_total = 0
        for st, s in enumerate(self.comm_meta.kv_stages):
            d = s.telemetry_dict(executed=exec_map[self._cast_kinds[st][0]])
            d["stage"] = st
            d["xprof_scope"] = f"group_cast_stage{st}"
            d["payload_bytes"] = d["payload_rows"] * row_bytes
            d["wire_bytes"] = d["wire_rows"] * row_bytes
            d["padding_bytes"] = d["padding_rows"] * row_bytes
            payload_total += d["payload_bytes"]
            wire_total += d["wire_bytes"]
            stages.append(d)
        payload = {
            "backend": self.backend,
            # run-history join keys (telemetry's _ATTN_KEY_FIELDS)
            "mask_sig": self._mask_signature(),
            "mesh_sig": self._mesh_signature(),
            "env_sig": self._env_signature(),
            "q_shape": list(q.shape),
            "kv_shape": list(v.shape),
            "cp_size": self.cp_size,
            "overlap_degree": self.num_stages,
            "use_overlap": self.use_overlap,
            "seqlen_q_shard": sq,
            "heads_q": hq, "head_dim": dh, "heads_kv": hk, "head_dim_v": dv,
            "dtype": q.dtype.name,
            "row_bytes": row_bytes,
            "stages": stages,
            "payload_bytes_total": payload_total,
            "wire_bytes_total": wire_total,
            "padding_bytes_total": wire_total - payload_total,
        }
        # kernel-plan work accounting (absent on the sdpa backends when the
        # deferred auto-tile policy never ran, i.e. no FFA plans exist)
        if getattr(self, "_bq", None) is not None:
            if getattr(self, "_tel_plan_groups", None) is None:
                self._plan_group_stats()  # telemetry enabled after build
            band = self._tel_band_elems
            padded = sum(g["padded_elems"] for g in self._tel_plan_groups)
            # backward execution mode the dispatch will pick for this
            # geometry (fused one-pass vs split dq+dkv) — resolved on the
            # representative (host/merged) plan dims
            dims0 = self._host_dims if self.use_overlap else self._merged_dims
            prm0 = self._ffa_params(dims0, 1.0, hq // hk)
            bwd_mode = resolved_bwd_mode(
                prm0, prm0.num_q_tiles * prm0.block_q, dh, dv,
                q.dtype.itemsize,
            )
            payload.update(
                block_q=self._bq, block_k=self._bk,
                plan_groups=self._tel_plan_groups,
                band_elems=band,
                padded_elems=padded,
                # fwd FLOPs, FlashAttention-2 convention (perf_report.py)
                est_flops_fwd=4 * band * dh * hq,
                padded_flops_fwd=4 * padded * dh * hq,
                bwd_mode=bwd_mode,
            )
        return payload

    def _plan_groups(self, bk: int):
        """``(name, per-rank args, sq, sk, block_k)`` of every plan group
        :meth:`_build_plans` stacks: the merged plan, and under overlap
        the host's and each stage's, whose key buffers clamp ``block_k``."""
        km = self.calc_meta
        shard, kv_shard = km.shard_len, km.kv_shard_len
        yield ("merged", km.merged_args, shard,
               kv_shard + sum(km.recv_len_per_stage), bk)
        if self.use_overlap:
            yield ("host", km.host_args, shard, kv_shard,
                   min(bk, _ceil_to(kv_shard, 128)))
            for st, rl in enumerate(km.recv_len_per_stage):
                yield (f"stage{st}", km.remote_args_per_stage[st], shard,
                       rl, min(bk, _ceil_to(rl, 128)))

    def _max_plan_work(self, bq: int, bk: int) -> int:
        """The longest work list, q-major or k-major, of any rank of any
        plan group this runtime builds at ``bq`` x ``bk``: what its largest
        plan table has to hold."""
        from ..kernels.tile_policy import max_ffa_work

        return max(
            max_ffa_work(a.q_ranges, a.k_ranges, a.d_lo, a.d_hi,
                         sq, sk, bq, g_bk)
            for _, args, sq, sk, g_bk in self._plan_groups(bk)
            for a in args)

    def _group_tile(self, d: int, dv: int, itemsize: int, group: int,
                    emit_max_logits: bool) -> tuple[int | None, str]:
        """``tile_policy.group_block_q`` over every plan this runtime
        builds (:meth:`_max_plan_work`)."""
        from ..kernels.ffa import default_blocks
        from ..kernels.tile_policy import group_block_q

        _, sq, sk = self._tile_geoms()
        blk_q, source = group_block_q(
            group, d, dv, itemsize, *default_blocks(sq, sk),
            self._max_plan_work, emit_max_logits)
        return (blk_q if source == "shape_rule" else None), source

    def _tile_geoms(self):
        # per-mask tile choice scored on the merged per-rank geometries
        # (every rank runs the max-W padded grid)
        km = self.calc_meta
        return (
            [
                (a.q_ranges, a.k_ranges, a.d_lo, a.d_hi)
                for a in km.merged_args
            ],
            km.shard_len,
            km.kv_shard_len + sum(km.recv_len_per_stage),
        )

    def _kind(self, stage: int):
        """Static lowering descriptor for one stage — the ONE place the
        hier-vs-flat branch is decided (``_cast_any`` dispatches on it)."""
        if self._hier:
            dcn_axis, ici_axis = self.cp_axis
            return ("hier", dcn_axis, ici_axis)
        return self._cast_kinds[stage]

    def _axis(self):
        return None if self._hier else self.cp_axis

    def _cast(self, x, ops, stage: int = 0):
        """One stage's GroupCast inside shard_map (flat / pp / hierarchical)."""
        with profile_scope(f"group_cast_stage{stage}"):
            return _cast_any(
                x, tuple(o[0] for o in ops), self._kind(stage), self._axis()
            )

    def _cast_kv(self, k, v, ops, stage: int = 0):
        """Fused K|V GroupCast: one collective for both tensors (the
        reference's asymmetric-KV comm fuses along head_dim the same way,
        comm_meta.py:588-591 — valid for any d_k/d_v since rows coincide).
        HP reduce does NOT route here — it uses :meth:`_hp_parts_kv`, whose
        fused all-stage VJP is the only correct fp32 accumulation."""
        if k.dtype == v.dtype and k.shape[1] == v.shape[1]:
            kv = jnp.concatenate([k, v], axis=-1)
            kv_r = self._cast(kv, ops, stage)
            return kv_r[..., : k.shape[-1]], kv_r[..., k.shape[-1]:]
        return self._cast(k, ops, stage), self._cast(v, ops, stage)

    def _hp_parts_kv(self, k, v, cast_ops):
        """fp32 (local, *per-stage) parts of k and v under HP reduce.

        Routes through the fused :func:`hp_group_cast_all` so the backward
        sums EVERY dkv partial — local shard included — in fp32 and
        downcasts once (ADVICE r4). K|V fuse into one collective when rows
        coincide, as in :meth:`_cast_kv`."""
        kinds = tuple(self._kind(st) for st in range(len(cast_ops)))
        opsl = tuple(tuple(a[0] for a in ops) for ops in cast_ops)
        with profile_scope("group_cast_hp_all"):
            if k.dtype == v.dtype and k.shape[1] == v.shape[1]:
                kv = jnp.concatenate([k, v], axis=-1)
                parts = hp_group_cast_all(
                    kv, opsl, kinds, self._axis(), kv.shape[0], kv.dtype.name
                )
                return (
                    [p[..., : k.shape[-1]] for p in parts],
                    [p[..., k.shape[-1]:] for p in parts],
                )
            kp = hp_group_cast_all(
                k, opsl, kinds, self._axis(), k.shape[0], k.dtype.name
            )
            vp = hp_group_cast_all(
                v, opsl, kinds, self._axis(), v.shape[0], v.dtype.name
            )
            return list(kp), list(vp)

    # ------------------------------------------------------------------

    def _ffa_params(
        self, dims, scale, group, emit_max_logits: bool = False
    ) -> FFAParams:
        nqt, nkt, w, wt, overrides = dims
        return FFAParams(
            num_work=w, num_work_t=wt, num_q_tiles=nqt, num_k_tiles=nkt,
            block_q=self._bq, block_k=self._bk, **overrides,
            softmax_scale=scale, softcap=self.softcap, group=group,
            interpret=_should_interpret(),
            # the max-logits output costs an (hq, sqp, 128) fp32 HBM write
            # per kernel call — emitted only when the caller asks
            emit_max_logits=emit_max_logits,
            label=self.label,
        )

    @instrument_scope(name="DistAttnRuntime.calc_attn")
    def calc_attn(
        self,
        q: jax.Array,
        k: jax.Array,
        v: jax.Array,
        return_max_logits: bool = False,
    ):
        """Distributed attention over dispatched tensors.

        Args:
            q/k/v: ``(cp*shard, h, d)`` dispatched (permuted) layout, sharded
                over the cp mesh axis on dim 0.
            return_max_logits: also return the per-head max logit ``[hq]``
                fp32, all-reduced MAX across the cp axis (ref
                dist_attn.py:550 reduce_max_logits) — replicated over cp,
                sharded over head_axis when set.

        Returns:
            (out ``(cp*shard, hq, dv)``, lse ``(cp*shard, hq)`` fp32), same
            sharded layout; plus max_logits when requested.
        """
        impl = self._calc_attn_impl
        if env_resilience.is_resilience_active():
            # guarded path: injection recovery + numeric sentinels
            # (resilience/fallback.py); never reached with the flags off
            from ..resilience.fallback import run_calc_attn

            impl = partial(run_calc_attn, self)
        if not telemetry.enabled():
            return impl(q, k, v, return_max_logits)
        # wall_ms spans dispatch + (on first call) trace/compile; per-stage
        # DEVICE time lives in the xprof spans the stages' xprof_scope
        # fields name (docs/observability.md)
        with telemetry.stage_timer("calc_attn"):
            result = impl(q, k, v, return_max_logits)
        wall_ms = telemetry.get_collector().gauges.get(
            "time.calc_attn.last_ms"
        )
        telemetry.record_event(
            "attn_step",
            xprof_scope="DistAttnRuntime.calc_attn",
            wall_ms=wall_ms,
            **self._attn_step_payload(q, k, v),
        )
        return result

    def _calc_attn_impl(
        self,
        q: jax.Array,
        k: jax.Array,
        v: jax.Array,
        return_max_logits: bool = False,
    ):
        sq, hq, dh = q.shape
        _, hk, dv = v.shape
        group = hq // hk
        if self.head_axis is not None:
            tp = self.mesh.shape[self.head_axis]
            if hq % tp or hk % tp:
                raise ValueError(
                    f"head_axis={self.head_axis!r} (size {tp}) must divide "
                    f"both num_heads_q ({hq}) and num_heads_kv ({hk}) — "
                    f"GQA kv heads shard over TP too"
                )
        scale = (
            float(dh) ** -0.5
            if self.softmax_scale is None
            else self.softmax_scale
        )
        axis = self.cp_axis
        # data spec: seq dim over cp, head dim over tp (when given)
        spec = P(axis, self.head_axis)
        ml_spec = P(self.head_axis)
        out_specs = (
            (spec, spec, ml_spec) if return_max_logits else (spec, spec)
        )

        if self.backend in ("sdpa", "sdpa_online"):
            # jnp fake-backend path (fp32/fp64-exact distributed testing,
            # mirroring the reference's sdpa backend strategy): merged concat
            # buffer + dense band-mask replay, AD end-to-end
            from ..kernels.sdpa import dense_max_logits, sdpa_attn
            from ..kernels.sdpa_online import sdpa_online_attn

            dense_fn = sdpa_attn if self.backend == "sdpa" else sdpa_online_attn
            softcap = self.softcap

            def f(q, k, v, cast_ops, slices):
                parts_k, parts_v = [k], [v]
                for st, ops in enumerate(cast_ops):
                    kr, vr = self._cast_kv(k, v, ops, st)
                    parts_k.append(kr)
                    parts_v.append(vr)
                k_all = jnp.concatenate(parts_k, axis=0)
                v_all = jnp.concatenate(parts_v, axis=0)
                qr, kr, lo, hi = (a[0] for a in slices)
                out, lse = dense_fn(
                    q, k_all, v_all, qr, kr, None,
                    softmax_scale=scale, softcap=softcap,
                    d_lo=lo, d_hi=hi,
                )
                # lse is non-differentiable on the ffa backend (custom VJP
                # drops its cotangent); keep backends in agreement
                lse = jax.lax.stop_gradient(lse)
                if return_max_logits:
                    ml = dense_max_logits(
                        q, k_all, qr, kr, None,
                        softmax_scale=scale, softcap=softcap,
                        d_lo=lo, d_hi=hi,
                    )
                    return out, lse, jax.lax.pmax(jax.lax.stop_gradient(ml), axis)
                return out, lse

            fn = shard_map(
                f,
                mesh=self.mesh,
                in_specs=(spec, spec, spec,
                          [tuple(P(axis) for _ in ops)
                           for ops in self._cast_ops],
                          tuple(P(axis) for _ in self._merged_slices)),
                out_specs=out_specs,
                check_vma=False,
            )
            return fn(q, k, v, self._cast_ops, self._merged_slices)

        # the tiles are chosen HERE (not __post_init__): the auto-tile
        # policy's VMEM guard needs the real head dims and dtype (r3 advisor
        # finding), the rule for block_q the GQA group
        self._ensure_plans(
            dh, dv, q.dtype.itemsize, group, return_max_logits)
        # the merged plan's params, or the host stage's on the overlap path
        params = self._ffa_params(
            self._host_dims if self.use_overlap else self._merged_dims,
            scale, group, return_max_logits,
        )
        note_tiles(params, dh, dv, q.dtype.itemsize, self._tile_source)

        # fp32 wire reduce for partial dkv (ref decision at dist_attn.py
        # :243-248; default off there and here). The sdpa/jnp backends keep
        # plain AD (they are fp32-exact test backends already).
        hp_bwd = env_comm.is_bwd_high_precision_reduce_enable()

        if not self.use_overlap:
            def f(q, k, v, cast_ops, arrays):
                if hp_bwd:
                    # fused all-stage hp cast: receive buffers AND the
                    # local shard are fp32, and all dkv partials sum in
                    # fp32 with one final downcast (ADVICE r4)
                    kv_parts_k, kv_parts_v = self._hp_parts_kv(k, v, cast_ops)
                else:
                    kv_parts_k, kv_parts_v = [k], [v]
                    for st, ops in enumerate(cast_ops):
                        kr, vr = self._cast_kv(k, v, ops, st)
                        kv_parts_k.append(kr)
                        kv_parts_v.append(vr)
                k_all = jnp.concatenate(kv_parts_k, axis=0)
                v_all = jnp.concatenate(kv_parts_v, axis=0)
                local_arrays = tuple(a[0] for a in arrays)
                if return_max_logits:
                    out, lse, ml = ffa_attn_with_plan(
                        q, k_all, v_all, local_arrays, params,
                        return_max_logits=True,
                    )
                    return out, lse, jax.lax.pmax(jax.lax.stop_gradient(ml), axis)
                return ffa_attn_with_plan(q, k_all, v_all, local_arrays, params)

            fn = shard_map(
                f,
                mesh=self.mesh,
                in_specs=(spec, spec, spec,
                          [tuple(P(axis) for _ in ops)
                           for ops in self._cast_ops],
                          tuple(P(axis) for _ in self._merged_arrays)),
                out_specs=out_specs,
                check_vma=False,
            )
            return fn(q, k, v, self._cast_ops, self._merged_arrays)

        # multi-stage overlap path
        host_params = params
        stage_params = [
            self._ffa_params(d, scale, group, return_max_logits)
            for d in self._stage_dims
        ]

        all_params = (host_params, *stage_params)

        def f(q, k, v, cast_ops, host_arrays, stage_arrays):
            # issue every stage's collective up front: no data dependence on
            # compute, XLA overlaps them with the host + earlier-stage kernels
            if hp_bwd:
                # fused all-stage hp cast (local shard fp32 too): every dkv
                # partial sums in fp32, one downcast — _multi_ffa is
                # dtype-polymorphic per part, so this costs residual HBM
                # only (the flag's documented price), not compute dtype
                ks, vs = self._hp_parts_kv(k, v, cast_ops)
            else:
                ks, vs = [k], [v]
                for st, ops in enumerate(cast_ops):
                    kr, vr = self._cast_kv(k, v, ops, st)
                    ks.append(kr)
                    vs.append(vr)
            arrays_list = (tuple(a[0] for a in host_arrays),) + tuple(
                tuple(a[0] for a in sa) for sa in stage_arrays
            )
            out, lse, ml = _multi_ffa(
                q, tuple(ks), tuple(vs), arrays_list, all_params
            )
            if return_max_logits:
                return out, lse, jax.lax.pmax(jax.lax.stop_gradient(ml), axis)
            return out, lse

        fn = shard_map(
            f,
            mesh=self.mesh,
            in_specs=(spec, spec, spec,
                      [tuple(P(axis) for _ in ops)
                       for ops in self._cast_ops],
                      tuple(P(axis) for _ in self._host_arrays),
                      [tuple(P(axis) for _ in sa) for sa in self._stage_arrays]),
            out_specs=out_specs,
            check_vma=False,
        )
        return fn(q, k, v, self._cast_ops,
                  self._host_arrays, self._stage_arrays)


def dist_attn_func(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    runtime: DistAttnRuntime,
    return_max_logits: bool = False,
):
    """Functional entry (ref dist_attn.py:3714): (out, lse[, max_logits])
    over dispatched tensors. Precision override via MAGI_ATTENTION_PRECISION."""
    if env_general.precision() == "bf16":
        q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    return runtime.calc_attn(q, k, v, return_max_logits=return_max_logits)


def _ceil_to(x: int, m: int) -> int:
    return max(m, -(-x // m) * m)
