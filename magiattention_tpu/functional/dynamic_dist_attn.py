"""Dynamic (qo-comm) CP engine.

Ref: magi_attention/functional/dist_attn.py qo-comm paths (_fetch_remote_q
:1625, _fetch_remote_qo_do_lse :1714, _reduce_partial_out_lse :1979,
_reduce_partial_dq :2302) — the execution of a `DynamicAttnPlan`:

forward (per rank, one shard_map program):
  q_buf  = [q | group_cast(q)]          k_buf/v_buf likewise
  out_buf, lse_buf = FFA(q_buf, k_buf, v_buf)
  partial rows return to q owners (group_cast of out/lse over `ret`),
  each owner lse-merges its row's contributions (merge_idx).

backward (custom VJP, the distributed-flash identity): the owner computes
delta = rowsum(do * out_final); (do, lse_final, delta) re-distribute to
compute ranks over the SAME q_cast plan (out_buf rows correspond 1:1 to
q_buf rows); each rank runs the FFA bwd kernels against the final lse/delta,
which makes per-part dq/dkv exact with no gradient through the merge
weights; dq/dkv partial rows reduce back to owners via the transposes of the
two forward casts (`group_reduce_rows`). No collective beyond the forward's
mirror image — zero-redundant in both directions.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..comm.primitives import cast_rows, reduce_rows
from ..env import resilience as env_resilience
from ..kernels.ffa import (
    FFAParams,
    _bwd_plan_slices,
    ffa_bwd_pallas_dispatch,
    ffa_delta_pallas_dispatch,
    _should_interpret,
    default_blocks,
    ffa_attn_with_plan,
    note_tiles,
    resolved_bwd_mode,
)
from ..meta.collection.dynamic_meta import DynamicAttnPlan
from ..utils.profiling import instrument_scope, profile_scope
from .dist_attn import DeferredTilePolicy, _head_major, _stack_plans
from .utils import lse_weighted_reduce
from .. import telemetry

NEG_INF = float("-inf")


def _merge_rows(out_buf, lse_buf, ret_out, ret_lse, merge_idx):
    """lse-merge each local row's contributions.

    merge_idx: (shard, M) into [out_buf | ret_buf | dummy]."""
    h, dv = out_buf.shape[1], out_buf.shape[2]
    cat_out = jnp.concatenate(
        [out_buf, ret_out, jnp.zeros((1, h, dv), out_buf.dtype)], axis=0
    )
    cat_lse = jnp.concatenate(
        [lse_buf, ret_lse, jnp.full((1, h), NEG_INF, jnp.float32)], axis=0
    )
    co = jnp.take(cat_out, merge_idx, axis=0)  # (shard, M, h, dv)
    cl = jnp.take(cat_lse, merge_idx, axis=0)  # (shard, M, h)
    return lse_weighted_reduce(
        co.transpose(1, 0, 2, 3), cl.transpose(1, 0, 2)
    )


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _dyn_attn_shard(q, k, v, static, axis, comm, arrays):
    out, lse, ml, _, _, _ = _dyn_fwd_impl(q, k, v, static, axis, comm, arrays)
    return out, lse, ml


def _dyn_fwd_impl(q, k, v, static, axis, comm, arrays):
    params, shard, kv_shard, kinds, fwd_hp, _ = static
    q_kind, k_kind, r_kind = kinds
    (q_ops, k_ops, r_ops, (merge_idx,)) = comm
    with profile_scope("qo_comm_cast"):
        q_rem = cast_rows(q, q_ops, q_kind, axis)
        q_buf = jnp.concatenate([q, q_rem], axis=0)
        k_rem = cast_rows(k, k_ops, k_kind, axis)
        v_rem = cast_rows(v, k_ops, k_kind, axis)
        k_buf = jnp.concatenate([k, k_rem], axis=0)
        v_buf = jnp.concatenate([v, v_rem], axis=0)
    with profile_scope("ffa_fwd_dyn"):
        out_buf, lse_buf, ml = ffa_attn_with_plan(
            q_buf, k_buf, v_buf, arrays, params,
            return_max_logits=True,  # constant -inf unless params emit it
        )
    # fwd high-precision reduce (ref _reduce_partial_out_lse + env decision,
    # dist_attn.py:243): partial out rows return to their owners in fp32 —
    # 2x this wire, better lse-merge precision. lse is fp32 either way.
    ret_src = out_buf.astype(jnp.float32) if fwd_hp else out_buf
    ret_out = cast_rows(ret_src, r_ops, r_kind, axis)
    ret_lse = cast_rows(lse_buf, r_ops, r_kind, axis)
    out, lse = _merge_rows(out_buf, lse_buf, ret_out, ret_lse, merge_idx)
    return out.astype(out_buf.dtype), lse, ml, q_buf, k_buf, v_buf


def _dyn_fwd(q, k, v, static, axis, comm, arrays):
    out, lse, ml, _, _, _ = _dyn_fwd_impl(q, k, v, static, axis, comm, arrays)
    return (out, lse, ml), (q, k, v, out, lse, comm, arrays)


def _dyn_bwd(static, axis, res, cts):
    do, _, _ = cts  # lse/max_logits are auxiliary
    q, k, v, out, lse, comm, arrays = res
    params, shard, kv_shard, kinds, _, bwd_hp = static
    q_kind, k_kind, _ = kinds
    (q_ops, k_ops, _, _) = comm

    # rebuild compute buffers (refetch — cheaper than saving the buffers,
    # matching the reference's bwd-side comm)
    q_rem = cast_rows(q, q_ops, q_kind, axis)
    q_buf = jnp.concatenate([q, q_rem], axis=0)
    k_rem = cast_rows(k, k_ops, k_kind, axis)
    v_rem = cast_rows(v, k_ops, k_kind, axis)
    k_buf = jnp.concatenate([k, k_rem], axis=0)
    v_buf = jnp.concatenate([v, v_rem], axis=0)

    # owner-side final quantities, re-distributed over the q cast; delta
    # runs on the local shard rows (pre-cast), so pad to a block_q multiple
    bq = params.block_q
    sp = -(-out.shape[0] // bq) * bq
    delta = ffa_delta_pallas_dispatch(
        params, _head_major(out, sp), _head_major(do, sp)
    ).T[: out.shape[0]]  # (shard, hq)
    do_buf = jnp.concatenate(
        [do, cast_rows(do, q_ops, q_kind, axis)], axis=0
    )
    lse_buf = jnp.concatenate(
        [lse, cast_rows(lse, q_ops, q_kind, axis)], axis=0
    )
    delta_buf = jnp.concatenate(
        [delta, cast_rows(delta, q_ops, q_kind, axis)], axis=0
    )

    sqp = params.num_q_tiles * params.block_q
    skp = params.num_k_tiles * params.block_k
    q_t = _head_major(q_buf, sqp)
    k_t = _head_major(k_buf, skp)
    v_t = _head_major(v_buf, skp)
    do_t = _head_major(do_buf, sqp)
    nbuf = q_buf.shape[0]
    lse_t = jnp.pad(
        lse_buf, ((0, sqp - nbuf), (0, 0)), constant_values=NEG_INF
    ).T
    delta_t = jnp.pad(delta_buf, ((0, sqp - nbuf), (0, 0))).T

    dq_arrs, dkv_arrs = _bwd_plan_slices(arrays)
    dq_t, dk_t, dv_t = ffa_bwd_pallas_dispatch(
        params, dq_arrs, dkv_arrs, q_t, k_t, v_t, do_t, lse_t, delta_t
    )
    # dk/dv already per kv head (dkv kernel sums the GQA group)

    dq_buf = dq_t.transpose(1, 0, 2)[:nbuf]
    dk_buf = dk_t.transpose(1, 0, 2)[: k_buf.shape[0]]
    dv_buf = dv_t.transpose(1, 0, 2)[: v_buf.shape[0]]

    # the kernels emit fp32 partials; MAGI_ATTENTION_BWD_HIGH_PRECISION_REDUCE
    # keeps them fp32 through the wire reduce (2x bwd comm bytes, ref
    # _reduce_partial_dq/_reduce_partial_dkv); default reduces in the input
    # dtype (ref bwd_local_dkv_lp_init / bwd_local_dq_lp_init, :245-253)
    if not bwd_hp:
        dq_buf = dq_buf.astype(q.dtype)
        dk_buf = dk_buf.astype(k.dtype)
        dv_buf = dv_buf.astype(v.dtype)
    dq = dq_buf[:shard] + reduce_rows(
        dq_buf[shard:], q_ops, q_kind, axis, shard
    )
    dk = dk_buf[:kv_shard] + reduce_rows(
        dk_buf[kv_shard:], k_ops, k_kind, axis, kv_shard
    )
    dv = dv_buf[:kv_shard] + reduce_rows(
        dv_buf[kv_shard:], k_ops, k_kind, axis, kv_shard
    )
    return (
        dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
        None, None,
    )


_dyn_attn_shard.defvjp(_dyn_fwd, _dyn_bwd)


@dataclass(eq=False)
class DynamicDistAttnRuntime(DeferredTilePolicy):
    """Executable runtime for one DynamicAttnPlan (qo-comm engine)."""

    plan: DynamicAttnPlan
    mesh: Mesh
    cp_axis: str
    softmax_scale: float | None = None
    softcap: float = 0.0
    block_q: int | None = None
    block_k: int | None = None

    def __post_init__(self) -> None:
        p = self.plan
        # auto-tile defers to the first calc_attn where the real head
        # dims/dtype are known (DeferredTilePolicy; r3 advisor finding)
        self._init_tile_policy(self.block_q, self.block_k)

        def ops_of(cast):
            # per-stage tier from the solver's AUTO choice (cast.lowering)
            if cast.lowering == "ragged":
                from .dist_attn import _ragged_arrays

                return (_ragged_arrays(cast), ("ragged", cast.r_max))
            if cast.lowering == "ppermute":
                cp = cast.send_counts.shape[0]
                return (
                    (jnp.asarray(cast.pp_send_idx),
                     jnp.asarray(cast.pp_recv_sel)),
                    ("pp", cast.pp_deltas, cast.pp_caps, cp),
                )
            return (
                (jnp.asarray(cast.send_idx), jnp.asarray(cast.recv_sel)),
                ("a2a",),
            )

        (q_ops, self._q_kind) = ops_of(p.q_cast)
        (k_ops, self._k_kind) = ops_of(p.kv_cast)
        (r_ops, self._r_kind) = ops_of(p.ret)
        self._comm = (q_ops, k_ops, r_ops, (jnp.asarray(p.merge_idx),))

    def _build_plans(self, blk_q, blk_k) -> None:
        # may run inside a jit trace (deferred auto-tile): force the plan
        # constants concrete so no tracer is cached on self
        with jax.ensure_compile_time_eval(), \
                telemetry.stage_timer("build_plans"):
            p = self.plan
            bq, bk = default_blocks(p.q_buf_len, p.k_buf_len, blk_q, blk_k)
            self._bq, self._bk = bq, bk
            pol_dq, pol_dkv = getattr(self, "_policy_bwd", (None, None))
            self._arrays, self._dims = _stack_plans(
                p.attn_args, p.q_buf_len, p.k_buf_len, bq, bk,
                policy_dq=pol_dq, policy_dkv=pol_dkv,
            )

    def _attn_step_payload(self, q, k, v) -> dict:
        """One qo-comm step's telemetry payload (callers gate on
        ``telemetry.enabled()``). Per-stage row bytes differ: q rows, fused
        k|v rows, and returned partial out+lse rows each have their own
        width, resolved here where dtypes/head dims are known."""
        from ..env import comm as env_comm

        p = self.plan
        sq, hq, dh = q.shape
        _, hk, dv = v.shape
        exec_map = {"pp": "ppermute", "a2a": "a2a", "ragged": "ragged"}
        # partial out rows ride the ret cast in fp32 under the fwd HP reduce
        out_itemsize = (
            4 if env_comm.is_fwd_high_precision_reduce_enable()
            else q.dtype.itemsize
        )
        stage_defs = (
            ("q_cast", p.q_cast, self._q_kind, "qo_comm_cast",
             hq * dh * q.dtype.itemsize),
            ("kv_cast", p.kv_cast, self._k_kind, "qo_comm_cast",
             hk * dh * k.dtype.itemsize + hk * dv * v.dtype.itemsize),
            ("ret", p.ret, self._r_kind, "ffa_fwd_dyn",
             hq * dv * out_itemsize + hq * 4),  # + fp32 lse
        )
        stages = []
        payload_total = wire_total = 0
        for name, cast, kind, scope, row_bytes in stage_defs:
            d = cast.telemetry_dict(executed=exec_map[kind[0]])
            d["stage"] = name
            d["xprof_scope"] = scope
            d["row_bytes"] = row_bytes
            d["payload_bytes"] = d["payload_rows"] * row_bytes
            d["wire_bytes"] = d["wire_rows"] * row_bytes
            d["padding_bytes"] = d["padding_rows"] * row_bytes
            payload_total += d["payload_bytes"]
            wire_total += d["wire_bytes"]
            stages.append(d)
        payload = {
            "planner": "dynamic",
            "backend": self.backend,
            # run-history join keys (telemetry's _ATTN_KEY_FIELDS)
            "mask_sig": self._mask_signature(),
            "mesh_sig": self._mesh_signature(),
            "env_sig": self._env_signature(),
            "q_shape": list(q.shape),
            "kv_shape": list(v.shape),
            "cp_size": self.mesh.shape[self.cp_axis],
            "overlap_degree": 1,  # qo-comm runs one compute stage
            "seqlen_q_shard": sq,
            "heads_q": hq, "head_dim": dh, "heads_kv": hk, "head_dim_v": dv,
            "dtype": q.dtype.name,
            "stages": stages,
            "payload_bytes_total": payload_total,
            "wire_bytes_total": wire_total,
            "padding_bytes_total": wire_total - payload_total,
        }
        if getattr(self, "_bq", None) is not None:
            cp = self.mesh.shape[self.cp_axis]
            w = self._dims[2]
            padded = cp * w * self._bq * self._bk
            band = sum(
                telemetry.band_area(a.q_ranges, a.k_ranges, a.d_lo, a.d_hi)
                for a in p.attn_args
            )
            # backward execution mode the combined dispatch will pick
            # (fused one-pass vs split dq+dkv) for this plan's geometry
            nqt, nkt, wn, wt, overrides = self._dims
            prm0 = FFAParams(
                num_work=wn, num_work_t=wt, num_q_tiles=nqt,
                num_k_tiles=nkt, block_q=self._bq, block_k=self._bk,
                **overrides, softmax_scale=1.0, softcap=self.softcap,
                group=hq // hk, interpret=_should_interpret(),
            )
            bwd_mode = resolved_bwd_mode(
                prm0, nqt * self._bq, dh, dv, q.dtype.itemsize
            )
            payload.update(
                block_q=self._bq, block_k=self._bk,
                band_elems=band,
                padded_elems=padded,
                est_flops_fwd=4 * band * dh * hq,
                padded_flops_fwd=4 * padded * dh * hq,
                bwd_mode=bwd_mode,
            )
        return payload

    def _tile_geoms(self):
        p = self.plan
        return (
            [
                (a.q_ranges, a.k_ranges, a.d_lo, a.d_hi)
                for a in p.attn_args
            ],
            p.q_buf_len,
            p.k_buf_len,
        )

    @instrument_scope(name="DynamicDistAttnRuntime.calc_attn")
    def calc_attn(
        self,
        q: jax.Array,
        k: jax.Array,
        v: jax.Array,
        return_max_logits: bool = False,
    ):
        """(out, lse[, max_logits]) over dispatched tensors, qo-comm
        execution. lse is a non-differentiable auxiliary output on every
        backend (the ffa custom VJP ignores its cotangent, so the jnp
        backends stop_gradient it for cross-backend agreement).

        q/k/v: ``(cp*shard, h, d)`` dispatched layout sharded over cp axis.
        """
        impl = self._calc_attn_impl
        if env_resilience.is_resilience_active():
            # guarded path (resilience/fallback.py); dead with flags off
            from ..resilience.fallback import run_calc_attn

            impl = partial(run_calc_attn, self)
        if not telemetry.enabled():
            return impl(q, k, v, return_max_logits)
        with telemetry.stage_timer("calc_attn"):
            result = impl(q, k, v, return_max_logits)
        wall_ms = telemetry.get_collector().gauges.get(
            "time.calc_attn.last_ms"
        )
        telemetry.record_event(
            "attn_step",
            xprof_scope="DynamicDistAttnRuntime.calc_attn",
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
        p = self.plan
        sq, hq, dh = q.shape
        _, hk, dv = v.shape
        group = hq // hk
        scale = (
            float(dh) ** -0.5
            if self.softmax_scale is None
            else self.softmax_scale
        )
        axis = self.cp_axis
        spec = P(axis)

        if self.backend in ("sdpa", "sdpa_online"):
            return self._calc_attn_sdpa(q, k, v, scale, return_max_logits)

        # auto-tile with the real head dims/dtype (r3 advisor finding);
        # this runtime has no rule over the group: default_blocks otherwise
        self._ensure_plans(dh, dv, q.dtype.itemsize, group)
        nqt, nkt, w, wt, overrides = self._dims
        params = FFAParams(
            num_work=w, num_work_t=wt, num_q_tiles=nqt, num_k_tiles=nkt,
            block_q=self._bq, block_k=self._bk, **overrides,
            softmax_scale=scale, softcap=self.softcap, group=group,
            interpret=_should_interpret(),
            emit_max_logits=return_max_logits,
        )
        note_tiles(params, dh, dv, q.dtype.itemsize, self._tile_source)
        from ..env import comm as env_comm

        static = (
            params, p.shard_len, p.kv_shard_len,
            (self._q_kind, self._k_kind, self._r_kind),
            env_comm.is_fwd_high_precision_reduce_enable(),
            env_comm.is_bwd_high_precision_reduce_enable(),
        )

        def f(q, k, v, comm, arrays):
            comm_local = tuple(
                tuple(a[0] for a in grp) for grp in comm
            )
            arrays_local = tuple(a[0] for a in arrays)
            # each rank's compute covers its assigned rectangles, so the
            # cp MAX of the kernel's per-head max is the global per-head
            # max (ref dist_attn.py:550 reduce_max_logits)
            out, lse, ml = _dyn_attn_shard(
                q, k, v, static, axis, comm_local, arrays_local
            )
            if return_max_logits:
                return out, lse, jax.lax.pmax(jax.lax.stop_gradient(ml), axis)
            return out, lse

        out_specs = (spec, spec, P()) if return_max_logits else (spec, spec)
        fn = shard_map(
            f,
            mesh=self.mesh,
            in_specs=(spec, spec, spec,
                      tuple(
                          tuple(P(axis) for _ in grp) for grp in self._comm
                      ),
                      tuple(P(axis) for _ in self._arrays)),
            out_specs=out_specs,
            check_vma=False,
        )
        return fn(q, k, v, self._comm, self._arrays)

    # -- jnp fake-backend path (fp32/fp64-exact distributed testing) -------

    def _calc_attn_sdpa(self, q, k, v, scale, return_max_logits=False):
        from ..kernels.sdpa import dense_max_logits, sdpa_attn
        from ..kernels.sdpa_online import sdpa_online_attn

        p = self.plan
        dense_fn = (
            sdpa_attn if self.backend == "sdpa" else sdpa_online_attn
        )
        axis = self.cp_axis
        spec = P(axis)
        softcap = self.softcap

        # per-rank slice arrays, stacked (pure jnp path, jax AD end-to-end —
        # including the lse cotangent through the merge)
        n_max = max(a.num_slices for a in p.attn_args) or 1
        padded = [a.pad_to(n_max) for a in p.attn_args]
        slices = tuple(
            jnp.asarray(np.stack([getattr(a, f) for a in padded]))
            for f in ("q_ranges", "k_ranges", "d_lo", "d_hi")
        )

        q_kind, k_kind, r_kind = self._q_kind, self._k_kind, self._r_kind
        from ..env import comm as env_comm

        fwd_hp = env_comm.is_fwd_high_precision_reduce_enable()

        def f(q, k, v, comm, slices):
            q_ops, k_ops, r_ops, (merge_idx,) = tuple(
                tuple(a[0] for a in grp) for grp in comm
            )
            q_buf = jnp.concatenate(
                [q, cast_rows(q, q_ops, q_kind, axis)], axis=0
            )
            k_buf = jnp.concatenate(
                [k, cast_rows(k, k_ops, k_kind, axis)], axis=0
            )
            v_buf = jnp.concatenate(
                [v, cast_rows(v, k_ops, k_kind, axis)], axis=0
            )
            qr, kr, lo, hi = (a[0] for a in slices)
            out_buf, lse_buf = dense_fn(
                q_buf, k_buf, v_buf, qr, kr, None,
                softmax_scale=scale, softcap=softcap, d_lo=lo, d_hi=hi,
            )
            ret_src = out_buf.astype(jnp.float32) if fwd_hp else out_buf
            ret_out = cast_rows(ret_src, r_ops, r_kind, axis)
            ret_lse = cast_rows(lse_buf, r_ops, r_kind, axis)
            out, lse = _merge_rows(
                out_buf, lse_buf, ret_out, ret_lse, merge_idx
            )
            out = out.astype(out_buf.dtype)
            # lse is non-differentiable on the ffa backend (custom VJP drops
            # its cotangent); stop_gradient keeps the backends in agreement
            lse = jax.lax.stop_gradient(lse)
            if return_max_logits:
                ml = dense_max_logits(
                    q_buf, k_buf, qr, kr, None,
                    softmax_scale=scale, softcap=softcap, d_lo=lo, d_hi=hi,
                )
                return out, lse, jax.lax.pmax(jax.lax.stop_gradient(ml), axis)
            return out, lse

        out_specs = (spec, spec, P()) if return_max_logits else (spec, spec)
        fn = shard_map(
            f,
            mesh=self.mesh,
            in_specs=(spec, spec, spec,
                      tuple(
                          tuple(P(axis) for _ in grp) for grp in self._comm
                      ),
                      tuple(P(axis) for _ in slices)),
            out_specs=out_specs,
            check_vma=False,
        )
        return fn(q, k, v, self._comm, slices)
