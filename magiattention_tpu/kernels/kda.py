"""Chunked gated delta rule with a per-channel decay (Kimi Delta Attention),
forward and backward, with document resets.

The recurrence, per head (``q_t``, ``k_t``, ``v_t`` of ``D`` channels, a
state ``S`` of ``D x D``, ``g_t`` a log-decay per key channel, ``beta_t`` a
scalar in (0, 2))::

    S_t = (I - beta_t k_t k_t^T) diag(exp g_t) S_{t-1} + beta_t k_t v_t^T     S = 0 before a document's first token
    o_t = D ** -0.5 * S_t^T q_t

computed a chunk of ``CHUNK`` tokens at a time in the WY form (Yang et al.
2024, "Parallelizing linear transformers with the delta rule"; the
per-channel gate as Kimi Linear's KDA). With ``G`` the running sum of ``g``
from the chunk's start and ``S0`` the state the chunk starts from::

    A   = strict_lower(beta_i (k_i * exp(G_i - G_j)) . k_j)      same document only
    T   = (I + A)^-1                                            forward substitution
    W   = T (beta k * exp(G)),  U = T (beta v)
    v'  = U - W S0
    o   = (q * exp(G)) S0 + tril((q_i * exp(G_i - G_j)) . k_j) v'
    S_C = exp(G_C) * S0 + (k * exp(G_C - G))^T v'

The carried ``S0`` reaches only the tokens of the document the previous
chunk ended in, as in ``ssd.py`` (:func:`ssd.segment_rows` with
``chunk=CHUNK``); ``A`` and the causal matrix are cut where the segment ids
differ.

**Numerics.** A decay is not bounded: ``exp(A_log)`` up to 16 times a
softplus that passes 1.5 in its tails makes ``G`` fall by tens a token, and
``exp(-G)`` of a factorised ``(q exp(G)) (k exp(-G))^T`` leaves float32
within a chunk. Nothing is clamped; every exponent that is taken is of a
difference ``G_i - G_j`` with ``i`` at or after ``j``, so at most 0. The
pair matrices are built in sub-chunks of ``SUB`` rows
(:func:`_pair_matrix`): a sub-chunk's rows against every EARLIER row by one
matmul about a reference point, the sub-chunk's first row ``r`` (``x_i
exp(G_i - r)`` and ``y_j exp(r - G_j)``, both factors at most 1), and its
own ``SUB x SUB`` diagonal block pair by pair, one column at a time, with
the exponent of each pair. ``T`` is forward substitution, one column of
``A`` a step (the Neumann series or its doubling products cancel
catastrophically at ``beta`` near 2 with aligned keys). Everything inside a
grid step is float32 (matmuls at ``HIGHEST``); the carried state and the
saved states are float32.

Two Pallas calls, bound by ``_named.pallas_call`` so that a device trace
shows ``magi_kda_fwd_kernel`` and ``magi_kda_bwd_kernel``. The grid is
``(heads, chunks)``, chunks innermost and sequential, one head's chunk a
grid step. The backward is a ``custom_vjp``: the forward then also writes
every chunk's incoming state, and the backward walks the chunks in reverse
carrying the state's gradient, recomputing the chunk's terms. The running
sum of ``g`` is the caller's (plain XLA, differentiated by JAX), as are the
convolution, the L2 norm, the gates and the softplus of the block.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry
from . import _named, registry
from .ssd import SEG_ROWS

CHUNK = 64
SUB = 16  # rows of a sub-chunk: its diagonal block is built pair by pair

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(
        a, b, dims, precision=_HIGHEST, preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _pair_matrix(x, y, g):
    """``M[i, j] = sum_d x[i, d] y[j, d] exp(g[i, d] - g[j, d])`` for ``j <=
    i`` (rows ``(C, D)``, ``g`` non-increasing down the rows); what lies
    above the diagonal is not defined. The module's docstring says how."""
    c = x.shape[0]
    col = _iota((SUB, c), 1)
    blocks = []
    for a in range(c // SUB):
        lo = a * SUB
        xa, ga = x[lo:lo + SUB], g[lo:lo + SUB]
        acc = jnp.zeros((SUB, c), _F32)
        if a:
            r = g[lo:lo + 1]
            xs = xa * jnp.exp(ga - r)
            ys = y * jnp.exp(jnp.minimum(r - g, 0.0))
            acc = jnp.where(col < lo, _dot(xs, ys, _NT), 0.0)
        for j in range(lo, lo + SUB):
            e = jnp.exp(jnp.minimum(ga - g[j:j + 1], 0.0))
            cj = jnp.sum(xa * y[j:j + 1] * e, axis=1, keepdims=True)
            acc = jnp.where(col == j, cj, acc)
        blocks.append(acc)
    return jnp.concatenate(blocks, axis=0)


def _pair_matrix_vjp(x, y, g, dm):
    """``(dx, dy)`` of :func:`_pair_matrix` for a cotangent ``dm`` that is 0
    above the diagonal. ``g``'s own is ``x * dx - y * dy``: ``g`` enters as
    ``x exp(g)`` and ``y exp(-g)``."""
    c, d = x.shape
    col = _iota((SUB, c), 1)
    row = _iota((SUB, d), 0)
    dy = jnp.zeros((c, d), _F32)
    dxs, dy_diag = [], []
    for a in range(c // SUB):
        lo = a * SUB
        xa, ga, dma = x[lo:lo + SUB], g[lo:lo + SUB], dm[lo:lo + SUB]
        dxa = jnp.zeros((SUB, d), _F32)
        if a:
            r = g[lo:lo + 1]
            ex = jnp.exp(ga - r)
            ey = jnp.exp(jnp.minimum(r - g, 0.0))
            off = jnp.where(col < lo, dma, 0.0)
            dxa = _dot(off, y * ey) * ex
            dy = dy + _dot(off, xa * ex, _TN) * ey
        dyd = jnp.zeros((SUB, d), _F32)
        for j in range(lo, lo + SUB):
            e = jnp.exp(jnp.minimum(ga - g[j:j + 1], 0.0))
            z = dma[:, j:j + 1] * e
            dxa = dxa + z * y[j:j + 1]
            dyd = jnp.where(row == j - lo, dyd + jnp.sum(
                z * xa, axis=0, keepdims=True), dyd)
        dxs.append(dxa)
        dy_diag.append(dyd)
    return jnp.concatenate(dxs, axis=0), dy + jnp.concatenate(dy_diag, axis=0)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` of a strictly lower-triangular ``a`` ``(C, C)``:
    forward substitution, one column of ``a`` a step (row ``j`` of the
    result is final when column ``j`` is taken)."""
    c = a.shape[0]
    x = (_iota((c, c), 0) == _iota((c, c), 1)).astype(_F32)
    for j in range(c - 1):
        x = x - a[:, j:j + 1] * x[j:j + 1, :]
    return x


def _chunk_terms(q_ref, k_ref, v_ref, g_ref, b_ref, sc_ref, sr_ref):
    """What forward and backward both need of one grid step, float32."""
    q, k, v = (r[...].astype(_F32) for r in (q_ref, k_ref, v_ref))
    g, beta = g_ref[...], b_ref[...]                    # (C, D), (C, 1)
    sc, sr = sc_ref[...], sr_ref[...]                   # (C, 8), (8, C)
    c = q.shape[0]
    seg_c, seg_r, ok = sc[:, 0:1], sr[0:1, :], sc[:, 1:2]
    rows, cols = _iota((c, c), 0), _iota((c, c), 1)
    same = seg_c == seg_r
    g_end = g[c - 1:c]                                  # (1, D)
    to_end = (seg_c == seg_r[:, c - 1:c]).astype(_F32)  # (C, 1)
    e_g = jnp.exp(g)
    return dict(
        q=q, k=k, v=v, g=g, beta=beta, ok=ok,
        strict=same & (rows > cols), incl=same & (rows >= cols),
        e_g=e_g, kd=k * e_g * ok, qd=q * e_g * ok,
        e_end=jnp.exp(g_end - g) * to_end,
        dec=jnp.exp(g_end) * sc[c - 1:c, 1:2],          # (1, D)
    )


def _wy(t, st):
    """``A``'s pair matrix, ``T``, ``W``, ``v'`` and the causal matrix of
    one chunk from its incoming state ``st`` (``S0^T``, ``(D, D)``)."""
    m_kk = _pair_matrix(t["k"], t["k"], t["g"])
    a = jnp.where(t["strict"], t["beta"] * m_kk, 0.0)
    tt = _unit_lower_inverse(a)
    w = _dot(tt, t["beta"] * t["kd"])
    vp = _dot(tt, t["beta"] * t["v"]) - _dot(w, st, _NT)
    p = jnp.where(t["incl"], _pair_matrix(t["q"], t["k"], t["g"]), 0.0)
    return m_kk, tt, w, vp, p


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, sc_ref, sr_ref, *rest,
                    scale: float, save: bool):
    if save:
        o_ref, hin_ref, st_ref = rest
    else:
        (o_ref, st_ref), hin_ref = rest, None

    @pl.when(pl.program_id(1) == 0)
    def _():
        st_ref[...] = jnp.zeros(st_ref.shape, _F32)

    st = st_ref[...]                                    # S0^T, (D, D)
    if save:
        hin_ref[...] = st
    t = _chunk_terms(q_ref, k_ref, v_ref, g_ref, b_ref, sc_ref, sr_ref)
    _, _, _, vp, p = _wy(t, st)
    o = scale * (_dot(t["qd"], st, _NT) + _dot(p, vp))
    o_ref[...] = o.astype(o_ref.dtype)
    ke = t["k"] * t["e_end"]
    st_ref[...] = st * t["dec"] + _dot(vp, ke, _TN)


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, sc_ref, sr_ref, do_ref,
                    hin_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dst_ref,
                    *, scale: float):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dst_ref[...] = jnp.zeros(dst_ref.shape, _F32)

    t = _chunk_terms(q_ref, k_ref, v_ref, g_ref, b_ref, sc_ref, sr_ref)
    st, dst1 = hin_ref[...], dst_ref[...]               # S0^T, dS_C^T
    q, k, v, g, beta = t["q"], t["k"], t["v"], t["g"], t["beta"]
    m_kk, tt, w, vp, p = _wy(t, st)
    c = q.shape[0]
    dos = scale * do_ref[...].astype(_F32)
    # o = scale (qd S0 + P v')
    dqd = _dot(dos, st)
    dst0 = _dot(dos, t["qd"], _TN)
    dp = jnp.where(t["incl"], _dot(dos, vp, _NT), 0.0)
    dvp = _dot(p, dos, _TN)
    # S_C = dec * S0 + ke^T v'
    ke = k * t["e_end"]
    dst0 = dst0 + dst1 * t["dec"]
    dg_end = t["dec"] * jnp.sum(st * dst1, axis=0, keepdims=True)
    dke = _dot(vp, dst1)
    dvp = dvp + _dot(ke, dst1, _NT)
    dk = dke * t["e_end"]
    dg_end = dg_end + jnp.sum(dke * ke, axis=0, keepdims=True)
    dg = -dke * ke
    # v' = T (beta v) - W S0,  W = T (beta kd)
    dw = -_dot(dvp, st)
    dst0 = dst0 - _dot(dvp, w, _TN)
    bv, bk = beta * v, beta * t["kd"]
    dtt = _dot(dvp, bv, _NT) + _dot(dw, bk, _NT)
    dbv, dbk = _dot(tt, dvp, _TN), _dot(tt, dw, _TN)
    # T = (I + A)^-1,  A = beta M_kk
    da = jnp.where(t["strict"], -_dot(tt, _dot(dtt, tt, _NT), _TN), 0.0)
    db = jnp.sum(da * m_kk, axis=1, keepdims=True)
    dx, dy = _pair_matrix_vjp(k, k, g, da * beta)
    dk = dk + dx + dy
    dg = dg + k * (dx - dy)
    dx, dy = _pair_matrix_vjp(q, k, g, dp)
    dq = dx
    dk = dk + dy
    dg = dg + q * dx - k * dy
    dv = beta * dbv
    db = db + jnp.sum(dbv * v, axis=1, keepdims=True) + jnp.sum(
        dbk * t["kd"], axis=1, keepdims=True)
    dkd = beta * dbk
    dk = dk + dkd * t["e_g"] * t["ok"]
    dq = dq + dqd * t["e_g"] * t["ok"]
    dg = dg + dkd * t["kd"] + dqd * t["qd"]
    dg = dg + jnp.where(_iota(dg.shape, 0) == c - 1, dg_end, 0.0)
    dst_ref[...] = dst0
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)
    dg_ref[...] = dg
    db_ref[...] = db


def _specs(d, n_chunks, reverse):
    """BlockSpecs for grid step ``(head, i)``: the chunk is ``i``, or
    ``n_chunks - 1 - i`` walking backwards."""
    def chunk(i):
        return n_chunks - 1 - i if reverse else i

    def vm(block, index):
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    return dict(
        wide=vm((CHUNK, d), lambda h, i: (chunk(i), h)),       # (T, H*D)
        beta=vm((None, None, CHUNK, 1), lambda h, i: (h, chunk(i), 0, 0)),
        sc=vm((None, CHUNK, SEG_ROWS), lambda h, i: (chunk(i), 0, 0)),
        sr=vm((None, SEG_ROWS, CHUNK), lambda h, i: (chunk(i), 0, 0)),
        state=vm((None, None, d, d), lambda h, i: (chunk(i), h, 0, 0)),
    )


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _interpret() -> bool:
    from .ffa import _should_interpret

    return _should_interpret()


def _fwd_call(q2, k2, v2, g2, b4, sc, sr, d: int, save: bool):
    heads, n_chunks = b4.shape[:2]
    s = _specs(d, n_chunks, reverse=False)
    out_specs = [s["wide"]]
    out_shape = [jax.ShapeDtypeStruct(q2.shape, q2.dtype)]
    if save:
        out_specs.append(s["state"])
        out_shape.append(jax.ShapeDtypeStruct((n_chunks, heads, d, d), _F32))
    return _named.pallas_call(
        partial(_kda_fwd_kernel, scale=d ** -0.5, save=save),
        grid=(heads, n_chunks),
        in_specs=[s["wide"]] * 4 + [s["beta"], s["sc"], s["sr"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((d, d), _F32)],
        interpret=_interpret(), compiler_params=_compiler_params(),
    )(q2, k2, v2, g2, b4, sc, sr)


@partial(jax.custom_vjp, nondiff_argnums=(7,))
def _kda_core(q2, k2, v2, g2, b4, sc, sr, d):
    """``o`` (T, H*D) of the chunked recurrence. ``q2``, ``k2``, ``v2``
    (T, H*D), ``g2`` (T, H*D) float32 running sums of the log-decay within
    each chunk, ``b4`` (H, chunks, CHUNK, 1) float32 betas, ``sc`` / ``sr``
    the segment rows a chunk a block in both orientations."""
    return _fwd_call(q2, k2, v2, g2, b4, sc, sr, d, save=False)[0]


def _kda_core_fwd(q2, k2, v2, g2, b4, sc, sr, d):
    o, hin = _fwd_call(q2, k2, v2, g2, b4, sc, sr, d, save=True)
    return o, (q2, k2, v2, g2, b4, sc, sr, hin)


def _kda_core_bwd(d, res, do):
    q2, k2, v2, g2, b4, sc, sr, hin = res
    heads, n_chunks = b4.shape[:2]
    s = _specs(d, n_chunks, reverse=True)
    dq, dk, dv, dg, db = _named.pallas_call(
        partial(_kda_bwd_kernel, scale=d ** -0.5),
        grid=(heads, n_chunks),
        in_specs=[s["wide"]] * 4 + [s["beta"], s["sc"], s["sr"], s["wide"],
                                    s["state"]],
        out_specs=[s["wide"]] * 4 + [s["beta"]],
        out_shape=[
            jax.ShapeDtypeStruct(q2.shape, q2.dtype),
            jax.ShapeDtypeStruct(k2.shape, k2.dtype),
            jax.ShapeDtypeStruct(v2.shape, v2.dtype),
            jax.ShapeDtypeStruct(g2.shape, _F32),
            jax.ShapeDtypeStruct(b4.shape, _F32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), _F32)],
        interpret=_interpret(), compiler_params=_compiler_params(),
    )(q2, k2, v2, g2, b4, sc, sr, do.astype(q2.dtype), hin)
    return dq, dk, dv, dg, db, jnp.zeros_like(sc), jnp.zeros_like(sr)


_kda_core.defvjp(_kda_core_fwd, _kda_core_bwd)


def _record_plan(seg_ids, *, tokens: int, heads: int) -> None:
    ids = np.asarray(seg_ids)
    rows = np.arange(ids.shape[0])
    starts = (ids == rows) & (rows % CHUNK != 0)
    telemetry.record_event(
        "kda_plan", chunk=CHUNK, sub_chunk=SUB, heads=heads,
        chunks=tokens // CHUNK, resets_inside_chunks=int(starts.sum()))


def kda_scan(q, k, v, g, beta, seg_rows) -> jax.Array:
    """The recurrence's output ``o_t = D ** -0.5 S_t^T q_t``, ``(T, H, D)``
    in ``q``'s type.

    Args:
        q, k, v: ``(T, H, D)``, ``D`` a multiple of 128 (the caller
            L2-normalises q and k).
        g: ``(T, H, D)`` float32 log-decay per key channel, at most 0.
        beta: ``(T, H)`` float32.
        seg_rows: :func:`ssd.segment_rows` of the tokens' document starts
            at ``chunk=CHUNK``.

    ``T`` that is no multiple of ``CHUNK`` is padded with inert tokens (zero
    ``q``, ``k``, ``v``, ``g`` and ``beta`` in a segment of their own).
    Differentiable in ``q``, ``k``, ``v``, ``g``, ``beta``.
    """
    t, h, d = q.shape
    if d % 128:
        raise ValueError(f"head_dim {d}: the kernel takes multiples of 128")
    pad = -t % CHUNK
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            for x in (q, k, v, g, beta))
        seg_rows = jnp.pad(seg_rows, ((0, 0), (0, pad)),
                           constant_values=-1.0).at[1, t:].set(0.0)
    tp = t + pad
    n = tp // CHUNK
    registry.note_choice("kda", (tp, h, d, CHUNK), "pallas_chunked", "default")
    if telemetry.enabled():
        jax.debug.callback(
            partial(_record_plan, tokens=tp, heads=h), seg_rows[0])
    g2 = jnp.cumsum(g.astype(_F32).reshape(n, CHUNK, h * d), axis=1)
    b4 = beta.astype(_F32).reshape(n, CHUNK, h).transpose(2, 0, 1)[..., None]
    sr = seg_rows.reshape(SEG_ROWS, n, CHUNK).transpose(1, 0, 2)
    o = _kda_core(
        q.reshape(tp, h * d), k.reshape(tp, h * d), v.reshape(tp, h * d),
        g2.reshape(tp, h * d), b4, sr.transpose(0, 2, 1), sr, d)
    return o[:t].reshape(t, h, d)


# Contracts of the two pallas_call sites for analysis/kernel_check.py, as
# ``ssd.py`` declares its own: a scan is no accumulate-and-flush kernel;
# every grid step writes its own output blocks and the scratch is the state
# carried from chunk to chunk (zeroed at a head's first chunk). The carried
# state is held to the recurrence by tests/test_attn/test_kda.py.
PALLAS_CONTRACTS: dict = {
    "_kda_fwd_kernel": dict(
        wrapper="_fwd_call",
        scratch=("st_ref",),
        outputs=("o_ref", "hin_ref"),
        out_dtypes=("input", "f32"),
        init_guard=None,
        flush_guard=None,
        group_inner=None,
    ),
    "_kda_bwd_kernel": dict(
        wrapper="_kda_core_bwd",
        scratch=("dst_ref",),
        outputs=("dq_ref", "dk_ref", "dv_ref", "dg_ref", "db_ref"),
        out_dtypes=("input", "input", "input", "f32", "f32"),
        init_guard=None,
        flush_guard=None,
        group_inner=None,
    ),
}
