"""Chunked Mamba-2 (SSD) scan with document resets, forward and backward.

The recurrence, per head (``x_t`` of ``P`` channels, a state of ``N x P``,
``B_t`` and ``C_t`` of ``N`` shared by the heads of a group)::

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t (x) x_t      h = 0 at a document's first token
    y_t = C_t . h_t

computed a chunk of ``CHUNK`` tokens at a time (Dao & Gu 2024, "state space
duality"): inside a chunk the masked quadratic form ``(L o C B^T) (dt o X)``
with ``L[t, s] = exp(cs_t - cs_s)`` for ``s <= t`` of the same document
(``cs`` the running sum of ``dt * A`` from the chunk's start), between chunks
the state carried in VMEM from one grid step to the next. A document may
start anywhere: ``L`` is cut where the segment ids differ, and the carried
state reaches only the tokens of the document that the previous chunk ended
in. Nothing here knows where the ids come from
(:func:`segment_rows` takes each token's document start, which
``api.get_document_starts`` reads from the runtime key).

Two Pallas calls, bound by ``_named.pallas_call`` so that a device trace
shows ``magi_ssd_fwd_kernel`` and ``magi_ssd_bwd_kernel``. The grid is
``(groups, chunks)``, chunks innermost and sequential; a grid step holds one
chunk of one group's heads. The backward is a ``custom_vjp``: the forward
then also writes every chunk's incoming state (float32), and the backward
walks the chunks in reverse carrying the state's gradient. The decay's
running sum, ``D * x`` and everything before and after are the caller's
(plain XLA, differentiated by JAX).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _named, registry

CHUNK = 128
SEG_ROWS = 8  # sublanes of the segment array: row 0 ids, row 1 carry flags

_F32, _BF16 = jnp.float32, jnp.bfloat16
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32)


def _last_row(col, width: int):
    """The last entry of a ``(Q, 1)`` column as a ``(1, width)`` row, by a
    masked sum over the rows: Mosaic broadcasts along lanes or along
    sublanes, not a ``(1, 1)`` value along both at once."""
    q = col.shape[0]
    last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    return jnp.sum(jnp.broadcast_to(jnp.where(last, col, 0.0), (q, width)),
                   axis=0, keepdims=True)


def segment_rows(doc_starts: jax.Array, chunk: int = CHUNK) -> jax.Array:
    """What the kernels read of the documents' boundaries, ``(SEG_ROWS, T)``
    float32: row 0 each token's segment id (its document's first row; exact
    in float32 below 2**24), row 1 whether the token's document is the one
    the previous chunk ended in (the carried state reaches it). One array a
    mask, shared by every scan layer of a step."""
    t = doc_starts.shape[0]
    seg = doc_starts.astype(jnp.int32)
    first = (jnp.arange(t, dtype=jnp.int32) // chunk) * chunk
    before = jnp.where(first > 0, seg[jnp.maximum(first - 1, 0)], -1)
    rows = jnp.zeros((SEG_ROWS, t), _F32)
    return rows.at[0].set(seg.astype(_F32)).at[1].set(
        (seg == before).astype(_F32))


def _chunk_terms(b_ref, c_ref, dt_ref, cs_ref, sr_ref):
    """What forward and backward both need of one grid step: ``B``, ``C``,
    ``C B^T``, the same-document causal mask, and ``dt``, ``cs``, the
    segment ids and carry flags in column orientation."""
    bm, cm = b_ref[...], c_ref[...]                      # (Q, N)
    q = bm.shape[0]
    dt_r, cs_r, sr = dt_ref[...], cs_ref[...], sr_ref[...]
    dt_c, cs_c, sc = dt_r.T, cs_r.T, sr.T                # (Q, heads) / (Q, 8)
    seg_r, seg_c, ok_c = sr[0:1, :], sc[:, 0:1], sc[:, 1:2]
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    mask = (seg_c == seg_r) & (row >= col)
    to_end = (seg_c == seg_r[:, q - 1:q]).astype(_F32)   # (Q, 1)
    return dict(bm=bm, cm=cm, g=_dot(cm, bm, _NT), mask=mask, dt_c=dt_c,
                cs_c=cs_c, cs_r=cs_r, ok_c=ok_c, to_end=to_end, q=q)


def _head_terms(t, j):
    """One head's decays within the chunk: ``L`` (Q, Q), the carry's reach
    ``ec`` (Q, 1), each token's reach to the chunk's end ``w`` (Q, 1)."""
    q, cs_c, cs_r = t["q"], t["cs_c"][:, j:j + 1], t["cs_r"][j:j + 1, :]
    decay = jnp.exp(jnp.where(t["mask"], cs_c - cs_r, -jnp.inf))
    ec = jnp.exp(cs_c) * t["ok_c"]
    w = jnp.exp(cs_r[:, q - 1:q] - cs_c) * t["to_end"]
    return decay, ec, w


def _ssd_fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cs_ref, sr_ref, *rest,
                    heads: int, p: int, save: bool):
    if save:
        y_ref, hin_ref, st_ref = rest
    else:
        (y_ref, st_ref), hin_ref = rest, None

    @pl.when(pl.program_id(1) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    if save:
        hin_ref[...] = st_ref[...]
    t = _chunk_terms(b_ref, c_ref, dt_ref, cs_ref, sr_ref)
    q, x = t["q"], x_ref[...]
    ys = []
    for j in range(heads):
        sl = slice(j * p, (j + 1) * p)
        decay, ec, w = _head_terms(t, j)
        m = (t["g"] * decay).astype(_BF16)
        xd = (x[:, sl].astype(_F32) * t["dt_c"][:, j:j + 1]).astype(_BF16)
        h = st_ref[:, sl]                                 # (N, P) float32
        ys.append(_dot(m, xd) + ec * _dot(t["cm"], h.astype(_BF16)))
        bw = (t["bm"].astype(_F32) * w).astype(_BF16)
        st_ref[:, sl] = _last_row(ec, p) * h + _dot(bw, xd, _TN)
    y_ref[...] = jnp.concatenate(ys, axis=-1).astype(y_ref.dtype)


def _ssd_bwd_kernel(x_ref, b_ref, c_ref, dt_ref, cs_ref, sr_ref, dy_ref,
                    hin_ref, dx_ref, db_ref, dc_ref, ddt_ref, dcs_ref,
                    dst_ref, *, heads: int, p: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    t = _chunk_terms(b_ref, c_ref, dt_ref, cs_ref, sr_ref)
    q, x, dy = t["q"], x_ref[...], dy_ref[...]
    bm32, cm32 = t["bm"].astype(_F32), t["cm"].astype(_F32)
    last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    dg = jnp.zeros((q, q), _F32)
    db = jnp.zeros(bm32.shape, _F32)
    dc = jnp.zeros(cm32.shape, _F32)
    dxs, ddt_c, dcs_c, dcs_r = [], [], [], []
    for j in range(heads):
        sl = slice(j * p, (j + 1) * p)
        decay, ec, w = _head_terms(t, j)
        dt_j = t["dt_c"][:, j:j + 1]
        m = t["g"] * decay
        x_j = x[:, sl].astype(_F32)
        xd = (x_j * dt_j).astype(_BF16)
        dy_j = dy[:, sl]
        h, dh = hin_ref[:, sl], dst_ref[:, sl]            # (N, P) float32
        h16, dh16 = h.astype(_BF16), dh.astype(_BF16)
        bw = (bm32 * w).astype(_BF16)
        dec = ec[q - 1:q, :]
        # y = m xd + ec (C h);  h_out = dec h + bw^T xd
        dxd = _dot(m.astype(_BF16), dy_j, _TN) + _dot(bw, dh16)   # (Q, P)
        dm = _dot(dy_j, xd, _NT)                                  # (Q, Q)
        dg += dm * decay
        e = dm * m
        dch = _dot(dy_j, h16, _NT)                                # (Q, N)
        dc += dch * ec
        d_ec = jnp.sum(cm32 * dch, axis=-1, keepdims=True)
        xdh = _dot(xd, dh16, _NT)                                 # (Q, N)
        db += xdh * w
        d_w = jnp.sum(bm32 * xdh, axis=-1, keepdims=True)
        dst_ref[:, sl] = _last_row(ec, p) * dh + _dot(
            (cm32 * ec).astype(_BF16), dy_j, _TN)
        end = jnp.sum(d_w * w, axis=0, keepdims=True) + dec * jnp.sum(
            jnp.sum(h * dh, axis=-1, keepdims=True), axis=0, keepdims=True)
        dcs_c.append(jnp.sum(e, axis=-1, keepdims=True) + d_ec * ec
                     - d_w * w + jnp.where(last, end, 0.0))
        dcs_r.append(-jnp.sum(e, axis=0, keepdims=True))
        dxs.append(dxd * dt_j)
        ddt_c.append(jnp.sum(dxd * x_j, axis=-1, keepdims=True))
    dg16 = dg.astype(_BF16)
    dc_ref[...] = (_dot(dg16, t["bm"]) + dc).astype(dc_ref.dtype)
    db_ref[...] = (_dot(dg16, t["cm"], _TN) + db).astype(db_ref.dtype)
    dx_ref[...] = jnp.concatenate(dxs, axis=-1).astype(dx_ref.dtype)
    ddt_ref[...] = jnp.concatenate(ddt_c, axis=-1).T
    dcs_ref[...] = jnp.concatenate(dcs_c, axis=-1).T + jnp.concatenate(
        dcs_r, axis=0)


def _specs(q, heads, p, n, n_chunks, reverse):
    """BlockSpecs of the operands both kernels share, for grid step
    ``(group, i)``: the chunk is ``i``, or ``n_chunks - 1 - i`` walking
    backwards."""
    def chunk(i):
        return n_chunks - 1 - i if reverse else i

    def vm(block, index):
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    wide = vm((q, heads * p), lambda g, i: (chunk(i), g))     # x, y, dy, dx
    state = vm((q, n), lambda g, i: (chunk(i), g))            # B, C, dB, dC
    per_head = vm((heads, q), lambda g, i: (g, chunk(i)))     # dt, cs
    seg = vm((SEG_ROWS, q), lambda g, i: (0, chunk(i)))
    hin = vm((None, None, n, heads * p), lambda g, i: (chunk(i), g, 0, 0))
    return wide, state, per_head, seg, hin


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _interpret() -> bool:
    from .ffa import _should_interpret

    return _should_interpret()


def _fwd_call(x2, b2, c2, dt_r, cs_r, sr, dims, save: bool):
    heads, p, n, groups = dims
    t, q = x2.shape[0], CHUNK
    n_chunks = t // q
    wide, state, per_head, seg, hin = _specs(
        q, heads, p, n, n_chunks, reverse=False)
    out_specs, out_shape = [wide], [jax.ShapeDtypeStruct(x2.shape, x2.dtype)]
    if save:
        out_specs.append(hin)
        out_shape.append(jax.ShapeDtypeStruct(
            (n_chunks, groups, n, heads * p), _F32))
    return _named.pallas_call(
        partial(_ssd_fwd_kernel, heads=heads, p=p, save=save),
        grid=(groups, n_chunks),
        in_specs=[wide, state, state, per_head, per_head, seg],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, heads * p), _F32)],
        interpret=_interpret(), compiler_params=_compiler_params(),
    )(x2, b2, c2, dt_r, cs_r, sr)


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd_core(x2, b2, c2, dt_r, cs_r, sr, dims):
    """``y`` (T, H*P) of the chunked scan without the ``D`` term. ``x2``
    (T, H*P), ``b2`` / ``c2`` (T, G*N), ``dt_r`` / ``cs_r`` (H, T) float32,
    ``sr`` from :func:`segment_rows`; ``dims`` = (heads a group, P, N, G)."""
    return _fwd_call(x2, b2, c2, dt_r, cs_r, sr, dims, save=False)[0]


def _ssd_core_fwd(x2, b2, c2, dt_r, cs_r, sr, dims):
    y, hin = _fwd_call(x2, b2, c2, dt_r, cs_r, sr, dims, save=True)
    return y, (x2, b2, c2, dt_r, cs_r, sr, hin)


def _ssd_core_bwd(dims, res, dy):
    x2, b2, c2, dt_r, cs_r, sr, hin = res
    heads, p, n, groups = dims
    t, q = x2.shape[0], CHUNK
    n_chunks = t // q
    wide, state, per_head, seg, hin_spec = _specs(
        q, heads, p, n, n_chunks, reverse=True)
    dx, db, dc, ddt, dcs = _named.pallas_call(
        partial(_ssd_bwd_kernel, heads=heads, p=p),
        grid=(groups, n_chunks),
        in_specs=[wide, state, state, per_head, per_head, seg, wide,
                  hin_spec],
        out_specs=[wide, state, state, per_head, per_head],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x2.dtype),
            jax.ShapeDtypeStruct(b2.shape, b2.dtype),
            jax.ShapeDtypeStruct(c2.shape, c2.dtype),
            jax.ShapeDtypeStruct(dt_r.shape, _F32),
            jax.ShapeDtypeStruct(cs_r.shape, _F32),
        ],
        scratch_shapes=[pltpu.VMEM((n, heads * p), _F32)],
        interpret=_interpret(), compiler_params=_compiler_params(),
    )(x2, b2, c2, dt_r, cs_r, sr, dy.astype(x2.dtype), hin)
    return dx, db, dc, ddt, dcs, jnp.zeros_like(sr)


_ssd_core.defvjp(_ssd_core_fwd, _ssd_core_bwd)


def ssd_scan(x, dt, a, b, c, seg_rows) -> jax.Array:
    """The scan's output ``y_t = C_t . h_t`` (no ``D`` term), ``(T, H, P)``
    in ``x``'s type.

    Args:
        x: ``(T, H, P)`` inputs, ``H`` heads of ``P`` channels.
        dt: ``(T, H)`` float32 step sizes (after the softplus).
        a: ``(H,)`` float32, negative: the decay is ``exp(dt * a)``.
        b, c: ``(T, G, N)``; head ``h`` uses group ``h // (H // G)``.
        seg_rows: :func:`segment_rows` of the tokens' document starts.

    ``T`` that is no multiple of ``CHUNK`` is padded with inert tokens
    (``dt`` = 0 in a segment of their own). Differentiable in ``x``, ``dt``,
    ``a``, ``b``, ``c``.
    """
    t, h, p = x.shape
    groups, n = b.shape[1:]
    if h % groups:
        raise ValueError(f"{h} heads do not divide into {groups} groups")
    pad = -t % CHUNK
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                       for v in (x, dt, b, c))
        seg_rows = jnp.pad(seg_rows, ((0, 0), (0, pad)),
                           constant_values=-1.0).at[1, t:].set(0.0)
    tp = t + pad
    registry.note_choice(
        "ssd", (tp, h, p, groups, n, CHUNK), "pallas_chunked", "default")
    dt = dt.astype(_F32)
    cs = jnp.cumsum(
        (dt * a.astype(_F32)).reshape(tp // CHUNK, CHUNK, h), axis=1)
    y = _ssd_core(
        x.reshape(tp, h * p), b.reshape(tp, groups * n),
        c.reshape(tp, groups * n), dt.T, cs.reshape(tp, h).T, seg_rows,
        (h // groups, p, n, groups))
    return y[:t].reshape(t, h, p)


# Contracts of the two pallas_call sites for analysis/kernel_check.py. K2's
# discipline is accumulate over the inner grid axis, flush once; a scan is
# not that: every grid step writes its own output blocks, and the scratch is
# the state carried from chunk to chunk (zeroed at a group's first chunk,
# ``pl.program_id(1) == 0``). So both are declared as map kernels (no init
# or flush guard: every output must be stored, every contraction float32),
# and the carried state is held to the recurrence by tests/test_attn/test_ssd.py.
PALLAS_CONTRACTS: dict = {
    "_ssd_fwd_kernel": dict(
        wrapper="_fwd_call",
        scratch=("st_ref",),
        outputs=("y_ref", "hin_ref"),
        out_dtypes=("input", "f32"),
        init_guard=None,
        flush_guard=None,
        group_inner=None,
    ),
    "_ssd_bwd_kernel": dict(
        wrapper="_ssd_core_bwd",
        scratch=("dst_ref",),
        outputs=("dx_ref", "db_ref", "dc_ref", "ddt_ref", "dcs_ref"),
        out_dtypes=("input", "input", "input", "f32", "f32"),
        init_guard=None,
        flush_guard=None,
        group_inner=None,
    ),
}
