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
``(groups * blocks, chunks)``, chunks innermost and sequential; a grid step
holds one chunk of ``hb`` heads of a group (:func:`heads_per_step`: every
head of the group where they fit, ``blocks`` = 1). It builds what the heads
share once (``C B^T``, the masks, ``C h`` for every head in one product) and
runs a head's arithmetic over a leading head axis (:func:`_over_heads`: one
head under ``jax.vmap``, each head's ``(Q, P)`` lanes of the ``(Q, hb * P)``
blocks stacked). A group over several blocks writes one part of ``dB`` and
``dC`` a block, summed after the call. The backward is a ``custom_vjp``:
the forward then also writes every chunk's incoming state (float32), and
the backward walks the chunks in reverse carrying the state's gradient. The
decay's running sum, ``D * x`` and everything before and after are the
caller's (plain XLA, differentiated by JAX).
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
# A grid step's heads (:func:`heads_per_step`) hold float32 ``(Q, Q)``
# tiles and ``(Q, P)``, ``(Q, N)``, ``(N, P)`` blocks: ``hb`` heads take
# ``hb * (3 Q^2 + (Q + N)(Q + P))`` numbers, at most ``STEP_WORDS``. Measure
# and bound fit a compile sweep for a v5e (default scoped VMEM, 16 MiB;
# PERF.md, section 6): over ten shapes (P and N 64 to 256, 8 to 20 heads)
# the backward compiled up to 1 474 560 and was refused from 1 572 864.
STEP_WORDS = 1_474_560

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


def _chunk_terms(b_ref, c_ref, sr_ref):
    """What every head of a grid step shares, forward and backward: ``B``,
    ``C``, ``C B^T``, the same-document causal mask, the carry flags and
    each token's reach to the chunk's end as columns."""
    bm, cm = b_ref[...], c_ref[...]                      # (Q, N)
    q = bm.shape[0]
    sr = sr_ref[...]
    sc = sr.T                                            # (Q, 8)
    seg_r, seg_c = sr[0:1, :], sc[:, 0:1]
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    mask = (seg_c == seg_r) & (row >= col)
    to_end = (seg_c == seg_r[:, q - 1:q]).astype(_F32)   # (Q, 1)
    return dict(bm=bm, cm=cm, g=_dot(cm, bm, _NT), mask=mask,
                ok_c=sc[:, 1:2], to_end=to_end)


def _decays(t, cs):
    """One head's decays within the chunk from its running sum ``cs``
    (Q,): ``L`` (Q, Q), the carry's reach ``ec`` (Q, 1), each token's
    reach to the chunk's end ``w`` (Q, 1)."""
    q = cs.shape[0]
    cs_c, cs_r = cs[:, None], cs[None, :]
    decay = jnp.exp(jnp.where(t["mask"], cs_c - cs_r, -jnp.inf))
    ec = jnp.exp(cs_c) * t["ok_c"]
    w = jnp.exp(cs_r[:, q - 1:q] - cs_c) * t["to_end"]
    return decay, ec, w


def _by_head(a, p: int):
    """A ``(rows, hb * P)`` block as ``(hb, rows, P)``: each head's lanes."""
    return jnp.stack([a[:, lo:lo + p] for lo in range(0, a.shape[1], p)])


def _wide(a):
    """``(hb, rows, P)`` back to ``(rows, hb * P)``."""
    return jnp.concatenate(list(a), axis=-1)


def _head_sum(a):
    """``(hb, ...)`` summed over the heads as a running sum in head order:
    the rounding does not depend on how the compiler reduces an axis."""
    total = a[0]
    for j in range(1, a.shape[0]):
        total = total + a[j]
    return total


def _over_heads(head):
    """``head``, the arithmetic of one head's chunk, run over a step's heads
    as a leading axis (the shared terms, its first argument, unbatched): a
    head's arithmetic is traced and lowered once whatever ``hb`` is, and the
    compiler sees each operation over ``hb`` heads, as ``kda.py`` runs its
    heads."""
    @jax.jit
    def run(t, *per_head):
        return jax.vmap(head, in_axes=(None,) + (0,) * len(per_head))(
            t, *per_head)
    return run


def _fwd_head(t, x, dt, cs, ch, h):
    """``(y, h_out)`` of one head's chunk: ``x`` (Q, P), ``dt`` and ``cs``
    (Q,), ``ch`` = ``C h`` (Q, P), the incoming state ``h`` (N, P)."""
    decay, ec, w = _decays(t, cs)
    m = (t["g"] * decay).astype(_BF16)
    xd = (x.astype(_F32) * dt[:, None]).astype(_BF16)
    y = _dot(m, xd) + ec * ch
    bw = (t["bm"].astype(_F32) * w).astype(_BF16)
    return y, _last_row(ec, h.shape[1]) * h + _dot(bw, xd, _TN)


def _ssd_fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cs_ref, sr_ref, *rest,
                    p: int, save: bool):
    if save:
        y_ref, hin_ref, st_ref = rest
    else:
        (y_ref, st_ref), hin_ref = rest, None

    @pl.when(pl.program_id(1) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    st = st_ref[...]                                     # (N, hb*P) float32
    if save:
        hin_ref[...] = st
    t = _chunk_terms(b_ref, c_ref, sr_ref)
    # C h of every head in one product: C is the group's
    ch = _dot(t["cm"], st.astype(_BF16))                 # (Q, hb*P)
    y, st_end = _fwd_heads(
        t, _by_head(x_ref[...], p), dt_ref[...], cs_ref[...],
        _by_head(ch, p), _by_head(st, p))
    y_ref[...] = _wide(y).astype(y_ref.dtype)
    st_ref[...] = _wide(st_end)


def _bwd_head(t, x, dt, cs, dy, h, dh):
    """One head's part of the chunk's cotangents from its blocks, ``dy``,
    the incoming state ``h`` and the outgoing state's cotangent ``dh``:
    ``dx``, its terms of ``dG``, ``dC`` and ``dB``, ``ddt``, ``dcs`` and
    the incoming state's cotangent."""
    decay, ec, w = _decays(t, cs)
    q = cs.shape[0]
    bm32, cm32 = t["bm"].astype(_F32), t["cm"].astype(_F32)
    last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    dt_c = dt[:, None]
    m = t["g"] * decay
    x32 = x.astype(_F32)
    xd = (x32 * dt_c).astype(_BF16)
    h16, dh16 = h.astype(_BF16), dh.astype(_BF16)
    bw = (bm32 * w).astype(_BF16)
    dec = ec[q - 1:q, :]
    # y = m xd + ec (C h);  h_out = dec h + bw^T xd
    dxd = _dot(m.astype(_BF16), dy, _TN) + _dot(bw, dh16)         # (Q, P)
    dm = _dot(dy, xd, _NT)                                        # (Q, Q)
    e = dm * m
    dch = _dot(dy, h16, _NT)                                      # (Q, N)
    d_ec = jnp.sum(cm32 * dch, axis=-1, keepdims=True)
    xdh = _dot(xd, dh16, _NT)                                     # (Q, N)
    d_w = jnp.sum(bm32 * xdh, axis=-1, keepdims=True)
    dh_in = _last_row(ec, h.shape[1]) * dh + _dot(
        (cm32 * ec).astype(_BF16), dy, _TN)
    end = jnp.sum(d_w * w, axis=0, keepdims=True) + dec * jnp.sum(
        jnp.sum(h * dh, axis=-1, keepdims=True), axis=0, keepdims=True)
    dcs_c = (jnp.sum(e, axis=-1, keepdims=True) + d_ec * ec - d_w * w
             + jnp.where(last, end, 0.0))
    dcs = dcs_c[:, 0] - jnp.sum(e, axis=0)
    ddt = jnp.sum(dxd * x32, axis=-1)
    return dxd * dt_c, dm * decay, dch * ec, xdh * w, ddt, dcs, dh_in


_fwd_heads, _bwd_heads = _over_heads(_fwd_head), _over_heads(_bwd_head)


def _ssd_bwd_kernel(x_ref, b_ref, c_ref, dt_ref, cs_ref, sr_ref, dy_ref,
                    hin_ref, dx_ref, db_ref, dc_ref, ddt_ref, dcs_ref,
                    dst_ref, *, p: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    t = _chunk_terms(b_ref, c_ref, sr_ref)
    dx, dg, dc, db, ddt, dcs, dst = _bwd_heads(
        t, _by_head(x_ref[...], p), dt_ref[...], cs_ref[...],
        _by_head(dy_ref[...], p), _by_head(hin_ref[...], p),
        _by_head(dst_ref[...], p))
    dg16 = _head_sum(dg).astype(_BF16)
    dc_ref[...] = (_dot(dg16, t["bm"]) + _head_sum(dc)).astype(dc_ref.dtype)
    db_ref[...] = (_dot(dg16, t["cm"], _TN) + _head_sum(db)).astype(
        db_ref.dtype)
    dx_ref[...] = _wide(dx).astype(dx_ref.dtype)
    ddt_ref[...] = ddt
    dcs_ref[...] = dcs
    dst_ref[...] = _wide(dst)


def heads_per_step(heads: int, q: int, p: int, n: int) -> int:
    """Heads of a group a grid step: the largest divisor ``hb`` of the
    group's ``heads`` whose ``hb * p`` lanes are whole vregs (or the whole
    group) and whose step fits :data:`STEP_WORDS`; the smallest such
    divisor where none fits."""
    lanes = [hb for hb in range(1, heads + 1)
             if heads % hb == 0 and (hb == heads or hb * p % 128 == 0)]
    words = 3 * q * q + (q + n) * (q + p)
    return max((hb for hb in lanes if hb * words <= STEP_WORDS),
               default=lanes[0])


def _specs(q, hb, p, n, blocks, n_chunks, reverse):
    """BlockSpecs for grid step ``(block, i)`` of ``hb`` heads, ``blocks``
    to a group: the chunk is ``i``, or ``n_chunks - 1 - i`` walking
    backwards."""
    def chunk(i):
        return n_chunks - 1 - i if reverse else i

    def vm(block, index):
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    return dict(
        wide=vm((q, hb * p), lambda k, i: (chunk(i), k)),         # x, y, dy, dx
        state=vm((q, n), lambda k, i: (chunk(i), k // blocks)),   # B, C
        part=vm((q, n), lambda k, i: (chunk(i), k)),              # dB, dC
        per_head=vm((None, hb, q), lambda k, i: (k, 0, chunk(i))),  # dt, cs
        seg=vm((SEG_ROWS, q), lambda k, i: (0, chunk(i))),
        hin=vm((None, None, n, hb * p),
               lambda k, i: (chunk(i), k // blocks, 0, k % blocks)),
    )


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _interpret() -> bool:
    from .ffa import _should_interpret

    return _should_interpret()


def _grid(dims, t):
    """``(hb, blocks, grid, specs)`` of a call over ``t`` tokens: grid step
    ``(block, chunk)``, ``hb`` heads a block, ``blocks`` blocks a group."""
    heads, p, n, groups = dims
    hb = heads_per_step(heads, CHUNK, p, n)
    blocks, n_chunks = heads // hb, t // CHUNK
    return hb, blocks, (groups * blocks, n_chunks), partial(
        _specs, CHUNK, hb, p, n, blocks, n_chunks)


def _fwd_call(x2, b2, c2, dt_r, cs_r, sr, dims, save: bool):
    heads, p, n, groups = dims
    hb, _, grid, specs = _grid(dims, x2.shape[0])
    s = specs(reverse=False)
    out_specs = [s["wide"]]
    out_shape = [jax.ShapeDtypeStruct(x2.shape, x2.dtype)]
    if save:
        out_specs.append(s["hin"])
        out_shape.append(jax.ShapeDtypeStruct(
            (grid[1], groups, n, heads * p), _F32))
    blocked = (dt_r.shape[0] // hb, hb, x2.shape[0])
    return _named.pallas_call(
        partial(_ssd_fwd_kernel, p=p, save=save),
        grid=grid,
        in_specs=[s["wide"], s["state"], s["state"], s["per_head"],
                  s["per_head"], s["seg"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, hb * p), _F32)],
        interpret=_interpret(), compiler_params=_compiler_params(),
    )(x2, b2, c2, dt_r.reshape(blocked), cs_r.reshape(blocked), sr)


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
    _, p, n, groups = dims
    t = x2.shape[0]
    hb, blocks, grid, specs = _grid(dims, t)
    s = specs(reverse=True)
    blocked = (dt_r.shape[0] // hb, hb, t)
    # a group's dB and dC sum over its heads: one part a block of its heads,
    # in float32 where there are several
    part = jax.ShapeDtypeStruct(
        (t, groups * blocks * n), b2.dtype if blocks == 1 else _F32)
    dx, db, dc, ddt, dcs = _named.pallas_call(
        partial(_ssd_bwd_kernel, p=p),
        grid=grid,
        in_specs=[s["wide"], s["state"], s["state"], s["per_head"],
                  s["per_head"], s["seg"], s["wide"], s["hin"]],
        out_specs=[s["wide"], s["part"], s["part"], s["per_head"],
                   s["per_head"]],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x2.dtype), part, part,
            jax.ShapeDtypeStruct(blocked, _F32),
            jax.ShapeDtypeStruct(blocked, _F32),
        ],
        scratch_shapes=[pltpu.VMEM((n, hb * p), _F32)],
        interpret=_interpret(), compiler_params=_compiler_params(),
    )(x2, b2, c2, dt_r.reshape(blocked), cs_r.reshape(blocked), sr,
      dy.astype(x2.dtype), hin)
    db, dc = (v.reshape(t, groups, blocks, n).sum(axis=2).astype(
        b2.dtype).reshape(b2.shape) for v in (db, dc))
    return (dx, db, dc, ddt.reshape(dt_r.shape), dcs.reshape(cs_r.shape),
            jnp.zeros_like(sr))


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
    hb = heads_per_step(h // groups, CHUNK, p, n)
    registry.note_choice(
        "ssd_heads", (h, p, n, groups), f"heads{hb}", "shape_rule")
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
# the states of the step's heads carried from chunk to chunk (zeroed at a
# head block's first chunk, ``pl.program_id(1) == 0``). So both are declared as map kernels (no init
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
