"""Grouped matmul over rows sorted by group, forward, ``d rows`` and ``dW``.

``grouped_matmul(rows[M, K], w[G, K, N], group_sizes[G]) -> [M, N]``: the
first ``group_sizes[0]`` rows times ``w[0]``, the next ``group_sizes[1]``
times ``w[1]``, ... (an expert layer's rows sorted by expert,
``models/moe.py``). ``group_sizes`` is data: a traced ``int32`` vector. Rows
past the groups (``M`` is the buffer's worst case) are never read into a
result and never written: what the output holds there is undefined, NaN
included, and the caller masks it (``moe._held_experts_block``).

**Live row tiles only.** From ``group_sizes`` the wrapper derives, with
``jnp`` on the device, three 1-D ``int32`` tables handed over by scalar
prefetch (:func:`visit_tables`: visit -> group, visit -> row tile, each
group's row span) and the number of visits, which is the grid's row-tile
axis: a dynamic bound, so a tile past the groups costs no grid step, no DMA
and no MXU pass. A tile that straddles two groups is visited once for each,
consecutively, and the store is masked to the visiting group's rows; the
rows keep their layout and no group is padded.

Two bodies, bound by ``_named.pallas_call`` so that a device trace shows
``magi_ragged_dot_kernel`` and ``magi_ragged_dot_dw_kernel``:

* ``_ragged_dot_kernel`` — a row tile times one column block of its group's
  weight, all of ``K`` in one float32 contraction, grid ``(column blocks,
  visits)``: a weight block stays in VMEM for its group's visits and is
  read once a call. ``d rows = grouped_matmul(dy, w^T)`` runs the same body
  with the weight block read transposed.
* ``_ragged_dot_dw_kernel`` — ``dW[g] = rows_g^T dy_g`` accumulated over a
  group's visits in a float32 VMEM scratch and written when the group
  changes. A group with no rows is visited once with nothing to add, so its
  ``dW`` is exact zeros.

**Which way a weight is stored.** A ``w[G, K, N]`` whose ``N`` is no
multiple of the 128 lanes while ``K`` is (an expert's up projection, 2688 x
1856) is handed to the bodies as each group's transpose, ``[G, N, K]``
(``tile_policy.grouped_weight_k_minor``): the forward then reads the block
transposed, ``d rows`` reads it plain, and ``dW^T[g] = dy_g^T rows_g`` is
the dW body with its operands swapped. XLA keeps such a parameter
``K``-minor of its own accord (an ``N``-minor one is padded to 1920 in HBM),
so the swap is a change of layout and no copy, and neither the bf16 weight
nor its gradient carries the padding (``PERF.md`` section 6, PR 32).

bf16 (or whatever the operands are) into the MXU, float32 out of it; each
result is rounded once, on the way out, to the declared type: ``out_dtype``
forward, ``rows.dtype`` for ``d rows``, ``w.dtype`` for ``dW``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry
from . import _named, tile_policy

_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _group_tiles(group_sizes, tile_rows: int, visit_empty: bool, xp=jnp):
    """Per group: its row span's start and end, its first row tile, and the
    row tiles it touches (an empty group none, or one to write its zeros).
    ``xp`` is ``jnp`` for the device's tables, ``numpy`` for a host count."""
    sizes = group_sizes.astype(xp.int32)
    ends = xp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tile_rows
    touched = xp.where(
        sizes > 0, (ends - 1) // tile_rows - first + 1, int(visit_empty))
    return starts, ends, first, touched


def visit_tables(group_sizes, m: int, tile_rows: int, visit_empty: bool):
    """``(visit -> group, visit -> row tile, group -> first row with the
    last row's end appended, visits)``: the grid's row-tile axis as 1-D
    ``int32`` tables (a 2-D table would pad every row to 512 bytes of SMEM).
    Visits are in row order, a straddled tile's visits adjacent; entries
    past ``visits`` repeat a valid index. The tables are ``cdiv(m,
    tile_rows) + G - 1`` long, the most visits any sizes can give."""
    g = group_sizes.shape[0]
    tiles_m = pl.cdiv(m, tile_rows)
    starts, ends, first, touched = _group_tiles(
        group_sizes, tile_rows, visit_empty)
    visit_end = jnp.cumsum(touched)
    v = jnp.arange(tiles_m + g - 1, dtype=jnp.int32)
    # a visit's group: how many groups' visits end at or before it. Compared
    # against all G ends at once, and the group's entries picked by a
    # one-hot sum: a [visits, G] fusion, where a search and a gather of so
    # few elements compile to hundreds of scalar operations a call
    group_of = jnp.minimum(
        jnp.sum(visit_end[None, :] <= v[:, None], axis=1, dtype=jnp.int32),
        g - 1)
    own = group_of[:, None] == jnp.arange(g, dtype=jnp.int32)[None, :]
    offset = jnp.sum(
        jnp.where(own, (first - (visit_end - touched))[None, :], 0),
        axis=1, dtype=jnp.int32)
    tile_of = jnp.clip(offset + v, 0, tiles_m - 1)
    spans = jnp.concatenate([starts, ends[-1:]])
    return group_of, tile_of, spans, visit_end[-1]


def tile_stats(group_sizes, tile_rows: int):
    """``(live tile visits, live rows / (visits x tile_rows))`` of sizes the
    host holds (numpy): how many grid steps a column block takes, and how
    full the row tiles the MXU is given are. 0 visits fill 1.0."""
    sizes = np.asarray(group_sizes)
    visits = int(_group_tiles(sizes, tile_rows, False, xp=np)[3].sum())
    return visits, float(sizes.sum() / (visits * tile_rows)) if visits else 1.0


def _record_tile_stats(
    group_sizes, *, tile_rows: int, row_buffer: int | None
) -> None:
    visits, fill = tile_stats(group_sizes, tile_rows)
    live_rows = int(np.sum(group_sizes))
    sized = {} if row_buffer is None else {
        "row_buffer": row_buffer, "fitted": live_rows <= row_buffer}
    telemetry.record_event(
        "grouped_matmul_plan", groups=int(np.size(group_sizes)),
        live_rows=live_rows, tile_rows=tile_rows,
        tile_visits=visits, tile_fill=fill, **sized)


def note_tile_stats(
    group_sizes, tile_rows: int, row_buffer: int | None = None
) -> None:
    """Tell telemetry the :func:`tile_stats` of one plan (the sizes one
    block of rows was sorted into; every product of the block shares them)
    and, where the caller sized a ``row_buffer`` by what it expected
    (``models/moe.py``), that size and whether the rows ``fitted`` it.
    Gated: with telemetry off nothing is traced into the program. It
    observes and steers nothing."""
    if telemetry.enabled():
        jax.debug.callback(
            partial(_record_tile_stats, tile_rows=tile_rows,
                    row_buffer=row_buffer), group_sizes)


def _own_rows(group_ref, tile_ref, span_ref, v, tile_rows: int):
    """``(whole, mask)`` of visit ``v``: whether every row of its tile is
    its group's, and the ``(tile_rows, 1)`` mask of the rows that are."""
    g = group_ref[v]
    lo, hi = span_ref[g], span_ref[g + 1]
    row0 = tile_ref[v] * tile_rows
    whole = (lo <= row0) & (row0 + tile_rows <= hi)
    r = row0 + jax.lax.broadcasted_iota(jnp.int32, (tile_rows, 1), 0)
    return whole, (r >= lo) & (r < hi)


def _ragged_dot_kernel(group_ref, tile_ref, span_ref, x_ref, w_ref, o_ref, *,
                       tile_rows: int, transposed: bool):
    whole, mine = _own_rows(
        group_ref, tile_ref, span_ref, pl.program_id(1), tile_rows)
    out = jax.lax.dot_general(
        x_ref[...], w_ref[...], _NT if transposed else _NN,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(whole)
    def _():
        o_ref[...] = out

    # a tile shared with another group (or with the rows past the groups):
    # the other group's visit is adjacent, the block stays in VMEM between
    @pl.when(jnp.logical_not(whole))
    def _():
        o_ref[...] = jnp.where(mine, out, o_ref[...])


def _ragged_dot_dw_kernel(group_ref, tile_ref, span_ref, x_ref, dy_ref,
                          dw_ref, acc_ref, *, tile_rows: int):
    v, last_v = pl.program_id(2), pl.num_programs(2) - 1
    g = group_ref[v]
    # a group's run of visits, read off the table: 1 at its first, its last
    # (integers compared with 1: the guard shape kernel_check's K2 reads)
    first = jnp.where(
        (v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != g), 1, 0)
    last = jnp.where(
        (v == last_v) | (group_ref[jnp.minimum(v + 1, last_v)] != g), 1, 0)
    whole, mine = _own_rows(group_ref, tile_ref, span_ref, v, tile_rows)

    @pl.when(first == 1)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(whole)
    def _():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], _TN, preferred_element_type=jnp.float32)

    # both operands masked: a row past the groups may hold anything
    @pl.when(jnp.logical_not(whole) & (span_ref[g + 1] > span_ref[g]))
    def _():
        x = jnp.where(mine, x_ref[...], jnp.zeros_like(x_ref))
        dy = jnp.where(mine, dy_ref[...], jnp.zeros_like(dy_ref))
        acc_ref[...] += jax.lax.dot_general(
            x, dy, _TN, preferred_element_type=jnp.float32)

    @pl.when(last == 1)
    def _():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def _interpret() -> bool:
    from .ffa import _should_interpret

    return _should_interpret()


def _vm(block, index):
    return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)


def _product_call(x, w, group_sizes, tile_rows: int, out_dtype,
                  transposed: bool):
    """``x[M, K]`` times each group's ``w[g]`` (``[K, N]``, or ``[N, K]``
    read transposed), ``[M, N]`` in ``out_dtype``."""
    m, k = x.shape
    n = w.shape[1] if transposed else w.shape[2]
    tn = tile_policy.grouped_col_tile(k, n, w.dtype.itemsize)
    group_of, tile_of, spans, visits = visit_tables(
        group_sizes, m, tile_rows, visit_empty=False)
    if transposed:
        w_spec = _vm((None, tn, k), lambda j, v, gr, ti, sp: (gr[v], j, 0))
    else:
        w_spec = _vm((None, k, tn), lambda j, v, gr, ti, sp: (gr[v], 0, j))
    return _named.pallas_call(
        partial(_ragged_dot_kernel, tile_rows=tile_rows,
                transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), visits),
            in_specs=[
                _vm((tile_rows, k), lambda j, v, gr, ti, sp: (ti[v], 0)),
                w_spec,
            ],
            out_specs=_vm(
                (tile_rows, tn), lambda j, v, gr, ti, sp: (ti[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(group_of, tile_of, spans, x, w)


def _dw_call(x, dy, group_sizes, tile_rows: int, out_dtype):
    """``dW[g] = x_g^T dy_g``, ``[G, K, N]`` in ``out_dtype``."""
    (m, k), n, g = x.shape, dy.shape[1], group_sizes.shape[0]
    tk, tn = tile_policy.grouped_dw_tiles(
        k, n, jnp.dtype(out_dtype).itemsize)
    group_of, tile_of, spans, visits = visit_tables(
        group_sizes, m, tile_rows, visit_empty=True)
    return _named.pallas_call(
        partial(_ragged_dot_dw_kernel, tile_rows=tile_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(k, tk), pl.cdiv(n, tn), visits),
            in_specs=[
                _vm((tile_rows, tk), lambda i, j, v, gr, ti, sp: (ti[v], i)),
                _vm((tile_rows, tn), lambda i, j, v, gr, ti, sp: (ti[v], j)),
            ],
            out_specs=_vm(
                (None, tk, tn), lambda i, j, v, gr, ti, sp: (gr[v], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g, k, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(group_of, tile_of, spans, x, dy)


def _stored(w):
    """``(the weight as the bodies are given it, whether that is each
    group's transpose)``: ``tile_policy.grouped_weight_k_minor`` of its
    shape. The transpose is a change of layout to XLA, which keeps a
    ``[G, K, N]`` whose ``N`` is no multiple of the lanes ``K``-minor of
    its own accord, and no copy where it does."""
    k_minor = tile_policy.grouped_weight_k_minor(*w.shape[1:])
    return (jnp.swapaxes(w, 1, 2) if k_minor else w), k_minor


def _forward(rows, w, group_sizes, tile_rows, out_dtype):
    stored, k_minor = _stored(w)
    return _product_call(
        rows, stored, group_sizes, tile_rows, out_dtype, k_minor)


_grouped = jax.custom_vjp(_forward, nondiff_argnums=(3, 4))


def _grouped_fwd(rows, w, group_sizes, tile_rows, out_dtype):
    out = _forward(rows, w, group_sizes, tile_rows, out_dtype)
    return out, (rows, w, group_sizes)


def _grouped_bwd(tile_rows, out_dtype, res, dy):
    rows, w, group_sizes = res
    dy = dy.astype(rows.dtype)  # the MXU's operand type, as rows and w are
    stored, k_minor = _stored(w)
    d_rows = _product_call(
        dy, stored, group_sizes, tile_rows, rows.dtype, not k_minor)
    if k_minor:  # dW^T[g] = dy_g^T rows_g, the same body
        dw = jnp.swapaxes(
            _dw_call(dy, rows, group_sizes, tile_rows, w.dtype), 1, 2)
    else:
        dw = _dw_call(rows, dy, group_sizes, tile_rows, w.dtype)
    return d_rows, dw, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(rows, w, group_sizes, *, tile_rows: int | None = None,
                   out_dtype=jnp.float32) -> jax.Array:
    """``rows[M, K]`` sorted by group times ``w[G, K, N]``, ``[M, N]`` in
    ``out_dtype`` (the float32 accumulator rounded once).

    ``group_sizes`` ``(G,)`` int32, traced; rows past their sum are neither
    read into a result nor written (the output is undefined there, and so
    is ``d rows``; ``dW`` takes nothing from them). ``tile_rows`` is the
    row tile, by default :func:`tile_policy.grouped_row_tile` of ``M / G``;
    a caller that knows how many rows a group expects gives the rule's
    answer for that. Differentiable in ``rows`` (``rows.dtype``) and ``w``
    (``w.dtype``; a group with no rows gets exact zeros).
    """
    (m, k), (g, k2, _) = rows.shape, w.shape
    if k != k2 or group_sizes.shape != (g,):
        raise ValueError(
            f"rows {rows.shape}, w {w.shape}, group_sizes "
            f"{group_sizes.shape} do not make a grouped product")
    if tile_rows is None:
        tile_rows = tile_policy.grouped_row_tile(m // g)
    return _grouped(rows, w, group_sizes.astype(jnp.int32), tile_rows,
                    jnp.dtype(out_dtype))


# Contracts of the two pallas_call sites for analysis/kernel_check.py. The
# product body is a map kernel: no scratch, all of K in one float32
# contraction, every visit stores its own rows of the block. The dW body
# accumulates over the innermost grid axis; a group's run of visits is found
# in the prefetched visit -> group table (zeroed at the run's first visit,
# flushed once at its last), which is what its guards are bound from.
PALLAS_CONTRACTS: dict = {
    "_ragged_dot_kernel": dict(
        wrapper="_product_call",
        scratch=(),
        outputs=("o_ref",),
        out_dtypes=("f32_or_input",),
        init_guard=None,
        flush_guard=None,
        group_inner=None,
    ),
    "_ragged_dot_dw_kernel": dict(
        wrapper="_dw_call",
        scratch=("acc_ref",),
        outputs=("dw_ref",),
        out_dtypes=("input",),
        init_guard="first",
        flush_guard="last",
        init_binding="group_ref",
        flush_binding="group_ref",
        group_inner=None,
    ),
}
