"""Host-side tiling plan for the Pallas FFA kernel.

The TPU replacement for the reference's range-aware persistent tile schedulers
(csrc/flexible_flash_attention/fwd_tile_scheduler.hpp, bwd_tile_scheduler.hpp):
instead of a device-side scheduler walking (q_range, k_range, mask_type) lists,
we precompute — on the host, from concrete slice metadata — the exact list of
(q_tile, k_tile, slice) work items the kernel grid will visit. Fully-masked
tiles are never visited; fully-unmasked tiles can skip mask evaluation. This is
the idiomatic TPU trade: static grids + scalar prefetch instead of dynamic
scheduling + atomics.

Slices are encoded as diagonal bands (q_range, k_range, d_lo <= j-i <= d_hi) —
see kernels/mask_utils.types_to_bands.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property, lru_cache

import numpy as np

from .mask_utils import BAND_INF
from .. import telemetry
from ..utils.profiling import instrument_host

# meta columns per work item. The first 9 are the band/run columns the
# native (C) builder also fills; EQ0..EK1 are the tile-LOCAL live-extent
# columns appended host-side by :func:`_extend_meta_extents` — the exact
# sub-rectangle of the tile the band touches, rounded out to the hardware
# quanta, consumed by the extent-clamped kernel bodies (kernels/ffa.py).
# QVF/QVL mark the first/last occurrence of the item's q tile across the
# WHOLE list (appended by :func:`_extend_meta_visits`): on the k-major list
# a q tile's visits are non-consecutive, and the fused one-pass backward
# zero-initializes its revisited dq output block on QVF and flushes
# (applies softmax_scale) on QVL. On the q-major list a q tile's items form
# one contiguous run, so there QVF/QVL coincide with IS_FIRST/IS_LAST.
QS, QE, KS, KE, DLO, DHI, IS_FIRST, IS_LAST, IS_FULL = range(9)
EQ0, EQ1, EK0, EK1 = 9, 10, 11, 12
QVF, QVL = 13, 14
META_DIM = 15
# rounding quanta for the live extents: q rows land in the sublane dim
# (fp32 register tiling), k cols in the lane dim
SUBLANE_QUANTUM = 8
LANE_QUANTUM = 128


@dataclass(frozen=True, eq=False)
class FFAPlan:
    """A flat, q-tile-major work list plus its k-tile-major transpose."""

    # q-major (forward + dq): runs of items grouped by q tile
    work_qt: np.ndarray  # (W,) int32 — q tile index per item
    work_kt: np.ndarray  # (W,) int32 — k tile index per item
    meta: np.ndarray  # (W, META_DIM) int32
    # k-major (dkv): runs of items grouped by k tile
    work_qt_t: np.ndarray
    work_kt_t: np.ndarray
    meta_t: np.ndarray
    num_q_tiles: int
    num_k_tiles: int
    block_q: int
    block_k: int

    @property
    def num_work(self) -> int:
        return len(self.work_qt)

    @property
    def num_work_t(self) -> int:
        return len(self.work_qt_t)

    @cached_property
    def min_revisit_distance(self) -> int:
        """:func:`min_revisit_distance` of the k-major list — what the
        one-pass backward's dq read-modify-write has to clear."""
        return min_revisit_distance(self.work_qt_t)


# min_revisit_distance of a list on which no q tile is ever left and visited
# again: any distance a pipeline could ask for is met
NO_REVISIT = 1 << 30


def min_revisit_distance(work_qt: np.ndarray) -> int:
    """Fewest grid steps between two NON-adjacent visits of one q tile.

    Adjacent items of one q tile keep the dq window resident (its block
    index does not change, nothing is written back or fetched) and do not
    count. Once the walk moves off a q tile, the window is written back
    after the run's last step ``i``; a later visit at step ``j`` fetches it
    again, and the fetch is issued a step ahead — the distance ``j - i`` is
    what must cover the pipeline's write-back (kernels/ffa.py
    ``FUSED_DQ_REVISIT_DISTANCE``). Filler rows of ``pad_plan`` repeat the
    last real item's tile, so they only lengthen its run. Returns
    ``NO_REVISIT`` when no q tile is visited twice.
    """
    w = np.asarray(work_qt).astype(np.int64).ravel()
    if len(w) < 3:
        return NO_REVISIT
    idx = np.arange(len(w))
    run_start = np.concatenate([[True], w[1:] != w[:-1]])
    run_end = np.concatenate([w[1:] != w[:-1], [True]])
    starts, ends = idx[run_start], idx[run_end]
    tiles = w[run_start]
    # runs of one tile, in list order: stable sort by tile keeps them so
    order = np.argsort(tiles, kind="stable")
    t, s, e = tiles[order], starts[order], ends[order]
    same = t[1:] == t[:-1]
    if not same.any():
        return NO_REVISIT
    return int((s[1:] - e[:-1])[same].min())


def _extend_meta_extents(
    meta9: np.ndarray,
    work_qt: np.ndarray,
    work_kt: np.ndarray,
    block_q: int,
    block_k: int,
) -> np.ndarray:
    """Append the tile-local live-extent columns EQ0..EK1 to 9-col meta rows.

    For each work item the band ``d_lo <= j - i <= d_hi`` restricted to the
    slice rectangle intersected with the tile gives a live sub-rectangle;
    its q rows are floored/ceiled to SUBLANE_QUANTUM, its k cols to
    LANE_QUANTUM (the granularities a kernel chunk can actually skip at).
    Items with an empty intersection — dummy items for empty tiles, and
    ``pad_plan`` filler — get the all-zero extent (0, 0, 0, 0), which the
    clamp path reads as "no live work". Full tiles come out as
    (0, block_q, 0, block_k) by construction. int64 internally: DLO/DHI
    carry ±BAND_INF and the un-clamped interval arithmetic must not wrap.
    """
    m = meta9.astype(np.int64)
    qb = work_qt.astype(np.int64) * block_q
    kb = work_kt.astype(np.int64) * block_k
    i0 = np.maximum(m[:, QS], qb)
    i1 = np.minimum(m[:, QE], qb + block_q)
    j0 = np.maximum(m[:, KS], kb)
    j1 = np.minimum(m[:, KE], kb + block_k)
    lo, hi = m[:, DLO], m[:, DHI]
    # band-live rows/cols inside the clipped rectangle: row i is live iff
    # some col j in [j0, j1) has lo <= j - i <= hi, and vice versa
    q0 = np.maximum(i0, j0 - hi)
    q1 = np.minimum(i1, j1 - lo)
    k0 = np.maximum(j0, i0 + lo)
    k1 = np.minimum(j1, i1 + hi)
    eq0 = (q0 - qb) // SUBLANE_QUANTUM * SUBLANE_QUANTUM
    eq1 = -(-(q1 - qb) // SUBLANE_QUANTUM) * SUBLANE_QUANTUM
    ek0 = (k0 - kb) // LANE_QUANTUM * LANE_QUANTUM
    ek1 = -(-(k1 - kb) // LANE_QUANTUM) * LANE_QUANTUM
    ext = np.stack(
        [
            np.clip(eq0, 0, block_q),
            np.clip(eq1, 0, block_q),
            np.clip(ek0, 0, block_k),
            np.clip(ek1, 0, block_k),
        ],
        axis=1,
    )
    empty = (i0 >= i1) | (j0 >= j1) | (q1 <= q0) | (k1 <= k0)
    ext[empty] = 0
    return np.concatenate([meta9, ext.astype(np.int32)], axis=1)


def _extend_meta_visits(meta13: np.ndarray, work_qt: np.ndarray) -> np.ndarray:
    """Append the q-visit flag columns QVF/QVL to 13-col meta rows.

    QVF (resp. QVL) is 1 on the row where the item's q tile appears for the
    first (resp. last) time in this list — across the WHOLE list, not per
    run, which is what makes them usable from the k-major traversal where a
    q tile's visits are interleaved with other q tiles. Dummy items count
    as visits (their contribution is zero, so an init or flush landing on
    one is benign); ``pad_plan`` filler is appended after the fact with
    QVF = QVL = 0 so the real flush row keeps the flag.
    """
    w = np.asarray(work_qt)
    n = len(w)
    qvf = np.zeros(n, dtype=np.int32)
    qvl = np.zeros(n, dtype=np.int32)
    if n:
        first_idx: dict[int, int] = {}
        last_idx: dict[int, int] = {}
        for i, qt in enumerate(w.tolist()):
            if qt not in first_idx:
                first_idx[qt] = i
            last_idx[qt] = i
        qvf[list(first_idx.values())] = 1
        qvl[list(last_idx.values())] = 1
    return np.concatenate(
        [meta13, np.stack([qvf, qvl], axis=1)], axis=1
    ).astype(np.int32)


def _late_revisit_order(
    work_qt_t: np.ndarray, work_kt_t: np.ndarray, meta9_t: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The k-major list with every k tile's run walked in the direction
    that brings a q tile of an earlier run back as late as it can. dk and
    dv sum over a run in any order; the one-pass backward's dq does care:
    its window leaves VMEM when the walk moves off a q tile and is fetched
    again at that tile's item in a later run (:func:`min_revisit_distance`,
    kernels/ffa.py ``FUSED_DQ_REVISIT_DISTANCE``).

    Each run, its items sorted by q tile, is walked from its last q tile
    down unless walking it up keeps every returning tile further away. A
    band's next k tile starts at or above this one's q tiles, so walking
    down puts a run's length (causal) or its length + 2 (a window) between
    the two visits, where walking up gives its length - 2: 4 grid steps,
    in place of 2, at the end of every causal document (256 x 512 tiles).
    Walking up wins where a short run sits between two longer ones over
    the same q tiles (a chunked rank at cp > 1: d c b a | d c | a b c d
    reads 3 where d c b a | d c | d c b a reads 2); the tile the walk is
    standing on stays resident and counts as far away."""
    n = len(work_kt_t)
    if n < 2:
        return work_qt_t, work_kt_t, meta9_t
    brk = np.flatnonzero(work_kt_t[1:] != work_kt_t[:-1]) + 1
    starts = np.concatenate([[0], brk])
    ends = np.concatenate([brk, [n]])
    # a run's items by q tile, so that two slices' items on one (q, k)
    # tile pair are neighbours and the second finds the window resident
    run = np.repeat(np.arange(len(starts)), ends - starts)
    perm = np.lexsort((work_qt_t, run))
    # the step a q tile was last visited at; far in the past when never
    last = np.full(int(work_qt_t.max()) + 1, -NO_REVISIT, dtype=np.int64)

    def nearest(tiles: np.ndarray, steps: np.ndarray) -> int:
        gap = steps - last[tiles]
        # resident: the head on the tile the walk stands on (a gap of 1),
        # and an item on its neighbour's tile
        gap[0] = NO_REVISIT if gap[0] == 1 else gap[0]
        gap[1:][tiles[1:] == tiles[:-1]] = NO_REVISIT
        return gap.min()

    for s, e in zip(starts.tolist(), ends.tolist()):
        steps = np.arange(s, e)
        up = perm[s:e].copy()
        down = up[::-1]
        if nearest(work_qt_t[down], steps) >= nearest(work_qt_t[up], steps):
            perm[s:e] = up = down
        last[work_qt_t[up]] = steps
    meta = meta9_t[perm]
    meta[:, [IS_FIRST, IS_LAST]] = 0
    meta[starts, IS_FIRST] = 1
    meta[ends - 1, IS_LAST] = 1
    return work_qt_t[perm], work_kt_t[perm], meta


def plan_extent_stats(plan: FFAPlan) -> dict:
    """Executed-vs-padded element accounting from the extent columns.

    Real items are rows with a non-empty q range (QE > QS) — dummy items
    for empty tiles and ``pad_plan`` filler carry QS == QE == 0 and are
    excluded from both counts (CP-stacking filler is not real work)."""
    meta = plan.meta.astype(np.int64)
    real = meta[:, QE] > meta[:, QS]
    n_real = int(real.sum())
    executed = int(
        (
            (meta[real, EQ1] - meta[real, EQ0])
            * (meta[real, EK1] - meta[real, EK0])
        ).sum()
    )
    return {
        "num_real_work": n_real,
        "padded_elems": n_real * plan.block_q * plan.block_k,
        "executed_elems": executed,
    }


# per-slice padded/band cover-ratio buckets for the fragmentation histogram
FRAG_BUCKETS: tuple[tuple[str, float], ...] = (
    ("lt_1.2", 1.2),
    ("lt_2", 2.0),
    ("lt_4", 4.0),
    ("lt_8", 8.0),
    ("ge_8", float("inf")),
)


def fragmentation_histogram(ratios: np.ndarray) -> dict[str, int]:
    """Bucket per-slice cover ratios (tile-cover elems / band elems) into
    the FRAG_BUCKETS histogram the telemetry record and the mixed-dispatch
    cost model share."""
    hist = {name: 0 for name, _ in FRAG_BUCKETS}
    for r in np.asarray(ratios, dtype=np.float64).ravel():
        for name, ub in FRAG_BUCKETS:
            if r < ub:
                hist[name] += 1
                break
    return hist


def _record_plan_telemetry(
    plan: FFAPlan,
    qr: np.ndarray,
    kr: np.ndarray,
    d_lo: np.ndarray,
    d_hi: np.ndarray,
    label: str | None = None,
) -> FFAPlan:
    """Gated per-build record (with the runtime key's ``label``, if it has
    one): the padded grid work the kernel would execute
    un-clamped, the post-clamp executed elements (live extents), and the
    true band area it needed — the estimated-vs-executed FLOP ratio at plan
    time (multiply elems by 4 * head_dim * num_heads_q for fwd FLOPs; the
    step record does, once dims are known)."""
    if telemetry.enabled():
        from ..env.kernel import ffa_extent_clamp
        from .tile_policy import slice_cover_ratios

        stats = plan_extent_stats(plan)
        padded = stats["padded_elems"]
        executed = stats["executed_elems"]
        band = telemetry.band_area(qr, kr, d_lo, d_hi)
        ratios = slice_cover_ratios(
            qr, kr, d_lo, d_hi, plan.block_q, plan.block_k
        )
        telemetry.record_event(
            "ffa_plan",
            num_slices=len(qr),
            block_q=plan.block_q,
            block_k=plan.block_k,
            num_q_tiles=plan.num_q_tiles,
            num_k_tiles=plan.num_k_tiles,
            num_work=plan.num_work,
            num_work_t=plan.num_work_t,
            min_revisit_distance=plan.min_revisit_distance,
            padded_elems=padded,
            band_elems=band,
            executed_elems=executed,
            padding_ratio=padded / band if band else 1.0,
            executed_ratio=executed / band if band else 1.0,
            extent_clamp=ffa_extent_clamp(),
            frag_histogram=fragmentation_histogram(ratios),
            **({} if label is None else {"label": label}),
        )
    return plan


def _band_tile_interaction(
    i0: int, i1: int, j0: int, j1: int, lo: int, hi: int
) -> tuple[bool, bool]:
    """(nonempty, fully_unmasked) of band [lo, hi] on rect [i0,i1) x [j0,j1)."""
    if i0 >= i1 or j0 >= j1:
        return False, False
    d_min = j0 - (i1 - 1)
    d_max = (j1 - 1) - i0
    nonempty = d_min <= hi and d_max >= lo
    full = nonempty and d_max <= hi and d_min >= lo
    return nonempty, full


@instrument_host
def build_ffa_plan(
    q_ranges: np.ndarray,
    k_ranges: np.ndarray,
    d_lo: np.ndarray,
    d_hi: np.ndarray,
    seqlen_q: int,
    seqlen_k: int,
    block_q: int,
    block_k: int,
    label: str | None = None,
) -> FFAPlan:
    """Build the work-item lists for the given band-slice metadata
    (``label``: the runtime key's, for the telemetry record alone).

    When ``MAGI_ATTENTION_RANGE_MERGE`` is on (default), band-compatible
    adjacent slices are merged first (mask_utils.merge_band_slices — the ref
    merges at its kernel entry, functional/flex_flash_attn.py:87). Exact:
    bands are global-coordinate, so the merged cover is identical; fragmented
    masks (block-sparse, video) collapse into fewer work items. This is the
    one choke point every planning path flows through (single-device
    ffa_attn, CP _stack_plans, dynamic runtime), so all of them benefit.
    """
    from ..env.general import is_range_merge_enable

    if is_range_merge_enable():
        from .mask_utils import merge_band_slices

        q_ranges, k_ranges, d_lo, d_hi = merge_band_slices(
            q_ranges, k_ranges, d_lo, d_hi
        )
    num_q_tiles = max(1, -(-seqlen_q // block_q))
    num_k_tiles = max(1, -(-seqlen_k // block_k))

    from ..env.kernel import ffa_native_plan

    mode = ffa_native_plan()
    if mode != "0":
        try:
            from ..csrc_backend.ops import ffa_plan_native

            arrays = ffa_plan_native(
                q_ranges, k_ranges, d_lo, d_hi,
                num_q_tiles, num_k_tiles, block_q, block_k, BAND_INF,
            )
            # the C fill writes 9-col rows (fixed stride, csrc/magi_host.cpp);
            # the k-major order, the extent and the q-visit columns are
            # made here so native and Python plans stay bit-identical
            arrays = (*arrays[:3], *_late_revisit_order(*arrays[3:6]))
            return _record_plan_telemetry(
                FFAPlan(
                    work_qt=arrays[0], work_kt=arrays[1],
                    meta=_extend_meta_visits(
                        _extend_meta_extents(
                            arrays[2], arrays[0], arrays[1], block_q, block_k
                        ),
                        arrays[0],
                    ),
                    work_qt_t=arrays[3], work_kt_t=arrays[4],
                    meta_t=_extend_meta_visits(
                        _extend_meta_extents(
                            arrays[5], arrays[3], arrays[4], block_q, block_k
                        ),
                        arrays[3],
                    ),
                    num_q_tiles=num_q_tiles, num_k_tiles=num_k_tiles,
                    block_q=block_q, block_k=block_k,
                ),
                q_ranges, k_ranges, d_lo, d_hi, label,
            )
        except ImportError:
            if mode == "1":
                raise
            # auto: native lib unavailable — pure-Python builder below

    n = len(q_ranges)
    q_items: list[list[tuple[int, ...]]] = [[] for _ in range(num_q_tiles)]
    k_items: list[list[tuple[int, ...]]] = [[] for _ in range(num_k_tiles)]

    for s in range(n):
        qs, qe = int(q_ranges[s, 0]), int(q_ranges[s, 1])
        ks, ke = int(k_ranges[s, 0]), int(k_ranges[s, 1])
        lo, hi = int(d_lo[s]), int(d_hi[s])
        if qs >= qe or ks >= ke or lo > hi:
            continue
        # same bounds validation as the native builder (csrc/magi_host.cpp:251
        # returns -1 -> ops.py raises): without it, negative starts would
        # silently wrap via Python negative indexing and corrupt the plan
        if (
            qs < 0
            or ks < 0
            or -(-qe // block_q) > num_q_tiles
            or -(-ke // block_k) > num_k_tiles
        ):
            raise ValueError(
                f"ffa plan slice {s} out of bounds: q[{qs},{qe}) "
                f"k[{ks},{ke}) vs grid {num_q_tiles}x{num_k_tiles} tiles"
            )
        qt_lo, qt_hi = qs // block_q, -(-qe // block_q)
        kt_lo, kt_hi = ks // block_k, -(-ke // block_k)
        for qt in range(qt_lo, qt_hi):
            i0, i1 = max(qs, qt * block_q), min(qe, (qt + 1) * block_q)
            for kt in range(kt_lo, kt_hi):
                j0, j1 = max(ks, kt * block_k), min(ke, (kt + 1) * block_k)
                nonempty, full = _band_tile_interaction(i0, i1, j0, j1, lo, hi)
                if not nonempty:
                    continue
                tile_full = (
                    full
                    and i0 == qt * block_q
                    and i1 == (qt + 1) * block_q
                    and j0 == kt * block_k
                    and j1 == (kt + 1) * block_k
                )
                item = (qt, kt, qs, qe, ks, ke, lo, hi, int(tile_full))
                q_items[qt].append(item)
                k_items[kt].append(item)

    def flatten(buckets, major_is_q: bool):
        work_a, work_b, metas = [], [], []
        for tile_idx, items in enumerate(buckets):
            if not items:
                # dummy item: empty k range -> all-masked -> finalize writes
                # zeros/-inf (fwd) or zero grads (bwd) for this tile
                items = [
                    (
                        tile_idx if major_is_q else 0,
                        0 if major_is_q else tile_idx,
                        0, 0, 0, 0, -BAND_INF, BAND_INF, 0,
                    )
                ]
            for pos, (qt, kt, qs, qe, ks, ke, lo, hi, full) in enumerate(items):
                m = np.zeros(9, dtype=np.int32)
                m[QS], m[QE], m[KS], m[KE] = qs, qe, ks, ke
                m[DLO], m[DHI] = lo, hi
                m[IS_FIRST] = 1 if pos == 0 else 0
                m[IS_LAST] = 1 if pos == len(items) - 1 else 0
                m[IS_FULL] = full
                work_a.append(qt)
                work_b.append(kt)
                metas.append(m)
        work_a = np.asarray(work_a, dtype=np.int32)
        work_b = np.asarray(work_b, dtype=np.int32)
        meta9 = np.stack(metas).astype(np.int32)
        if not major_is_q:
            work_a, work_b, meta9 = _late_revisit_order(work_a, work_b, meta9)
        return (
            work_a,
            work_b,
            _extend_meta_visits(
                _extend_meta_extents(meta9, work_a, work_b, block_q, block_k),
                work_a,
            ),
        )

    work_qt, work_kt, meta = flatten(q_items, major_is_q=True)
    work_qt_t, work_kt_t, meta_t = flatten(k_items, major_is_q=False)

    return _record_plan_telemetry(
        FFAPlan(
            work_qt=work_qt,
            work_kt=work_kt,
            meta=meta,
            work_qt_t=work_qt_t,
            work_kt_t=work_kt_t,
            meta_t=meta_t,
            num_q_tiles=num_q_tiles,
            num_k_tiles=num_k_tiles,
            block_q=block_q,
            block_k=block_k,
        ),
        q_ranges, k_ranges, d_lo, d_hi, label,
    )


def pad_plan(plan: FFAPlan, num_work: int, num_work_t: int) -> FFAPlan:
    """Pad work lists with no-op items (same tile as the last real item,
    is_first=is_last=0, empty ranges) so plans from different CP ranks share
    one static shape and can be fed to the kernel as traced arrays."""

    def pad(work_a, work_b, meta, target, tile_col_is_q: bool):
        w = len(work_a)
        if w > target:
            raise ValueError(f"plan has {w} items > target {target}")
        if w == target:
            return work_a, work_b, meta
        pad_n = target - w
        pa = np.full(pad_n, work_a[-1], dtype=np.int32)
        pb = np.full(pad_n, work_b[-1], dtype=np.int32)
        # filler rows keep the all-zero live extent (EQ0..EK1 == 0): the
        # clamp path skips them and plan_extent_stats excludes them from
        # the padded/executed accounting (QS == QE flags them as non-real).
        # QVF/QVL stay 0 too — filler revisits the last real tile's dq
        # window with a zero contribution, after its real flush row
        pm = np.zeros((pad_n, META_DIM), dtype=np.int32)
        pm[:, DLO], pm[:, DHI] = -BAND_INF, BAND_INF
        return (
            np.concatenate([work_a, pa]),
            np.concatenate([work_b, pb]),
            np.concatenate([meta, pm]),
        )

    wq, wk, m = pad(plan.work_qt, plan.work_kt, plan.meta, num_work, True)
    wqt, wkt, mt = pad(
        plan.work_qt_t, plan.work_kt_t, plan.meta_t, num_work_t, False
    )
    return FFAPlan(
        work_qt=wq, work_kt=wk, meta=m,
        work_qt_t=wqt, work_kt_t=wkt, meta_t=mt,
        num_q_tiles=plan.num_q_tiles, num_k_tiles=plan.num_k_tiles,
        block_q=plan.block_q, block_k=plan.block_k,
    )


@lru_cache(maxsize=256)
def _cached_plan(
    qr_bytes: bytes,
    kr_bytes: bytes,
    lo_bytes: bytes,
    hi_bytes: bytes,
    n: int,
    seqlen_q: int,
    seqlen_k: int,
    block_q: int,
    block_k: int,
    range_merge: bool,  # cache-key only: build reads the env flag itself
) -> FFAPlan:
    qr = np.frombuffer(qr_bytes, dtype=np.int32).reshape(n, 2)
    kr = np.frombuffer(kr_bytes, dtype=np.int32).reshape(n, 2)
    lo = np.frombuffer(lo_bytes, dtype=np.int32)
    hi = np.frombuffer(hi_bytes, dtype=np.int32)
    return build_ffa_plan(qr, kr, lo, hi, seqlen_q, seqlen_k, block_q, block_k)


def get_ffa_plan(
    q_ranges: np.ndarray,
    k_ranges: np.ndarray,
    d_lo: np.ndarray,
    d_hi: np.ndarray,
    seqlen_q: int,
    seqlen_k: int,
    block_q: int,
    block_k: int,
) -> FFAPlan:
    """LRU-cached plan lookup keyed by the full metadata contents."""
    qr = np.ascontiguousarray(q_ranges, dtype=np.int32)
    kr = np.ascontiguousarray(k_ranges, dtype=np.int32)
    lo = np.ascontiguousarray(d_lo, dtype=np.int32)
    hi = np.ascontiguousarray(d_hi, dtype=np.int32)
    from ..env.general import is_range_merge_enable

    return _cached_plan(
        qr.tobytes(), kr.tobytes(), lo.tobytes(), hi.tobytes(), len(qr),
        seqlen_q, seqlen_k, block_q, block_k, is_range_merge_enable(),
    )
