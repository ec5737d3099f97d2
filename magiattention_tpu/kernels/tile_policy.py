"""Automatic FFA tile-size selection (the TPU analogue of the reference's
per-arch tile tables, ref magi_attention/functional/_flex_flash_attn_jit.py:41-57
and csrc/flexible_flash_attention/tile_size.h).

The reference hard-codes (head_dim, arch) -> tile tables tuned offline; on
TPU the equivalent decision is (block_q, block_k), and the right choice
depends on the *mask geometry*: wide dense masks amortize per-step
bookkeeping best with big tiles, narrow bands waste padded MXU work unless
tiles shrink. Because the host-side plan builder is cheap (native C path,
LRU-cached), the policy can *measure* each candidate's true padded work for
the actual slice set instead of guessing from mask type:

    score(bq, bk) = W * bq * bk            # padded elements actually run
                  + W * OVERHEAD_ELEMS     # per-grid-step fixed cost,
                                           # expressed in element units

``OVERHEAD_ELEMS`` is the one free constant (per-step softmax bookkeeping +
pipeline bubble, in score-matrix-element equivalents). It is deliberately
conservative pending silicon calibration from ``benchmarks/history``
sweeps; at 0 the policy reduces to pure padded-area minimization.

Selection is gated by ``MAGI_ATTENTION_FFA_AUTO_TILE=1`` and only applies
when the caller didn't pin blocks (env or argument) — explicit settings
always win, mirroring the reference's env-override contract.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .. import telemetry
from ..env.general import _get_int
from ..resilience.inject import maybe_inject

NUM_LANES = 128
# per-grid-step fixed cost in score-element equivalents: ~the VPU work of
# one (8, 128) bookkeeping pass per lane group. Refine from silicon sweeps
# (benchmarks/history/true_rate.csv A/Bs) — see docs/performance.md.
OVERHEAD_ELEMS = 8 * 1024
# candidate tilings: bq multiples of 8 (fp32) / MXU-friendly, bk multiples
# of 128 (lane tiling); spans the sweep grid the silicon harnesses measure.
# The small-bk rows exist for thin bands (sliding-window, varlen tails):
# a 128-wide band inside a 512-wide k tile runs 4x the padded MXU work,
# and the exact per-slice work counting below is what detects that.
CANDIDATES: tuple[tuple[int, int], ...] = (
    (128, 128),
    (256, 128),
    (512, 128),
    (128, 256),
    (256, 256),
    (512, 256),
    (128, 512),
    (256, 512),
    (256, 1024),
    (512, 512),
    (512, 1024),
    (1024, 512),
    (1024, 1024),
)
# VMEM budget for one grid step's resident blocks (bytes), double-buffered;
# ~16 MB/core on v5e minus headroom
VMEM_BUDGET = 10 * 1024 * 1024


def auto_tile_enabled() -> bool:
    return _get_int("MAGI_ATTENTION_FFA_AUTO_TILE", 0) == 1


def count_ffa_work(
    qr: np.ndarray,
    kr: np.ndarray,
    d_lo: np.ndarray,
    d_hi: np.ndarray,
    sq: int,
    sk: int,
    bq: int,
    bk: int,
) -> int:
    """Exact work-item count of :func:`ffa_plan.build_ffa_plan` for this
    tiling WITHOUT building (or LRU-caching) the plan arrays — candidate
    scoring must not evict live plans from the shared plan cache.

    One work item per (slice, q_tile, k_tile) whose diagonal band
    intersects the clipped tile rect (per q tile the intersecting k tiles
    form one contiguous run, so that part is closed-form per (slice,
    q_tile)) — plus the builder's one dummy item for every q tile whose
    bucket stays empty (those tiles still need a grid step to write their
    zeros/-inf outputs). Parity with the builder is pinned by test.
    """
    total = 0
    num_q_tiles = max(1, -(-sq // bq))
    num_k_tiles = max(1, -(-sk // bk))
    covered = np.zeros(num_q_tiles, dtype=bool)
    for s in range(len(qr)):
        qs, qe = int(qr[s, 0]), int(qr[s, 1])
        ks, ke = int(kr[s, 0]), int(kr[s, 1])
        lo, hi = int(d_lo[s]), int(d_hi[s])
        if qs >= qe or ks >= ke or lo > hi:
            continue
        t = np.arange(qs // bq, (qe - 1) // bq + 1, dtype=np.int64)
        i0 = np.maximum(qs, t * bq)  # clipped row span per q tile
        i1 = np.minimum(qe, (t + 1) * bq)
        # attended column window of the clipped rows, clipped to [ks, ke)
        j0 = np.maximum(ks, i0 + lo)
        j1 = np.minimum(ke - 1, (i1 - 1) + hi)
        nonempty = j0 <= j1  # empty window ⟺ band misses the clipped rect
        kt0 = np.clip(j0 // bk, 0, num_k_tiles - 1)
        kt1 = np.clip(j1 // bk, 0, num_k_tiles - 1)
        total += int(np.sum((kt1 - kt0 + 1)[nonempty]))
        covered[t[nonempty]] = True
    return total + int(num_q_tiles - covered.sum())


def count_ffa_work_t(
    qr: np.ndarray,
    kr: np.ndarray,
    d_lo: np.ndarray,
    d_hi: np.ndarray,
    sq: int,
    sk: int,
    bq: int,
    bk: int,
) -> int:
    """Exact K-MAJOR work-item count (the dkv pass's grid length) for this
    tiling, mirroring :func:`count_ffa_work`'s closed form with the roles
    of q and k swapped: one item per (slice, k_tile, q_tile) whose band
    intersects the clipped tile rect (per k tile the attended row span is
    one interval, so the intersecting q tiles form a contiguous run), plus
    the builder's one dummy item per never-covered k tile (those still
    need a grid step to write their zero dk/dv). Parity with the builder's
    ``num_work_t`` is pinned by test.
    """
    total = 0
    num_q_tiles = max(1, -(-sq // bq))
    num_k_tiles = max(1, -(-sk // bk))
    covered = np.zeros(num_k_tiles, dtype=bool)
    for s in range(len(qr)):
        qs, qe = int(qr[s, 0]), int(qr[s, 1])
        ks, ke = int(kr[s, 0]), int(kr[s, 1])
        lo, hi = int(d_lo[s]), int(d_hi[s])
        if qs >= qe or ks >= ke or lo > hi:
            continue
        t = np.arange(ks // bk, (ke - 1) // bk + 1, dtype=np.int64)
        j0 = np.maximum(ks, t * bk)  # clipped col span per k tile
        j1 = np.minimum(ke, (t + 1) * bk)
        # attended row window of the clipped cols (lo <= j - i <= hi  ⟺
        # j - hi <= i <= j - lo), clipped to [qs, qe)
        i0 = np.maximum(qs, j0 - hi)
        i1 = np.minimum(qe - 1, (j1 - 1) - lo)
        nonempty = i0 <= i1
        qt0 = np.clip(i0 // bq, 0, num_q_tiles - 1)
        qt1 = np.clip(i1 // bq, 0, num_q_tiles - 1)
        total += int(np.sum((qt1 - qt0 + 1)[nonempty]))
        covered[t[nonempty]] = True
    return total + int(num_k_tiles - covered.sum())


def _vmem_bytes(bq: int, bk: int, d: int, dv: int, itemsize: int) -> int:
    """Per-step fwd-kernel VMEM residency — ONE estimator for the whole
    package (utils/mem_budget.ffa_vmem_budget)."""
    from ..utils.mem_budget import ffa_vmem_budget

    return ffa_vmem_budget(bq, bk, d, head_dim_v=dv, dtype_bytes=itemsize)


def choose_blocks_multi(
    rank_geoms: list[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]],
    sq: int,
    sk: int,
    d: int = 128,
    dv: int = 128,
    itemsize: int = 2,
) -> tuple[int, int]:
    """Pick (block_q, block_k) minimizing modeled kernel time over a group
    of per-rank slice sets that share one padded grid (the CP runtime
    stacks per-rank plans padded to the max work count, so every rank runs
    max-W grid steps): score = max_rank(W) * (bq*bk + OVERHEAD_ELEMS),
    VMEM-guarded. Falls back to the clamped default if every candidate is
    excluded."""
    maybe_inject("vmem_check")
    seen: set[tuple[int, int]] = set()
    best = None
    best_score = None
    for bq, bk in CANDIDATES:
        # clamp to the problem (same rule as default_blocks), then dedupe
        bq = min(bq, _round_up(sq, 16))
        bk = min(bk, _round_up(sk, NUM_LANES))
        if (bq, bk) in seen:
            continue
        seen.add((bq, bk))
        if _vmem_bytes(bq, bk, d, dv, itemsize) > VMEM_BUDGET:
            continue
        w = max(
            count_ffa_work(qr, kr, lo, hi, sq, sk, bq, bk)
            for qr, kr, lo, hi in rank_geoms
        )
        score = w * (bq * bk + OVERHEAD_ELEMS)
        if best_score is None or score < best_score:
            best, best_score = (bq, bk), score
    chosen = best or (
        min(256, _round_up(sq, 16)), min(512, _round_up(sk, NUM_LANES))
    )
    if telemetry.enabled():
        telemetry.record_event(
            "tile_policy",
            mode="fwd_only",
            sq=sq, sk=sk, d=d, dv=dv, itemsize=itemsize,
            num_geoms=len(rank_geoms),
            candidates_scored=len(seen),
            fwd_blocks=list(chosen),
            fallback=best is None,
        )
    return chosen


def choose_blocks(
    qr: np.ndarray,
    kr: np.ndarray,
    d_lo: np.ndarray,
    d_hi: np.ndarray,
    sq: int,
    sk: int,
    d: int,
    dv: int,
    itemsize: int = 2,
) -> tuple[int, int]:
    """Single-slice-set entry of :func:`choose_blocks_multi`."""
    return choose_blocks_multi(
        [(qr, kr, d_lo, d_hi)], sq, sk, d, dv, itemsize
    )


def _bwd_vmem_bytes(
    kind: str, bq: int, bk: int, d: int, dv: int, itemsize: int
) -> int:
    """Per-step VMEM residency of the bwd kernels — ONE estimator for the
    whole package (utils/mem_budget.ffa_bwd_vmem_budget), shared with the
    static kernel checker (analysis/kernel_check K1) and verifier R5."""
    from ..utils.mem_budget import ffa_bwd_vmem_budget

    return ffa_bwd_vmem_budget(kind, bq, bk, d, head_dim_v=dv, dtype_bytes=itemsize)


def _band_candidates(
    rank_geoms: list, sq: int, sk: int
) -> tuple[tuple[int, int], ...]:
    """CANDIDATES extended with a block_k derived from the narrowest
    band in the slice set: thin bands (sliding window, varlen tails)
    waste padded MXU columns in any k tile wider than the band, so the
    band width itself (rounded up to the lane quantum) is always worth
    scoring alongside the fixed grid."""
    widths = []
    for qr, kr, lo, hi in rank_geoms:
        for s in range(len(qr)):
            if qr[s, 0] >= qr[s, 1] or kr[s, 0] >= kr[s, 1]:
                continue
            band = int(hi[s]) - int(lo[s]) + 1
            rect = int(kr[s, 1]) - int(kr[s, 0])
            widths.append(min(max(band, 0), rect))
    if not widths:
        return CANDIDATES
    bk_band = min(max(_round_up(min(widths), NUM_LANES), NUM_LANES), 1024)
    extra = tuple((bq, bk_band) for bq in (128, 256, 512))
    return CANDIDATES + extra


def choose_blocks_per_pass_multi(
    rank_geoms: list[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]],
    sq: int,
    sk: int,
    d: int = 128,
    dv: int = 128,
    itemsize: int = 2,
) -> tuple[
    tuple[int, int], tuple[int, int] | None, tuple[int, int] | None
]:
    """Per-PASS tile choice: ``(fwd_blocks, dq_blocks, dkv_blocks)``.

    The three passes score differently over the same slice set: fwd and
    dq run the q-major plan, dkv the k-major plan (its work count — and
    so its padded-area profile — differs whenever bands are thin or
    ragged), and each pass has its own VMEM residency (the dkv kernel
    holds (bk, d+dv) fp32 scratch). A bwd entry is None when the fwd
    choice is already optimal for that pass (inherit — the plan tuple
    stays at 6 arrays). Bwd candidates are constrained to divide the
    fwd-padded geometry, the same gate :func:`ffa.resolve_bwd_overrides`
    applies to env overrides.
    """
    maybe_inject("vmem_check")
    cands = _band_candidates(rank_geoms, sq, sk)

    def score_pass(kind: str, allowed=None):
        seen: set[tuple[int, int]] = set()
        best = None
        best_score = None
        counter = count_ffa_work_t if kind == "dkv" else count_ffa_work
        for bq, bk in cands:
            bq = min(bq, _round_up(sq, 16))
            bk = min(bk, _round_up(sk, NUM_LANES))
            if (bq, bk) in seen:
                continue
            seen.add((bq, bk))
            if allowed is not None and not allowed(bq, bk):
                continue
            if kind == "fwd":
                vmem = _vmem_bytes(bq, bk, d, dv, itemsize)
            else:
                vmem = _bwd_vmem_bytes(kind, bq, bk, d, dv, itemsize)
            if vmem > VMEM_BUDGET:
                continue
            w = max(
                counter(qr, kr, lo, hi, sq, sk, bq, bk)
                for qr, kr, lo, hi in rank_geoms
            )
            score = w * (bq * bk + OVERHEAD_ELEMS)
            if best_score is None or score < best_score:
                best, best_score = (bq, bk), score
        return best

    fwd = score_pass("fwd") or (
        min(256, _round_up(sq, 16)), min(512, _round_up(sk, NUM_LANES))
    )
    sqp = _round_up(sq, fwd[0])
    skp = _round_up(sk, fwd[1])

    def divides(bq: int, bk: int) -> bool:
        return sqp % bq == 0 and skp % bk == 0

    dq = score_pass("dq", allowed=divides)
    dkv = score_pass("dkv", allowed=divides)
    if dq == fwd:
        dq = None
    if dkv == fwd:
        dkv = None
    if telemetry.enabled():
        telemetry.record_event(
            "tile_policy",
            mode="per_pass",
            sq=sq, sk=sk, d=d, dv=dv, itemsize=itemsize,
            num_geoms=len(rank_geoms),
            candidates_scored=len(cands),
            fwd_blocks=list(fwd),
            # None = inherit fwd (the plan tuple stays at 6 arrays)
            dq_blocks=list(dq) if dq else None,
            dkv_blocks=list(dkv) if dkv else None,
        )
    return fwd, dq, dkv


def choose_blocks_per_pass(
    qr: np.ndarray,
    kr: np.ndarray,
    d_lo: np.ndarray,
    d_hi: np.ndarray,
    sq: int,
    sk: int,
    d: int,
    dv: int,
    itemsize: int = 2,
) -> tuple[
    tuple[int, int], tuple[int, int] | None, tuple[int, int] | None
]:
    """Single-slice-set entry of :func:`choose_blocks_per_pass_multi`."""
    return choose_blocks_per_pass_multi(
        [(qr, kr, d_lo, d_hi)], sq, sk, d, dv, itemsize
    )


def reachable_block_space(
    sq: int,
    sk: int,
    kind: str = "fwd",
    d: int = 128,
    dv: int = 128,
    itemsize: int = 2,
) -> list[tuple[int, int]]:
    """Every ``(block_q, block_k)`` this policy can emit for a pass of the
    given ``kind`` ("fwd" | "dq" | "dkv") at problem size (sq, sk) —
    the closure the static kernel checker (analysis/kernel_check) proves
    K1/K3 over, so a tiling the policy can choose is by construction a
    tiling the checker has audited.

    The space is the union of:

    - the clamped default (``ffa.default_blocks`` fallback, also the
      score-loop fallback when every candidate busts VMEM),
    - every VMEM-feasible clamped :data:`CANDIDATES` entry,
    - the band-derived grid ``{128, 256, 512} x {128, 256, ..., 1024}``
      (:func:`_band_candidates` emits ``bk_band`` = the narrowest band
      width rounded to the lane quantum and clamped to [128, 1024] —
      data-dependent, so the whole reachable range is enumerated).

    Env overrides (MAGI_ATTENTION_FFA_BLOCK_*) are intentionally NOT
    bounded here: they pass through ``resolve_bwd_overrides``'s
    divisibility/quantum gate and the kernels' own VMEM dispatch guards,
    and the audit CLI checks the documented defaults explicitly.
    """
    if kind not in ("fwd", "dq", "dkv"):
        raise ValueError(f"kind must be 'fwd'|'dq'|'dkv', got {kind!r}")
    cands = set(CANDIDATES)
    cands.update(
        (bq, bk_band)
        for bq in (128, 256, 512)
        for bk_band in range(NUM_LANES, 1024 + 1, NUM_LANES)
    )
    space: set[tuple[int, int]] = set()
    for bq, bk in cands:
        bq = min(bq, _round_up(sq, 16))
        bk = min(bk, _round_up(sk, NUM_LANES))
        if kind == "fwd":
            vmem = _vmem_bytes(bq, bk, d, dv, itemsize)
        else:
            vmem = _bwd_vmem_bytes(kind, bq, bk, d, dv, itemsize)
        if vmem > VMEM_BUDGET:
            continue
        space.add((bq, bk))
    # the clamped default is reachable regardless of the VMEM filter
    # (score-loop fallback + ffa.default_blocks)
    space.add(
        (min(256, _round_up(sq, 16)), min(512, _round_up(sk, NUM_LANES)))
    )
    return sorted(space)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# block_q follows the GQA group: a packed grid step carries group x block_q
# rows, and the compiler takes at most ffa.Q_MAJOR_PACK_MAX_ROWS of them
# ---------------------------------------------------------------------------

# what the default block_q is divided by where the group is too large for it
GROUP_TILE_DIVISORS = (2, 4)


def group_block_q(
    group: int,
    d: int,
    dv: int,
    itemsize: int,
    block_q: int,
    block_k: int,
    max_work,
    emit_max_logits: bool = False,
) -> tuple[int, str]:
    """``(block_q, source)`` of a call of ``group`` q heads a kv head whose
    default tile is ``block_q`` x ``block_k``: the rule that lets a pass buy
    its rows a grid step by packing (PERF.md §6, PR 25) at every group, not
    only where ``group x 256 <= 1024``.

    It engages only where the ROW bound refuses the default tile (g >= 8 at
    256 rows) and then takes the largest of ``block_q / 2, block_q / 4``
    that the bound admits — 128 at g = 8, 64 at g = 16: a packed step of
    1024 rows, the products and residency of g = 4 at 256 — if EVERY body
    packs there (``ffa.gqa_pack_admitted``: forward, dq, dkv and one-pass; a
    pass left plain would run fewer rows a step than it does today, and the
    plain dq body does not lower at 64 rows) and the plan's table fits:
    ``max_work(block_q, block_k)``, the largest work count of any plan the
    caller would build at that tile, within ``ffa.PLAN_TABLE_MAX_WORK`` (a
    smaller ``block_q`` is more work items, a 512-byte row of SMEM each).
    ``source`` says what happened: ``"shape_rule"`` the tile moved;
    ``"table_guard"`` it would have and the table does not fit, so the
    default stays; ``"default"`` the rule had nothing to say — the default
    tile packs or the group is 1, no tile of the set packs (g = 32), a pack
    flag is off or the call wants max-logits (the packed forward emits
    none), or the BYTE budget refuses a body (at the default, d =
    256: nothing has measured a smaller tile there; at the candidate: d =
    256 again, a wide head in float32)."""
    from . import ffa

    if (
        group <= 1 or emit_max_logits
        or group * block_q <= ffa.Q_MAJOR_PACK_MAX_ROWS
    ):
        return block_q, "default"
    candidate = next(
        (block_q // div for div in GROUP_TILE_DIVISORS
         if block_q % (16 * div) == 0
         and group * (block_q // div) <= ffa.Q_MAJOR_PACK_MAX_ROWS),
        None)
    if candidate is None or not all(
        ffa.gqa_pack_admitted(
            kind, group, candidate, block_k, d, dv, itemsize)
        for kind in ("fwd", "dq", "dkv", "fused")
    ):
        return block_q, "default"
    num_work = max_work(candidate, block_k)
    fits = num_work <= ffa.PLAN_TABLE_MAX_WORK
    if telemetry.enabled():
        telemetry.record_event(
            "tile_policy",
            mode="group",
            group=group, d=d, dv=dv, itemsize=itemsize,
            default_blocks=[block_q, block_k],
            candidate_blocks=[candidate, block_k],
            num_work=num_work, table_capacity=ffa.PLAN_TABLE_MAX_WORK,
            fwd_blocks=[candidate if fits else block_q, block_k],
        )
    return (candidate, "shape_rule") if fits else (block_q, "table_guard")


def max_ffa_work(
    qr: np.ndarray,
    kr: np.ndarray,
    d_lo: np.ndarray,
    d_hi: np.ndarray,
    sq: int,
    sk: int,
    bq: int,
    bk: int,
) -> int:
    """The longer of a plan's two work lists (q-major and k-major) at a
    tile: what its largest table holds."""
    geom = (qr, kr, d_lo, d_hi, sq, sk, bq, bk)
    return max(count_ffa_work(*geom), count_ffa_work_t(*geom))


# ---------------------------------------------------------------------------
# Mixed-granularity dispatch: per-slice fragmentation + two-pass plan split.
#
# A single (block_q, block_k) choice is a compromise: dense slices amortize
# per-step overhead best under big tiles, while fragmented slices (block-
# sparse, video windows) waste most of each big tile on padding. When the
# gap is large enough, splitting the slice set into a coarse-block dense
# pass and a fine-block fragmented pass — merged through the standard LSE
# merge — beats any single tiling. The split is judged by the same exact
# work counters the tile scorer uses, so the decision cannot drift from
# what the plans actually cost.
# ---------------------------------------------------------------------------

# a slice is "fragmented" when its tile cover runs >= 2x its band area
FRAG_THRESHOLD = 2.0
# LSE-merge overhead in score-element equivalents: one extra read+combine
# pass over out/lse rows (VPU) plus the second pass's outputs round-tripping
# HBM — charged per merged q row at lane granularity
MERGE_OVERHEAD_PER_ROW = 2 * NUM_LANES


def slice_cover_tiles(
    qr: np.ndarray,
    kr: np.ndarray,
    d_lo: np.ndarray,
    d_hi: np.ndarray,
    block_q: int,
    block_k: int,
) -> np.ndarray:
    """Per-slice count of (q_tile, k_tile) pairs the slice's band touches.

    Per q tile of a slice the intersecting k tiles form one contiguous run
    (the band's column window of the clipped rows is a single interval),
    so the cover is closed-form per (slice, q_tile) — same counting core
    as :func:`count_ffa_work`, kept per-slice instead of summed, and
    without the one-dummy-per-empty-q-tile floor the grid needs.
    """
    n = len(qr)
    tiles = np.zeros(n, dtype=np.int64)
    for s in range(n):
        qs, qe = int(qr[s, 0]), int(qr[s, 1])
        ks, ke = int(kr[s, 0]), int(kr[s, 1])
        lo, hi = int(d_lo[s]), int(d_hi[s])
        if qs >= qe or ks >= ke or lo > hi:
            continue
        t = np.arange(qs // block_q, (qe - 1) // block_q + 1, dtype=np.int64)
        i0 = np.maximum(qs, t * block_q)
        i1 = np.minimum(qe, (t + 1) * block_q)
        j0 = np.maximum(ks, i0 + lo)
        j1 = np.minimum(ke - 1, (i1 - 1) + hi)
        nonempty = j0 <= j1
        tiles[s] = int(np.sum((j1 // block_k - j0 // block_k + 1)[nonempty]))
    return tiles


def slice_cover_ratios(
    qr: np.ndarray,
    kr: np.ndarray,
    d_lo: np.ndarray,
    d_hi: np.ndarray,
    block_q: int,
    block_k: int,
) -> np.ndarray:
    """Per-slice fragmentation ratio: padded tile-cover elements / band
    elements under this tiling. 1.0 = the tiles fit the band exactly;
    large values flag slices whose tiles are mostly padding. Empty or
    degenerate slices get ratio 1.0 (nothing to rescue).
    """
    from .. import telemetry as _telemetry

    n = len(qr)
    tiles = slice_cover_tiles(qr, kr, d_lo, d_hi, block_q, block_k)
    ratios = np.ones(n, dtype=np.float64)
    for s in range(n):
        if tiles[s] <= 0:
            continue
        band = _telemetry.band_area(
            qr[s : s + 1], kr[s : s + 1], d_lo[s : s + 1], d_hi[s : s + 1]
        )
        if band <= 0:
            continue
        ratios[s] = int(tiles[s]) * block_q * block_k / band
    return ratios


@dataclass(frozen=True)
class MixedDispatch:
    """A profitable two-pass split of one slice set."""

    dense_idx: np.ndarray  # slice indices for the coarse-block pass
    frag_idx: np.ndarray  # slice indices for the fine-block pass
    coarse_blocks: tuple[int, int]
    fine_blocks: tuple[int, int]
    single_score: int  # modeled cost of coarse blocks over ALL slices
    split_score: int  # modeled cost of the split incl. merge overhead


def choose_mixed_dispatch(
    qr: np.ndarray,
    kr: np.ndarray,
    d_lo: np.ndarray,
    d_hi: np.ndarray,
    sq: int,
    sk: int,
    d: int = 128,
    dv: int = 128,
    itemsize: int = 2,
    coarse_blocks: tuple[int, int] | None = None,
) -> MixedDispatch | None:
    """Decide whether to split the slice set into a coarse-block dense pass
    plus a fine-block fragmented pass (merged via LSE merge), or run one
    plan as usual (None).

    Selection flows through the backend registry's ``ffa_dispatch``
    decision (kernels/registry.py): a 'single'/'mixed' pin
    (MAGI_ATTENTION_BACKEND_MIXED_BLOCKS) wins — 'mixed' still degrades to
    None when the mask yields no non-trivial partition with distinct
    tilings; unpinned geometries take the cost model: split wins when
    score(coarse on dense) + score(fine on fragmented) + merge overhead <
    score(coarse on everything), with score the same padded-work +
    per-step-overhead model the tile scorer minimizes.
    """
    from ..env import backend as env_backend
    from . import registry as _registry

    pin = env_backend.mixed_blocks_pin()
    if pin == "single" or len(qr) < 2:
        return None
    coarse = coarse_blocks or (
        min(256, _round_up(sq, 16)), min(512, _round_up(sk, NUM_LANES))
    )
    ratios = slice_cover_ratios(qr, kr, d_lo, d_hi, coarse[0], coarse[1])
    frag = ratios >= FRAG_THRESHOLD
    frag_idx = np.nonzero(frag)[0]
    dense_idx = np.nonzero(~frag)[0]
    if len(frag_idx) == 0 or len(dense_idx) == 0:
        return None
    fi = frag_idx
    fine = choose_blocks(
        qr[fi], kr[fi], d_lo[fi], d_hi[fi], sq, sk, d, dv, itemsize
    )
    if fine == coarse:
        return None

    def score(idx: np.ndarray, blocks: tuple[int, int]) -> int:
        # grid steps (incl. one dummy per empty q tile) pay fixed overhead;
        # only band-touching tiles pay compute — with extent clamping on,
        # dummy items skip their dots entirely, so charging them a full
        # bq*bk tile would bias auto mode against fine-block passes
        w = count_ffa_work(
            qr[idx], kr[idx], d_lo[idx], d_hi[idx],
            sq, sk, blocks[0], blocks[1],
        )
        tiles = int(
            slice_cover_tiles(
                qr[idx], kr[idx], d_lo[idx], d_hi[idx], blocks[0], blocks[1]
            ).sum()
        )
        return tiles * blocks[0] * blocks[1] + w * OVERHEAD_ELEMS

    all_idx = np.arange(len(qr))
    single = score(all_idx, coarse)
    split = (
        score(dense_idx, coarse)
        + score(frag_idx, fine)
        + sq * MERGE_OVERHEAD_PER_ROW
    )
    profitable = split < single
    if pin == "mixed":
        choice = "mixed"
    else:
        key = _mixed_dispatch_key(
            qr, kr, d_lo, d_hi, sq, sk, d, dv, itemsize, coarse
        )
        choice = _registry.resolve(
            "ffa_dispatch", key,
            lambda: "mixed" if profitable else "single",
        ).name
    if choice != "mixed":
        return None
    result = MixedDispatch(
        dense_idx=dense_idx,
        frag_idx=frag_idx,
        coarse_blocks=coarse,
        fine_blocks=fine,
        single_score=single,
        split_score=split,
    )
    if telemetry.enabled():
        telemetry.record_event(
            "mixed_dispatch",
            num_slices=len(qr),
            num_dense=len(dense_idx),
            num_frag=len(frag_idx),
            coarse_blocks=list(coarse),
            fine_blocks=list(fine),
            single_score=single,
            split_score=split,
            forced=not profitable,
        )
    return result


def _mixed_dispatch_key(
    qr, kr, d_lo, d_hi, sq, sk, d, dv, itemsize, coarse
) -> tuple:
    """Registry/store key of one mixed-dispatch decision: a digest of the
    slice geometry (the mask-class signature) plus the static dims the
    cost model consumes."""
    import hashlib

    h = hashlib.md5()
    for arr in (qr, kr, d_lo, d_hi):
        h.update(np.ascontiguousarray(arr).tobytes())
    return (h.hexdigest()[:16], sq, sk, d, dv, itemsize, coarse[0], coarse[1])


# ---------------------------------------------------------------------------
# Backward execution mode: fused one-pass vs split dq + dkv.
#
# Per work item the split backward spends 7 tile matmuls (dq pass: s, dp,
# dq; dkv pass: s_t, dp_t, dk, dv) where the fused kernel spends 5 (s_t,
# dp_t, dk, dv, dq) — the FlashAttention-2 work-partitioning count — at
# the price of moving the dq window in and out every k-major step. On the
# chip a grid step costs a fixed part plus the LARGER of its matmuls and
# its DMA (the pipeline moves the next step's blocks under this step's
# matmuls), so the chooser compares the two modes' grid steps in
# microseconds, from the STATIC plan counts (work items, blocks, dims,
# group): the decision is trace-time stable.
# ---------------------------------------------------------------------------

# tile matmuls per work item (asserted 7 -> 5 by unit test)
BWD_TILE_MATMULS_SPLIT_DQ = 3  # s, dp, dq
BWD_TILE_MATMULS_SPLIT_DKV = 4  # s_t, dp_t, dk, dv
BWD_TILE_MATMULS_SPLIT = BWD_TILE_MATMULS_SPLIT_DQ + BWD_TILE_MATMULS_SPLIT_DKV
BWD_TILE_MATMULS_FUSED = 5  # s_t, dp_t, dk, dv, dq
# Readings of a TPU v5e, bf16, d 128, GQA-packed steps of 1024 rows x 512
# keys (one tile matmul of 256 x 512 x 128 is 16.8 MMAC):
# a grid step's fixed part — the dq body's 0.35 + 0.82 us per 256 rows
# (PERF.md §6; my chip runs, PR 25)
BWD_STEP_FIXED_US = 0.35
# the q-major dq body: 0.82 us per 3 tile matmuls (same reading)
BWD_QMAJOR_US_PER_GMAC = 16.3
# the k-major bodies: dkv 3.60 us per 16 tile matmuls (PR 25), the
# one-pass body 4.49 us per 20 (37.96 ms a layer over 8 x 1056 steps,
# nemo12b.longdoc.cp1; my chip run, PR 30) — 82% of the MXU's 98.5 TMAC/s
BWD_KMAJOR_US_PER_GMAC = 12.3
# HBM at its published 819 GB/s (cellbench/peaks.py names the source)
HBM_US_PER_MB = 1.0 / 0.819


# a backward body's tile matmuls a work item, and what a GMAC costs it
_BWD_BODIES = {
    "dq": (BWD_TILE_MATMULS_SPLIT_DQ, BWD_QMAJOR_US_PER_GMAC),
    "dkv": (BWD_TILE_MATMULS_SPLIT_DKV, BWD_KMAJOR_US_PER_GMAC),
    "fused": (BWD_TILE_MATMULS_FUSED, BWD_KMAJOR_US_PER_GMAC),
}


def bwd_step_macs(kind: str, bq: int, bk: int, d: int, group: int = 1) -> int:
    """MXU MACs of one work item of a backward body ("dq" | "dkv" |
    "fused"): its tile matmuls x the (g x bq, bk, d) volume — a work item
    covers the whole query group, in one packed step or in g plain ones."""
    return _BWD_BODIES[kind][0] * group * bq * bk * d


def bwd_step_bytes(
    kind: str, bq: int, bk: int, d: int, dv: int, itemsize: int = 2,
    group: int = 1,
) -> int:
    """HBM bytes ONE grid step of a backward body moves — the blocks whose
    index changes with the step; what stays for a run (the q-major body's
    q, dO and dq, the k-major bodies' k, v, dk and dv) is not counted.
    ``kind``: "dq" (k and v stream past the resident q rows), "dkv" (q, dO,
    lse and delta stream past the resident k tile), "fused" (dkv's, plus
    the fp32 dq window written back and fetched again)."""
    rows = group * bq
    if kind == "dq":
        return bk * (d + dv) * itemsize
    streamed = rows * (d + dv) * itemsize + 2 * rows * 4
    if kind == "dkv":
        return streamed
    return streamed + 2 * rows * d * 4


def bwd_step_us(
    kind: str, bq: int, bk: int, d: int, dv: int, itemsize: int = 2,
    group: int = 1,
) -> float:
    """Modeled microseconds of one grid step of a backward body on the
    chip: the fixed part plus the larger of its matmuls' time and its
    DMA's (the step's blocks move under the neighbouring step's
    matmuls)."""
    mxu = bwd_step_macs(kind, bq, bk, d, group) * (
        _BWD_BODIES[kind][1] * 1e-9)
    dma = bwd_step_bytes(kind, bq, bk, d, dv, itemsize, group) * (
        HBM_US_PER_MB * 1e-6)
    return BWD_STEP_FIXED_US + max(mxu, dma)


def choose_bwd_mode(
    w_dq: int,
    bq_dq: int,
    bk_dq: int,
    wt: int,
    bq_dkv: int,
    bk_dkv: int,
    d: int,
    dv: int,
    itemsize: int = 2,
    group: int = 1,
) -> str:
    """"fused" or "split" by the modeled time of the two modes' grid steps
    (:func:`bwd_step_us`), a kv head.

    Fused wins wherever the two lists are comparably sized and a step is
    bound by its matmuls — every cell of the benchmark, by 31 to 39% of
    the pair's time on the chip (PERF.md §6, PR 30). Split wins when the
    dq window's traffic binds the one-pass step (thin k tiles under many
    packed rows, wide fp32 heads), or when the q-major dq plan is much
    cheaper than the k-major plan — a mask whose k-major tiling fragments
    far worse than its q-major one. Feasibility (VMEM, plan meta columns,
    the plan's revisit distance) is the caller's job
    (kernels/ffa.ffa_bwd_mode)."""
    hbm = (d, dv, itemsize, group)
    split_us = w_dq * bwd_step_us("dq", bq_dq, bk_dq, *hbm) + (
        wt * bwd_step_us("dkv", bq_dkv, bk_dkv, *hbm))
    fused_us = wt * bwd_step_us("fused", bq_dkv, bk_dkv, *hbm)
    return "fused" if fused_us <= split_us else "split"


# ---------------------------------------------------------------------------
# the grouped matmul's tiles (kernels/grouped_matmul.py): rules over static
# shapes, as the FFA tiles are
# ---------------------------------------------------------------------------

GROUPED_ROW_TILES = (512, 256, 128, 64, 32, 16)
# how many row tiles a group of the expected size should fill: a group's
# first and last tile are shared with its neighbours (boundaries fall
# anywhere), so with r tiles a group the MXU is given (r + 1) / r times the
# live rows
GROUPED_TILES_PER_GROUP = 3
# VMEM one double-buffered weight block of the product body, and the dW
# body's accumulator with its double-buffered output block, may take: half
# of what a kernel is allowed, the rest is the row tiles, the result and
# Mosaic's own
GROUPED_BLOCK_BUDGET = 7 * 1024 * 1024


def grouped_row_tile(rows_per_group: int) -> int:
    """Row tile of the grouped matmul for groups expected to hold
    ``rows_per_group`` rows (an expert layer: ``tokens x top_k / n_experts``,
    known at trace time): the largest of ``GROUPED_ROW_TILES`` that still
    gives such a group ``GROUPED_TILES_PER_GROUP`` tiles. A larger tile
    spends its MXU passes on rows masked away at the groups' boundaries; a
    smaller one pays the grid step's fixed cost more often."""
    for tile in GROUPED_ROW_TILES:
        if tile * GROUPED_TILES_PER_GROUP <= rows_per_group:
            return tile
    return GROUPED_ROW_TILES[-1]


# how far above the rows a block of tokens EXPECTS for the experts held its
# row buffer reaches: a block whose live rows pass it runs at the worst case
# (models/moe.py), so the margin buys how rarely that happens with the size
# of every pass over the buffer
GROUPED_ROW_MARGIN = 1.5


def grouped_row_capacity(
    expected_rows: float, worst_rows: int, tile_rows: int
) -> int:
    """Rows of the buffer a block of tokens sorts its (token, choice) pairs
    into: ``GROUPED_ROW_MARGIN`` times the rows it expects for the experts
    held (``tokens x top_k x held / n_experts``), rounded up to whole row
    tiles, never above the worst case ``tokens x top_k``. A chip that holds
    every expert expects the worst case and gets it."""
    rows = _round_up(
        int(np.ceil(expected_rows * GROUPED_ROW_MARGIN)), tile_rows)
    return min(rows, worst_rows)


def grouped_weight_k_minor(k: int, n: int) -> bool:
    """Whether the bodies are given each group's weight ``[K, N]`` as its
    transpose ``[N, K]``: where ``N`` is no multiple of the lanes and ``K``
    is (an expert's up projection, 2688 x 1856). A ``[G, K, N]`` array with
    such an ``N`` minor is padded to the next 128 in HBM and its column
    blocks end off the lane grid; XLA itself keeps such a parameter
    ``K``-minor, so asking for it that way also saves the relayout."""
    return n % NUM_LANES != 0 and k % NUM_LANES == 0


def _even_col_tile(n: int, widest: int) -> int:
    """``n`` columns in the fewest lane-aligned blocks of at most ``widest``,
    evened out (1856 under 682 -> 3 blocks of 640, not 512 x 3 + 320); all
    of ``n`` where it fits (a block as wide as the array needs no
    alignment)."""
    if n <= widest:
        return n
    blocks = _round_up(n, NUM_LANES) // NUM_LANES
    widest = max(widest // NUM_LANES, 1)
    return NUM_LANES * -(-blocks // -(-blocks // widest))


def grouped_col_tile(k: int, n: int, itemsize: int) -> int:
    """Columns of a group's weight the product body holds at a time: all of
    ``K`` deep (one contraction), double-buffered within
    ``GROUPED_BLOCK_BUDGET``."""
    return _even_col_tile(n, GROUPED_BLOCK_BUDGET // (2 * k * itemsize))


def grouped_dw_tiles(k: int, n: int, itemsize: int) -> tuple[int, int]:
    """``(tk, tn)`` of the dW body's block of ``dW[g]``: the float32
    accumulator and the double-buffered output block within
    ``GROUPED_BLOCK_BUDGET``, and of the lane-aligned even splits that fit,
    the one that reads least (the rows once a column block, ``dy`` once a
    row block of ``K``)."""
    most = GROUPED_BLOCK_BUDGET // (4 + 2 * itemsize)

    def splits(x: int) -> set[int]:
        lanes = _round_up(x, NUM_LANES) // NUM_LANES
        return {x} | {
            NUM_LANES * -(-lanes // parts) for parts in range(2, lanes + 1)}

    return min(
        ((-(-n // tn) * k + -(-k // tk) * n, -tk * tn), (tk, tn))
        for tk in splits(k) for tn in splits(n)
        if tk * tn <= most or (tk, tn) == (NUM_LANES, NUM_LANES))[1]
