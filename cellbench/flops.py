"""Arithmetic on a mask: areas, and the operations and bytes of an
attention call over it. What depends on a block's equations (which layers
attend, how many weights a layer multiplies by) is the family file's:
``required_flops_per_step(cfg, spec)`` and ``ffa_calls(cfg)`` of
``family_<family>.py``.

Two conventions, always named:

* **required** (model FLOPs): what forward and backward need, recomputation
  not counted. A matmul with ``p`` weights costs ``2 p`` per token forward
  and ``4 p`` backward (:func:`matmul_flops`); attention forward is ``2 *
  area * q_heads * (d_qk + d_v)`` (QK^T and PV over the ``area`` unmasked
  pairs; ``4 * area * head_dim * q_heads`` where the two are equal) and its
  backward 2.5 times that where they are (five matmuls against two, three
  of them over ``d_qk``). This is the reference's convention and
  ``BASELINE.md``'s.
* **executed by the FFA calls**: under ``remat`` the forward runs twice, so
  a layer makes three calls a step (forward, re-forward, backward) worth
  ``(1 + 1 + 2.5) = 4.5`` forwards. Only the kernel's roofline uses it.

Areas are exact counts of unmasked (query, key) pairs, closed form per
document; ``mask_array`` is the brute-force twin the tests compare with.
"""

from __future__ import annotations

import numpy as np

from .traffic_gen import MaskSpec

ATTN_BWD_OVER_FWD = 2.5


def rows_area(spec: MaskSpec) -> np.ndarray:
    """Keys each query row attends to, ``(tokens,)`` int64."""
    out = np.empty(spec.tokens, dtype=np.int64)
    for start, n in zip(spec.cu_seqlens[:-1], spec.doc_lengths()):
        seen = np.arange(1, n + 1, dtype=np.int64)
        if spec.window is not None:
            seen = np.minimum(seen, spec.window)
        out[start:start + n] = seen
    return out


def band_area(spec: MaskSpec) -> int:
    """Unmasked pairs of the whole mask, closed form."""
    total = 0
    for n in spec.doc_lengths().tolist():
        w = n if spec.window is None else min(n, spec.window)
        total += w * (w + 1) // 2 + (n - w) * w
    return total


def mask_array(spec: MaskSpec) -> np.ndarray:
    """The mask as a boolean ``(tokens, tokens)`` array, row = query."""
    mask = np.zeros((spec.tokens, spec.tokens), dtype=bool)
    for start, n in zip(spec.cu_seqlens[:-1], spec.doc_lengths().tolist()):
        block = np.tri(n, dtype=bool)  # key <= query
        if spec.window is not None:
            block &= ~np.tri(n, k=-spec.window, dtype=bool)
        mask[start:start + n, start:start + n] = block
    return mask


def keys_needed(spec: MaskSpec, rows: np.ndarray) -> int:
    """Distinct key rows that the query rows ``rows`` attend to."""
    area = rows_area(spec)
    need = np.zeros(spec.tokens + 1, dtype=np.int64)
    np.add.at(need, rows - area[rows] + 1, 1)
    np.add.at(need, rows + 1, -1)
    return int(np.count_nonzero(np.cumsum(need[:-1])))


def matmul_flops(weights: int, tokens: int) -> int:
    """Required convention: ``tokens`` rows through matrices of ``weights``
    entries in all, forward (2 a weight) and backward (4)."""
    return 6 * tokens * weights


def attn_fwd_flops(area: int, q_heads: int, d_qk: int, d_v: int) -> int:
    """One attention forward over ``area`` unmasked pairs a head: QK^T over
    ``d_qk``, PV over ``d_v``."""
    return 2 * area * q_heads * (d_qk + d_v)


def attn_bwd_flops(area: int, q_heads: int, d_qk: int, d_v: int) -> int:
    """Its backward: the scores again, dQ and dK over ``d_qk``, dP and dV
    over ``d_v``; ``ATTN_BWD_OVER_FWD`` forwards where the two are equal."""
    return 2 * area * q_heads * (3 * d_qk + 2 * d_v)


def ffa_least_seconds(
    calls: list[dict], spec: MaskSpec, rows: np.ndarray, peaks: dict
) -> dict[str, float]:
    """The least time one device could spend in the step's FFA calls when
    it owns the query rows ``rows``: per call the larger of FLOPs over peak
    FLOP/s and bytes over peak bytes/s (each tensor read or written once).

    ``calls`` is the family's ``ffa_calls(cfg)``: one entry per group of
    like layers, ``{"layers": n, "passes": ("fwd", "fwd", "bwd"), "hq",
    "hk", "d_qk", "d_v"}``: ``n`` layers that each make those calls a step
    (a re-forward under remat is a ``"fwd"`` of its own). Returns the
    seconds, and the seconds each bound alone would give, so that a reader
    can say which binds."""
    area = int(rows_area(spec)[rows].sum())
    keys = keys_needed(spec, rows)
    out = {"least_s": 0.0, "flops_s": 0.0, "bytes_s": 0.0}
    for group in calls:
        hq, hk = group["hq"], group["hk"]
        d_qk, d_v = group["d_qk"], group["d_v"]
        fwd_bytes = (
            len(rows) * hq * (d_qk + d_v) * 2   # bf16 q and o
            + keys * hk * (d_qk + d_v) * 2      # bf16 k and v
            + len(rows) * hq * 4)               # fp32 lse
        one = {
            "fwd": (attn_fwd_flops(area, hq, d_qk, d_v), fwd_bytes),
            # and dq, do, dk, dv, delta: every tensor of the forward twice
            "bwd": (attn_bwd_flops(area, hq, d_qk, d_v), 2 * fwd_bytes),
        }
        passes = [one[p] for p in group["passes"]]
        n = group["layers"]
        out["flops_s"] += n * sum(
            f / peaks["bf16_flops"] for f, _ in passes)
        out["bytes_s"] += n * sum(
            b / peaks["hbm_bytes_per_s"] for _, b in passes)
        out["least_s"] += n * sum(
            max(f / peaks["bf16_flops"], b / peaks["hbm_bytes_per_s"])
            for f, b in passes)
    return out
