"""Operations and bytes a cell's step needs, from shapes and the mask alone.

Two conventions, always named:

* **required** (model FLOPs): what forward and backward need, recomputation
  not counted. A matmul with ``p`` weights costs ``2 p`` per token forward
  and ``4 p`` backward; attention forward is ``4 * area * head_dim *
  q_heads`` (QK^T and PV over the ``area`` unmasked pairs) and its backward
  2.5 times that (five matmuls against two). This is the reference's
  convention and ``BASELINE.md``'s.
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


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one block's seven projections."""
    dim, dh = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (
        dim * hq * dh + 2 * dim * hk * dh + hq * dh * dim
        + 3 * dim * cfg["intermediate_size"]
    )


def attn_fwd_flops(cfg: dict, area: int) -> int:
    """One layer's attention forward over ``area`` unmasked pairs."""
    return 4 * area * cfg["head_dim"] * cfg["num_attention_heads"]


def model_flops_per_step(cfg: dict, spec: MaskSpec) -> int:
    """Required convention: forward + backward of the whole step."""
    layers = cfg["num_hidden_layers"]
    matmul = 6 * spec.tokens * (
        layers * layer_matmul_params(cfg)
        + cfg["hidden_size"] * cfg["vocab_size"]  # untied head; embed is a gather
    )
    attn = layers * (1 + ATTN_BWD_OVER_FWD) * attn_fwd_flops(
        cfg, band_area(spec))
    return int(matmul + attn)


def ffa_least_seconds(
    cfg: dict, spec: MaskSpec, rows: np.ndarray, peaks: dict
) -> dict[str, float]:
    """The least time one device could spend in the step's FFA calls when
    it owns the query rows ``rows``: per call the larger of FLOPs over peak
    FLOP/s and bytes over peak bytes/s (each tensor read or written once),
    summed over the three calls of each layer. Returns the seconds, and
    the seconds each bound alone would give, so that a reader can say which
    binds."""
    area = int(rows_area(spec)[rows].sum())
    dh, hq = cfg["head_dim"], cfg["num_attention_heads"]
    hk = cfg["num_key_value_heads"]
    q_bytes = len(rows) * hq * dh * 2          # bf16 q, o, do, dq
    kv_bytes = keys_needed(spec, rows) * hk * dh * 2   # each of k, v, dk, dv
    lse_bytes = len(rows) * hq * 4             # fp32 lse, delta
    fwd = attn_fwd_flops(cfg, area)
    calls = {
        "fwd": (fwd, 2 * q_bytes + 2 * kv_bytes + lse_bytes),
        "refwd": (fwd, 2 * q_bytes + 2 * kv_bytes + lse_bytes),
        "bwd": (ATTN_BWD_OVER_FWD * fwd,
                4 * q_bytes + 4 * kv_bytes + 2 * lse_bytes),
    }
    layers = cfg["num_hidden_layers"]
    by_flops = layers * sum(
        f / peaks["bf16_flops"] for f, _ in calls.values())
    by_bytes = layers * sum(
        b / peaks["hbm_bytes_per_s"] for _, b in calls.values())
    least = layers * sum(
        max(f / peaks["bf16_flops"], b / peaks["hbm_bytes_per_s"])
        for f, b in calls.values()
    )
    return {"least_s": least, "flops_s": by_flops, "bytes_s": by_bytes}
