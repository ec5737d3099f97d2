"""Traffic: one general generator per mask family, driven by data files.

A traffic mix is a file ``cellbench/traffic/<name>.json``::

    {"generator": "packed_lognormal", "tokens": 32768,
     "params": {...}, "window": null, "check_tokens_per_chip": 4096,
     "batches": 4}

``generator`` names a function of this module (or, for a family this module
does not know, a file ``cellbench/traffic_gen_<generator>.py`` with a
function ``generate`` of the same signature). Every generator returns a
:class:`MaskSpec`: documents packed into one sequence, each attending
causally to itself, optionally through a sliding window. The mask is fixed
by the file: ``--seed`` draws the token ids and the weights and nothing that
changes the work or the program, so runs with different seeds measure the
same thing and find the same compiled step in the cache. (On the chip, the
same eight documents in another order moved the FFA kernels' time by 6% and
the step by 0.5%, and compiled anew in every run; PR 22.)
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class MaskSpec:
    """Packed causal documents: document ``j`` owns rows ``cu_seqlens[j]`` to
    ``cu_seqlens[j + 1]``; a query attends to the keys of its own document
    at or before it and, where ``window`` is set, at most ``window`` keys
    counting itself."""

    tokens: int
    cu_seqlens: tuple[int, ...]
    window: int | None = None

    def doc_lengths(self) -> np.ndarray:
        return np.diff(np.asarray(self.cu_seqlens, dtype=np.int64))


def single_document(params: dict, tokens: int, scale: float, rng) -> list[int]:
    """One document of ``tokens`` tokens. Nothing to draw."""
    del params, scale, rng
    return [tokens]


def packed_lognormal(
    params: dict, tokens: int, scale: float, rng
) -> list[int]:
    """Documents whose lengths are the stratified sample of a clipped
    log-normal law (``median``, ``sigma``, ``min``, ``max``, all times
    ``scale``): the ``(i + 1/2) / n`` quantiles for the smallest ``n`` that
    fills ``tokens``, shrunk to sum to it exactly, packed in the order that
    ``order_seed`` draws (a parameter of the file, not ``--seed``)."""
    del rng  # nothing here may depend on --seed
    lo = max(1.0, params["min"] * scale)
    hi = params["max"] * scale
    median, sigma = params["median"] * scale, params["sigma"]
    normal = NormalDist()

    def quantiles(n: int) -> list[float]:
        return [
            min(hi, max(lo, median * math.exp(
                sigma * normal.inv_cdf((i + 0.5) / n))))
            for i in range(n)
        ]

    n = 1
    while sum(quantiles(n)) < tokens:
        n += 1
    raw = quantiles(n)
    shrink = tokens / sum(raw)
    lens = [max(1, int(x * shrink)) for x in raw]
    # the rounding's remainder goes to the longest documents, one token each
    for i in sorted(range(n), key=lambda i: -lens[i])[: tokens - sum(lens)]:
        lens[i] += 1
    order = np.random.default_rng(params["order_seed"]).permutation(n)
    return [lens[i] for i in order]


def make_mask(
    traffic: dict, tokens: int, window: int | None, seed: int, generate
) -> MaskSpec:
    """The mask of ``traffic`` at ``tokens`` tokens (the cell's own size or
    the smaller one of the reference check, whose length law shrinks in
    proportion)."""
    rng = np.random.default_rng([seed, 0])
    lens = generate(
        traffic.get("params", {}), tokens, tokens / traffic["tokens"], rng
    )
    cu = np.concatenate([[0], np.cumsum(lens)])
    if cu[-1] != tokens or min(lens) < 1:
        raise ValueError(
            f"generator {traffic['generator']!r} returned lengths {lens} "
            f"that do not fill {tokens} tokens"
        )
    return MaskSpec(tokens, tuple(int(c) for c in cu), window)


def token_batches(
    spec: MaskSpec, vocab_size: int, seed: int, count: int
) -> list[tuple[np.ndarray, np.ndarray]]:
    """``count`` batches of seeded token ids with next-token labels; the
    last token of each document has no target (label -1)."""
    rng = np.random.default_rng([seed, 1])
    ends = np.asarray(spec.cu_seqlens[1:]) - 1
    out = []
    for _ in range(count):
        toks = rng.integers(0, vocab_size, spec.tokens, dtype=np.int32)
        labels = np.roll(toks, -1)
        labels[ends] = -1
        out.append((toks, labels))
    return out
