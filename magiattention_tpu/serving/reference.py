"""Sequential per-request replay — the engine's equality oracle.

Each request runs alone against a fresh single-sequence cache with every
page pre-assigned, using the SAME :func:`~.prefill.prefill_request` chunk
schedule and the SAME gather+FFA decode call the engine's reference rung
makes. Per-row FFA results depend only on the unmasked rows (masked scores
are the MASK_VALUE constant regardless of what garbage the gathered pages
hold, and their exp2 contributions underflow to exactly 0.0), so with an
identical chunk schedule, ``max_pages`` and env snapshot, the engine under
``MAGI_ATTENTION_BACKEND_SERVE_DECODE=gather_ffa`` must reproduce this replay
BITWISE — the serve-smoke acceptance gate.

This one-token-per-tick replay is ALSO the oracle for the speculative
engine (``spec_tokens`` k > 1): a verify row attends its own causal
prefix, so whenever a row's draft input chain is correct its output is the
exact sequential output — the same masked-row invariance as above makes
the multi-row gather+FFA call bitwise-equal to issuing its rows
sequentially. Commits (the longest accepted prefix) are therefore bitwise
prefixes of this replay regardless of where rejection lands, and rollback
only ever discards rows the oracle never produced.

The int8 story is the same with one extra ingredient: quantized append is
a pure function of a page's append history (monotone per-page scales,
reset on release), so an int8 engine pinned to the gather rung is bitwise
vs an int8 oracle (``config.kv_dtype='int8'`` here), while int8-vs-f32 is
a tolerance comparison (the quantization error itself).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..kernels.paged_kv import PagedKVCache, append_kv, assign_pages, paged_attn
from .engine import DraftFn, ServeConfig
from .model import ToyModel
from .prefill import prefill_request
from .scheduler import ServeRequest


def generate_reference(
    model: ToyModel, req: ServeRequest, config: ServeConfig
) -> list[np.ndarray]:
    """Generate ``req``'s tokens in isolation; returns the per-step hidden
    rows (same objects the engine stores in ``req.generated``)."""
    P = config.max_pages_per_seq
    cache = PagedKVCache.create(
        num_pages=P,
        page_size=config.page_size,
        n_kv_heads=model.n_kv_heads,
        head_dim=model.head_dim,
        max_seqs=1,
        max_pages_per_seq=P,
        dtype=jnp.int8 if config.kv_dtype == "int8" else jnp.float32,
    )
    cache = assign_pages(cache, 0, np.arange(P, dtype=np.int32))

    cache, last_hidden = prefill_request(
        model, cache, 0, req.prompt, config.prefill_chunk,
        config.softmax_scale,
    )
    length = req.prompt_len
    x = model.next_input(last_hidden)

    outs: list[np.ndarray] = []
    for _ in range(req.max_new_tokens):
        q, k, v = model.qkv(x[None])
        cache = append_kv(cache, 0, k, v)
        length += 1
        out, _ = paged_attn(
            q, cache, 0,
            q_start=length - 1,
            max_pages=P,
            softmax_scale=config.softmax_scale,
        )
        hidden = model.project(out)[0]
        outs.append(np.asarray(hidden))
        x = model.next_input(hidden)
    return outs


def run_reference(
    model: ToyModel, requests: list[ServeRequest], config: ServeConfig
) -> dict[int, list[np.ndarray]]:
    """Replay every request sequentially; keyed by ``req_id``."""
    return {
        req.req_id: generate_reference(model, req, config)
        for req in requests
    }


def oracle_draft_fn(
    ref_outputs: dict[int, list[np.ndarray]]
) -> DraftFn:
    """A draft function that drafts the TRUE next inputs (from a completed
    :func:`run_reference` replay), so the speculative engine's verify
    accepts every row — the full-accept end of the accept/rollback
    spectrum, used by tests and serve-smoke to pin accept_rate == 1.
    Positions past the replay fall back to the model's greedy draft."""

    def draft(model: ToyModel, req: ServeRequest, x, j: int):
        # draft j's input is next_input(hidden_{n+j-1}) where n tokens are
        # committed so far (draft 0 == pending_x == next_input(hidden_{n-1}))
        idx = len(req.generated) + j - 1
        hiddens = ref_outputs.get(req.req_id, [])
        if 0 <= idx < len(hiddens):
            return model.next_input(jnp.asarray(hiddens[idx]))
        return model.draft_next(x)

    return draft
