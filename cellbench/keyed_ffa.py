"""FFA time and roofline per runtime key, by the key's label.

A model whose layers attend under several runtime keys a step labels them
(``DistAttnRuntimeKey.label``; the ``afmoe`` family: ``window``, ``full``)
and the program binds each Pallas call under its body's name followed by the
label (``magiattention_tpu/kernels/_named.py``): ``magi_fwd_kernel_window``,
``magi_bwd_fused_kernel_gqa_full``, and where the scope is the outermost one
``transpose_jvp_magi_bwd_dq_kernel_window__``. ``kernel_times.kind_of`` and
the FFA classes still find the body; this file reads the label. A program
that labels nothing has nothing to read: every reader returns ``None``.
"""

from __future__ import annotations

import dataclasses

from cellbench import flops, named_ops


def pattern(label: str) -> str:
    return rf"magi\w*_kernel\w*_{label}(?![a-z])"


def ms_per_step(ctx, label: str) -> float | None:
    """Self milliseconds per step of the kernels called under ``label``."""
    return named_ops.ms_per_step(ctx.trace, pattern(label))


def roofline(ctx, label: str) -> float | None:
    """Least time of the family's ``ffa_calls`` groups of kind ``label``,
    each on the cell's documents under ITS ``window``, over the time of the
    kernels called under the label, %."""
    spent = ms_per_step(ctx, label)
    groups = [g for g in ctx.family.ffa_calls(ctx.config)
              if g.get("kind") == label]
    if not spent or not groups:
        return None
    least = 0.0
    for group in groups:
        spec = dataclasses.replace(ctx.spec, window=group["window"])
        per_rank = [
            flops.ffa_least_seconds([group], spec, rows, ctx.peaks)["least_s"]
            for rows in ctx.facts["rank_rows"]]
        least += sum(per_rank) / len(per_rank)
    return 100.0 * least / (spent * 1e-3)
