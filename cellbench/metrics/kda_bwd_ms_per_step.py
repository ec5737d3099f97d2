"""Device time of the gated delta rule's backward call, by instruction
name."""

from cellbench import named_ops


def read(ctx):
    return named_ops.ms_per_step(ctx.trace, r"magi_kda_bwd_kernel")
