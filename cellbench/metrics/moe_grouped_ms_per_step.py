"""Device time of the compiler's grouped products, by instruction name."""

from cellbench import named_ops


def read(ctx):
    return named_ops.ms_per_step(ctx.trace, named_ops.GROUPED)
