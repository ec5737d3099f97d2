"""Device time of the scan's two Pallas calls, by instruction name."""

from cellbench import named_ops


def read(ctx):
    return named_ops.ms_per_step(ctx.trace, named_ops.SSD_FWD, named_ops.SSD_BWD)
