"""Device time of the region head_loss."""

from cellbench import regions


def read(ctx):
    return regions.region_ms_per_step(ctx, "head_loss")
