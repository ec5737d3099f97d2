"""Device time of the region embed."""

from cellbench import regions


def read(ctx):
    return regions.region_ms_per_step(ctx, "embed")
