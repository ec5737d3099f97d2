"""Device time of the region mla_assemble."""

from cellbench import regions


def read(ctx):
    return regions.region_ms_per_step(ctx, "mla_assemble")
