"""Device time of the region moe_rows."""

from cellbench import regions


def read(ctx):
    return regions.region_ms_per_step(ctx, "moe_rows")
