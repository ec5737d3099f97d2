"""Device time of the regions mlp + moe_shared."""

from cellbench import regions


def read(ctx):
    return regions.region_ms_per_step(ctx, "mlp", "moe_shared")
