"""Device time of the region kda less the magi_kda_* kernels."""

from cellbench import regions


def read(ctx):
    return regions.glue_ms_per_step(ctx, "kda", "magi_kda_")
