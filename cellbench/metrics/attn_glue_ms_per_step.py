"""Device time of class other_compute inside DistAttnRuntime.calc_attn."""

from cellbench import regions


def read(ctx):
    return regions.glue_ms_per_step(ctx, regions.ATTN)
