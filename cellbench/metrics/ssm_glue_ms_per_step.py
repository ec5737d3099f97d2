"""Device time of the region ssm less the magi_ssd_* kernels."""

from cellbench import regions


def read(ctx):
    return regions.glue_ms_per_step(ctx, "ssm", regions.SSD_KERNEL)
