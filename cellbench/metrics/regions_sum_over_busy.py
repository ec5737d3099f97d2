"""The join's closure: time the compiled step's table places in a region, over busy time."""

from cellbench import regions


def read(ctx):
    return regions.regions_sum_over_busy(ctx)
