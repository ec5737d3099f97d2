"""Exposed time of class group_comm, the reduces (a cast's transpose)."""

from cellbench import regions


def read(ctx):
    return regions.group_comm_ms_per_step(ctx, "reduce")
