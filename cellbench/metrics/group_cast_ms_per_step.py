"""Exposed time of class group_comm, the casts (passes fwd and refwd)."""

from cellbench import regions


def read(ctx):
    return regions.group_comm_ms_per_step(ctx, "cast")
