"""Busy time in jax.checkpoint's re-run of the forward (pass refwd), %."""

from cellbench import regions


def read(ctx):
    return regions.remat_recompute_share(ctx)
