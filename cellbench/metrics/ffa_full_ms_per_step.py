"""Device time of the FFA calls made under the key labelled ``full``."""

from cellbench import keyed_ffa


def read(ctx):
    return keyed_ffa.ms_per_step(ctx, "full")
