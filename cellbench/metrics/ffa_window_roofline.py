"""Share of its roofline the FFA calls under the key labelled ``window``
reach: the group's least time on its own mask over its kernels' time."""

from cellbench import keyed_ffa


def read(ctx):
    return keyed_ffa.roofline(ctx, "window")
