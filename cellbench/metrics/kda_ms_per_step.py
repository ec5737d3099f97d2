"""Device time of the gated delta rule's two Pallas calls, by instruction
name."""

from cellbench import named_ops

KDA_FWD = r"magi_kda_fwd_kernel"
KDA_BWD = r"magi_kda_bwd_kernel"


def read(ctx):
    return named_ops.ms_per_step(ctx.trace, KDA_FWD, KDA_BWD)
