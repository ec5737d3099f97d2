"""Device time of the regions attn_qkv + attn_out."""

from cellbench import regions


def read(ctx):
    return regions.region_ms_per_step(ctx, "attn_qkv", "attn_out")
