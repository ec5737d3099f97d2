"""Share of its roofline the scan kernels reach: least time over kernel
time. The calls' FLOPs and bytes a token are the family file's."""

from cellbench import named_ops


def read(ctx):
    spent = named_ops.ms_per_step(
        ctx.trace, named_ops.SSD_FWD, named_ops.SSD_BWD)
    if not spent or not hasattr(ctx.family, "ssd_calls"):
        return None
    tokens = ctx.spec.tokens / ctx.cell.chips
    least = sum(
        group["layers"] * max(
            tokens * group["flops_per_token"][p] / ctx.peaks["bf16_flops"],
            tokens * group["bytes_per_token"][p]
            / ctx.peaks["hbm_bytes_per_s"])
        for group in ctx.family.ssd_calls(ctx.config)
        for p in group["passes"])
    return 100.0 * least / (spent * 1e-3)
