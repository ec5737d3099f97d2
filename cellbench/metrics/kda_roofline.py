"""Share of its roofline the gated delta rule's kernels reach: least time
over kernel time. The calls' FLOPs and bytes a token are the family
file's."""

from cellbench import named_ops


def read(ctx):
    spent = named_ops.ms_per_step(
        ctx.trace, r"magi_kda_fwd_kernel", r"magi_kda_bwd_kernel")
    if not spent or not hasattr(ctx.family, "kda_calls"):
        return None
    tokens = ctx.spec.tokens / ctx.cell.chips
    least = sum(
        group["layers"] * max(
            tokens * group["flops_per_token"][p] / ctx.peaks["bf16_flops"],
            tokens * group["bytes_per_token"][p]
            / ctx.peaks["hbm_bytes_per_s"])
        for group in ctx.family.kda_calls(ctx.config)
        for p in group["passes"])
    return 100.0 * least / (spent * 1e-3)
