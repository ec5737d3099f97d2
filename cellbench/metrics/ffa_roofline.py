"""Share of its roofline the FFA kernels reach: least time over kernel
time, both summed over the devices."""

from cellbench import flops


def read(ctx):
    if ctx.trace is None:
        return None
    spent = ctx.trace.self_ms_per_step(["ffa_fwd", "ffa_bwd"]) * 1e-3
    if not spent:
        return None
    calls = ctx.family.ffa_calls(ctx.config)
    least = [
        flops.ffa_least_seconds(calls, ctx.spec, rows, ctx.peaks)
        for rows in ctx.facts["rank_rows"]
    ]
    mean_least = sum(x["least_s"] for x in least) / len(least)
    return 100.0 * mean_least / spent
