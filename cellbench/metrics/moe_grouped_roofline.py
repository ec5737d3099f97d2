"""Share of its roofline the held experts' grouped products reach: the
least time of the products a step makes, at the rows the program's routing
counted, over their kernels' time. The products' shapes and counts are the
family file's."""

from cellbench import named_ops


def read(ctx):
    spent = named_ops.ms_per_step(ctx.trace, named_ops.GROUPED)
    calls = getattr(ctx.family, "grouped_calls", None)
    counters = getattr(ctx.family, "routing_counters", lambda: None)()
    if not spent or calls is None or counters is None:
        return None
    groups = calls(ctx.config)
    tokens = ctx.spec.tokens // ctx.cell.chips
    layer_rows = counters["routed_rows"] / sum(g["layers"] for g in groups)
    least = 0.0
    for group in groups:
        blocks = max(1, tokens // group["token_block"])
        rows = layer_rows / blocks  # a call's
        for product in group["products"]:
            k, n = product["k"], product["n"]
            least += group["layers"] * blocks * product["calls"] * max(
                2 * rows * k * n / ctx.peaks["bf16_flops"],
                2 * (rows * (k + n) + group["held"] * k * n)
                / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (spent * 1e-3)
