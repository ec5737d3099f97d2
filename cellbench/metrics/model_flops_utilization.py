"""Model FLOP utilisation: required FLOPs of a step over what the chips
could do in its median time. Restates ``tokens_per_s`` against the peak."""

import statistics

from cellbench import flops


def read(ctx):
    if not ctx.facts.get("step_ms"):
        return None
    step_s = statistics.median(ctx.facts["step_ms"]) * 1e-3
    need = flops.model_flops_per_step(ctx.config, ctx.spec)
    return 100.0 * need / (step_s * ctx.cell.chips * ctx.peaks["bf16_flops"])
