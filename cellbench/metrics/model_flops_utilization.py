"""Model FLOP utilisation: required FLOPs of a step (the family's count)
over what the chips could do in its median time. Restates ``tokens_per_s``
against the peak."""

import statistics


def read(ctx):
    if not ctx.facts.get("step_ms"):
        return None
    step_s = statistics.median(ctx.facts["step_ms"]) * 1e-3
    need = ctx.family.required_flops_per_step(ctx.config, ctx.spec)
    return 100.0 * need / (step_s * ctx.cell.chips * ctx.peaks["bf16_flops"])
