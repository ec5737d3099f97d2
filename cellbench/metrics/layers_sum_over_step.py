"""Does the classification close? Layer times per step over the traced
steps' own host-clock time."""

import statistics


def read(ctx):
    if ctx.trace is None or not ctx.facts.get("traced_step_ms"):
        return None
    t = ctx.trace
    parts = t.self_ms_per_step(t.classes()) + t.idle_ms_per_step()
    return 100.0 * parts / statistics.fmean(ctx.facts["traced_step_ms"])
