"""A routing counter of the first timed batch, from the program's own
routing through the family file."""


def read(ctx):
    counters = getattr(ctx.family, "routing_counters", lambda: None)()
    return None if counters is None else counters["load_max_over_mean"]
