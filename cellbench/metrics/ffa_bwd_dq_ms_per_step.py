"""Self time per step of the ``bwd_dq`` kernel bodies, by their names."""

from cellbench import kernel_times


def read(ctx):
    return kernel_times.ms_per_step(ctx, "bwd_dq")
