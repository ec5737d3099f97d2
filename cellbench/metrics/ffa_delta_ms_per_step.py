"""Self time per step of the ``delta`` kernel bodies, by their names."""

from cellbench import kernel_times


def read(ctx):
    return kernel_times.ms_per_step(ctx, "delta")
