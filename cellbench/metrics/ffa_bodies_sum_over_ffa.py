"""Do the kernels' names and the result-type classes agree on what is FFA?"""

from cellbench import kernel_times


def read(ctx):
    return kernel_times.bodies_sum_over_ffa(ctx)
