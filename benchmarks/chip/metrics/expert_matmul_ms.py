"""Device time a step of the held experts' grouped matrix products (the
``gmm`` and ``tgmm`` kernels: forward and both gradients, and the
forward recomputed for the backward), summed per chip from the trace and
averaged over chips."""

from chip import moe_trace


def read(run):
    s = moe_trace.grouped_matmul_seconds(run)
    return None if s is None else 1e3 * s
