"""Layer: kernels.  Share of the device's busy time in instructions that
hold a ``convolution`` or ``dot`` (alone or as the hero of a fusion), in
percent: the rest is passes over memory."""
from benchmark.harness import program_ops


def read(ctx):
    return program_ops.share(ctx, "mxu_s")
