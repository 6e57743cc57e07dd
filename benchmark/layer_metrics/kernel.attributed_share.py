"""Layer: kernels.  Share of the device's busy time in instructions that the
program's op map charges to a Program op, in percent.  The trust gauge of
the metrics by Program op: under 95 they are not to be believed."""
from benchmark.harness import program_ops


def read(ctx):
    return program_ops.share(ctx, "attributed_s")
