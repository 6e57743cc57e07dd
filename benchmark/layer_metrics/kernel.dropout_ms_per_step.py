"""Layer: kernels.  Milliseconds per step of device time in ``dropout`` ops,
forward and grad: the Mosaic kernels on one chip, XLA's
``rng-bit-generator`` lowering in a partitioned program."""
from benchmark.harness import program_ops


def read(ctx):
    return program_ops.family_ms(ctx, "dropout")
