"""Layer: kernels.  Milliseconds per step of device time in the attention
``softmax`` and its grad (not ``softmax_with_cross_entropy``, the loss's)."""
from benchmark.harness import program_ops


def read(ctx):
    return program_ops.family_ms(ctx, "softmax")
