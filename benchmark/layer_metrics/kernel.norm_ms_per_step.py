"""Layer: kernels.  Milliseconds per step of device time in ``layer_norm`` /
``batch_norm`` ops and their grads."""
from benchmark.harness import program_ops


def read(ctx):
    return program_ops.family_ms(ctx, "norm")
