"""Layer: device.  Milliseconds per step of device time (self time, mean over
the devices) in instructions charged to Program ops of role ``backward``."""
from benchmark.harness import program_ops


def read(ctx):
    return program_ops.role_ms(ctx, "backward")
