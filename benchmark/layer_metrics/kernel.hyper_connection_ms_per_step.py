"""Layer: kernels.  Milliseconds per step of device time in the mixers of a
multi-stream residual path: the ops ``hyper_connection_mix`` (the token's
projection, its gates and the Sinkhorn normalisation, the branch's input)
and ``hyper_connection_merge`` (the new streams) and their grads.  The sums
that add a stream's two gradients (it feeds its mixer's both ops) are ``sum``
ops and not in here.  ``None`` where the program holds no such op."""
from benchmark.harness import program_ops

TYPES = ("hyper_connection_mix", "hyper_connection_merge")


def seconds_per_step(ctx):
    """Seconds a traced step spends in the mixer ops, or None."""
    t = program_ops.table(ctx)
    if t is None:
        return None
    labels = TYPES + tuple(name + "_grad" for name in TYPES)
    seconds = [r["seconds"] for r in t["labels"] if r["label"] in labels]
    if not seconds:
        return None
    return sum(seconds) / ctx["traced_steps"]


def read(ctx):
    seconds = seconds_per_step(ctx)
    return None if seconds is None else 1e3 * seconds
