"""Layer: kernels.  Milliseconds per step of device time in the selective
scans of the state-space layers: the ``selective_scan`` ops (the recurrence
over the sequence, one kernel call a layer) and their grads (a chunk's
states computed again, then the reverse sweep), with what the lowering puts
around the kernel (the operands' layout, the sums of the partial gradients).
``None`` where the program holds no such op."""
from benchmark.harness import program_ops

TYPES = ("selective_scan", "selective_scan_grad")


def seconds_per_step(ctx):
    """Seconds a traced step spends in the scan ops, or None."""
    t = program_ops.table(ctx)
    if t is None:
        return None
    seconds = [r["seconds"] for r in t["labels"] if r["label"] in TYPES]
    if not seconds:
        return None
    return sum(seconds) / ctx["traced_steps"]


def read(ctx):
    seconds = seconds_per_step(ctx)
    return None if seconds is None else 1e3 * seconds
