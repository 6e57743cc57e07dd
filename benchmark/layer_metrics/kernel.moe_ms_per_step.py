"""Layer: kernels.  Milliseconds per step of device time in the expert
layer's ops and their grads: the router and its plan (``moe_route``), the
permutation (``moe_dispatch``, ``moe_combine``), the grouped matmuls
(``moe_grouped_matmul``) and the gate between them (``swiglu``).  ``None``
where the program holds no such op."""
from benchmark.harness import program_ops

TYPES = ("moe_route", "moe_dispatch", "moe_grouped_matmul", "swiglu",
         "moe_combine")


def read(ctx):
    t = program_ops.table(ctx)
    if t is None:
        return None
    labels = TYPES + tuple(name + "_grad" for name in TYPES)
    seconds = [r["seconds"] for r in t["labels"] if r["label"] in labels]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / ctx["traced_steps"]
