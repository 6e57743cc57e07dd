"""Layer: kernels.  Milliseconds per step of device time in the expert
layers, forward and backward, of a configuration whose ``model.py`` names
every output of an expert layer ``layer_<i>.moe.…`` (an MTP module's
``mtp.moe.…``): the router with its plan (sigmoid scores, the correction
bias, the renormalised top-k), the permutation, the grouped matmuls over the
held experts and the gate between them, and the shared expert's three
matmuls and gate.  A Program op's scope carries its first output as the
instance; a grad op's first output is the gradient of such a variable, or of
the layer's input (``….moe.tokens.…``).  ``None`` where no instance of the
traced program carries that name."""
import re

from benchmark.harness import program_ops

LAYER = re.compile(r"\.moe\.")


def read(ctx):
    t = program_ops.table(ctx)
    if t is None:
        return None
    seconds = [r["seconds"] for r in t["instances"]
               if LAYER.search(r["instance"])]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / ctx["traced_steps"]
