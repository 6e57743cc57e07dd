"""Layer: kernels.  Milliseconds per step of device time in the
``fused_multihead_attention`` ops and their grads: the fused attention
kernel where the lowering took it (scores, softmax, dropout on the
probabilities and both matmuls of a head in one call).  ``None`` where the
program holds no such op (the pass left the chains alone, or an older
commit), so the ``softmax`` and ``dropout`` families hold attention's share
there."""
from benchmark.harness import program_ops

TYPES = ("fused_multihead_attention", "fused_multihead_attention_grad")


def read(ctx):
    t = program_ops.table(ctx)
    if t is None:
        return None
    seconds = [r["seconds"] for r in t["labels"] if r["label"] in TYPES]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / ctx["traced_steps"]
