"""Layer: kernels.  The index scores', the selection's and the indexer
loss's share of their roofline, in percent: the least time the chip could
take for them in a step (the configuration's ``indexer_flops_and_bytes``:
the scores of every causal pair forward, their gradient over the selected
pairs only, the operands and the selection's bits; the FLOPs bound it) over
the device time of the ``sparse_attention_index`` and
``sparse_attention_index_loss`` ops and the latter's grad a step.  The
search for each query's threshold (32 counting passes over its scores) and
the attention probabilities the loss computes again are no required work, so
this reads low while they are most of the time.  ``None`` where the program
has no such op or the configuration no such function."""
from benchmark.harness import program_ops
from benchmark.harness.peaks import roofline_seconds

TYPES = ("sparse_attention_index", "sparse_attention_index_loss",
         "sparse_attention_index_loss_grad")


def read(ctx):
    t = program_ops.table(ctx)
    model = ctx["model"]
    if t is None or ctx["peaks"] is None \
            or not hasattr(model, "indexer_flops_and_bytes"):
        return None
    seconds = sum(r["seconds"] for r in t["labels"] if r["label"] in TYPES)
    if not seconds:
        return None
    per_chip = ctx["batch"] // ctx["chips"]
    flops, nbytes = model.indexer_flops_and_bytes(ctx["cfg"], ctx["mix"])
    least, _bound = roofline_seconds(per_chip * flops, per_chip * nbytes,
                                     ctx["peaks"])
    return 100.0 * least / (seconds / ctx["traced_steps"])
