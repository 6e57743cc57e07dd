"""Layer: kernels.  Milliseconds per step of device time in the indexer,
forward and backward: its three projections, the key's LayerNorm, the
partial rotary with its splits and concatenations, the index scores and the
selection (``sparse_attention_index``) and the indexer's loss
(``sparse_attention_index_loss``, which also computes the heads' mean
attention probabilities again).  The configuration's ``model.py`` names every
output of it ``layer_<i>.attention.indexer.…``, and a Program op's scope
carries its first output as the instance (a grad op's is the gradient of
such a variable).  ``None`` where no instance of the traced program carries
that name."""
from benchmark.harness import program_ops

BRANCH = ".attention.indexer."


def read(ctx):
    t = program_ops.table(ctx)
    if t is None:
        return None
    seconds = [r["seconds"] for r in t["instances"]
               if BRANCH in r["instance"]]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / ctx["traced_steps"]
