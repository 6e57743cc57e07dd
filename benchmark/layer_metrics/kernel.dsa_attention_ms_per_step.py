"""Layer: kernels.  Milliseconds per step of device time in the attention
over selected keys, forward and backward: the ``fused_multihead_attention``
ops that bring a selection (``layer_<i>.attention.kernel…``) and their
grads.  The program's ``sparse_attention.lowering.<path>`` counter names the
lowering those rows ran (the selected kernel, or XLA's row blocks); both
compute every causal tile that holds a selected pair and mask, so this time
buys 4.27 x the pairs the mathematics requires at 16384 tokens.  ``None``
where the configuration has no ``sa_config`` or the traced program no such
op."""
from benchmark.harness import program_ops

TYPES = ("fused_multihead_attention", "fused_multihead_attention_grad")


def seconds_per_step(ctx):
    t = program_ops.table(ctx)
    if t is None or "sa_config" not in ctx["cfg"]:
        return None
    seconds = [r["seconds"] for r in t["labels"] if r["label"] in TYPES]
    if not seconds:
        return None
    return sum(seconds) / ctx["traced_steps"]


def read(ctx):
    seconds = seconds_per_step(ctx)
    return None if seconds is None else 1e3 * seconds
