"""Layer: kernels.  Causal attention's share of its roofline, in percent:
the least time the chip could take for the attention ops of a step — the
FLOPs of the unmasked (query, key) pairs of every layer, forward and
backward, and the bytes of q, k, v, the output and their gradients, from the
configuration's ``attention_flops_per_sample`` / ``attention_bytes_per_sample``
— over the device time of the ``fused_multihead_attention`` ops and their
grads a step.  ``None`` where the program has no such op or the configuration
no such function."""
from benchmark.harness import program_ops
from benchmark.harness.peaks import roofline_seconds

TYPES = ("fused_multihead_attention", "fused_multihead_attention_grad")


def read(ctx):
    t = program_ops.table(ctx)
    model = ctx["model"]
    if t is None or ctx["peaks"] is None \
            or not hasattr(model, "attention_flops_per_sample"):
        return None
    seconds = sum(r["seconds"] for r in t["labels"] if r["label"] in TYPES)
    if not seconds:
        return None
    per_chip = ctx["batch"] // ctx["chips"]
    least, _bound = roofline_seconds(
        3.0 * per_chip * model.attention_flops_per_sample(ctx["cfg"],
                                                          ctx["mix"]),
        per_chip * model.attention_bytes_per_sample(ctx["cfg"], ctx["mix"]),
        ctx["peaks"])
    return 100.0 * least / (seconds / ctx["traced_steps"])
