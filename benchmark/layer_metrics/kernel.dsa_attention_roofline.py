"""Layer: kernels.  The selected attention's share of its roofline, in
percent: the least time the chip could take for a step's attention over the
SELECTED pairs only (3 x the configuration's ``attention_flops_per_sample``:
the two matmuls of every head over ``selected_pairs``, forward and twice
that backward; ``attention_bytes_per_sample``; the FLOPs bound it) over the
device time of the ``fused_multihead_attention`` ops and their grads a step.
A lowering that computes every causal pair and masks earns nothing for the
masked ones, so a perfect such kernel reads 23 % at 16384 tokens and
``topk`` 2048, and no lowering can pass 100.  ``None`` where
``kernel.dsa_attention_ms_per_step`` finds nothing to read."""
import os

from benchmark.harness import registry
from benchmark.harness.peaks import roofline_seconds


def read(ctx):
    model = ctx["model"]
    if ctx["peaks"] is None \
            or not hasattr(model, "attention_flops_per_sample"):
        return None
    seconds = registry.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "kernel.dsa_attention_ms_per_step.py")).seconds_per_step(ctx)
    if not seconds:
        return None
    per_chip = ctx["batch"] // ctx["chips"]
    least, _bound = roofline_seconds(
        3.0 * per_chip * model.attention_flops_per_sample(ctx["cfg"],
                                                          ctx["mix"]),
        per_chip * model.attention_bytes_per_sample(ctx["cfg"], ctx["mix"]),
        ctx["peaks"])
    return 100.0 * least / seconds
