"""Layer: kernels.  Differential attention's share of its roofline, in
percent: ``kernel.causal_attention_roofline``'s reading (3 x the forward's
FLOPs over the allowed pairs and the bytes of q, k, v, the output and their
gradients, over the device time of the ``fused_multihead_attention`` ops and
their grads a step) of a configuration whose ``attention_flops_per_sample``
/ ``attention_bytes_per_sample`` count a head that scores over 64 numbers
and carries a pair's two values side by side, 128: windowed pairs on the
windowed layers, causal pairs on the full and the cross layers, each of the
two softmaxes once.  A score head padded to 128, or four calls of 64-wide
values, would earn nothing: the FLOPs are the required ones.  ``None`` where
that reader finds nothing to read."""
import os

from benchmark.harness import registry


def read(ctx):
    return registry.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "kernel.causal_attention_roofline.py")).read(ctx)
