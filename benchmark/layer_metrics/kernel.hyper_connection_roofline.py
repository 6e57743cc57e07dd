"""Layer: kernels.  The mixers' share of their roofline, in percent: the
least time the chip could take for a step's mixers, forward and backward —
the larger of their FLOPs over the peak FLOP/s and their bytes over the peak
bytes/s (the float32 streams read and written once forward, read twice and
written once backward, phi once: the configuration's
``hyper_connection_flops_and_bytes_per_sample``; the bytes bound it) — over
the device time of the ``hyper_connection_mix`` / ``hyper_connection_merge``
ops and their grads a step.  ``None`` where the program has no such op or
the configuration no such function."""
import os

from benchmark.harness import registry
from benchmark.harness.peaks import roofline_seconds


def read(ctx):
    model = ctx["model"]
    if ctx["peaks"] is None or not hasattr(
            model, "hyper_connection_flops_and_bytes_per_sample"):
        return None
    seconds = registry.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "kernel.hyper_connection_ms_per_step.py")).seconds_per_step(ctx)
    if not seconds:
        return None
    per_chip = ctx["batch"] // ctx["chips"]
    flops, nbytes = model.hyper_connection_flops_and_bytes_per_sample(
        ctx["cfg"], ctx["mix"])
    least, _bound = roofline_seconds(per_chip * flops, per_chip * nbytes,
                                     ctx["peaks"])
    return 100.0 * least / seconds
