"""Layer: kernels.  The whole step's share of its roofline, in percent: the
least time one chip could take for its part of a step — the larger of the
required FLOPs over the peak FLOP/s and the required bytes over the peak
bytes/s, both from the configuration's shapes functions — over the time its
device was busy per step.  For both BERT mixes the FLOPs bound it."""
from benchmark.harness.peaks import roofline_seconds


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx["traced_steps"] or ctx["peaks"] is None:
        return None
    per_chip = ctx["batch"] // ctx["chips"]
    model = ctx["model"]
    least, _bound = roofline_seconds(
        model.flops_per_sample(ctx["cfg"], ctx["mix"]) * per_chip,
        model.bytes_per_step(ctx["cfg"], ctx["mix"], per_chip), ctx["peaks"])
    return 100.0 * least / (t["busy_s"] / ctx["traced_steps"])
