"""Layer: kernels.  The selective scans' share of what the memory could
stream, in percent: the least time the chip could take for a step's scans,
forward and backward, from the configuration's ``ssm_scan_flops_and_bytes``
(x, dt, B, C read and y written forward; those and y's gradient read and
four gradients written backward, in bfloat16) over the device time of the
``selective_scan`` ops and their grads a step.  The recurrence is S
sequential steps of vector-unit work, 7 operations a (token, channel, state)
forward, and ``harness/peaks.py`` has no vector-unit peak: the bytes bound
this roofline by construction, so it says how far the scan is from being as
cheap as reading its operands, and single digits to 20 % are what a
latency-bound kernel reads.  The program's operands are float32 (the op is
on the AMP black list), so it moves twice the bytes counted here.  ``None``
where the program has no such op or the configuration no such function."""
import os

from benchmark.harness import registry
from benchmark.harness.peaks import roofline_seconds


def read(ctx):
    model = ctx["model"]
    if ctx["peaks"] is None or not hasattr(model, "ssm_scan_flops_and_bytes"):
        return None
    seconds = registry.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "kernel.ssm_scan_ms_per_step.py")).seconds_per_step(ctx)
    if not seconds:
        return None
    per_chip = ctx["batch"] // ctx["chips"]
    ops, nbytes = model.ssm_scan_flops_and_bytes(ctx["cfg"], ctx["mix"])
    least, _bound = roofline_seconds(per_chip * ops, per_chip * nbytes,
                                     ctx["peaks"])
    return 100.0 * least / seconds
