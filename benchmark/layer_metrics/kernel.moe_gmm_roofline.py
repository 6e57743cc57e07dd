"""Layer: kernels.  The grouped matmuls' share of their roofline, in percent:
the least time the chip could take for a step's grouped matmuls, forward and
backward (the larger of their FLOPs over the peak FLOP/s and their bytes over
the peak bytes/s, from the configuration's ``moe_gmm_flops_and_bytes`` at the
assignments to held experts the device itself counted, a step and a layer),
over the device time of the ``moe_grouped_matmul`` ops and their grads a
step.  ``None`` where the program has no such op or keeps no such count."""
from benchmark.harness import program_ops
from benchmark.harness.peaks import roofline_seconds

TYPES = ("moe_grouped_matmul", "moe_grouped_matmul_grad")


def assignments_per_layer_step(cfg):
    """Mean rows a layer's held experts received a step, from the gauges
    the runner's drain publishes (``moe.layer_<i>.moe.…``), or None."""
    try:
        from paddle_tpu.fluid import trace
    except ImportError:
        return None
    per_layer = []
    for i in range(cfg["num_hidden_layers"]):
        pre = f"moe.layer_{i}.moe."
        steps = trace.gauge_value(pre + "steps", 0.0)
        if not steps:
            return None
        rows = sum(trace.gauge_value(f"{pre}tokens_per_expert.{e}", 0.0)
                   for e in range(cfg["num_experts"]))
        per_layer.append(rows / steps)
    return sum(per_layer) / len(per_layer) if per_layer else None


def read(ctx):
    t = program_ops.table(ctx)
    if t is None or ctx["peaks"] is None:
        return None
    seconds = sum(r["seconds"] for r in t["labels"] if r["label"] in TYPES)
    assignments = assignments_per_layer_step(ctx["cfg"])
    if not seconds or not assignments:
        return None
    least, _bound = roofline_seconds(
        *ctx["model"].moe_gmm_flops_and_bytes(ctx["cfg"], assignments),
        ctx["peaks"])
    return 100.0 * least / (seconds / ctx["traced_steps"])
