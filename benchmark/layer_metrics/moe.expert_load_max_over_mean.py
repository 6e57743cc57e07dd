"""Layer: kernels.  The fullest held expert's tokens over the mean of the
held experts, of the layer where that is largest: 1 is even load.  From the
counts the expert layer keeps on the device and the runner's drain publishes
as gauges (``moe.layer_<i>.moe.tokens_per_expert.<e>``), over every step
since start-up.  ``None`` where the program keeps no such count.  Each
layer's rows a step and its own ratio go to standard error."""
import sys


def read(ctx):
    try:
        from paddle_tpu.fluid import trace
    except ImportError:
        return None
    cfg = ctx["cfg"]
    worst = None
    for i in range(cfg.get("num_hidden_layers", 0)):
        counts = [trace.gauge_value(
            f"moe.layer_{i}.moe.tokens_per_expert.{e}", 0.0)
            for e in range(cfg.get("num_experts", 0))]
        if not counts or not sum(counts):
            continue
        ratio = max(counts) * len(counts) / sum(counts)
        steps = trace.gauge_value(f"moe.layer_{i}.moe.steps", 0.0) or 1.0
        print(f"[expert_load] layer_{i}: {sum(counts) / steps:.1f} rows a "
              f"step to {len(counts)} held experts, fullest {ratio:.3f} x "
              f"the mean, emptiest {min(counts) * len(counts) / sum(counts):.3f}"
              f" x", file=sys.stderr, flush=True)
        worst = ratio if worst is None else max(worst, ratio)
    return worst
