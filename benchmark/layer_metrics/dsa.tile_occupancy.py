"""Layer: kernels.  The share of the causal 512 x 512 tiles that hold at
least one selected (query, key) pair, of the layer where it is largest: what
a block-skipping attention kernel still has to visit (1: nothing to skip).
From the gauges the indexer keeps on the device and the runner's drain
publishes (``dsa.layer_<i>.tile_occupancy``, the last step's); each layer's
reading goes to standard error with its mean selected keys a query and its
indexer loss.  ``None`` where the program keeps no such gauge."""
import sys


def read(ctx):
    try:
        from paddle_tpu.fluid import trace
    except ImportError:
        return None
    worst = None
    for i in range(ctx["cfg"].get("num_hidden_layers", 0)):
        share = trace.gauge_value(f"dsa.layer_{i}.tile_occupancy", None)
        if share is None:
            continue
        print(f"[dsa] layer_{i}: tile occupancy {share:.4f}, "
              f"{trace.gauge_value(f'dsa.layer_{i}.selected_keys_mean', 0.0):.4f}"
              f" selected keys a query, index KL "
              f"{trace.gauge_value(f'dsa.layer_{i}.index_kl', 0.0):.5f}",
              file=sys.stderr, flush=True)
        worst = share if worst is None else max(worst, share)
    return worst
