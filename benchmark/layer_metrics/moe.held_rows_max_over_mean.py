"""Layer: kernels.  ``moe.expert_load_max_over_mean``'s reading (the fullest
held expert's rows over the mean of the held experts, of the layer where
that is largest, from the ``moe.layer_<i>.moe.tokens_per_expert.<e>`` gauges
the runner's drain publishes) of a configuration that counts its held
experts under ``n_routed_experts``: sigmoid scores, top-k of score +
correction bias.  ``None`` where the configuration has no such key or the
program keeps no such count."""
import os

from benchmark.harness import registry


def read(ctx):
    held = ctx["cfg"].get("n_routed_experts")
    if not held:
        return None
    return registry.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "moe.expert_load_max_over_mean.py")).read(
        {**ctx, "cfg": {**ctx["cfg"], "num_experts": held}})
