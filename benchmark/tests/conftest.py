"""The benchmark's own tests: ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q`` from the root of the checkout.  Not part of tier-1.

They run on the CPU at tiny sizes: control flow, counts and correctness
against the references, never a speed.  Four virtual CPU devices stand in
for the four chips of the one cell that spans chips.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# BERT at a size the CPU runs in seconds: every number shrunk, the graph kept
TINY_BERT = {
    "config": {
        "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 256,
        "max_position_embeddings": 64,
        "check": {"samples": 4,
                  "parameters": ["word_embedding", "layer_0.attention.query.w",
                                 "layer_1.ffn.output.w"],
                  "loss_rel_tol": 1e-3, "grad_rel_l2_tol": 6e-2}},
    "mix": {"seq_len": 32, "samples_per_chip": 4,
            "max_predictions_per_seq": 5},
}

# ResNet-50 likewise: the same stages and blocks' structure, narrow and short
TINY_RESNET = {
    "config": {
        "image_size": 64, "stem_width": 8, "stage_blocks": [1, 2, 1, 1],
        "stage_widths": [8, 16, 32, 64], "num_classes": 10,
        "check": {"samples": 4,
                  "set_parameters": {"stage_*.conv3.bn.scale": 0.1},
                  "parameters": ["conv1.w", "stage_0.block_0.conv2.w",
                                 "stage_1.block_0.shortcut.w",
                                 "stage_3.block_0.conv2.w",
                                 "stage_3.block_0.conv3.bn.scale", "fc.w"],
                  "loss_rel_tol": 1e-2,
                  "grad_rel_l2_tol": {"conv1.w": 0.5,
                                      "stage_0.block_0.conv2.w": 0.5,
                                      "stage_1.block_0.shortcut.w": 0.5,
                                      "stage_3.block_0.conv2.w": 0.5,
                                      "stage_3.block_0.conv3.bn.scale": 0.3,
                                      "fc.w": 0.05}}},
    "mix": {"image_size": 64, "samples_per_chip": 4},
}
TINY = {"bert_base_pretrain": TINY_BERT, "resnet50": TINY_RESNET}
