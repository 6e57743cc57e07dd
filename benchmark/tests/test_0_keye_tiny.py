"""The Keye-VL-2.0 configuration at a tiny size, entered into
``conftest.TINY``.

``test_run.py`` looks every cell's configuration up in ``TINY`` as it is
imported, and ``conftest.py`` is not this configuration's to edit: this file
sorts before ``test_run.py`` (as ``test_0_xing4_tiny.py`` does), so the entry
is there when that lookup runs.  The configuration's tests are in
``test_keye.py``."""
import json
import time

from benchmark.harness.cell import run_cell

CELL, CONFIG = "keye_vl2_train_seq16384", "keye_vl2_30b_a3b_train"
# every number shrunk, the graph kept: two layers, 4 : 2 heads of 8 under
# three rows of positions, 3 index heads of 8 that keep 6 of up to 24 keys,
# 4 of 16 experts held, top-8
TINY_KEYE = {
    "config": {
        "hidden_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 8,
        "moe_intermediate_size": 16, "vocab_size": 128, "num_experts": 4,
        "num_hidden_layers": 2, "expert_rows_bound": 8.0,
        "rope_scaling": {"mrope_section": [1, 2, 1],
                         "rope_type": "default", "type": "default"},
        "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 3,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 6},
        "published": {"num_hidden_layers": 48, "num_experts": 16,
                      "vocab_size": 1024},
        "build_strategy": {},
        "check": {"samples": 2,
                  "parameters": ["embed_tokens", "layer_0.attention.query.w",
                                 "layer_1.attention.indexer.query.w",
                                 "layer_0.attention.indexer.key.w",
                                 "layer_1.router.w", "layer_0.experts.down"],
                  "loss_rel_tol": 1e-4, "grad_rel_l2_tol": 1e-2}},
    "mix": {"seq_len": 24, "samples_per_chip": 2},
}
from benchmark.tests.conftest import TINY  # noqa: E402
TINY.setdefault(CONFIG, TINY_KEYE)


def test_cell_runs_shrunk_on_the_cpu_and_prints_the_contracts_line():
    line = run_cell(CELL, 2 ** 31 + 7, 1.0, 0, time.perf_counter(),
                    override=TINY_KEYE)
    json.dumps(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"samples_per_s_per_chip",
                                    "peak_hbm_gib", "setup_s"}
    assert line["device"]["platform"] == "cpu"
    # the drained gauges: the indexer's and the expert layers'
    from paddle_tpu.fluid import trace
    assert trace.gauge_value("dsa.layer_1.selected_keys_mean", -1.0) \
        == (21 + 18 * 6) / 24
    assert trace.gauge_value("dsa.layer_0.index_kl", -1.0) > 0
    assert trace.gauge_value("moe.layer_1.moe.steps", 0.0) > 0
