"""The Xing4.0 configuration at a tiny size, entered into ``conftest.TINY``.

``test_run.py`` looks every cell's configuration up in ``TINY`` as it is
imported, and ``conftest.py`` is not this configuration's to edit: this file
sorts before ``test_run.py`` (as ``test_mellum2.py`` does by its name), so
the entry is there when that lookup runs.  The configuration's tests are in
``test_xing4.py``."""
import json
import time

from benchmark.harness.cell import run_cell

CELL, CONFIG = "xing4_train_seq4096", "xing4_29b_a4b_train"
# every number shrunk, the graph kept: a dense layer and two expert layers,
# heads of 8 + 4 scores and 8 values, 4 of 16 experts held, top-4, one shared
TINY_XING4 = {
    "config": {
        "hidden_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 4, "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 4, "v_head_dim": 8, "q_lora_rank": 16,
        "kv_lora_rank": 12, "intermediate_size": 48,
        "moe_intermediate_size": 16, "vocab_size": 128,
        "n_routed_experts": 4, "num_hidden_layers": 3,
        "published": {"num_hidden_layers": 40, "first_k_dense_replace": 2,
                      "n_routed_experts": 16, "vocab_size": 1024,
                      "num_nextn_predict_layers": 1},
        "build_strategy": {},
        "check": {"samples": 2,
                  "set_parameters": {"*.hc.alpha": 1.0, "*.hc.b": 0.0},
                  "parameters": ["embed_tokens", "layer_1.attn.hc.phi",
                                 "layer_0.attention.q_b.w",
                                 "layer_2.attention.kv_a.w",
                                 "layer_2.router.w", "layer_1.experts.down"],
                  "loss_rel_tol": 1e-4, "grad_rel_l2_tol": 1e-2}},
    "mix": {"seq_len": 16, "samples_per_chip": 2},
}
from benchmark.tests.conftest import TINY  # noqa: E402
TINY.setdefault(CONFIG, TINY_XING4)



def test_cell_runs_shrunk_on_the_cpu_and_prints_the_contracts_line():
    line = run_cell(CELL, 2 ** 31 + 7, 1.0, 0, time.perf_counter(),
                    override=TINY_XING4)
    json.dumps(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"samples_per_s_per_chip",
                                    "peak_hbm_gib", "setup_s"}
    assert line["device"]["platform"] == "cpu"
    # the drained gauges: the mixers' and the expert layers'
    from paddle_tpu.fluid import trace
    assert 0.0 <= trace.gauge_value(
        "hc.layer_2.ffn.res_row_sum_error", -1.0) < 1e-4
    assert trace.gauge_value("moe.layer_1.moe.steps", 0.0) > 0
