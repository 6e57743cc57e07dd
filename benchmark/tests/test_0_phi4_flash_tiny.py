"""The Phi-4-mini-flash configuration at a tiny size, entered into
``conftest.TINY``.

``test_run.py`` looks every cell's configuration up in ``TINY`` as it is
imported, and ``conftest.py`` is not this configuration's to edit: this file
sorts before ``test_run.py`` (as ``test_0_xing4_tiny.py`` does), so the entry
is there when that lookup runs.  The configuration's tests are in
``test_phi4_flash.py``."""
import json
import time

from benchmark.harness.cell import run_cell

CELL, CONFIG = "phi4_flash_train_seq4096", "phi4_mini_flash_train"
# every number shrunk, the graph kept: the six held layers (Mamba, windowed
# differential attention, the memory's Mamba, the shared keys' attention, a
# gated memory unit, a cross layer), 4 : 2 heads of 16 in pairs, a window of
# 16 over 64 tokens, 128 channels of 8 states
TINY_PHI4 = {
    "config": {
        "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "vocab_size": 96, "sliding_window": 16,
        "mamba": {"d_state": 8, "d_conv": 4, "expand": 2, "dt_rank": 4,
                  "dt_init": [0.001, 0.1]},
        "build_strategy": {},
        "check": {"samples": 2,
                  "parameters": ["embed_tokens", "layer_0.ssm.A_log",
                                 "layer_0.ssm.conv.w",
                                 "layer_16.ssm.in_proj.w",
                                 "layer_17.attention.qkv.w",
                                 "layer_1.attention.lambda_q1",
                                 "layer_18.gmu.in_proj.w",
                                 "layer_19.attention.q.w"],
                  "loss_rel_tol": 1e-4, "grad_rel_l2_tol": 1e-3}},
    "mix": {"seq_len": 64, "samples_per_chip": 2},
}
from benchmark.tests.conftest import TINY  # noqa: E402
TINY.setdefault(CONFIG, TINY_PHI4)


def test_cell_runs_shrunk_on_the_cpu_and_prints_the_contracts_line():
    line = run_cell(CELL, 2 ** 31 + 7, 1.0, 0, time.perf_counter(),
                    override=TINY_PHI4)
    json.dumps(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"samples_per_s_per_chip",
                                    "peak_hbm_gib", "setup_s"}
    assert line["device"]["platform"] == "cpu"
    # the drained gauges: the scans' and the lambdas'
    from paddle_tpu.fluid import trace
    for i in (0, 16):
        assert trace.gauge_value(f"ssm.layer_{i}.state_abs_max", -1.0) > 0
        assert 1e-3 < trace.gauge_value(f"ssm.layer_{i}.dt_mean", -1.0) < 0.2
    for i in (1, 17, 19):
        lam0 = 0.8 - 0.6 * 2.718281828459045 ** (-0.3 * i)
        assert abs(trace.gauge_value(f"diff_attention.layer_{i}.lambda", 9.0)
                   - lam0) < 0.3
    assert trace.metrics().counter("ssm.lowering.xla").value > 0
