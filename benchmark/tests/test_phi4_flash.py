"""The Phi-4-mini-flash configuration and its cell, rehearsed on the CPU at a
tiny size (this configuration brings its own entry of ``conftest.TINY`` from
``test_0_phi4_flash_tiny.py``, which has to sort before ``test_run.py``)."""
import json
import os

from benchmark.harness.registry import Registry
from benchmark.tests.test_0_phi4_flash_tiny import CELL, CONFIG, TINY_PHI4

NEW_METRICS = ("kernel.ssm_scan_ms_per_step", "kernel.ssm_scan_roofline",
               "kernel.ssm_layer_ms_per_step",
               "kernel.diff_attention_ms_per_step",
               "kernel.diff_attention_roofline", "kernel.gmu_ms_per_step")


def test_mix_and_cell_are_the_issues():
    reg = Registry()
    cell = reg.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "causal_lm_seq4096", 1)
    mix = reg.mix(cell["traffic"])
    assert (mix["kind"], mix["seq_len"], mix["samples_per_chip"],
            mix["distinct_batches"], mix["layout"]) == (
                "causal_lm", 4096, 1, 8, {})
    assert len(cell["why"]) <= 200


def test_configuration_keeps_every_published_number_but_the_cut():
    reg = Registry()
    cfg, cfg_dir = reg.config(CONFIG)
    entry = reg._entry("configs", CONFIG)
    assert cfg["source"] == entry["source"]
    assert "Phi-4-mini-flash-reasoning" in cfg["source"]
    assert cfg["reduced"] == entry["reduced"] == ["num_hidden_layers",
                                                  "vocab_size"]
    assert cfg["held_layers"] == [0, 1, 16, 17, 18, 19]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Phi-4-mini-flash-reasoning")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value and cfg[key] != value
            else:
                assert cfg[key] == value, key
    for name in ("model.py", "reference.py"):
        assert os.path.exists(os.path.join(cfg_dir, name))
    for key in ("mamba", "biases", "memory", "lambda_init", "lambda_std",
                "initializer_range", "conv_init", "optimizer", "packing",
                "residual_stream"):
        assert key in cfg["assumed"], key
    check = cfg["check"]
    assert set(check["grad_rel_l2_tol"]) == set(check["parameters"])
    assert len(check["parameters"]) >= 10


def test_new_metrics_list_the_cell_alone():
    reg = Registry()
    listed = {m["name"]: m for m in reg.spec["per_layer"]}
    for name in NEW_METRICS:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "mfu"
        assert listed[name]["layer"] == "kernels"
        assert listed[name]["unit"] == ("%" if name.endswith("roofline")
                                        else "ms")
        assert os.path.exists(os.path.join(reg.bench_dir, "layer_metrics",
                                           name + ".py"))
    assert sum(w["chips"] == 4 for w in reg.spec["workloads"]) == 1
    assert sum(w["config"] == CONFIG for w in reg.spec["workloads"]) == 1


def test_readers_return_nothing_without_a_trace_or_on_another_program():
    """A run without a traced slice, and a program without the new ops (the
    parent's), give ``None`` and raise nothing."""
    reg = Registry()
    cfg, _ = reg.config(CONFIG)
    model = reg.module("configs", CONFIG, "model.py")
    ctx = {"cell": reg.cell(CELL), "cfg": cfg,
           "mix": reg.mix("causal_lm_seq4096"), "model": model, "chips": 1,
           "batch": 1, "peaks": {"bf16_flops_per_s": 197e12,
                                 "hbm_bytes_per_s": 819e9},
           "trace": None, "traced_steps": 0}
    for name in NEW_METRICS:
        assert reg.module("layer_metrics", name + ".py").read(
            dict(ctx)) is None, name
    # a traced program that holds none of the new ops or names
    table = {"labels": [{"label": "mul", "seconds": 1.0}],
             "instances": [{"instance": "layer_0.ffn.tmp_0",
                            "seconds": 1.0}]}
    traced = dict(ctx, trace={"busy_s": 1.0}, traced_steps=4,
                  program_ops=table)
    for name in NEW_METRICS:
        assert reg.module("layer_metrics", name + ".py").read(
            dict(traced)) is None, name


def test_readers_read_their_rows():
    reg = Registry()
    cfg, _ = reg.config(CONFIG)
    model = reg.module("configs", CONFIG, "model.py")
    mix = reg.mix("causal_lm_seq4096")
    table = {
        "labels": [{"label": "selective_scan", "seconds": 0.004},
                   {"label": "selective_scan_grad", "seconds": 0.016},
                   {"label": "fused_multihead_attention", "seconds": 0.02},
                   {"label": "fused_multihead_attention_grad",
                    "seconds": 0.04},
                   {"label": "mul", "seconds": 1.0}],
        "instances": [
            {"instance": "layer_0.ssm.scan.tmp_0", "seconds": 0.01},
            {"instance": "layer_16.ssm.in_proj.tmp_0@GRAD", "seconds": 0.03},
            {"instance": "layer_1.attention.kernel.tmp_0", "seconds": 0.02},
            {"instance": "layer_18.gmu.gate.tmp_0", "seconds": 0.006},
            {"instance": "layer_0.mlp.down.tmp_0", "seconds": 0.5}]}
    ctx = {"cell": reg.cell(CELL), "cfg": cfg, "mix": mix, "model": model,
           "chips": 1, "batch": 1,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "trace": {"busy_s": 1.0}, "traced_steps": 2, "program_ops": table}

    def read(name):
        return reg.module("layer_metrics", name + ".py").read(dict(ctx))
    assert abs(read("kernel.ssm_scan_ms_per_step") - 10.0) < 1e-9
    assert abs(read("kernel.ssm_layer_ms_per_step") - 20.0) < 1e-9
    assert abs(read("kernel.diff_attention_ms_per_step") - 10.0) < 1e-9
    assert abs(read("kernel.gmu_ms_per_step") - 3.0) < 1e-9
    _, nbytes = model.ssm_scan_flops_and_bytes(cfg, mix)
    assert abs(read("kernel.ssm_scan_roofline")
               - 100.0 * (nbytes / 819e9) / 0.010) < 1e-6
    flops = 3.0 * model.attention_flops_per_sample(cfg, mix)
    assert abs(read("kernel.diff_attention_roofline")
               - 100.0 * (flops / 197e12) / 0.030) < 1e-6


def test_the_tiny_configuration_is_in_conftests_table():
    from benchmark.tests.conftest import TINY
    assert TINY[CONFIG] == TINY_PHI4
