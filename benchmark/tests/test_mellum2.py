"""The Mellum2 configuration, its traffic kind and its cell, rehearsed on
the CPU at a tiny size (``conftest.py``'s ``TINY`` is for the configurations
it names; this one brings its own)."""
import json
import os
import time

import numpy as np
import pytest

from benchmark.harness.cell import run_cell
from benchmark.harness.registry import Registry

CELL = "mellum2_train_seq8192"
# every number shrunk, the graph kept: one period of layers, 8:2 grouped
# heads, a window shorter than the sequence, 4 of 16 experts held, top-4
TINY_MELLUM2 = {
    "config": {
        "hidden_size": 64, "head_dim": 16, "num_attention_heads": 8,
        "num_key_value_heads": 2, "vocab_size": 256,
        "moe_intermediate_size": 32, "num_experts": 4,
        "num_experts_per_tok": 4, "sliding_window": 8,
        "published": {"num_hidden_layers": 28, "num_experts": 16,
                      "vocab_size": 1024},
        "build_strategy": {},
        "check": {"samples": 2,
                  "parameters": ["embed_tokens", "layer_0.attention.query.w",
                                 "layer_3.attention.key.w",
                                 "layer_1.router.w", "layer_2.experts.down"],
                  "loss_rel_tol": 1e-4, "grad_rel_l2_tol": 1e-3}},
    "mix": {"seq_len": 32, "samples_per_chip": 2},
}
# test_run.py looks every cell's configuration up in conftest's TINY as it
# is imported; conftest.py is not this configuration's to edit, so the entry
# is added here (this file is collected first)
from benchmark.tests.conftest import TINY  # noqa: E402
TINY.setdefault("mellum2_12b_a2_5b_train", TINY_MELLUM2)


def test_traffic_kind_is_a_pure_function_of_its_arguments():
    reg = Registry()
    mix = reg.mix(reg.cell(CELL)["traffic"])
    kind = reg.module("traffic_kinds", mix["kind"] + ".py")
    cfg = {"vocab_size": 24576}
    mix = dict(mix, seq_len=64)
    a = kind.generate(mix, cfg, 2 ** 31 + 11, 3)
    b = kind.generate(mix, cfg, 2 ** 31 + 11, 3)
    assert len(a) == mix["distinct_batches"] == 8
    for x, y in zip(a, b):
        assert sorted(x) == ["input_ids", "labels"]
        for name in x:
            assert x[name].shape == (3, 64) and x[name].dtype == np.int32
            np.testing.assert_array_equal(x[name], y[name])
        # the labels are the inputs shifted by one
        np.testing.assert_array_equal(x["input_ids"][:, 1:],
                                      x["labels"][:, :-1])
        assert 0 <= x["input_ids"].min() and x["labels"].max() < 24576
    assert not np.array_equal(a[0]["input_ids"], a[1]["input_ids"])
    other = kind.generate(mix, cfg, 2 ** 31 + 12, 3)
    assert not np.array_equal(a[0]["input_ids"], other[0]["input_ids"])
    check = kind.generate(mix, cfg, 2 ** 31 + 11, 3, n_batches=1, stream=1)
    assert len(check) == 1
    assert not np.array_equal(a[0]["input_ids"], check[0]["input_ids"])
    # ids spread over the whole held slice
    assert len(np.unique(a[0]["input_ids"])) > 150
    with pytest.raises(ValueError, match="unknown id distribution"):
        kind.generate(dict(mix, id_dist={"dist": "zipf", "a": 1.2}), cfg, 5, 3)


def test_mix_is_the_issues():
    reg = Registry()
    cell = reg.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mellum2_12b_a2_5b_train", "causal_lm_seq8192", 1)
    mix = reg.mix(cell["traffic"])
    assert (mix["kind"], mix["seq_len"], mix["samples_per_chip"],
            mix["distinct_batches"], mix["layout"]) == (
                "causal_lm", 8192, 1, 8, {})
    assert mix["length"] == {"dist": "full"}
    assert mix["id_dist"] == {"dist": "uniform"}


def test_configuration_states_source_cut_and_published_counts():
    reg = Registry()
    cfg, cfg_dir = reg.config("mellum2_12b_a2_5b_train")
    entry = reg._entry("configs", "mellum2_12b_a2_5b_train")
    assert cfg["source"] == entry["source"] and "Mellum2" in cfg["source"]
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                                "vocab_size": 98304}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 24576)
    for key in ("optimizer", "initializer_range", "mtp_head", "qk_norm",
                "router_aux_loss", "window_edge", "packing", "flags"):
        assert key in cfg["assumed"], key
    assert "four chips" in cfg["deployment"]
    assert len(cfg["check"]["parameters"]) >= 5 and cfg["check"]["why"]
    for name in ("model.py", "reference.py"):
        assert os.path.exists(os.path.join(cfg_dir, name))
    # every number of the published config under its own key, but the cut
    catalog = {"head_dim": 128, "hidden_size": 2304,
               "intermediate_size": 7168, "max_position_embeddings": 131072,
               "max_window_layers": 0, "moe_intermediate_size": 896,
               "num_attention_heads": 32, "num_experts_per_tok": 8,
               "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
               "sliding_window": 1024}
    assert {k: cfg[k] for k in catalog} == catalog
    assert cfg["rope_parameters"]["full_attention"]["factor"] == 16
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == 28


def test_new_metrics_list_the_cell_alone():
    reg = Registry()
    names = {"kernel.moe_ms_per_step": "mfu",
             "kernel.moe_gmm_roofline": "mfu",
             "kernel.causal_attention_roofline": "mfu",
             "moe.expert_load_max_over_mean": "samples_per_s_per_chip"}
    for m in reg.spec["per_layer"]:
        if m["name"] in names:
            assert m["workloads"] == [CELL] and m["moves"] == names[m["name"]]
            assert os.path.exists(os.path.join(
                reg.bench_dir, "layer_metrics", m["name"] + ".py"))
    assert names.keys() <= {m["name"] for m in reg.spec["per_layer"]}


def test_readers_find_nothing_without_a_trace_or_counts():
    reg = Registry()
    cfg, _ = reg.config("mellum2_12b_a2_5b_train")
    ctx = {"trace": None, "traced_steps": 0, "peaks": None,
           "cfg": dict(cfg, num_hidden_layers=0), "model": object()}
    for name in ("kernel.moe_ms_per_step", "kernel.moe_gmm_roofline",
                 "kernel.causal_attention_roofline",
                 "moe.expert_load_max_over_mean"):
        assert reg.module("layer_metrics", name + ".py").read(dict(ctx)) \
            is None, name


def test_cell_runs_shrunk_on_the_cpu_and_prints_the_contracts_line(capsys):
    line = run_cell(CELL, 2 ** 31 + 5, 1.0, 0, time.perf_counter(),
                    override=TINY_MELLUM2)
    json.dumps(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"samples_per_s_per_chip",
                                    "peak_hbm_gib", "setup_s"}
    assert line["device"]["platform"] == "cpu"
    # the drained counters reach the load reader
    reg = Registry()
    cfg, _ = reg.config("mellum2_12b_a2_5b_train")
    cfg.update(TINY_MELLUM2["config"])
    load = reg.module("layer_metrics",
                      "moe.expert_load_max_over_mean.py").read({"cfg": cfg})
    assert 1.0 <= load < 4.0
    gmm = reg.module("layer_metrics", "kernel.moe_gmm_roofline.py")
    rows = gmm.assignments_per_layer_step(cfg)
    # 64 tokens x top-4 of 16 experts, 4 held: 64 a step at even routing
    assert 16 < rows < 160
