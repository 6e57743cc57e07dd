"""The Xing4.0 configuration and its cell, rehearsed on the CPU at a tiny
size (``conftest.py``'s ``TINY`` is for the configurations it names; this one
brings its own as ``test_mellum2.py`` does, from ``test_0_xing4_tiny.py``,
which has to sort before ``test_run.py``)."""
import json
import os

from benchmark.harness.registry import Registry
from benchmark.tests.test_0_xing4_tiny import CELL, CONFIG, TINY_XING4

def test_mix_and_cell_are_the_issues():
    reg = Registry()
    cell = reg.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "causal_lm_seq4096", 1)
    mix = reg.mix(cell["traffic"])
    assert (mix["kind"], mix["seq_len"], mix["samples_per_chip"],
            mix["distinct_batches"], mix["layout"]) == (
                "causal_lm", 4096, 1, 8, {})
    assert mix["length"] == {"dist": "full"}
    assert mix["id_dist"] == {"dist": "uniform"}
    assert len(cell["why"]) <= 200


def test_configuration_keeps_every_published_number_but_the_cut():
    reg = Registry()
    cfg, cfg_dir = reg.config(CONFIG)
    entry = reg._entry("configs", CONFIG)
    assert cfg["source"] == entry["source"] and "Xing4.0" in cfg["source"]
    assert cfg["reduced"] == entry["reduced"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value and cfg[key] != value
            else:
                assert cfg[key] == value, key
    for name in ("model.py", "reference.py"):
        assert os.path.exists(os.path.join(cfg_dir, name))


def test_new_metrics_list_the_cell_alone():
    reg = Registry()
    moves = {"kernel.mla_attention_roofline": "mfu",
             "kernel.mla_ms_per_step": "mfu",
             "kernel.hyper_connection_ms_per_step": "mfu",
             "kernel.hyper_connection_roofline": "mfu",
             "kernel.expert_layer_ms_per_step": "mfu",
             "moe.held_rows_max_over_mean": "samples_per_s_per_chip"}
    listed = {m["name"]: m for m in reg.spec["per_layer"]}
    for name, moved in moves.items():
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == moved
        assert os.path.exists(os.path.join(reg.bench_dir, "layer_metrics",
                                           name + ".py"))
    # appended: the accepted entries keep their places
    assert [m["name"] for m in reg.spec["per_layer"]][-6:] == list(moves)
    assert reg.spec["workloads"][-1]["name"] == CELL
    assert reg.spec["configs"][-1]["name"] == CONFIG




def test_the_tiny_configuration_is_in_conftests_table():
    from benchmark.tests.conftest import TINY
    assert TINY[CONFIG] == TINY_XING4
    assert TINY_XING4["config"]["check"]["set_parameters"] == {
        "*.hc.alpha": 1.0, "*.hc.b": 0.0}
