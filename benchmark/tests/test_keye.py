"""The Keye-VL-2.0 configuration and its cell, rehearsed on the CPU at a tiny
size (this configuration brings its own entry of ``conftest.TINY`` from
``test_0_keye_tiny.py``, which has to sort before ``test_run.py``)."""
import json
import os

from benchmark.harness.registry import Registry
from benchmark.tests.test_0_keye_tiny import CELL, CONFIG, TINY_KEYE

NEW_METRICS = {"kernel.dsa_attention_ms_per_step": "mfu",
               "kernel.dsa_attention_roofline": "mfu",
               "kernel.dsa_indexer_ms_per_step": "mfu",
               "kernel.dsa_indexer_roofline": "mfu",
               "dsa.tile_occupancy": "samples_per_s_per_chip"}


def test_mix_and_cell_are_the_issues():
    reg = Registry()
    cell = reg.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "causal_lm_seq16384", 1)
    mix = reg.mix(cell["traffic"])
    assert (mix["kind"], mix["seq_len"], mix["samples_per_chip"],
            mix["distinct_batches"], mix["layout"]) == (
                "causal_lm", 16384, 1, 8, {})
    assert mix["length"] == {"dist": "full"}
    assert mix["id_dist"] == {"dist": "uniform"}
    assert len(cell["why"]) <= 200


def test_configuration_keeps_every_published_number_but_the_cut():
    reg = Registry()
    cfg, cfg_dir = reg.config(CONFIG)
    entry = reg._entry("configs", CONFIG)
    assert cfg["source"] == entry["source"] and "Keye-VL-2.0" in cfg["source"]
    assert cfg["reduced"] == entry["reduced"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Keye-VL-2.0-30B-A3B")
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
    listed = {m["name"]: m for m in reg.spec["per_layer"]}
    for name, moved in NEW_METRICS.items():
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == moved
        assert listed[name]["layer"] == "kernels"
        assert os.path.exists(os.path.join(reg.bench_dir, "layer_metrics",
                                           name + ".py"))
    # appended: the accepted entries keep their places
    assert [m["name"] for m in reg.spec["per_layer"]][-5:] \
        == list(NEW_METRICS)
    assert reg.spec["workloads"][-1]["name"] == CELL
    assert reg.spec["configs"][-1]["name"] == CONFIG
    assert sum(w["chips"] == 4 for w in reg.spec["workloads"]) == 1


def test_readers_return_nothing_without_a_trace_or_on_another_program():
    """A run without a traced slice, and a program without the new ops (the
    parent's), give ``None`` and raise nothing."""
    reg = Registry()
    cfg, cfg_dir = reg.config(CONFIG)
    model = reg.module("configs", CONFIG, "model.py")
    ctx = {"cell": reg.cell(CELL), "cfg": cfg, "mix": reg.mix(
        "causal_lm_seq16384"), "model": model, "chips": 1, "batch": 1,
        "peaks": None, "trace": None, "traced_steps": 0}
    for name in NEW_METRICS:
        reader = reg.module("layer_metrics", name + ".py")
        if name == "dsa.tile_occupancy":
            continue                # reads the process's gauges: below
        assert reader.read(dict(ctx)) is None, name
    other = dict(ctx, cfg={"num_hidden_layers": 3})
    assert reg.module("layer_metrics", "dsa.tile_occupancy.py").read(
        dict(other, cfg={"num_hidden_layers": 0})) is None


def test_the_tiny_configuration_is_in_conftests_table():
    from benchmark.tests.conftest import TINY
    assert TINY[CONFIG] == TINY_KEYE
