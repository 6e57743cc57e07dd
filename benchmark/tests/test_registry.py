"""The harness finds a cell, configuration, mix, loop and per-layer metric
that a later PR adds as new files plus entries in BENCHMARK.json, with no
edit to a file that is there."""
import json
import os
import shutil

import pytest

from benchmark.harness import cell as cell_mod
from benchmark.harness.registry import BENCH_DIR, ROOT, Registry


@pytest.fixture
def grown(tmp_path):
    """A copy of the benchmark with one of everything added as new files."""
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)

    cfg_dir = bench / "configs" / "dummy"
    cfg_dir.mkdir()
    (cfg_dir / "config.json").write_text(json.dumps(
        {"name": "dummy", "loop": "dummy_loop", "width": 3}))
    (bench / "traffic" / "dummy_mix.json").write_text(json.dumps(
        {"kind": "dummy_kind", "n": 5}))
    (bench / "traffic" / "dummy_mix_long.json").write_text(json.dumps(
        {"base": "dummy_mix", "n": 7}))
    (bench / "loops" / "dummy_loop.py").write_text(
        "def run(cell, cfg, cfg_dir, mix, reg, seed, seconds, trace, "
        "t_start, allow_cpu=False, out_dir=None):\n"
        "    kind = reg.module('traffic_kinds', mix['kind'] + '.py')\n"
        "    items = kind.generate(mix, cfg, seed)\n"
        "    return {'correct': True, 'attempted': len(items), 'failed': 0,\n"
        "            'end_to_end': {'setup_s': 1.0, 'dummy_rate': 2.0},\n"
        "            'device': {'platform': 'none', 'kind': 'none',\n"
        "                       'count': 1, 'memory_peak_bytes': 0},\n"
        "            'layer_ctx': {'items': items, 'trace': {\n"
        "                'busy_s': 1.0, 'window_s': 2.0, 'ops': {'a': 1.0},\n"
        "                'devices': {'d': {'gaps': [(0.0, 1.0, 'x')]}}}}}\n")
    (bench / "traffic_kinds" / "dummy_kind.py").write_text(
        "def generate(mix, cfg, seed):\n"
        "    return [seed + i * cfg['width'] for i in range(mix['n'])]\n")
    (bench / "layer_metrics" / "dummy.items.py").write_text(
        "def read(ctx):\n    return float(sum(ctx['items']))\n")
    (bench / "layer_metrics" / "dummy.nothing.py").write_text(
        "def read(ctx):\n    return None\n")

    spec["configs"].append({"name": "dummy", "source": "none", "reduced": [],
                            "file": "benchmark/configs/dummy/config.json",
                            "why": "test"})
    spec["workloads"].append({"name": "dummy_cell", "config": "dummy",
                              "traffic": "dummy_mix", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] != "setup_s":       # the old metrics stay with old cells
            m["workloads"] = [w["name"] for w in spec["workloads"][:-1]]
    spec["end_to_end"].append({"name": "dummy_rate", "unit": "1/s",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["dummy_cell"]})
    for m in spec["per_layer"]:
        m.setdefault("workloads", [w["name"] for w in spec["workloads"][:-1]])
    for name in ("dummy.items", "dummy.nothing"):
        spec["per_layer"].append({"name": name, "unit": "n",
                                  "better": "higher", "layer": "dummy",
                                  "source": "program_counter",
                                  "moves": "dummy_rate",
                                  "workloads": ["dummy_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return Registry(root=str(tmp_path), bench_dir=str(bench))


def test_new_cell_runs_end_to_end_without_an_edit(grown):
    line = cell_mod.run_cell("dummy_cell", 10, 1.0, 0, 0.0, reg=grown)
    assert line["correct"] and line["attempted"] == 5
    assert line["metrics"] == {
        "setup_s": {"value": 1.0, "unit": "s"},
        "dummy_rate": {"value": 2.0, "unit": "1/s"}}


def test_new_layer_metric_is_read_by_its_own_file(grown):
    line = cell_mod.run_cell("dummy_cell", 10, 1.0, 1, 0.0, reg=grown)
    # 10 + 13 + 16 + 19 + 22; the reader that finds nothing is left out
    assert line["metrics"] == {"dummy.items": {"value": 80.0, "unit": "n"}}
    assert line["device"]["busy_s"] == 1.0
    assert line["device"]["window_s"] == 2.0
    assert line["breakdown"] == {"device_ops": [["a", 1.0]],
                                 "idle_gaps": [["x", 1.0]]}


def test_old_cells_still_resolve_beside_the_new_one(grown):
    cell = grown.cell("bert_base_seq128")
    cfg, cfg_dir = grown.config(cell["config"])
    assert cfg["hidden_size"] == 768 and os.path.isdir(cfg_dir)
    assert grown.mix(cell["traffic"])["seq_len"] == 128
    names = [m["name"] for m in grown.metrics_of("per_layer",
                                                 "bert_base_seq128")]
    assert "dummy.items" not in names and "step_roofline" in names


def test_a_mix_states_only_what_differs_from_its_base(grown):
    assert grown.mix("dummy_mix_long") == {"kind": "dummy_kind", "n": 7}
    # the one such mix today: seq128's traffic under another layout
    one, four = Registry().mix("pretrain_seq128"), \
        Registry().mix("pretrain_seq128_dp4")
    assert four["layout"] == {"sharding": "dp"} and one["layout"] == {}
    assert {k: v for k, v in four.items() if k not in ("layout", "why")} \
        == {k: v for k, v in one.items() if k not in ("layout", "why")}


def test_every_listed_file_exists():
    reg = Registry()
    for c in reg.spec["configs"]:
        cfg, cfg_dir = reg.config(c["name"])
        for f in ("model.py", "reference.py"):
            assert os.path.exists(os.path.join(cfg_dir, f)), (c["name"], f)
        assert os.path.exists(os.path.join(BENCH_DIR, "loops",
                                           cfg["loop"] + ".py"))
    for w in reg.spec["workloads"]:
        mix = reg.mix(w["traffic"])
        assert os.path.exists(os.path.join(BENCH_DIR, "traffic_kinds",
                                           mix["kind"] + ".py"))
    for m in reg.spec["per_layer"]:
        assert os.path.exists(os.path.join(BENCH_DIR, "layer_metrics",
                                           m["name"] + ".py")), m["name"]


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="no workloads entry"):
        Registry().cell("no_such_cell")
