"""BENCHMARK.json against the static rules of the benchmark's contract (the
ones a file can be checked for without a chip), so that a later PR that adds
an entry sees a refusal here before the driver's."""
import json
import os
import re

from benchmark.harness.registry import ROOT, Registry

SPEC = Registry().spec
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(SPEC["run_seconds"], int) \
        and 1 <= SPEC["run_seconds"] <= 51
    assert 1 <= len(SPEC["paths"]) <= 16 and len(SPEC["command"]) <= 32
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    for word in SPEC["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:              # a file of the repo: under paths
            assert any(word.startswith(p + "/") for p in SPEC["paths"])
    assert 1 <= len(SPEC["configs"]) <= 24
    assert 2 <= len(SPEC["workloads"]) <= 24
    assert 1 <= len(SPEC["end_to_end"]) <= 16
    assert 1 <= len(SPEC["per_layer"]) <= 128


def test_names_are_plain_and_used_once():
    names = [e["name"] for section in ("configs", "workloads", "end_to_end",
                                       "per_layer") for e in SPEC[section]]
    assert all(NAME.match(n) for n in names), names
    assert len(names) == len(set(names))
    for section in ("configs", "workloads"):
        for e in SPEC[section]:
            assert len(e["why"]) <= 200, (e["name"], len(e["why"]))


def test_configs_and_cells():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used, f"{c['name']} is used by no cell"
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert isinstance(c["reduced"], list)
        assert sorted(cfg.get("reduced", [])) == sorted(c["reduced"])
        for key in c["reduced"]:     # a width is never reduced
            assert not re.search(r"(_dim|_rank|_size)$", key), key
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] in (1, 4) for w in SPEC["workloads"])
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 4)


def test_metrics():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and m["moves"] in e2e
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert m["better"] in ("higher", "lower")
        assert set(m.get("workloads", cells)) <= cells
    reg = Registry()
    for cell in cells:               # what every cell has to report
        mine = {m["name"] for m in reg.metrics_of("end_to_end", cell)}
        assert "setup_s" in mine and len(mine) >= 2
        layer = [m for m in reg.metrics_of("per_layer", cell)
                 if m["moves"] in mine]
        assert layer, f"{cell} reports no per-layer metric"


def test_files_under_paths_have_plain_names():
    for p in SPEC["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert PATH.match(rel), rel
