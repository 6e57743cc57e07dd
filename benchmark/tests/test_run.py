"""The command itself: on a CPU it exits non-zero and prints no result; the
control flow of every cell, at a tiny size (the override exists for these
tests alone — run.py's command line cannot reach it)."""
import json
import os
import subprocess
import sys
import time

import pytest

from benchmark.harness import cell as cell_mod
from benchmark.harness.registry import ROOT, Registry
from benchmark.tests.conftest import TINY

CELLS = [w["name"] for w in Registry().spec["workloads"]]
TINY_OF = {w["name"]: TINY[w["config"]] for w in Registry().spec["workloads"]}


def test_run_py_refuses_the_cpu_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_run_py_has_no_size_option():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--help"],
        cwd=ROOT, capture_output=True, text=True, timeout=60)
    options = {w for w in proc.stdout.split() if w.startswith("--")}
    assert options == {"--help", "--workload", "--seed", "--seconds",
                       "--trace"}, options


@pytest.mark.parametrize("name", CELLS)
def test_cell_control_flow_at_tiny_size(name):
    line = cell_mod.run_cell(name, 11, 2.0, 0, time.perf_counter(),
                             override=TINY_OF[name])
    json.dumps(line)                         # the line is JSON as it stands
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 10
    # mfu needs a published peak, which the CPU has not: left out here
    assert set(line["metrics"]) == {"samples_per_s_per_chip", "peak_hbm_gib",
                                    "setup_s"}
    assert line["device"]["platform"] == "cpu"


def test_a_traced_run_without_device_operations_is_refused():
    # the CPU's trace has no /device:TPU plane: the reduction must refuse it
    # rather than report an idle share of nothing
    with pytest.raises(ValueError, match="no"):
        cell_mod.run_cell(CELLS[0], 12, 1.0, 1, time.perf_counter(),
                          override=TINY_OF[CELLS[0]])
