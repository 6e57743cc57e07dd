"""The entry points that measure the chip must not hide its absence.

chip_smoke.py's control flow and exit codes run here on the CPU (--tiny);
what it proves about the chip, only a chip run can say.  bench.py's device
table and its refusal to run full-size on a CPU-only host ride along."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args, code=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)          # one CPU device, as in the sandbox
    cmd = [sys.executable, "-c", code] if code \
        else [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args]
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)


def test_tiny_passes_and_names_the_cpu():
    r = _smoke("--tiny")
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(r.stdout.strip().splitlines()[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert "NOT a chip result" in r.stdout
    for leg in ("eager", "executor", "kernels"):
        assert f"== {leg} ok" in r.stdout


def test_refuses_the_cpu_without_tiny():
    r = _smoke()
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_a_raised_leg_is_a_nonzero_exit_and_no_result():
    r = _smoke(code=(
        "import chip_smoke\n"
        "def boom(*a):\n"
        "    raise RuntimeError('leg failed')\n"
        "chip_smoke.leg_eager = boom\n"
        "chip_smoke.main(['--tiny'])\n"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "leg failed" in r.stderr


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(ROOT, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_peak_table_raises_on_an_unknown_device(bench):
    assert bench.device_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError, match="no published peak"):
        bench.device_peaks("TPU v99")
    with pytest.raises(ValueError, match="no published peak"):
        bench.device_peaks("cpu")


def test_bench_full_size_refuses_the_cpu(bench):
    assert bench.device_info(quick=True)["platform"] == "cpu"
    with pytest.raises(SystemExit, match="no accelerator"):
        bench.device_info(quick=False)


def test_bench_cpu_row_carries_no_mfu(bench, capsys):
    cpu = {"platform": "cpu", "device_kind": "cpu", "device_count": 1}
    bench.report("m", "tokens/sec/chip", 1000.0, 1e12, cpu)
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["platform"] == "cpu"
    assert not {"mfu", "mfu_measured", "vs_baseline"} & set(row)
    tpu = {"platform": "tpu", "device_kind": "TPU v5 lite",
           "device_count": 1}
    bench.report("m", "tokens/sec/chip", 1000.0, 98.5e12, tpu)
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["mfu"] == 0.5 and row["device_kind"] == "TPU v5 lite"
    with pytest.raises(ValueError, match="no published peak"):
        bench.report("m", "u", 1.0, 1.0, dict(tpu, device_kind="TPU v99"))
