"""examples/ smoke: every example script runs to completion on CPU.
They are the user-facing entry documentation — a broken example is a
broken front door."""
import os
import subprocess
import sys

import pytest
pytestmark = pytest.mark.slow


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = ["mnist_static.py", "bert_dygraph.py", "ctr_boxps.py",
            "multi_chip.py", "fleet_decode.py"]


@pytest.mark.parametrize("script", EXAMPLES)
def test_example_runs(script):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)      # each script owns its device config
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", script)],
        capture_output=True, text=True, timeout=420, env=env, cwd=ROOT)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    assert "loss" in r.stdout or "saved" in r.stdout


def test_cpp_model_inspect(tmp_path):
    """The C++ ProgramDesc consumer (examples/cpp_model_inspect) builds
    with protoc+g++ and reads both a reference-layout __model__ and one
    exported by this framework — the wire format is language-neutral."""
    import shutil
    if not shutil.which("g++") or not shutil.which("protoc"):
        pytest.skip("native toolchain unavailable")
    probe = subprocess.run(
        ["g++", "-E", "-x", "c++", "-", "-o", os.devnull],
        input="#include <google/protobuf/message.h>\n",
        capture_output=True, text=True, timeout=120)
    if probe.returncode != 0:
        pytest.skip("libprotobuf dev headers unavailable")
    build = os.path.join(ROOT, "examples", "cpp_model_inspect",
                         "build.sh")
    r = subprocess.run(["sh", build], capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-1500:]
    exe = os.path.join(ROOT, "examples", "cpp_model_inspect",
                       "inspect_model")
    fixture = os.path.join(ROOT, "tests", "fixtures", "ref_fc_model",
                           "__model__")
    r = subprocess.run([exe, fixture], capture_output=True, text=True,
                       timeout=60)
    assert r.returncode == 0 and "OK" in r.stdout
    assert "op mul(" in r.stdout and "persistable" in r.stdout

    # and a model THIS framework exports parses identically
    gen = subprocess.run(
        [sys.executable, "-c", f"""
import jax; jax.config.update('jax_platforms', 'cpu')
import paddle_tpu.fluid as fluid
prog, st = fluid.Program(), fluid.Program()
with fluid.program_guard(prog, st):
    x = fluid.data('x', [-1, 4])
    out = fluid.layers.fc(x, 2)
exe = fluid.Executor(); exe.run(st)
fluid.io.save_inference_model(r'{tmp_path}', ['x'], [out], exe,
                              main_program=prog)
"""],
        capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert gen.returncode == 0, gen.stderr[-1000:]
    r = subprocess.run([exe, str(tmp_path / "__model__")],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0 and "OK" in r.stdout
    assert "op feed(" in r.stdout and "op versions:" in r.stdout


def test_cpp_trainer(tmp_path):
    """The C++ standalone trainer (reference fluid/train/demo analog):
    a host binary embedding CPython trains through the fluid API, the
    loss falls, and the exported __model__ parses."""
    import shutil
    if not shutil.which("g++") or not shutil.which("python3-config"):
        pytest.skip("native toolchain unavailable")
    probe = subprocess.run(["python3-config", "--embed", "--ldflags"],
                           capture_output=True, text=True, timeout=60)
    if probe.returncode != 0:
        pytest.skip("libpython embed config unavailable")
    build = os.path.join(ROOT, "examples", "cpp_trainer", "build.sh")
    r = subprocess.run(["sh", build], capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-1500:]
    exe = os.path.join(ROOT, "examples", "cpp_trainer", "cpp_trainer")
    out_dir = str(tmp_path / "m")
    env = dict(os.environ, CPP_TRAINER_PLATFORM="cpu")
    env.pop("XLA_FLAGS", None)          # the trainer owns device config
    env["PYTHONPATH"] = ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    r = subprocess.run([exe, out_dir], capture_output=True, text=True,
                       timeout=400, env=env)
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-800:])
    assert "OK" in r.stdout
    assert os.path.exists(os.path.join(out_dir, "__model__"))


def test_serve_reference_model_example():
    """The migration example serves the reference-layout fixture."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "examples", "serve_reference_model.py"),
         os.path.join(ROOT, "tests", "fixtures", "ref_fc_model")],
        capture_output=True, text=True, timeout=420, env=env, cwd=ROOT)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    assert "softmax_out" in r.stdout
