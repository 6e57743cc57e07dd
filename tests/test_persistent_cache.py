"""Persistent compile cache (ISSUE 2): fresh-executor and fresh-process
warm starts under FLAGS_persistent_cache_dir; fingerprint invalidation."""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core, trace
from paddle_tpu.fluid import compile_cache as cc

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir(tmp_path):
    saved = core.get_flag("persistent_cache_dir")
    core.set_flags({"FLAGS_persistent_cache_dir": str(tmp_path)})
    yield str(tmp_path)
    core._FLAGS["persistent_cache_dir"] = saved


def _counters():
    m = trace.metrics()
    return (m.counter("executor.compile_cache_cold_miss").value,
            m.counter("executor.compile_cache_persistent_hit").value,
            m.counter("executor.compile_cache_miss").value)


def _build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 8])
        h = fluid.layers.fc(x, 4, act="relu")
        loss = fluid.layers.mean(h)
    return main, startup, loss


class TestPersistentCache:
    def test_fresh_executor_is_persistent_warm(self, cache_dir):
        """A second Executor in the same process misses its own in-memory
        cache but the persistent index already knows the key: zero cold
        misses, one persistent hit per program."""
        main, startup, loss = _build()
        feed = {"x": np.ones((16, 8), "float32")}
        exe1 = fluid.Executor()
        exe1.run(startup)
        exe1.run(main, feed=feed, fetch_list=[loss])
        c0, p0, m0 = _counters()
        exe2 = fluid.Executor()
        exe2.run(main, feed=feed, fetch_list=[loss])
        c1, p1, m1 = _counters()
        assert m1 - m0 == 1          # in-memory miss (fresh executor)
        assert c1 - c0 == 0          # ... but persistent-warm: no cold miss
        assert p1 - p0 == 1
        assert cc.persistent_cache().keys()

    def test_fingerprint_change_invalidates(self, cache_dir):
        main, startup, loss = _build()
        feed = {"x": np.ones((16, 8), "float32")}
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        c0, _, _ = _counters()
        # in-place attr rewrite (same op count): set_attr bumps the
        # version, the digest changes, and the persistent key misses
        scale_ops = [op for op in main.global_block().ops
                     if op.type == "scale"]
        mut = scale_ops[0] if scale_ops else main.global_block().ops[0]
        mut.set_attr("__salt__", 1.25)
        exe.run(main, feed=feed, fetch_list=[loss])
        c1, _, _ = _counters()
        assert c1 - c0 == 1          # cold again: program changed

    def test_index_metadata(self, cache_dir):
        main, startup, loss = _build()
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((4, 8), "float32")},
                fetch_list=[loss])
        pc = cc.persistent_cache()
        metas = [pc.get(k) for k in pc.keys()]
        assert all(m and "fingerprint" in m and "compile_seconds" in m
                   for m in metas)

    def test_second_process_zero_cold_misses(self, cache_dir):
        """Acceptance: a second process reusing FLAGS_persistent_cache_dir
        reports ZERO program-level cold misses for an identical
        program+bucket signature (and cold-compiles again once the
        program changes)."""
        code = (
            "import numpy as np\n"
            "import paddle_tpu.fluid as fluid\n"
            "from paddle_tpu.fluid import trace\n"
            "main, startup = fluid.Program(), fluid.Program()\n"
            "with fluid.program_guard(main, startup):\n"
            "    x = fluid.data('x', [-1, 8])\n"
            "    h = fluid.layers.fc(x, 4, act='relu')\n"
            "    loss = fluid.layers.mean({LOSS})\n"
            "exe = fluid.Executor()\n"
            "exe.run(startup)\n"
            "for n in (16, 7):\n"
            "    exe.run(main, feed={'x': np.ones((n, 8), 'float32')},\n"
            "            fetch_list=[loss])\n"
            "m = trace.metrics()\n"
            "print('COLD', m.counter('executor.compile_cache_cold_miss')"
            ".value,\n"
            "      'PHIT', m.counter('executor.compile_cache_persistent_hit')"
            ".value)\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   FLAGS_persistent_cache_dir=cache_dir,
                   FLAGS_shape_bucketing="1")

        def child(loss_expr):
            r = subprocess.run(
                [sys.executable, "-c", code.replace("{LOSS}", loss_expr)],
                env=env, cwd=_ROOT, capture_output=True, text=True,
                timeout=300)
            assert r.returncode == 0, r.stderr
            line = [ln for ln in r.stdout.splitlines()
                    if ln.startswith("COLD")][0].split()
            return int(line[1]), int(line[3])

        cold1, phit1 = child("h")
        assert cold1 == 3 and phit1 == 0    # startup + 2 buckets (16, 8)
        cold2, phit2 = child("h")
        assert cold2 == 0, "restart must be persistent-warm"
        assert phit2 == 3
        # a different program under the same dir cold-compiles
        cold3, _ = child("h * 2.0")
        assert cold3 > 0


class TestJaxCacheResolver:
    """Where jax's own compilation cache lives is decided in one place
    (compile_cache.jax_cache_dir): outside the code when
    JAX_COMPILATION_CACHE_DIR is set, else one fixed path."""

    @staticmethod
    def _spy(monkeypatch):
        import jax
        calls = []
        # record, do not apply: the session's jax config stays untouched
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: calls.append((k, v)))
        return calls

    def test_variable_set_means_code_never_sets_the_dir(self, monkeypatch):
        calls = self._spy(monkeypatch)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
        assert cc.enable_jax_cache() == "/x"
        assert cc.PersistentCache(os.path.join(_ROOT, ".jax_cache")).root
        keys = [k for k, _ in calls]
        assert "jax_compilation_cache_dir" not in keys
        # the thresholds are still zeroed: every entry is worth persisting
        assert "jax_persistent_cache_min_compile_time_secs" in keys

    def test_unset_is_one_fixed_path_in_every_process(self, monkeypatch):
        calls = self._spy(monkeypatch)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        fixed = os.path.join(_ROOT, ".jax_cache")
        assert cc.enable_jax_cache() == fixed
        assert ("jax_compilation_cache_dir", fixed) in calls
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        other = subprocess.run(
            [sys.executable, "-c",
             "from paddle_tpu.fluid import compile_cache as cc; "
             "print(cc.jax_cache_dir())"],
            cwd="/", env=dict(env, PYTHONPATH=_ROOT), capture_output=True,
            text=True, timeout=120)
        assert other.returncode == 0, other.stderr[-1000:]
        assert other.stdout.strip().splitlines()[-1] == fixed
