"""CI smoke gate: import, 5-step MNIST static train, dygraph step,
op-sweep subset, DataLoader workers, `bench.py --quick` on CPU.

Run: python tools/ci_smoke.py      (exit 0 = healthy)
Kept minutes-cheap so it can gate every commit; the full suite
(`pytest tests/`) is the nightly tier."""
from __future__ import annotations

import os
import subprocess
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)       # runnable as `python tools/ci_smoke.py`


def step(name):
    print(f"[smoke] {name}", flush=True)


def main():
    t0 = time.time()
    # a CPU gate wherever it runs; the children inherit the variable
    os.environ["JAX_PLATFORMS"] = "cpu"

    step("import + version")
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import compile_cache
    assert paddle.__version__
    # every gate below — and every child it starts — shares jax's one
    # compilation cache and keeps its program-level index beside it
    cache_root = compile_cache.enable_jax_cache()

    step("static 5-step MNIST-shaped train (loss falls)")
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = fluid.data("x", [-1, 1, 8, 8])
        y = fluid.data("y", [-1, 1], dtype="int64")
        h = fluid.layers.fc(fluid.layers.reshape(x, [-1, 64]), 32,
                            act="relu")
        logits = fluid.layers.fc(h, 10)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.AdamOptimizer(1e-2).minimize(loss)
    exe = fluid.Executor()
    exe.run(startup)
    rng = np.random.RandomState(0)
    xs = rng.randn(64, 1, 8, 8).astype("float32")
    ys = rng.randint(0, 10, (64, 1)).astype("int64")
    for i in range(64):
        xs[i, 0, ys[i, 0] % 8, :] += 2.0
    losses = []
    for i in range(5):
        lv, = exe.run(main_p, feed={"x": xs, "y": ys}, fetch_list=[loss])
        losses.append(float(np.asarray(lv).ravel()[0]))
    assert losses[-1] < losses[0], losses

    step("dygraph train step + backward")
    from paddle_tpu.dygraph import base as dybase
    from paddle_tpu import nn, optimizer as opt
    dybase.enable_dygraph()
    try:
        net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
        o = opt.Adam(1e-3, parameters=net.parameters())
        xb = dybase.to_variable(rng.randn(8, 16).astype("float32"))
        out = net(xb)
        l2 = paddle.nn.functional.mse_loss(
            out, dybase.to_variable(np.zeros((8, 4), "float32")))
        l2.backward()
        o.step()
        assert np.isfinite(float(l2.numpy()))
    finally:
        dybase.disable_dygraph()

    step("DataLoader worker pool")
    from paddle_tpu.fluid.reader import DataLoader

    class DS:
        def __len__(self):
            return 32

        def __getitem__(self, i):
            return np.full((4,), float(i), "float32"), np.int64(i)

    n = sum(1 for _ in DataLoader(DS(), batch_size=8, num_workers=2))
    assert n == 4, n

    step("op-sweep subset (grad checks)")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "tests/test_op_grads_auto.py::test_full_registry_accounting",
         "tests/test_op_grads_auto.py::test_grad[matmul]",
         "tests/test_op_grads_auto.py::test_grad[softmax]",
         "tests/test_op_grads_auto.py::test_grad[conv2d]",
         "tests/test_op_grads_auto.py::test_grad[layer_norm]",
         "tests/test_op_grads_auto.py::test_grad[fused_dropout_add]"],
        cwd=_ROOT)
    assert r.returncode == 0, "op-sweep subset failed"

    step("AOT artifact served framework-free (examples/aot_serve.py)")
    import tempfile
    from paddle_tpu.fluid import io as fio
    from paddle_tpu.inference import (AnalysisConfig, create_predictor,
                                      save_aot_model)
    with tempfile.TemporaryDirectory() as td:
        mdir = os.path.join(td, "m")
        test_p = main_p.clone(for_test=True)
        fio.save_inference_model(mdir, ["x"], [logits], exe,
                                 main_program=test_p)
        pred = create_predictor(AnalysisConfig(mdir))
        adir = os.path.join(td, "aot")
        save_aot_model(adir, pred, {"x": xs[:4]})
        r = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "examples",
                                          "aot_serve.py"),
             adir, "--random"],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert r.returncode == 0, r.stderr
        assert "served without paddle_tpu" in r.stdout

    step("observability: traced 2-op program -> schema-valid timeline "
         "(1 compile miss, >=1 hit)")
    import importlib.util
    code = (
        "import numpy as np\n"
        "import paddle_tpu.fluid as fluid\n"
        "main, startup = fluid.Program(), fluid.Program()\n"
        "with fluid.program_guard(main, startup):\n"
        "    x = fluid.data('x', [4])\n"
        "    y = fluid.layers.scale(x, scale=2.0)\n"
        "    z = fluid.layers.mean(y)\n"
        "exe = fluid.Executor()\n"
        "for _ in range(2):\n"
        "    exe.run(main, feed={'x': np.ones(4, 'float32')},\n"
        "            fetch_list=[z])\n")
    with tempfile.TemporaryDirectory() as td:
        tj = os.path.join(td, "timeline.json")
        r = subprocess.run(
            [sys.executable, "-c", code],
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     FLAGS_enable_trace="1", FLAGS_trace_path=tj),
            cwd=_ROOT, capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr
        spec = importlib.util.spec_from_file_location(
            "timeline", os.path.join(_ROOT, "tools", "timeline.py"))
        tl = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tl)
        evs = tl.validate_timeline(tj)
        assert evs, "timeline is empty"
        names = [e.get("name") for e in evs]
        assert names.count("compile_cache_miss") == 1, names
        assert names.count("compile_cache_hit") >= 1, names
        assert any(e.get("cat") == "op" for e in evs), \
            "no per-op spans in timeline"

    step("shape bucketing: ragged epoch compiles <= bucket count")
    from paddle_tpu.fluid import trace as tr
    fluid.core.set_flags({"FLAGS_shape_bucketing": True})
    try:
        m2, s2 = fluid.Program(), fluid.Program()
        with fluid.program_guard(m2, s2):
            xb = fluid.data("xb", [-1, 16])
            hb = fluid.layers.fc(xb, 8, act="relu")
            lb = fluid.layers.mean(hb)
            fluid.optimizer.SGDOptimizer(0.1).minimize(lb)
        exe2 = fluid.Executor()
        exe2.run(s2)
        miss0 = tr.metrics().counter("executor.compile_cache_miss").value
        rngb = np.random.RandomState(1)
        for nrows in (32, 32, 7, 5, 3, 32, 6):
            hv, = exe2.run(m2, feed={"xb": rngb.randn(nrows, 16)
                                     .astype("float32")}, fetch_list=[hb])
            assert np.asarray(hv).shape[0] == nrows  # true-batch fetches
        misses = tr.metrics().counter(
            "executor.compile_cache_miss").value - miss0
        # 5 distinct tail shapes land in 3 pow2 buckets {4, 8, 32}
        assert misses <= 3, f"ragged epoch recompiled {misses}x (want <=3)"
    finally:
        fluid.core.set_flags({"FLAGS_shape_bucketing": False})

    step("IR passes: DCE+fusion drops >=15% ops, loss unchanged")
    from paddle_tpu.fluid import trace as tr2
    from paddle_tpu.fluid.framework import reset_unique_name

    def build_demo():
        mp, sp = fluid.Program(), fluid.Program()
        with fluid.program_guard(mp, sp):
            xd = fluid.data("xd", [-1, 16])
            yd = fluid.data("yd", [-1, 1], dtype="int64")
            h = fluid.layers.fc(xd, 32, act="relu")
            h = fluid.layers.fc(h, 32, act="relu")
            h = fluid.layers.fc(h, 16, act="relu")
            logits = fluid.layers.fc(h, 10)
            lo = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, yd))
            fluid.optimizer.SGDOptimizer(0.1).minimize(lo)
        return mp, sp, lo

    demo_feed = {"xd": rng.randn(16, 16).astype("float32"),
                 "yd": rng.randint(0, 10, (16, 1)).astype("int64")}

    def run_demo(with_passes):
        reset_unique_name()
        mp, sp, lo = build_demo()
        ex = fluid.Executor()
        from paddle_tpu.fluid.core import Scope, scope_guard
        with scope_guard(Scope()):
            ex.run(sp)
            prog = mp
            if with_passes:
                bs = fluid.BuildStrategy()
                bs.fuse_elewise_add_act_ops = True
                bs.fuse_bn_act_ops = True
                bs.enable_dce = True
                bs.constant_folding = True
                prog = fluid.CompiledProgram(mp, build_strategy=bs)
            lvs = [float(np.asarray(ex.run(prog, feed=demo_feed,
                                           fetch_list=[lo])[0]).ravel()[0])
                   for _ in range(3)]
            nops = tr2.metrics().gauge("executor.ops_per_step").value
        return lvs, nops

    loss_off, ops_off = run_demo(False)
    loss_on, ops_on = run_demo(True)
    assert np.allclose(loss_off, loss_on, rtol=1e-5, atol=1e-6), \
        (loss_off, loss_on)
    drop = (ops_off - ops_on) / max(ops_off, 1)
    assert drop >= 0.15, \
        f"pass pipeline dropped only {drop:.1%} ops ({ops_off}->{ops_on})"
    print(f"[smoke]   ops/step {ops_off:.0f} -> {ops_on:.0f} "
          f"(-{drop:.0%}), loss parity OK", flush=True)

    step("async pipeline: inflight=2 K=4 bit-identical, overlap visible")
    from paddle_tpu.fluid.async_pipeline import AsyncStepRunner
    from paddle_tpu.fluid import trace as tr4
    from paddle_tpu.fluid.core import Scope, scope_guard

    async_feeds = [{"xd": rng.randn(16, 16).astype("float32"),
                    "yd": rng.randint(0, 10, (16, 1)).astype("int64")}
                   for _ in range(16)]

    hw_hist = tr4.metrics().histogram("executor.host_wait_seconds")

    def run_loop(async_mode, epochs=4):
        """Epoch 1 warms the compile cache; the rest are steady-state
        candidates — the BEST (min-wall) epoch is the measurement, so a
        CI scheduler hiccup in one epoch can't flip the gate.  Returns
        (losses over all epochs, final params, best wall seconds,
        host-wait seconds within that same best epoch)."""
        reset_unique_name()
        mp, sp, lo = build_demo()
        ex = fluid.Executor()
        losses, timings = [], []
        with scope_guard(Scope()):
            ex.run(sp)
            runner = AsyncStepRunner(ex, mp, [lo], max_inflight=2,
                                     steps_per_dispatch=4) \
                if async_mode else None
            for epoch in range(epochs):
                hw0 = hw_hist.stats()["total"]
                t0 = time.perf_counter()
                if async_mode:
                    futs = [runner.submit(f) for f in async_feeds]
                    runner.drain()
                    vals = [np.asarray(f[0]) for f in futs]
                else:
                    vals = [np.asarray(ex.run(mp, feed=f,
                                              fetch_list=[lo])[0])
                            for f in async_feeds]
                if epoch > 0:
                    timings.append((time.perf_counter() - t0,
                                    hw_hist.stats()["total"] - hw0))
                losses += [float(np.ravel(v)[0]) for v in vals]
            scope = fluid.global_scope()
            params = {p.name: np.asarray(scope.find_var(p.name))
                      for p in mp.all_parameters()}
        wall, waited = min(timings)
        return losses, params, wall, waited

    sync_losses, sync_params, sync_wall, _ = run_loop(False)
    async_losses, async_params, async_wall, host_wait = run_loop(True)
    assert async_losses == sync_losses, \
        (async_losses[:4], sync_losses[:4])
    for name in sync_params:
        assert np.array_equal(sync_params[name], async_params[name]), name
    # the host must not be blocked for the whole loop (overlap exists) ...
    assert host_wait < async_wall, (host_wait, async_wall)
    # ... and the async loop must not be slower than the blocking loop
    # (1.25x tolerance absorbs CI scheduler noise on the tiny cpu demo)
    assert async_wall <= sync_wall * 1.25, (async_wall, sync_wall)
    print(f"[smoke]   async wall {async_wall*1e3:.0f}ms vs sync "
          f"{sync_wall*1e3:.0f}ms, host-wait share "
          f"{host_wait/max(async_wall, 1e-9):.0%}, bit-identical OK",
          flush=True)

    step("AMP plane: bf16 compiles once, loss parity, >=50% casts pruned")
    from paddle_tpu.fluid import trace as tr5

    def run_amp_demo(amp_on, n_steps=5):
        reset_unique_name()
        mp, sp, lo = build_demo()
        ex5 = fluid.Executor()
        with scope_guard(Scope()):
            ex5.run(sp)
            prog = mp
            if amp_on:
                bs5 = fluid.BuildStrategy()
                bs5.amp = True
                prog = fluid.CompiledProgram(mp, build_strategy=bs5)
            miss0 = tr5.metrics().counter(
                "executor.compile_cache_miss").value
            lvs = [float(np.asarray(ex5.run(prog, feed=demo_feed,
                                            fetch_list=[lo])[0]).ravel()[0])
                   for _ in range(n_steps)]
            misses = tr5.metrics().counter(
                "executor.compile_cache_miss").value - miss0
        return lvs, misses

    cast0 = tr5.metrics().counter("amp.ops_cast").value
    pruned0 = tr5.metrics().counter("amp.casts_pruned").value
    loss_fp32, _ = run_amp_demo(False)
    loss_bf16, misses_bf16 = run_amp_demo(True)
    # one executable for the whole bf16 epoch: the AMP rewrite runs once,
    # before fingerprinting — per-step recompiles would mean the pass
    # left the program version churning
    assert misses_bf16 == 1, f"bf16 demo compiled {misses_bf16}x (want 1)"
    assert np.allclose(loss_bf16, loss_fp32, rtol=0.05, atol=0.05), \
        (loss_bf16, loss_fp32)
    inserted = tr5.metrics().counter("amp.ops_cast").value - cast0
    pruned = tr5.metrics().counter("amp.casts_pruned").value - pruned0
    assert inserted > 0, "amp_bf16 inserted no casts on the mlp demo"
    assert pruned >= 0.5 * inserted, \
        f"prune_redundant_casts removed {pruned}/{inserted} casts (<50%)"
    print(f"[smoke]   amp: {inserted} casts inserted, {pruned} pruned "
          f"({pruned/inserted:.0%}), 1 compile, loss parity OK", flush=True)

    step("elastic: crash-safe save, warm-restart SLO, no step-window stall")
    import json
    import shutil
    import tempfile

    from paddle_tpu.fluid.checkpoint import (CheckpointManager,
                                             InjectedCrash, faults,
                                             list_checkpoint_steps)

    elastic_dir = tempfile.mkdtemp(prefix="smoke-elastic-")
    try:
        # -- gate 1: a crash-injected save leaves a loadable newest-intact
        # checkpoint (the mid-save process death never corrupts state)
        ck_root = os.path.join(elastic_dir, "ckpt")
        reset_unique_name()
        mp6, sp6, lo6 = build_demo()
        ex6 = fluid.Executor()
        with scope_guard(Scope()):
            ex6.run(sp6)
            losses6 = [float(np.asarray(
                ex6.run(mp6, feed=demo_feed, fetch_list=[lo6])[0]).ravel()[0])
                for _ in range(4)]
            cm6 = CheckpointManager(ck_root)
            cm6.save(program=mp6, executor=ex6, step=2, sync=True)
            faults.arm("crash_after_tmp_write")
            try:
                cm6.save(program=mp6, executor=ex6, step=4, sync=True)
                raise AssertionError("injected crash did not fire")
            except InjectedCrash:
                pass
            assert list_checkpoint_steps(ck_root) == [2], \
                "crashed save must commit nothing"
        reset_unique_name()
        mp6b, sp6b, lo6b = build_demo()
        ex6b = fluid.Executor()
        with scope_guard(Scope()):
            ex6b.run(sp6b)
            st6 = CheckpointManager(ck_root).restore(program=mp6b,
                                                     executor=ex6b)
            assert st6 is not None and st6.step == 2
            ex6b.run(mp6b, feed=demo_feed, fetch_list=[lo6b])
        print("[smoke]   crash-injected save: newest-intact checkpoint "
              "loadable OK", flush=True)

        # -- gate 2: async snapshots add no step-window stall — armed
        # slow-disk IO (1s total) rides the writer thread, not the loop
        def step_loop(ckpt_root=None):
            reset_unique_name()
            mpA, spA, loA = build_demo()
            exA = fluid.Executor()
            with scope_guard(Scope()):
                exA.run(spA)
                cmA = CheckpointManager(ckpt_root) if ckpt_root else None
                runner = AsyncStepRunner(exA, mpA, [loA], max_inflight=2)
                runner.submit(dict(demo_feed)).result()  # warm compile
                t0 = time.perf_counter()
                for i in range(8):
                    runner.submit(dict(demo_feed))
                    if cmA is not None and (i + 1) % 4 == 0:
                        cmA.save(program=mpA, executor=exA, step=i + 1)
                runner.drain()
                wall = time.perf_counter() - t0
                if cmA is not None:
                    cmA.wait()
                    assert list_checkpoint_steps(ckpt_root) == [4, 8]
                    cmA.close()
            return wall

        wall_base = step_loop()
        injected_s = 1.0
        faults.arm("slow_disk", times=4, delay=injected_s / 4)
        wall_ckpt = step_loop(os.path.join(elastic_dir, "ckpt-async"))
        faults.clear()
        stall = wall_ckpt - wall_base
        assert stall < injected_s / 2, \
            (f"async checkpoint stalled the step window {stall:.2f}s "
             f"against {injected_s:.1f}s of injected IO")
        print(f"[smoke]   async snapshot stall {max(stall, 0)*1e3:.0f}ms "
              f"over {injected_s:.1f}s slow-disk IO (loop {wall_base*1e3:.0f}"
              f"ms -> {wall_ckpt*1e3:.0f}ms) OK", flush=True)

        # -- gate 3: restart-to-first-step SLO on a warm persistent
        # compile cache (PR-2): the restarted process restores the newest
        # checkpoint and reaches its first post-resume step with ZERO cold
        # compiles, inside the budget
        slo_s = float(os.environ.get("GRAFT_ELASTIC_SLO_S", "60"))
        child_code = (
            "import json, time\n"
            "t_start = time.perf_counter()\n"
            "import numpy as np\n"
            "import paddle_tpu.fluid as fluid\n"
            "from paddle_tpu.fluid import trace\n"
            "main, startup = fluid.Program(), fluid.Program()\n"
            "with fluid.program_guard(main, startup):\n"
            "    x = fluid.data('x', [-1, 16])\n"
            "    y = fluid.data('y', [-1, 1], dtype='int64')\n"
            "    h = fluid.layers.fc(x, 32, act='relu')\n"
            "    logits = fluid.layers.fc(h, 10)\n"
            "    loss = fluid.layers.mean(\n"
            "        fluid.layers.softmax_with_cross_entropy(logits, y))\n"
            "    fluid.optimizer.AdamOptimizer(1e-2).minimize(loss)\n"
            "exe = fluid.Executor()\n"
            "rng = np.random.RandomState(0)\n"
            "feed = {'x': rng.randn(8, 16).astype('float32'),\n"
            "        'y': rng.randint(0, 10, (8, 1)).astype('int64')}\n"
            "cm = fluid.CheckpointManager({ROOT})\n"
            "st = cm.restore(program=main, executor=exe)\n"
            "if st is None:\n"
            "    exe.run(startup)\n"
            "    for _ in range(3):\n"
            "        exe.run(main, feed=feed, fetch_list=[loss])\n"
            "    cm.save(program=main, executor=exe, sync=True)\n"
            "    print(json.dumps({'phase': 'cold'}))\n"
            "else:\n"
            "    t_restored = time.perf_counter()\n"
            "    exe.run(main, feed=feed, fetch_list=[loss])\n"
            "    t_first = time.perf_counter()\n"
            "    m = trace.metrics()\n"
            "    print(json.dumps({'phase': 'resume',\n"
            "        'total_s': t_first - t_start,\n"
            "        'restore_to_step_s': t_first - t_restored,\n"
            "        'cold': m.counter("
            "'executor.compile_cache_cold_miss').value,\n"
            "        'phit': m.counter("
            "'executor.compile_cache_persistent_hit').value}))\n"
        ).replace("{ROOT}", repr(os.path.join(elastic_dir, "ckpt-slo")))
        env6 = dict(os.environ, JAX_PLATFORMS="cpu",
                    FLAGS_persistent_cache_dir=cache_root)

        def run_child():
            r6 = subprocess.run([sys.executable, "-c", child_code],
                                env=env6, cwd=_ROOT, capture_output=True,
                                text=True, timeout=300)
            assert r6.returncode == 0, r6.stderr
            line = [ln for ln in r6.stdout.splitlines()
                    if ln.startswith("{")][-1]
            return json.loads(line)

        first = run_child()
        assert first["phase"] == "cold", first
        resume = run_child()
        assert resume["phase"] == "resume", resume
        assert resume["cold"] == 0, \
            f"restart cold-compiled {resume['cold']}x (want 0: warm cache)"
        assert resume["total_s"] < slo_s, \
            (f"restart-to-first-step {resume['total_s']:.1f}s exceeds the "
             f"{slo_s:.0f}s SLO")
        print(f"[smoke]   restart-to-first-step {resume['total_s']:.1f}s "
              f"(restore+step {resume['restore_to_step_s']*1e3:.0f}ms, "
              f"0 cold compiles, {resume['phit']} persistent hits) "
              f"within {slo_s:.0f}s SLO OK", flush=True)
    finally:
        shutil.rmtree(elastic_dir, ignore_errors=True)

    step("observability: goodput attribution, device footprints, "
         "live metrics export")
    import threading
    import urllib.request
    from paddle_tpu.fluid import trace as tr8, goodput, metrics_export

    obs_dir = tempfile.mkdtemp(prefix="smoke-obs-")
    fluid.core.set_flags({"FLAGS_enable_trace": True,
                          "FLAGS_device_cost_analysis": True})
    try:
        t_gate_us = tr8.elapsed_us()
        reset_unique_name()
        mp8, sp8, lo8 = build_demo()
        ex8 = fluid.Executor()
        srv = metrics_export.start_http(port=0)
        scrapes, scrape_err = [], []

        def scrape_loop():
            try:
                for _ in range(4):
                    body = urllib.request.urlopen(
                        f"http://127.0.0.1:{srv.port}/metrics",
                        timeout=10).read().decode()
                    scrapes.append(body)
                    time.sleep(0.02)
            except Exception as e:      # noqa: BLE001 — surfaced below
                scrape_err.append(e)

        with scope_guard(Scope()):
            ex8.run(sp8)
            cm8 = CheckpointManager(os.path.join(obs_dir, "ckpt"))
            # scrape concurrently with the training loop: the live
            # endpoint must serve the registry WHILE counters mutate
            scraper = threading.Thread(target=scrape_loop)
            scraper.start()
            for i in range(8):
                ex8.run(mp8, feed=demo_feed, fetch_list=[lo8])
                if i == 3:
                    cm8.save(program=mp8, executor=ex8, step=i + 1,
                             sync=True)
            scraper.join(timeout=60)
            cm8.close()
        assert not scrape_err, scrape_err
        assert not scraper.is_alive(), "metrics scrape deadlocked"

        # gate 1: attribution is exhaustive and exclusive — the buckets
        # sum to wall-clock (5% slack for float accumulation only) and
        # the demo populated the compute/compile/checkpoint buckets
        rep = goodput.snapshot(t0_us=t_gate_us)
        total = sum(rep["buckets"].values())
        assert abs(total - rep["wall_seconds"]) \
            <= 0.05 * max(rep["wall_seconds"], 1e-9), (total, rep)
        for b in ("device_compute", "compile", "checkpoint_stall"):
            assert rep["buckets"][b] > 0, (b, rep)

        # gate 2: device truth — per-executable HBM footprint gauges
        names8 = tr8.metrics().names()
        mem8 = [n for n in names8 if n.startswith("xla.mem.exe.")
                and n.endswith(".peak_bytes")]
        assert mem8 and any(tr8.metrics().gauge(n).value > 0
                            for n in mem8), names8
        assert tr8.metrics().gauge("xla.mem.lru_total_peak_bytes").value \
            > 0

        # gate 3: the concurrent scrapes served >=1 sample from each of
        # the executor./ckpt./goodput. families, with no torn lines
        assert len(scrapes) == 4, len(scrapes)
        last = scrapes[-1]
        for family in ("executor_", "ckpt_", "goodput_"):
            assert any(ln.startswith(family) for ln in last.splitlines()
                       if not ln.startswith("#")), (family, last[:2000])
        gp8 = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/goodput", timeout=10)
            .read().decode())
        assert 0.0 <= gp8["ratio"] <= 1.0 and "buckets" in gp8, gp8

        # gate 4: JSONL metrics snapshot round-trips
        snap8 = os.path.join(obs_dir, "metrics.jsonl")
        metrics_export.write_snapshot(snap8)
        with open(snap8) as f:
            row8 = json.loads(f.read().splitlines()[-1])
        assert row8["metrics"]["executor.compile_cache_miss"] == \
            tr8.metrics().counter("executor.compile_cache_miss").value
        assert "goodput" in row8 and "p95" in \
            row8["metrics"]["executor.compile_seconds"]
        print(f"[smoke]   goodput {rep['ratio']:.0%} over "
              f"{rep['wall_seconds']:.1f}s "
              f"(compile {rep['buckets']['compile']*1e3:.0f}ms, ckpt "
              f"{rep['buckets']['checkpoint_stall']*1e3:.0f}ms), "
              f"{len(mem8)} executable footprints, 4 live scrapes OK",
              flush=True)
    finally:
        metrics_export.stop_http()
        fluid.core.set_flags({"FLAGS_enable_trace": False,
                              "FLAGS_device_cost_analysis": "auto"})
        tr8.reset()
        shutil.rmtree(obs_dir, ignore_errors=True)

    step("serving: warmup -> 200-request open-loop burst, 0 cold "
         "compiles under load, batched == sequential, p99 finite")
    import json as _json
    import urllib.request as _url
    from paddle_tpu import serving as srv
    from paddle_tpu.fluid import trace as tr9, metrics_export as mx9
    from paddle_tpu.fluid.core import Scope, scope_guard
    from paddle_tpu.fluid.framework import reset_unique_name

    reset_unique_name()
    sm, ss = fluid.Program(), fluid.Program()
    with fluid.program_guard(sm, ss):
        sx = fluid.data("sx", [-1, 16])
        sh = fluid.layers.fc(sx, 32, act="relu")
        sh = fluid.layers.fc(sh, 32, act="relu")
        slogits = fluid.layers.fc(sh, 10)
    sexe = fluid.Executor()
    with scope_guard(Scope()):
        sexe.run(ss)
        sfrozen = srv.freeze_program(sm, ["sx"], [slogits])
        seng = srv.ServingEngine(sfrozen, executor=sexe, max_batch=16,
                                 max_wait_us=2000)
        msrv = mx9.start_http(port=0)
        try:
            wrep = seng.warmup()
            assert wrep["compiles"] >= 1, wrep
            m9 = tr9.metrics()
            cold0 = m9.counter("executor.compile_cache_cold_miss").value
            miss0 = m9.counter("executor.compile_cache_miss").value
            srng = np.random.RandomState(7)
            pool = srng.randn(16, 16).astype("float32")
            sizes = [1 + (i * 5) % 8 for i in range(200)]   # mixed 1..8
            with seng:
                futs = [seng.submit({"sx": pool[:s] + 0.01 * i})
                        for i, s in enumerate(sizes)]
                souts = [f.result(timeout=60) for f in futs]
            # zero COLD compiles during load: every bucket precompiled
            # (in-process warm hits are allowed to be misses=0 too)
            cold = m9.counter(
                "executor.compile_cache_cold_miss").value - cold0
            miss = m9.counter("executor.compile_cache_miss").value - miss0
            assert cold == 0 and miss == 0, \
                f"serving load compiled (cold={cold}, miss={miss})"
            # batched == sequential per-request, bit-identical
            for i, (s, o) in enumerate(zip(sizes[:40], souts[:40])):
                seq, = sexe.run(sfrozen, feed={"sx": pool[:s] + 0.01 * i},
                                fetch_list=[slogits])
                got = o[slogits.name]
                assert got.shape[0] == s
                assert np.array_equal(np.asarray(seq), got), \
                    (i, s, np.abs(np.asarray(seq) - got).max())
            sstats = seng.stats()
            p99 = sstats["latency_seconds"]["p99"]
            assert np.isfinite(p99) and p99 > 0, sstats
            assert sstats["batches"] < len(sizes), \
                "continuous batcher never coalesced"
            # live /metrics carries the serving family mid-plane
            body = _url.urlopen(
                f"http://127.0.0.1:{msrv.port}/metrics",
                timeout=10).read().decode()
            assert any(ln.startswith("serving_")
                       for ln in body.splitlines()
                       if not ln.startswith("#")), body[:2000]
        finally:
            mx9.stop_http()

        # rejection path: an undersized queue sheds load at submit
        # (auto_start=False holds the batcher so the admission bound is
        # what rejects — deterministic, no race with the drain thread)
        tiny = srv.ServingEngine(sfrozen, executor=sexe, max_batch=4,
                                 max_wait_us=200000, queue_depth=2,
                                 auto_start=False)
        accepted, rejected = [], 0
        for i in range(8):
            try:
                accepted.append(tiny.submit({"sx": pool[:2]}))
            except srv.QueueFullError:
                rejected += 1
        assert rejected == 6 and len(accepted) == 2, (rejected, accepted)
        tiny.start()                       # backlog drains and completes
        for f in accepted:
            assert f.result(timeout=60)[slogits.name].shape[0] == 2
        tiny.close()
    print(f"[smoke]   serving: {len(souts)} reqs, "
          f"{sstats['batches']} batches "
          f"(avg {sstats['batch_size']['avg']:.1f} rows), p50 "
          f"{sstats['latency_seconds']['p50']*1e3:.1f}ms p99 "
          f"{p99*1e3:.1f}ms, 0 cold compiles under load, "
          f"{rejected} overload rejections OK", flush=True)

    step("serving fleet: /healthz-verdict ejection + readmission, "
         "kill mid-burst -> 0 lost + warm replacement")
    import urllib.request as _urlG
    from paddle_tpu.serving import fleet as FL
    from paddle_tpu.fluid import trace as trG

    fleet_dir = tempfile.mkdtemp(prefix="smoke-fleet-")
    mG = trG.metrics()
    flG = FL.ServingFleet(
        spec=FL.demo_mlp_spec(watchdog_stall_s=0.5, queue_depth=64),
        n_replicas=2, scrape_interval_s=0.15, missed_scrape_limit=2,
        auto_replace=True,
        persistent_cache_dir=cache_root,
        rpc_timeout_s=3.0, quiet_children=True)
    try:
        rngG = np.random.RandomState(3)
        poolG = rngG.randn(16, 16).astype("float32")
        fail0 = mG.counter("fleet.failures").value

        def _wait(cond, timeout, what):
            deadline = time.time() + timeout
            while not cond():
                assert time.time() < deadline, f"timed out: {what}"
                time.sleep(0.05)

        # mixed burst lands on BOTH replicas
        futsG = [flG.submit({"x": poolG[: 1 + i % 8]}) for i in range(40)]
        [f.result(timeout=60) for f in futsG]
        assert {f.replica for f in futsG} == {"r0", "r1"}, \
            {f.replica for f in futsG}

        # gate A: VERDICT-driven ejection — wedge r0 (its batcher holds
        # every dispatch), its own SLO watchdog flips /healthz to
        # `stalled`, and the router ejects on that verdict while the
        # process is alive and scrapes keep succeeding (NOT a
        # router-local timeout)
        r0 = flG._resolve("r0")
        r0.pause()
        futsA = [flG.submit({"x": poolG[: 1 + i % 8]}) for i in range(20)]
        _wait(lambda: r0.state == "ejected", 30, "verdict ejection")
        assert r0.ejected_reason == "stalled", r0.ejected_reason
        assert r0.alive(), "verdict ejection needs a LIVE wedged replica"
        hz = _urlG.urlopen(
            f"http://127.0.0.1:{r0.metrics_port}/healthz",
            timeout=5).read().decode().strip()
        assert hz == "stalled", hz
        outsA = [f.result(timeout=90) for f in futsA]
        assert len(outsA) == 20     # redispatch preserved every request
        r0.resume()
        _wait(lambda: r0.state == "up", 30, "readmission after recovery")

        # gate B: kill mid-burst — SIGKILL one replica while requests
        # stream; zero accepted requests lost, replacement reaches
        # serving with 0 cold compiles off the shared persistent cache
        futsB = [flG.submit({"x": poolG[: 1 + i % 8]}) for i in range(10)]
        victim = flG.kill_replica("r1")
        futsB += [flG.submit({"x": poolG[: 1 + i % 8]})
                  for i in range(30)]
        outsB = [f.result(timeout=90) for f in futsB]
        assert len(outsB) == 40
        assert mG.counter("fleet.failures").value == fail0, \
            "an accepted request was lost in the kill drill"
        _wait(lambda: flG.events_of("replace"), 90, "warm replacement")
        rep = flG.events_of("replace")[0]
        assert (rep.get("warmup") or {}).get("cold_misses") == 0, rep
        kills = flG.events_of("kill")
        ejects = [e for e in flG.events_of("eject")
                  if e["replica"] == victim.name]
        eject_s = ejects[0]["t_mono"] - kills[0]["t_mono"]
        # the replacement serves real traffic
        _wait(lambda: len(flG.router.admitted()) >= 2, 30,
              "replacement admitted")
        futsC = [flG.submit({"x": poolG[:4]}) for _ in range(8)]
        [f.result(timeout=60) for f in futsC]
        redisp = mG.counter("fleet.redispatches").value
    finally:
        flG.close()
        shutil.rmtree(fleet_dir, ignore_errors=True)
    print(f"[smoke]   fleet: verdict eject+readmit (live /healthz -> "
          f"'stalled'), kill drill 0/40 lost ({redisp} redispatches), "
          f"eject {eject_s:.2f}s after SIGKILL, replacement warm "
          f"(0 cold compiles) OK", flush=True)

    step("chaos transport: seeded fault schedule -> 0 lost, every "
         "corruption checksum-caught, breaker opens + re-closes")
    from paddle_tpu.distributed import faultline as FLT

    fluid.core.set_flags({"FLAGS_fleet_breaker_failures": 3,
                          "FLAGS_fleet_breaker_cooldown_s": 0.5})
    chaos_dir = tempfile.mkdtemp(prefix="smoke-chaos-")
    flC = FL.ServingFleet(
        spec=FL.demo_mlp_spec(queue_depth=128),
        n_replicas=2, scrape_interval_s=0.15, missed_scrape_limit=8,
        persistent_cache_dir=cache_root,
        rpc_timeout_s=2.0, max_attempts=30, quiet_children=True)
    t_chaos0 = time.monotonic()
    try:
        victimC = flC._resolve("r1")
        # fixed-seed schedule: background latency + a few drops, one
        # all-frames corruption window, one partition-shaped reset
        # window aimed at r1's RPC port (drives the breaker)
        chaos_spec = {"seed": 20260804, "faults": [
            {"kind": "latency", "prob": 0.3, "ms": 4, "jitter_ms": 8},
            {"kind": "drop", "prob": 0.05, "max_injections": 5},
            {"kind": "corrupt", "prob": 1.0, "start_s": 0.8,
             "end_s": 1.1},
            {"kind": "reset", "prob": 1.0, "start_s": 1.6, "end_s": 3.2,
             "endpoint": f"*:{victimC.rpc_port}"},
        ]}
        # replay contract: same seed => same injected-fault decision
        # streams
        assert (FLT.Faultline(chaos_spec).decision_fingerprint(256)
                == FLT.Faultline(chaos_spec).decision_fingerprint(256))
        flt = FLT.install(chaos_spec)
        futsC2 = []
        for i in range(110):            # paced load spanning all windows
            futsC2.append(flC.submit({"x": poolG[: 1 + i % 8]}))
            time.sleep(0.035)
        outsC2 = [f.result(timeout=120) for f in futsC2]
        assert len(outsC2) == 110       # zero accepted requests lost
        inj_corrupt = flt.injected.get("corrupt", 0)
        assert inj_corrupt >= 1, flt.injected
        # every injected corruption was caught by a replica's frame
        # checksum (scraped off /stats) — none surfaced as a torn array
        detC = 0
        for r in flC.router.replicas:
            st = r.scrape(timeout_s=5.0)
            detC += (st.get("rpc") or {}).get("corrupt_frames", 0)
        assert detC == inj_corrupt, (detC, inj_corrupt)
        _wait(lambda: flC.events_of("breaker_open"), 30, "breaker open")
        _wait(lambda: flC.events_of("breaker_close"), 60, "breaker close")
        _wait(lambda: victimC.state == "up", 30,
              "readmission after breaker close")
        assert victimC.breaker.state == "closed"
        chaos_wall = time.monotonic() - t_chaos0
        assert chaos_wall < 90, f"chaos drill blew the wall budget: " \
                                f"{chaos_wall:.1f}s"
        injC = dict(flt.injected)
    finally:
        FLT.uninstall()
        fluid.core.set_flags({"FLAGS_fleet_breaker_failures": 5,
                              "FLAGS_fleet_breaker_cooldown_s": 3.0})
        flC.close()
        shutil.rmtree(chaos_dir, ignore_errors=True)
    print(f"[smoke]   chaos: {sum(injC.values())} faults injected {injC}, "
          f"110/110 served, {detC}/{inj_corrupt} corruptions "
          f"checksum-caught, breaker open->probe->closed, "
          f"{chaos_wall:.1f}s wall OK", flush=True)

    step("host partition: seeded faultline cuts one host agent "
         "mid-burst -> heartbeat ejects its replicas, 0 lost, "
         "readmission after the window heals")
    # breaker headroom: this drill must prove the HOST path (heartbeat
    # -> host_down -> eject(host_partition)), not the per-replica
    # breaker racing it to the ejection
    fluid.core.set_flags({"FLAGS_fleet_breaker_failures": 50})
    host_dir = tempfile.mkdtemp(prefix="smoke-hosts-")
    agentsH, agent_portsH = [], []
    flH = fltH = None
    t_part0 = time.monotonic()
    try:
        for _ in range(2):
            p = subprocess.Popen(
                [sys.executable, "-m", "paddle_tpu.distributed.launch",
                 "--host-agent", "--port", "0"],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True)
            ready = json.loads(p.stdout.readline())
            agentsH.append(p)
            agent_portsH.append(int(ready["port"]))
        flH = FL.ServingFleet(
            spec=FL.demo_mlp_spec(queue_depth=128), n_replicas=2,
            hosts=[f"127.0.0.1:{pt}" for pt in agent_portsH],
            scrape_interval_s=0.15, missed_scrape_limit=2,
            auto_replace=False,
            persistent_cache_dir=cache_root,
            rpc_timeout_s=3.0, max_attempts=30, quiet_children=True)
        assert flH.stats()["hosts_up"] == 2
        r1H = flH._resolve("r1")        # round-robin: r1 sits on agent 2
        assert r1H.host_endpoint == f"127.0.0.1:{agent_portsH[1]}"
        # the partition: every connection to agent 2's box — the agent's
        # heartbeat port AND its replica's RPC port — resets for 3s.
        # HTTP scrapes are NOT faultline-hooked, so detection must come
        # from the framed-RPC heartbeat, not a scrape miss.
        part_spec = {"seed": 20260807, "faults": [
            {"kind": "latency", "prob": 0.2, "ms": 3, "jitter_ms": 5},
            {"kind": "reset", "prob": 1.0, "start_s": 0.5, "end_s": 3.5,
             "endpoint": f"*:{agent_portsH[1]}"},
            {"kind": "reset", "prob": 1.0, "start_s": 0.5, "end_s": 3.5,
             "endpoint": f"*:{r1H.rpc_port}"},
        ]}
        # replay contract: same seed => same decision streams
        assert (FLT.Faultline(part_spec).decision_fingerprint(256)
                == FLT.Faultline(part_spec).decision_fingerprint(256))
        fltH = FLT.install(part_spec)
        futsH = []
        for i in range(80):             # paced burst spanning the window
            futsH.append(flH.submit({"x": poolG[: 1 + i % 8]}))
            time.sleep(0.04)
        _wait(lambda: flH.events_of("host_down"), 30, "host_down event")
        assert r1H.state == "ejected", r1H.state
        assert r1H.ejected_reason == "host_partition", r1H.ejected_reason
        assert flH.stats()["hosts_up"] == 1
        outsH = [f.result(timeout=120) for f in futsH]
        assert len(outsH) == 80         # zero accepted requests lost
        # after the window the heartbeat heals: host_up readmits exactly
        # the replicas the partition ejected
        _wait(lambda: flH.events_of("host_up"), 60, "host_up event")
        _wait(lambda: r1H.state == "up", 30,
              "readmission after partition heals")
        assert flH.stats()["hosts_up"] == 2
        # the readmitted replica serves real traffic again
        futsH2 = [flH.submit({"x": poolG[:4]}) for _ in range(8)]
        [f.result(timeout=60) for f in futsH2]
        part_wall = time.monotonic() - t_part0
        assert part_wall < 90, f"host-partition drill blew the wall " \
                               f"budget: {part_wall:.1f}s"
        injH = dict(fltH.injected)
    finally:
        if fltH is not None:
            FLT.uninstall()
        fluid.core.set_flags({"FLAGS_fleet_breaker_failures": 5})
        if flH is not None:
            flH.close()
        for p in agentsH:
            p.kill()
            p.wait(timeout=10)
        shutil.rmtree(host_dir, ignore_errors=True)
    print(f"[smoke]   host partition: {sum(injH.values())} faults "
          f"{injH}, heartbeat -> host_down -> eject(host_partition), "
          f"80/80 served, hosts_up 2->1->2, {part_wall:.1f}s wall OK",
          flush=True)

    step("decode: batched join/leave bit-identical to sequential "
         "across prefill/decode buckets")
    from paddle_tpu.serving import decode as DC

    dmodel = DC.build_demo_decode_model(vocab=23, d_model=8, max_len=16,
                                        seed=9)
    dprompts = [[3, 1, 4], [2, 7], [5, 9, 2, 6, 5], [1], [8, 8, 3, 1],
                [4, 4]]
    dbudgets = [5, 7, 4, 6, 3, 5]
    dseq = DC.decode_sequential(dmodel, dprompts,
                                max_new_tokens=dbudgets,
                                collect_logits=True, max_batch=4)
    dengine = DC.DecodeEngine(dmodel, max_batch=4, collect_logits=True)
    with dengine:
        dfuts = [dengine.submit(p, max_new_tokens=b)
                 for p, b in zip(dprompts[:3], dbudgets[:3])]
        time.sleep(0.25)        # stagger: joins land mid-flight
        dfuts += [dengine.submit(p, max_new_tokens=b)
                  for p, b in zip(dprompts[3:], dbudgets[3:])]
        dbatched = [f.result(timeout=180) for f in dfuts]
    for i, (a, b) in enumerate(zip(dseq, dbatched)):
        assert np.array_equal(a["tokens"], b["tokens"]), \
            (i, a["tokens"], b["tokens"])
        assert np.array_equal(a["logits"], b["logits"]), \
            (i, float(np.abs(a["logits"] - b["logits"]).max()))
    dstats = dengine.stats()
    # the run crossed prefill buckets (prompt lens 1..5) and ran real
    # join/leave churn (more prefills+steps than a single static batch)
    from paddle_tpu.fluid import compile_cache as _cc
    dbuckets = {_cc.bucket_for(len(p), dengine.prefill_edges)
                for p in dprompts}
    assert len(dbuckets) >= 2, dbuckets
    assert dstats["joins"] >= len(dprompts) \
        and dstats["leaves"] >= len(dprompts)
    print(f"[smoke]   decode: {len(dprompts)} reqs "
          f"({sum(dbudgets)} tokens) joining/leaving mid-flight "
          f"bit-identical to sequential across {sorted(dbuckets)} "
          f"prefill buckets, {dstats['steps']} batched steps OK",
          flush=True)

    step("decode paged: block-paged KV (prefix cache off AND on) "
         "bit-identical to sequential under join/leave churn")
    pmodel = DC.build_demo_decode_model(vocab=23, d_model=8, max_len=16,
                                        seed=9, page_size=4)
    pseq = DC.decode_sequential(pmodel, dprompts, max_new_tokens=dbudgets,
                                collect_logits=True, max_batch=4)
    for cache in (False, True):
        peng = DC.DecodeEngine(pmodel, max_batch=4, collect_logits=True,
                               paged=True, prefix_cache=cache)
        with peng:
            pfuts = [peng.submit(p, max_new_tokens=b)
                     for p, b in zip(dprompts[:3], dbudgets[:3])]
            time.sleep(0.25)    # joins land mid-flight, as in the dense
            pfuts += [peng.submit(p, max_new_tokens=b)  # gate above
                      for p, b in zip(dprompts[3:], dbudgets[3:])]
            pouts = [f.result(timeout=180) for f in pfuts]
            pstats = peng.stats()
        for i, (a, b) in enumerate(zip(pseq, pouts)):
            assert np.array_equal(a["tokens"], b["tokens"]), \
                (cache, i, a["tokens"], b["tokens"])
            assert np.array_equal(a["logits"], b["logits"]), (cache, i)
        if not cache:
            # every page went back to the pool on retirement; with the
            # prefix cache on, registered pages stay warm by design
            assert pstats["paged"]["kv_pages_in_use"] == 0, pstats["paged"]
    print(f"[smoke]   decode paged: cache off+on bit-identical to "
          f"sequential, pool drained to "
          f"{pstats['paged']['kv_page_pool_free']} free pages OK",
          flush=True)

    step("decode speculative: greedy draft-and-verify token-identical "
         "to plain decode across prefill buckets with mid-flight joins")
    sdraft = DC.build_demo_decode_model(vocab=23, d_model=4, max_len=16,
                                        seed=3, page_size=4)
    seng = DC.DecodeEngine(pmodel, max_batch=4, paged=True,
                           draft_model=sdraft, spec_k=4)
    with seng:
        sfuts = [seng.submit(p, max_new_tokens=b)
                 for p, b in zip(dprompts[:3], dbudgets[:3])]
        time.sleep(0.25)        # same join/leave stagger
        sfuts += [seng.submit(p, max_new_tokens=b)
                  for p, b in zip(dprompts[3:], dbudgets[3:])]
        souts = [f.result(timeout=180) for f in sfuts]
        sstats = seng.stats()
    for i, (a, b) in enumerate(zip(pseq, souts)):
        assert np.array_equal(a["tokens"], b["tokens"]), \
            (i, a["tokens"], b["tokens"])
    assert len(dbuckets) >= 2, dbuckets    # same multi-bucket workload
    sp = sstats["paged"]
    assert sp["spec_proposed"] > 0 and sp["spec_accepted"] > 0, sp
    print(f"[smoke]   decode speculative: {len(dprompts)} reqs "
          f"token-identical to plain decode, "
          f"{sp['spec_accepted']}/{sp['spec_proposed']} proposals "
          f"accepted (rate {sp['spec_accept_rate']}) OK", flush=True)

    step("forensics: recorder overhead <=5%, induced stall -> one "
         "bundle, /healthz flips stalled and back")
    import urllib.request as _urlF
    from paddle_tpu.fluid import flight_recorder as flrec
    from paddle_tpu.fluid import metrics_export as mxF
    from paddle_tpu.fluid import trace as trF
    from paddle_tpu.fluid import watchdog as wdog

    # gate 1: the always-on flight recorder must be provably cheap —
    # a recorder-on demo loop within 5% of recorder-off.  Measurement
    # discipline for busy CI boxes: PAIRED off/on epochs interleave over
    # one warmed program (each pair shares one load window, so machine
    # drift hits both variants), and the BEST pair's on/off ratio is
    # the verdict — min-of-each-variant across separate blocks was
    # biased whenever load ramped during the gate and flipped it flaky.
    def forensic_overhead(pairs=6, steps=60):
        reset_unique_name()
        mpF, spF, loF = build_demo()
        exF = fluid.Executor()
        ratios, walls = [], []
        try:
            with scope_guard(Scope()):
                exF.run(spF)
                exF.run(mpF, feed=demo_feed, fetch_list=[loF])  # warm
                for _ in range(pairs):
                    pair = []
                    for rec_on in (False, True):
                        flrec.configure(enabled=rec_on)
                        t0 = time.perf_counter()
                        for _ in range(steps):
                            exF.run(mpF, feed=demo_feed,
                                    fetch_list=[loF])
                        pair.append(time.perf_counter() - t0)
                    ratios.append(pair[1] / pair[0])
                    walls.append(pair)
        finally:
            flrec.configure(enabled=True)
        best = min(range(len(ratios)), key=lambda i: ratios[i])
        return ratios[best], walls[best], pairs * steps

    ratio_on, (wall_off, wall_on), n_on_steps = forensic_overhead()
    overhead = ratio_on - 1.0
    assert ratio_on <= 1.05, \
        (f"flight recorder added {overhead:.1%} to the demo loop in "
         f"EVERY off/on pair (best pair {wall_off*1e3:.0f}ms -> "
         f"{wall_on*1e3:.0f}ms; want <=5%)")
    n_steps_rec = sum(1 for r in flrec.recorder().snapshot()
                      if r.get("kind") == "step")
    assert n_steps_rec >= min(n_on_steps, 60), n_steps_rec

    # gate 2: an induced stall (a wedged dispatch: inflight > 0,
    # nothing completing) produces EXACTLY one valid bundle, and
    # /healthz flips to `stalled` and back to `ok` on recovery
    fdir = tempfile.mkdtemp(prefix="smoke-forensics-")
    wd = wdog.SloWatchdog(stall_s=0.2, interval_s=0.05, p99_ms=0.0,
                          diagnostic_dir=fdir)
    wdog._watchdog = wd
    srvF = mxF.start_http(port=0)
    try:
        wd.start()
        baseF = f"http://127.0.0.1:{srvF.port}"

        def healthzF():
            return _urlF.urlopen(baseF + "/healthz",
                                 timeout=10).read().decode().strip()

        assert healthzF() == "ok"
        t_stall_us = trF.elapsed_us()
        trF.metrics().gauge("executor.inflight_steps").set(1)
        deadline = time.time() + 15
        while healthzF() != "stalled":
            assert time.time() < deadline, "stall never detected"
            time.sleep(0.05)
        time.sleep(0.3)                 # extra ticks: still ONE bundle
        bundlesF = wdog.list_bundles(fdir)
        assert len(bundlesF) == 1, bundlesF
        docF = wdog.load_bundle(bundlesF[0])
        assert docF["reason"] == "stall"
        assert docF["watchdog"]["status"] == "stalled"
        # the goodput report and wide events cover the stall window:
        # the report's wall reaches past the stall start, and the
        # recorder retained the pre-stall steps from gate 1
        assert docF["goodput"]["wall_seconds"] * 1e6 >= t_stall_us, docF[
            "goodput"]
        assert abs(sum(docF["goodput"]["buckets"].values())
                   - docF["goodput"]["wall_seconds"]) \
            <= 0.05 * max(docF["goodput"]["wall_seconds"], 1e-9)
        stepsF = [r for r in docF["wide_events"]
                  if r.get("kind") == "step"]
        assert len(stepsF) >= 30, len(stepsF)
        assert stepsF[-1]["ts_us"] <= t_stall_us, \
            "wide events do not reach the stall window"
        # recovery: work completes again -> ok, and still one bundle
        trF.metrics().gauge("executor.inflight_steps").set(0)
        flrec.record("step")
        deadline = time.time() + 15
        while healthzF() != "ok":
            assert time.time() < deadline, "stall never cleared"
            time.sleep(0.05)
        assert len(wdog.list_bundles(fdir)) == 1
        # the bundle renders without the producing process
        rF = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools", "diagnose.py"),
             bundlesF[0]], capture_output=True, text=True, timeout=120)
        assert rF.returncode == 0, rF.stderr
        assert "STALL" in rF.stdout
    finally:
        mxF.stop_http()
        wd.stop()
        wdog._watchdog = None
        trF.metrics().gauge("executor.inflight_steps").set(0)
        shutil.rmtree(fdir, ignore_errors=True)
    print(f"[smoke]   forensics: recorder overhead {overhead:+.1%} "
          f"(off {wall_off*1e3:.0f}ms / on {wall_on*1e3:.0f}ms), "
          f"stall -> 1 bundle ({len(stepsF)} wide events), healthz "
          f"ok->stalled->ok OK", flush=True)

    step("fleet forensics: one trace id across processes, stitched "
         "timeline, /fleet/metrics rollup, wedge -> one fleet bundle")
    import json as _ojson
    import urllib.request as _urlO
    from paddle_tpu.fluid import metrics_export as mxO
    from paddle_tpu.fluid import trace as trO
    from paddle_tpu.fluid import watchdog as wdO

    obs_dir = tempfile.mkdtemp(prefix="smoke-fleetobs-")
    obs_traces = os.path.join(obs_dir, "traces")
    trO.reset()
    trO.enable()                       # router-side spans + propagation
    srvO = mxO.start_http(port=0)
    flO = FL.ServingFleet(
        spec=FL.demo_mlp_spec(watchdog_stall_s=0.5, queue_depth=64),
        n_replicas=2, policy="round_robin", scrape_interval_s=0.15,
        missed_scrape_limit=2,
        persistent_cache_dir=cache_root,
        trace_dir=obs_traces, diagnostic_dir=obs_dir,
        rpc_timeout_s=3.0, quiet_children=True)
    try:
        rngO = np.random.RandomState(11)
        poolO = rngO.randn(16, 16).astype("float32")

        def _waitO(cond, timeout, what):
            deadline = time.time() + timeout
            while not cond():
                assert time.time() < deadline, f"timed out: {what}"
                time.sleep(0.05)

        # traced requests land on BOTH replicas; the router allocates
        # every trace id and the RPC header carries it down
        futsO = [flO.submit({"x": poolO[: 1 + i % 8]})
                 for i in range(12)]
        [f.result(timeout=60) for f in futsO]
        assert {f.replica for f in futsO} == {"r0", "r1"}
        fut_ids = {f.trace_id for f in futsO}
        assert len(fut_ids) == 12 and all(fut_ids), fut_ids

        # gate A: /fleet/metrics — per-replica samples keep a
        # replica= label and the fleet: rollup is their SUM
        ftext = _urlO.urlopen(
            f"http://127.0.0.1:{srvO.port}/fleet/metrics",
            timeout=5).read().decode()
        famsO = {f["name"]: f
                 for f in mxO.parse_prometheus_text(ftext)}
        per_rep = [(lbl.get("replica"), v)
                   for (sn, lbl, v)
                   in famsO["serving_requests"]["samples"]
                   if sn == "serving_requests"]
        assert {r for r, _ in per_rep} == {"r0", "r1"}, per_rep
        totO = famsO["fleet:serving_requests"]["samples"][0][2]
        assert totO == sum(v for _, v in per_rep) and totO >= 12, \
            (totO, per_rep)

        # gate B: wedge r0 with work outstanding — the verdict
        # ejection freezes exactly ONE fleet bundle (router view +
        # the wedged replica's own watchdog bundle fetched over HTTP
        # before any teardown), and diagnose.py --fleet renders it
        # from a process that never saw the incident
        r0O = flO._resolve("r0")
        r0O.pause()
        futsW = [flO.submit({"x": poolO[: 1 + i % 8]})
                 for i in range(10)]
        _waitO(lambda: r0O.state == "ejected", 30, "verdict ejection")
        [f.result(timeout=90) for f in futsW]    # redispatched to r1
        _waitO(lambda: wdO.list_fleet_bundles(obs_dir), 30,
               "fleet bundle freeze")
        time.sleep(0.3)                # a second freeze would race in
        fbundles = wdO.list_fleet_bundles(obs_dir)
        assert len(fbundles) == 1, fbundles
        with open(fbundles[0]) as fh:
            fdoc = _ojson.load(fh)
        assert fdoc["schema"] == "paddle_tpu.fleet_bundle.v1"
        assert isinstance(fdoc["replicas"].get("r0"), dict) and \
            "schema" in fdoc["replicas"]["r0"], \
            "wedged replica's own bundle missing from the fleet bundle"
        rO = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "tools", "diagnose.py"),
             "--fleet", fbundles[0]],
            capture_output=True, text=True, timeout=120)
        assert rO.returncode == 0, rO.stderr
        assert "FLEET post-mortem" in rO.stdout, rO.stdout[:2000]
        assert "replica r0" in rO.stdout, rO.stdout[:2000]
        r0O.resume()
        _waitO(lambda: r0O.state == "up", 30, "readmission")

        # gate C: graceful close exports one trace file per replica;
        # stitch them with the router's and every request
        # reconstructs under ONE trace id across >= 2 processes
        flO.close()
        router_trace = os.path.join(obs_traces, "router.json")
        trO.export_chrome_trace(router_trace)
        child_traces = sorted(
            os.path.join(obs_traces, f)
            for f in os.listdir(obs_traces) if f.startswith("trace-"))
        assert len(child_traces) == 2, child_traces
        stitched = os.path.join(obs_dir, "fleet-timeline.json")
        rS = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "tools", "timeline.py"), "stitch",
             "--trace_path", ",".join([router_trace] + child_traces),
             "--timeline_path", stitched],
            capture_output=True, text=True, timeout=120)
        assert rS.returncode == 0, rS.stderr
        with open(stitched) as fh:
            tdoc = _ojson.load(fh)
        evsO = tdoc["traceEvents"]
        pnameO = {e["pid"]: e["args"]["name"] for e in evsO
                  if e.get("ph") == "M"
                  and e.get("name") == "process_name"}
        servedO = [e for e in evsO
                   if e.get("name") == "serving::request"
                   and e.get("ph") == "X"
                   and (e.get("args") or {}).get("trace_id") in fut_ids
                   and str(pnameO.get(e["pid"], "")
                           ).startswith("trace-")]
        assert len({e["pid"] for e in servedO}) == 2, \
            "stitched serving spans do not span both replica processes"
        coveredO = {e["args"]["trace_id"] for e in servedO}
        assert coveredO == fut_ids, \
            (len(coveredO), len(fut_ids), fut_ids - coveredO)
        flowsO = [e for e in evsO if e.get("ph") in ("s", "f")
                  and e.get("name") == "router->replica"]
        assert flowsO, "no router->replica flow arrows in the stitch"
        stitch_rep = (tdoc.get("metadata") or {}).get("stitch") or {}
        rpc_files = [v for v in stitch_rep.values()
                     if v.get("method") == "rpc"]
        assert len(rpc_files) == 2, stitch_rep
    finally:
        flO.close()
        mxO.stop_http()
        trO.disable()
        shutil.rmtree(obs_dir, ignore_errors=True)
    print(f"[smoke]   fleet forensics: 12/12 trace ids stitched across "
          f"{len(child_traces) + 1} processes "
          f"({len(flowsO) // 2} flow arrows, clock via rpc pairs), "
          f"fleet:serving_requests {totO:g} == sum(replica), wedge -> "
          f"1 fleet bundle rendered by diagnose --fleet OK", flush=True)

    step("sharding plane: 8-device whole-step DP parity + per-shard "
         "reshard + 0 dispatched collectives")
    # both gates run in children: the emulated 8-device mesh must be
    # fixed BEFORE jax initialises (tests/sharding_worker.py)
    import json as _sjson
    env8 = dict(os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8"))

    def _sharding_child(mode):
        r = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tests",
                                          "sharding_worker.py"), mode],
            env=env8, capture_output=True, text=True, timeout=600,
            cwd=_ROOT)
        assert r.returncode == 0, f"{mode}: {r.stdout}\n{r.stderr}"
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("{")][-1]
        return _sjson.loads(line)

    # gate 1: whole-step sharded DP — loss parity with the single-chip
    # baseline, every fleet allreduce implied (0 dispatched per-op
    # collectives in the compiled step), one executable per step
    infoS = _sharding_child("dp_parity")
    assert infoS["devices"] == 8 and infoS["collectives_dispatched"] == 0
    assert infoS["collectives_implied"] > 0
    rel = max(abs(a - b) / max(abs(a), 1e-9)
              for a, b in zip(infoS["loss_base"], infoS["loss_sharded"]))
    assert rel < 1e-3, (rel, infoS)
    # gate 2: per-shard checkpoint IO — fsdp-8 save (gather-spy armed)
    # round-trips bit-exactly into an fsdp-4 restore AND a meshless one
    infoR = _sharding_child("reshard")
    assert infoR["saved_devices"] == 8 and infoR["restored_devices"] == 4
    print(f"[smoke]   sharding: DP-8 parity rel_err {rel:.2e}, "
          f"{infoS['collectives_implied']} implied / 0 dispatched "
          f"collectives, reshard 8->4 bit-exact "
          f"({infoR['vars_checked']} vars)", flush=True)

    step("parameter server: 4-shard spawn bit-parity vs single table, "
         "SIGKILL mid-train -> restore, no accepted push lost")
    import shutil as _psh
    import tempfile as _pst
    from paddle_tpu.distributed.ps.sharded import ShardedSparseTable
    from paddle_tpu.distributed.ps.table import (CtrAccessorConfig,
                                                 CtrSparseTable,
                                                 IdHashInitializer)

    _ps_t0 = time.time()
    _ps_acc = {"embedx_dim": 8, "embedx_threshold": 2}
    # the oracle: ONE local table with the identical id-deterministic
    # initializer — 4 consistent-hash shards must be bit-indistinguishable
    refP = CtrSparseTable(CtrAccessorConfig.from_dict(_ps_acc), "sgd", 0.05,
                          initializer=IdHashInitializer(scale=0.07, seed=0))
    _ps_dir = _pst.mkdtemp(prefix="smoke-ps-")
    tblP = ShardedSparseTable("smoke_emb", accessor=_ps_acc,
                              optimizer="sgd", lr=0.05, n_shards=4,
                              state_dir=_ps_dir, staleness=0,
                              snapshot_every=40, heartbeat_s=0.25)
    _ps_rng = np.random.RandomState(11)
    try:
        dimP = tblP.dim
        for sP in range(30):
            idsP = np.unique(_ps_rng.randint(0, 5000,
                                             size=96)).astype(np.int64)
            gP = ((idsP[:, None] % 97 + sP) * 1e-3
                  * np.ones((1, dimP))).astype(np.float32)
            shP = np.ones(len(idsP), np.float32)
            ckP = (idsP % 3 == 0).astype(np.float32)
            tblP.push(idsP, gP, shows=shP, clicks=ckP)
            refP.push(idsP, gP, shows=shP, clicks=ckP)
            if sP == 9:
                tblP.end_day()
                refP.end_day()
            if sP == 14:
                tblP.kill_shard(2)      # SIGKILL mid-train; pushes to
                # shard 2 park on its breaker until the supervisor
                # restores it from snapshot+WAL, then apply exactly once
            if sP == 21:
                assert tblP.shrink() == refP.shrink()
        tblP.flush()
        probeP = np.arange(0, 5000, 13, dtype=np.int64)
        rowsP, rowsR = tblP.pull(probeP), refP.pull(probeP)
        assert np.array_equal(rowsP, rowsR), \
            float(np.abs(rowsP - rowsR).max())
        assert tblP.size() == refP.size(), (tblP.size(), refP.size())
        deadP = tblP.events_of("shard_dead")
        restP = tblP.events_of("shard_restarted")
        assert deadP and restP, tblP.events
    finally:
        tblP.close()
        _psh.rmtree(_ps_dir, ignore_errors=True)
    _ps_dt = time.time() - _ps_t0
    assert _ps_dt < 90.0, _ps_dt
    print(f"[smoke]   ps: 4-shard parity bit-exact over 30 steps "
          f"(end_day+shrink in-loop), shard2 SIGKILL -> "
          f"{len(restP)} restart, {refP.size()} rows, {_ps_dt:.1f}s",
          flush=True)

    step("autotune: tuned >= untuned paired epochs, OOM priced out "
         "pre-execution, serving tuner never commits a breach, "
         "seeded + warm-restart replay")
    import shutil as _atsh
    import tempfile as _attmp
    from paddle_tpu.fluid import autotune as at
    from paddle_tpu.fluid import trace as trAT
    from paddle_tpu.fluid.core import Scope as _ATScope, \
        scope_guard as _at_scope_guard
    from paddle_tpu.fluid.executor import _fingerprint as _at_fp

    _at_dir = _attmp.mkdtemp(prefix="smoke-autotune-")
    _at_saved = {k: fluid.core.get_flag(k) for k in
                 ("auto_tune", "auto_tune_dir", "auto_tune_probe_steps",
                  "auto_tune_hbm_budget_mb")}
    fluid.core._FLAGS.update({"auto_tune": False,
                              "auto_tune_dir": _at_dir,
                              "auto_tune_probe_steps": 4,
                              "auto_tune_hbm_budget_mb": 0})
    at.reset_for_tests()

    def _at_counts():
        return {k: trAT.counter_value(f"autotune.{k}") for k in
                ("probes", "accepts", "rejects", "warm_starts",
                 "errors")}

    try:
        # gate 1: the search commits a config that is never slower than
        # the untuned baseline.  Same measurement discipline as the
        # forensics gate: PAIRED baseline/tuned probe windows interleave
        # over one warmed program, best pair is the verdict.
        reset_unique_name()
        mpA, spA, loA = build_demo()
        mpA.random_seed = 11
        mpA._hints["auto_tune"] = True
        exA = fluid.Executor()
        with _at_scope_guard(_ATScope()):
            exA.run(spA)
            c0 = _at_counts()
            exA.run(mpA, feed=demo_feed, fetch_list=[loA])  # tunes here
            c1 = _at_counts()
            assert c1["accepts"] - c0["accepts"] == 1, (c0, c1)
            assert c1["probes"] - c0["probes"] > 0
            assert c1["errors"] - c0["errors"] == 0
            dA = [d for d in at.decisions()
                  if d.get("surface") == "train"
                  and d.get("action") == "accept"][-1]
            tuned_cfg, base_cfg = dA["config"], dA["baseline"]
            spaceA = at.training_space(mpA, demo_feed)
            fluid.core._FLAGS["auto_tune_probe_steps"] = 20
            exA._in_autotune = True      # measurement, not re-tuning
            ratios = []
            try:
                for _ in range(4):
                    pair = []
                    for cfg in (base_cfg, tuned_cfg):
                        s = at._probe_training(
                            exA, mpA, demo_feed, [loA.name],
                            fluid.core._global_scope, spaceA, cfg)
                        assert s is not None, cfg
                        pair.append(s)
                    ratios.append(pair[1] / pair[0])
            finally:
                exA._in_autotune = False
                spaceA.apply(tuned_cfg, program=mpA)
                fluid.core._FLAGS["auto_tune_probe_steps"] = 4
            best_ratio = min(ratios)
            assert best_ratio <= 1.05, \
                (f"tuned config slower than untuned in every pair "
                 f"(best tuned/untuned {best_ratio:.3f}; "
                 f"tuned={tuned_cfg} base={base_cfg})")

        # gate 2: a budget below the program's own peak prices every
        # candidate out from memory_analysis alone — rejected without
        # executing a single probe step
        reset_unique_name()
        mpB, spB = fluid.Program(), fluid.Program()
        mpB.random_seed = 11
        with fluid.program_guard(mpB, spB):
            xb = fluid.data("xb", [-1, 16])
            hb = fluid.layers.fc(xb, 8, act="tanh")
            lob = fluid.layers.mean(fluid.layers.fc(hb, 4))
        mpB._hints["auto_tune"] = True
        fluid.core._FLAGS["auto_tune_hbm_budget_mb"] = 1e-6
        exB = fluid.Executor()
        with _at_scope_guard(_ATScope()):
            exB.run(spB)
            c0 = _at_counts()
            exB.run(mpB, feed={"xb": rng.randn(8, 16).astype("float32")},
                    fetch_list=[lob])
            c1 = _at_counts()
        fluid.core._FLAGS["auto_tune_hbm_budget_mb"] = 0
        assert c1["probes"] - c0["probes"] == 0, \
            "OOM-predicted candidates executed probe steps"
        assert c1["rejects"] - c0["rejects"] > 0
        oomB = [d for d in at.decisions()
                if d.get("reason") == "oom_predicted"]
        assert oomB and all(not d["executed"] for d in oomB)
        assert all(d["peak_bytes"] > d["budget_bytes"] for d in oomB)

        # gate 3: the serving tuner under live load converges without
        # ever committing a config whose probe window breached the SLO
        from paddle_tpu import serving as _at_serving
        reset_unique_name()
        engT = _at_serving.build_engine_from_spec(
            _at_serving.demo_mlp_spec(max_batch=8, max_wait_us=1000,
                                      auto_tune=True))
        try:
            engT.start()
            tunerT = engT._autotuner
            assert tunerT is not None
            tunerT._slo_ms = 5_000.0
            tunerT._window()             # drain earlier gates' records

            def _at_load(n):
                fs = [engT.submit({"x": rng.rand(2, 16)
                                   .astype("float32")})
                      for _ in range(n)]
                for f in fs:
                    f.result(timeout=30)

            for _ in range(4):           # propose/judge rounds
                _at_load(16)
                tunerT.tick()
            servD = [d for d in at.decisions()
                     if d.get("surface") == "serving"]
            assert servD, "serving tuner never judged a window"
            for d in servD:
                if d.get("action") == "accept" and d.get("window"):
                    assert d["window"]["p99_ms"] <= d["slo_ms"], \
                        f"committed a breaching config: {d}"
            assert engT.max_batch >= 1 and engT.max_wait_us >= 200
            assert tunerT.committed == {
                "max_batch": engT.max_batch,
                "max_wait_us": engT.max_wait_us} or tunerT._pending, \
                "engine drifted from the tuner's committed config"
        finally:
            engT.close()

        # gate 4: seeded determinism — same seed, same proposal order,
        # for both surfaces (the decision log replays)
        seqs = [at.training_space(mpA, demo_feed).candidates(seed=5)
                for _ in range(2)]
        assert seqs[0] == seqs[1]
        t1 = at.ServingAutoTuner(engT, seed=9, persist=False)
        t2 = at.ServingAutoTuner(engT, seed=9, persist=False)
        assert [t1._neighbours() for _ in range(3)] \
            == [t2._neighbours() for _ in range(3)]

        # gate 5: warm restart — a fresh "process" (cleared memo, same
        # regenerated program names) starts tuned with ZERO probes
        at.reset_for_tests()
        reset_unique_name()
        mpW, spW, loW = build_demo()
        mpW.random_seed = 11
        assert _at_fp(mpW) == _at_fp(mpA), "restart fingerprint drifted"
        mpW._hints["auto_tune"] = True
        exW = fluid.Executor()
        with _at_scope_guard(_ATScope()):
            exW.run(spW)
            c0 = _at_counts()
            exW.run(mpW, feed=demo_feed, fetch_list=[loW])
            c1 = _at_counts()
        assert c1["probes"] - c0["probes"] == 0, \
            "warm restart re-probed a persisted config"
        assert c1["warm_starts"] - c0["warm_starts"] == 1
        dW = at.decisions()[-1]
        assert dW["source"] == "persisted" and dW["config"] == tuned_cfg
        atb = at.bench_block()
        assert atb["enabled"] and atb["chosen"] == tuned_cfg, atb
    finally:
        fluid.core._FLAGS.update(_at_saved)
        at.reset_for_tests()
        _atsh.rmtree(_at_dir, ignore_errors=True)
    print(f"[smoke]   autotune: train commit {tuned_cfg} "
          f"(best tuned/untuned {best_ratio:.3f}), "
          f"{c1['rejects'] - 0:.0f} total rejects incl. "
          f"{len(oomB)} OOM-priced (0 probe steps), serving "
          f"{len(servD)} judged windows 0 breach commits, warm "
          f"restart 0 probes OK", flush=True)

    step("bench.py --quick prints one JSON row naming the cpu, no mfu")
    r = subprocess.run(
        [sys.executable, "bench.py", "--quick"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=_ROOT, capture_output=True, text=True,
        timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 1, r.stdout
    info = json.loads(lines[0])
    assert info["platform"] == "cpu" and "goodput" in info, info
    # a CPU row never carries a device metric
    assert "mfu" not in info and "mfu_measured" not in info, info

    print(f"[smoke] OK in {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
