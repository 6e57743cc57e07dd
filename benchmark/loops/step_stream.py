"""The training loop of a cell whose job is a stream of equal steps:
Program -> passes -> ``fluid.Executor``, batches from an in-memory dataset
through the system's loader (``utils.prefetch.Prefetcher`` with the
``async_pipeline.batch_stack`` staging hook) into its bounded async window
(``AsyncStepRunner``).  The loop itself is the benchmark's: a user's train
loop, with a span around each call into a layer.

Set-up (all before the window, all counted in ``setup_s``): build the
Program, run the startup program on the device with ``--seed`` as its PRNG
key, compare a dropout-free build with the configuration's float32 reference,
generate the batches from ``--seed``, warm up the one shape the window uses.
``--seed`` reaches the programs only as data (the PRNG key is an argument of
the executable), so every seed shares one compile-cache entry.

The window is ``CHUNKS`` chunks; chunk ``c`` submits steps until ``(c + 1) *
--seconds / CHUNKS`` of the window have passed, then waits for them all, so
every step counted completed inside its chunk and the window lasts
``--seconds`` and one last wait.  The throughput is the median of the chunks'
rates (steps over the chunk's own seconds): one hiccup does not move it.
With ``--trace 1`` the profiler is on for the ``TRACE_CHUNK``-th chunk (and
the one before it, which is not read: the loop says why).
"""
from __future__ import annotations

import itertools
import math
import os
import shutil
import statistics
import sys
import time

import numpy as np

from benchmark.harness import compare, device as device_mod, peaks as peaks_mod
from benchmark.harness import registry, spans as spans_mod, trace_reduce
from benchmark.harness.strategy import build_strategy

CHUNKS = 10
TRACE_CHUNK = 4
WARMUP_STEPS = 4
HISTOGRAMS = ("executor.dispatch_seconds", "executor.host_wait_seconds",
              "loader.produce_seconds", "loader.consume_wait_seconds")
COUNTERS = ("executor.compile_cache_miss", "executor.async_dispatch_errors")


def run(cell, cfg, cfg_dir, mix, reg, seed, seconds, trace, t_start,
        allow_cpu=False, out_dir=None):
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import compile_cache, trace as ptrace
    from paddle_tpu.fluid.async_pipeline import AsyncStepRunner, batch_stack
    from paddle_tpu.fluid.core import Scope, scope_guard
    from paddle_tpu.utils.prefetch import Prefetcher

    chips = cell["chips"]
    used = device_mod.require(chips, allow_cpu=allow_cpu)
    compile_cache.enable_jax_cache()
    backend_compiles = _count_backend_compiles()

    model = registry.load_module(os.path.join(cfg_dir, "model.py"))
    reference = registry.load_module(os.path.join(cfg_dir, "reference.py"))
    kind = reg.module("traffic_kinds", mix["kind"] + ".py")
    batch = mix["samples_per_chip"] * chips
    log = _Log(t_start)

    def compiled(built):
        # the seed is the executor's PRNG key, an argument of the program:
        # set after the build, so that no op attribute depends on it
        built["main"].random_seed = built["startup"].random_seed = int(seed)
        return fluid.CompiledProgram(
            built["main"], build_strategy=build_strategy(cfg, mix))

    train = model.build(cfg, mix, train=True)
    program = compiled(train)
    n_params = sum(int(np.prod(p.shape))
                   for p in train["main"].all_parameters() if p.trainable)
    log(f"built: {len(train['main'].global_block().ops)} ops, "
        f"{n_params} parameters ({n_params / 1e6:.1f} M)")

    exe = fluid.Executor()
    spans = spans_mod.Spans()
    with scope_guard(Scope()):
        exe.run(train["startup"])
        log("startup program ran")

        # -- correct: the program against the float32 reference -----------
        check_batch = kind.generate(mix, cfg, seed, cfg["check"]["samples"],
                                    n_batches=1, stream=1)[0]
        ok, report = compare.program_against_reference(
            exe, compiled, model, reference, cfg, mix, check_batch)
        log(f"reference check ok={ok}: {report}")

        # -- data and the loader -------------------------------------------
        batches = kind.generate(mix, cfg, seed, batch)
        plan = program._ensure_sharding_plan()
        loader = Prefetcher(itertools.cycle(batches),
                            stage=batch_stack(1, mesh=plan.mesh if plan
                                              is not None else None),
                            capacity=2)
        runner = AsyncStepRunner(exe, program, [train["loss"]])
        metrics = ptrace.metrics()
        host_wait = metrics.histogram("executor.host_wait_seconds")
        steps = []                   # (loader_s, submit_s, host_wait_s)
        losses = []

        def chunk(n_steps=None, until=None):
            """Submit ``n_steps`` steps, or steps until the clock reads
            ``until``, then wait for them all.  Returns (seconds, steps,
            failed)."""
            futures = []
            t0 = time.perf_counter()
            try:
                while True:
                    with spans.span("loader") as s_load:
                        feed = loader.get()
                    w0 = host_wait.total
                    with spans.span("executor.submit") as s_sub:
                        futures.append(runner.submit(feed))
                    steps.append((s_load.seconds, s_sub.seconds,
                                  host_wait.total - w0))
                    if n_steps is not None and len(futures) >= n_steps:
                        break
                    if until is not None and time.perf_counter() >= until:
                        break
                with spans.span("fetch_wait"):
                    runner.drain()
            except Exception as e:      # noqa: BLE001 — counted as failed
                log(f"a step raised: {type(e).__name__}: {e}")
                runner.abort()
                return time.perf_counter() - t0, len(futures), len(futures)
            dt = time.perf_counter() - t0
            values = [float(f.handles()[0]) for f in futures]
            losses.extend(values)
            return dt, len(futures), sum(not math.isfinite(v)
                                         for v in values)

        try:
            dt, _, bad = chunk(n_steps=WARMUP_STEPS)
            log(f"warm-up: {WARMUP_STEPS} steps in {dt:.2f}s, loss "
                f"{losses[-1]:.4f}")
            if bad:
                raise SystemExit("benchmark: a warm-up step failed")
            del steps[:], losses[:], spans.records[:]

            # -- the measured window ---------------------------------------
            before = _snapshot(metrics)
            compiles0 = backend_compiles[0]
            setup_s = time.perf_counter() - t_start
            log(f"set-up took {setup_s:.2f}s; measuring {seconds}s")
            chunks, trace_dir, traced_steps = [], None, 0
            t_window = time.perf_counter()
            for c in range(CHUNKS):
                # the profiler starts one chunk before the slice it is read
                # for: starting it can stall the device for seconds (seen
                # once: 6 steps in a 3.8 s slice), and that belongs to no
                # steady slice.  Its own start and stop are not the window's
                # time.
                if trace and c == TRACE_CHUNK - 1:
                    t_off = time.perf_counter()
                    trace_dir = _start_trace(out_dir, cell["name"])
                    t_window += time.perf_counter() - t_off
                until = t_window + (c + 1) * seconds / CHUNKS
                if trace and c == TRACE_CHUNK:
                    first = len(steps)
                    with spans.span("window"):
                        res = chunk(until=until)
                    t_off = time.perf_counter()
                    jax.profiler.stop_trace()
                    t_window += time.perf_counter() - t_off
                    traced_steps = len(steps) - first
                else:
                    res = chunk(until=until)
                chunks.append(res)
                if res[2]:
                    break
            after = _snapshot(metrics)
            window_compiles = backend_compiles[0] - compiles0
        finally:
            loader.close()
    exe.close()

    attempted = sum(n for _, n, _ in chunks)
    failed = sum(bad for _, _, bad in chunks)
    window_s = sum(dt for dt, _, _ in chunks)
    rate = statistics.median(n * batch / chips / dt for dt, n, _ in chunks)
    dev = device_mod.describe(used)
    end_to_end = {"samples_per_s_per_chip": rate,
                  "peak_hbm_gib": dev["memory_peak_bytes"] / 2 ** 30,
                  "setup_s": setup_s}
    if dev["platform"] != "cpu":
        pk = peaks_mod.peaks_of(dev["kind"])
        end_to_end["mfu"] = 100.0 * model.flops_per_sample(cfg, mix) * rate \
            / pk["bf16_flops_per_s"]
    else:
        pk = None
    counters = {k: after[k] - before[k] for k in after}
    log(f"window: {attempted} steps in {window_s:.2f}s "
        f"({[n for _, n, _ in chunks]} a chunk), {failed} failed, "
        f"median {rate:.2f} samples/s/chip, final loss "
        f"{losses[-1] if losses else math.nan:.4f}, "
        f"backend compiles in window {window_compiles}, "
        f"program compile misses {counters['executor.compile_cache_miss']}")

    log(f"memory_stats of {used[0]}: {used[0].memory_stats()}")

    reduced = None
    if trace_dir is not None:
        loaded = trace_reduce.load(trace_reduce.newest_xplane(trace_dir))
        reduced = trace_reduce.reduce(
            loaded, spans.on_timeline(loaded["session_start_ns"]))
    correct = bool(ok and failed == 0 and window_compiles == 0
                   and counters["executor.compile_cache_miss"] == 0)
    return {
        "correct": correct, "attempted": attempted, "failed": failed,
        "end_to_end": end_to_end, "device": dev,
        "layer_ctx": {
            "cell": cell, "cfg": cfg, "mix": mix, "model": model,
            "chips": chips, "batch": batch, "peaks": pk,
            "steps": steps, "spans": spans, "window_s": window_s,
            "counters": counters, "trace": reduced,
            "traced_steps": traced_steps, "check": report,
        },
    }


class _Log:
    """Progress on stderr, stamped with seconds since process start."""

    def __init__(self, t_start):
        self.t_start = t_start

    def __call__(self, msg):
        print(f"[bench {time.perf_counter() - self.t_start:7.2f}s] {msg}",
              file=sys.stderr, flush=True)


def _count_backend_compiles():
    """A one-element list that counts XLA backend compiles of this process
    (cache hits included: each is a program that was not in memory)."""
    import jax.monitoring
    count = [0]

    def on_duration(event, _secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return count


def _snapshot(metrics):
    snap = {name: metrics.counter(name).value for name in COUNTERS}
    for name in HISTOGRAMS:
        h = metrics.histogram(name)
        snap[name] = h.total
        snap[name + ".count"] = h.count
    return snap


def _start_trace(out_dir, cell_name):
    import jax
    trace_dir = os.path.join(out_dir, "trace", cell_name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    options = jax.profiler.ProfileOptions()
    # the device's half of the profiler only: see harness/spans.py for why
    # the host's half stays off and how the spans get onto the timeline
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    return trace_dir
