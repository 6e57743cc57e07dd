"""Benchmark legs: train-step throughput of the BASELINE configs on one chip.

``python bench.py [--model NAME] [--quick]`` runs ONE leg in this process and
prints one JSON row that names the device it ran on (``platform``,
``device_kind``, ``device_count`` as JAX reports them).  Any failure is a
non-zero exit.  The default leg is BASELINE config #3, BERT-base
pretraining with bf16 autocast, through the dygraph->functional bridge as two
jitted XLA programs per step (grad, fused Adam).

Size comes from ``--quick`` and never from the backend: without it a leg runs
at its full size and refuses to start where JAX finds no accelerator; with it
the leg runs toy shapes anywhere, as a control-flow check.  ``mfu`` is graded
against the published peak of the device in ``DEVICE_PEAKS``; an unknown
device is an error and a CPU row carries no ``mfu``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def build_train_step(vocab, hidden, layers, heads, ffn, seq, batch, lr=1e-4,
                     amp=True):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.dygraph import base as dybase
    from paddle_tpu.dygraph.functional import functional_loss
    from paddle_tpu.models.bert import BertForPretraining
    from paddle_tpu.optimizer.fused import make_fused_adam

    dybase.enable_dygraph()
    tracer = dybase._dygraph_tracer()
    tracer._amp_enabled = amp           # bf16 autocast on matmul/conv (MXU)
    model = BertForPretraining(vocab_size=vocab, hidden_size=hidden,
                               num_layers=layers, num_heads=heads,
                               intermediate_size=ffn, max_position=seq)
    model.train()

    def loss_fn(input_ids, mlm_labels, nsp_labels):
        mlm_logits, nsp_logits = model(input_ids)
        return model.loss(mlm_logits, nsp_logits, mlm_labels, nsp_labels)

    param_values, lfn = functional_loss(model, loss_fn)
    jstep, opt_state = make_two_program_step(param_values, lfn, lr)
    n_params = sum(int(np.prod(p.shape)) for p in param_values)
    return jstep, opt_state, n_params


def make_two_program_step(param_values, lfn, lr):
    """TWO XLA programs per step, like the reference's backward-ops /
    optimizer-ops split, so the grad program cannot fuse the Adam update
    into its dW matmuls.  What the split buys against one fused program —
    in step time or in compile time — is not measured on this code;
    ``chip_smoke.py``'s Executor leg compiles the fused whole-block form.
    Shared by the bench and tools/mfu_sweep.py so the sweep always measures
    EXACTLY the bench's step."""
    import jax
    from paddle_tpu.optimizer.fused import make_fused_adam

    opt_state, _spec, fused_update = make_fused_adam(param_values, lr=lr)
    jgrad = jax.jit(lambda params, *xs: jax.value_and_grad(lfn)(params, *xs))
    jupdate = jax.jit(fused_update, donate_argnums=(0, 1))
    jparams = jax.jit(fused_update.params_of)
    cache = {"params": None}      # jupdate already returns fresh params —
                                  # reuse them instead of re-unflattening

    def params_of(state):
        return cache["params"] if cache["params"] is not None \
            else jparams(state)

    def jstep(state, *xs):
        loss, grads = jgrad(params_of(state), *xs)
        state, cache["params"] = jupdate(state, grads)
        return state, loss

    def measured_flops(state, xs):
        """Measured FLOPs per step: XLA cost_analysis of BOTH programs
        (grad + fused Adam), lowered at ShapeDtypeStruct twins so
        donated buffers are never touched — the device-truth numerator
        `mfu_measured` reports beside the analytic Chinchilla count.
        The AOT re-lower rides XLA's compile caches (the executables
        were just built by the warmup)."""
        from paddle_tpu.fluid import device_stats
        p_sds = device_stats.sds_tree(params_of(state))
        x_sds = [device_stats.sds_tree(x) for x in xs]
        f = device_stats.flops_of(jgrad, (p_sds, *x_sds))
        # grads share the params' tree/avals — reuse the twin
        f += device_stats.flops_of(jupdate,
                                   (device_stats.sds_tree(state), p_sds))
        return f

    jstep.measured_flops = measured_flops
    # chip_smoke.py reads the grad program's compiled HLO
    jstep.grad_program = jgrad
    jstep.params_of = params_of
    return jstep, opt_state


# Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e"'},
}


def device_peaks(device_kind):
    """The published peaks for ``device_kind``; a device that is not in
    the table is an error, never a default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device_kind {device_kind!r}: add it to "
            f"bench.DEVICE_PEAKS with its source") from None


def device_info(quick):
    """The device as JAX reports it.  A full-size leg measures the chip:
    it refuses to start on a CPU-only host."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}
    if info["platform"] == "cpu" and not quick:
        raise SystemExit(
            "bench.py: JAX found no accelerator (platform cpu).  Full-size "
            "legs measure the chip; pass --quick for a toy-size control-flow "
            "run on the CPU.")
    return info


def flops_per_token(hidden, layers, ffn, seq, vocab):
    """fwd+bwd matmul FLOPs per token (Chinchilla-style accounting)."""
    per_layer = 2 * (4 * hidden * hidden + 2 * hidden * ffn)   # qkvo + mlp
    attn = 2 * 2 * seq * hidden                                # scores + av
    head = 2 * hidden * vocab
    fwd = layers * (per_layer + attn) + head
    return 3 * fwd                                             # bwd = 2x fwd


def build_resnet_step(num_classes, lr=0.1, data_format="NHWC"):
    """ResNet-50 training step (BASELINE config #2): SGD+momentum,
    softmax cross-entropy, bf16 conv compute via AMP autocast.  NHWC is
    the default layout: channels-last puts C on the 128-lane minor
    dimension.  Neither layout's step time is measured on this code."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.dygraph import base as dybase
    from paddle_tpu.dygraph.functional import functional_loss
    from paddle_tpu.vision.models import resnet50
    from paddle_tpu.fluid import layers as L

    dybase.enable_dygraph()
    tracer = dybase._dygraph_tracer()
    tracer._amp_enabled = True
    model = resnet50(num_classes=num_classes, data_format=data_format)
    model.train()

    def loss_fn(images, labels):
        logits = model(images)
        return L.nn.mean(L.softmax_with_cross_entropy(logits, labels))

    param_values, lfn = functional_loss(model, loss_fn)

    def sgd_momentum(params, vel, grads, mu=0.9):
        new_v = [mu * v + g.astype(jnp.float32)
                 for v, g in zip(vel, grads)]
        new_p = [(p.astype(jnp.float32) - lr * v).astype(p.dtype)
                 for p, v in zip(params, new_v)]
        return new_p, new_v

    jgrad = jax.jit(jax.value_and_grad(lfn))
    jupd = jax.jit(sgd_momentum, donate_argnums=(0, 1))
    state = {"p": param_values,
             "v": [jax.numpy.zeros(p.shape, jax.numpy.float32)
                   for p in param_values]}

    def jstep(images, labels):
        loss, grads = jgrad(state["p"], images, labels)
        state["p"], state["v"] = jupd(state["p"], state["v"], grads)
        return loss

    return jstep


def resnet50_flops_per_image(image=224):
    """ResNet-50 fwd is ~4.1 GMACs = 8.2 GFLOPs at 224 (XLA cost analysis
    on this model: 7.98e9); bwd = 2x fwd."""
    fwd = 8.2e9 * (image / 224.0) ** 2
    return 3 * fwd


_LAST_CHUNKS = []


def timed_run(step_fn, steps, warmup):
    """Warmup, sync, timed loop in 4 synced chunks, total returned.
    float(loss) is the sync: a device->host transfer waits for the step,
    whether the loss is a jax array or an executor's lazy fetch.
    Per-chunk wall times land in _LAST_CHUNKS."""
    for _ in range(max(1, warmup)):     # >=1: compile outside the timing
        loss = step_fn()
    float(loss)
    del _LAST_CHUNKS[:]
    n_chunks = min(4, steps)
    done = 0
    for c in range(n_chunks):
        quota = (steps * (c + 1)) // n_chunks - done
        t0 = time.perf_counter()
        for _ in range(quota):
            loss = step_fn()
        float(loss)
        _LAST_CHUNKS.append(round(time.perf_counter() - t0, 4))
        done += quota
    return sum(_LAST_CHUNKS)


def _compile_stats():
    """Recompile cost alongside throughput: the bench trajectory must show
    compile-cache regressions (a miss is a whole-block XLA recompile), not
    just steady-state step rate (docs/performance.md)."""
    from paddle_tpu.fluid import trace as _tr
    m = _tr.metrics()
    out = {"compile_misses":
           m.counter("executor.compile_cache_miss").value,
           "compile_seconds": round(m.histogram(
               "executor.compile_seconds").stats()["total"], 3)}
    ops = m.gauge("executor.ops_per_step").value
    if ops:                 # static-Executor benches only
        out["ops_per_step"] = int(ops)
    # async pipeline depth + host-wait vs dispatch split
    # (docs/performance.md "Async step pipeline"): how deep the
    # in-flight window got and how much of the loop the host spent
    # blocked on device results vs dispatching new work
    hw = m.histogram("executor.host_wait_seconds").stats()["total"]
    dp = m.histogram("executor.dispatch_seconds").stats()["total"]
    peak = m.gauge("executor.inflight_peak").value
    if peak:
        out["inflight_depth"] = int(peak)
        out["host_wait_seconds"] = round(hw, 3)
        out["dispatch_seconds"] = round(dp, 3)
    # goodput attribution (fluid/goodput.py): tracing is off in the
    # bench, so this is the metrics-totals estimate — the named
    # badput buckets are measured, the remainder is credited to
    # device_compute (an upper bound, goodput_src says so)
    from paddle_tpu.fluid import goodput as _gp
    rep = _gp.from_metrics(_tr.elapsed_us() / 1e6)
    out["goodput"] = round(rep["ratio"], 4)
    out["goodput_src"] = rep["source"]
    badput = {b: round(v, 3) for b, v in rep["buckets"].items()
              if b != "device_compute" and v >= 0.001}
    if badput:
        out["badput_seconds"] = badput
    # device-truth HBM footprint of the live executables (populated
    # when FLAGS_device_cost_analysis captured; static benches only)
    mem_total = m.gauge("xla.mem.lru_total_peak_bytes").value
    if mem_total:
        out["hbm_peak_bytes_total"] = int(mem_total)
        out["hbm_peak_bytes_largest"] = int(
            m.gauge("xla.mem.largest_peak_bytes").value)
    return out


def _autotune_block():
    """The `autotune` block every leg carries (docs/performance.md
    "Auto-tuning"): chosen config, probe cost, tuned-vs-untuned delta —
    {"enabled": False, ...} when the tuner never ran in this process."""
    from paddle_tpu.fluid import autotune as _at
    return _at.bench_block()


def dtype_mix():
    """Share of the value plane per dtype from the AMP plane's
    amp.dtype_hist.* gauges (populated by the amp_bf16 pass on static
    programs); {} when no AMP rewrite ran this process."""
    from paddle_tpu.fluid import trace as _tr
    m = _tr.metrics()
    out = {}
    for name in m.names():
        if name.startswith("amp.dtype_hist."):
            v = m.gauge(name).value
            if v:
                out[name[len("amp.dtype_hist."):]] = int(v)
    return out


def report(metric, unit, rate, flops_rate, device, config=None,
           extras=None, dtype="bfloat16", measured_flops_rate=None):
    """One JSON row naming its device.  Off the CPU, `mfu` is
    analytic-model-FLOPs over the device's published bf16 peak,
    `vs_baseline` is MFU / 0.35 (BASELINE.md north star), and
    `mfu_measured` grades the same wall time with XLA's own cost_analysis
    FLOPs instead of the analytic count (a >1.5x divergence warns on
    stderr — the analytic matmul-only model and the compiled HLO
    disagree).  A CPU row carries none of the three."""
    out = {"metric": metric, "value": round(rate, 1), "unit": unit,
           **device, "amp_dtype": dtype, "config": config or {},
           "chunk_secs": list(_LAST_CHUNKS)}
    if device["platform"] != "cpu":
        peak = device_peaks(device["device_kind"])["bf16_flops"]
        mfu = flops_rate / peak
        out["mfu"] = round(mfu, 4)
        out["vs_baseline"] = round(mfu / 0.35, 4)
        if measured_flops_rate:
            mfu_m = measured_flops_rate / peak
            out["mfu_measured"] = round(mfu_m, 4)
            if mfu and not (2 / 3 <= mfu_m / mfu <= 1.5):
                print(f"# WARNING: mfu_measured {mfu_m:.2%} diverges from "
                      f"analytic mfu {mfu:.2%} (x{mfu_m / mfu:.2f}): the "
                      f"Chinchilla matmul-only count and XLA cost_analysis "
                      f"disagree on this program", file=sys.stderr)
    out.update(extras or {})
    out["autotune"] = _autotune_block()
    mix = dtype_mix()
    if mix:
        out["dtype_mix"] = mix
    out.update(_compile_stats())
    print(json.dumps(out))


def main_resnet(quick, layout="nhwc"):
    import jax.numpy as jnp

    device = device_info(quick)
    if quick:
        image, batch, classes, steps, warmup = 32, 4, 10, 3, 1
    else:
        image, batch, classes, steps, warmup = 224, 128, 1000, 20, 3
    fmt = layout.upper()

    jstep = build_resnet_step(classes, data_format=fmt)
    rng = np.random.RandomState(0)
    shape = ((batch, 3, image, image) if fmt == "NCHW"
             else (batch, image, image, 3))
    imgs = jnp.asarray(rng.randn(*shape).astype("float32"))
    lbls = jnp.asarray(rng.randint(0, classes, (batch, 1)).astype("int32"))

    dt = timed_run(lambda: jstep(imgs, lbls), steps, warmup)
    ips = steps * batch / dt
    report("resnet50_train_throughput", "images/sec/chip", ips,
           ips * resnet50_flops_per_image(image), device,
           config={"image": image, "batch": batch, "classes": classes,
                   "steps": steps, "layout": fmt})


def main_nmt(quick):
    """Transformer NMT dygraph training step (BASELINE config #4)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.dygraph import base as dybase
    from paddle_tpu.dygraph.functional import functional_loss
    from paddle_tpu.models.transformer import TransformerModel
    from paddle_tpu.fluid import layers as L

    device = device_info(quick)
    if quick:
        vocab, d_model, heads, layers_n, ffn = 500, 64, 2, 2, 128
        seq, batch, steps, warmup = 16, 4, 3, 1
    else:
        # Transformer-big-ish at trainable single-chip scale
        vocab, d_model, heads, layers_n, ffn = 32000, 1024, 16, 6, 4096
        seq, batch, steps, warmup = 64, 32, 20, 3

    dybase.enable_dygraph()
    tracer = dybase._dygraph_tracer()
    tracer._amp_enabled = True
    model = TransformerModel(src_vocab=vocab, tgt_vocab=vocab,
                             d_model=d_model, nhead=heads,
                             num_encoder_layers=layers_n,
                             num_decoder_layers=layers_n,
                             dim_feedforward=ffn, dropout=0.1,
                             max_len=seq + 1)
    model.train()

    def loss_fn(src, tgt_in, tgt_out):
        logits = model(src, tgt_in)
        return L.mean(L.softmax_with_cross_entropy(
            L.reshape(logits, [-1, vocab]), L.reshape(tgt_out, [-1, 1])))

    values, lfn = functional_loss(model, loss_fn)
    jg = jax.jit(jax.value_and_grad(lfn))
    state = {"v": values}
    rng = np.random.RandomState(0)
    src = jnp.asarray(rng.randint(0, vocab, (batch, seq)).astype("int64"))
    tin = jnp.asarray(rng.randint(0, vocab, (batch, seq)).astype("int64"))
    tout = jnp.asarray(rng.randint(0, vocab, (batch, seq)).astype("int64"))

    def one_step():
        loss, grads = jg(state["v"], src, tin, tout)
        state["v"] = [v - 1e-4 * g for v, g in zip(state["v"], grads)]
        return loss

    dt = timed_run(one_step, steps, warmup)
    tok_s = steps * batch * seq / dt
    # per-token fwd matmul flops.  Encoder layer: qkvo (4 d^2 MACs) + MLP;
    # decoder layer: self-attn qkvo + CROSS-attn qkvo (8 d^2) + MLP; score/
    # context matmuls (2*2*seq*d) count PER attention, per layer.
    d2 = d_model * d_model
    enc_layer = 2 * (4 * d2 + 2 * d_model * ffn) + 2 * 2 * seq * d_model
    dec_layer = (2 * (8 * d2 + 2 * d_model * ffn)
                 + 2 * (2 * 2 * seq * d_model))
    head = 2 * d_model * vocab
    fwd = layers_n * (enc_layer + dec_layer) + head
    report("transformer_nmt_train_throughput", "tokens/sec/chip",
           tok_s, tok_s * 3 * fwd, device,
           config={"vocab": vocab, "d_model": d_model, "layers": layers_n,
                   "ffn": ffn, "seq": seq, "batch": batch, "steps": steps})


def main_ctr(quick):
    """Wide&Deep CTR training throughput (BASELINE config #5): the sparse
    embedding is served by the BoxPS tier (distributed/ps/box.py) — a
    host-RAM table over a 2^40 feasign space (structurally larger than any
    HBM: the device never holds the table, only the pass's working-set
    cache), trained through the STATIC framework path (Program + Executor
    + begin/end pass).  examples/sec is the metric (CTR is lookup-bound,
    MFU is not meaningful)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.distributed.ps.box import get_box_wrapper
    from paddle_tpu.fluid.core import global_scope

    device = device_info(quick)
    if quick:
        slots, dim, batch, steps, warmup = 6, 8, 64, 3, 1
    else:
        slots, dim, batch, steps, warmup = 26, 16, 4096, 20, 3

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", [-1, slots], dtype="int64")
        dense = fluid.data("dense", [-1, 13])
        label = fluid.data("label", [-1, 1])
        box = get_box_wrapper("bench_box", dim=dim, init_kind="gaussian",
                              init_scale=0.01)
        emb = fluid.layers.pull_box_sparse(ids, dim,
                                           table_name="bench_box")
        flat = fluid.layers.reshape(emb, [-1, slots * dim])
        deep = fluid.layers.concat([flat, dense], axis=1)
        h = fluid.layers.fc(deep, 256, act="relu")
        h = fluid.layers.fc(h, 128, act="relu")
        wide = fluid.layers.fc(dense, 1)
        logit = fluid.layers.fc(h, 1) + wide
        loss = fluid.layers.mean(
            fluid.layers.sigmoid_cross_entropy_with_logits(logit, label))
        fluid.optimizer.SGDOptimizer(0.01).minimize(loss)

    exe = fluid.Executor()
    exe.run(startup)

    # IR pass pipeline (docs/passes.md): fuse fc's add+relu pairs (fwd +
    # grad) and fold constant chains.  ops_per_step before/after rides in
    # the JSON beside throughput — the pipeline's win is visible in the
    # bench trajectory, not just the test suite.  "before" applies the
    # same fetch-reachability prune the executor does, so the delta
    # credits the passes only, not the executor's own prune.
    from paddle_tpu.fluid.framework import prune_ops
    _gb = main.global_block()
    ops_before = len(prune_ops(
        _gb, [op for op in _gb.ops if op.type not in ("feed", "fetch")],
        targets=[loss.name], keep_state_writes=True))
    bs = fluid.BuildStrategy()
    bs.fuse_elewise_add_act_ops = True
    bs.constant_folding = True
    # FLAGS_auto_tune=1 closes the loop here: the first tuned step sweeps
    # dispatch knobs in probe windows and commits the winner (persisted —
    # the next bench round starts tuned at zero probe cost)
    bs.auto_tune = bool(fluid.core.get_flag("auto_tune"))
    train_prog = fluid.CompiledProgram(main, build_strategy=bs)

    rng = np.random.RandomState(0)
    n_batches = steps + warmup
    # 64-bit feasign draws: ~every id unique -> the pass working set is
    # batch*slots*n_batches rows while the table SPACE is 2^40
    all_ids = rng.randint(0, 2 ** 40, (n_batches, batch, slots),
                          dtype=np.int64)
    cache = box.begin_pass(all_ids)
    global_scope().set_var("bench_box@HBMCACHE", cache)
    feeds = []
    for b in range(n_batches):
        feeds.append({
            "ids": box.slots_of(all_ids[b].reshape(-1)).reshape(batch,
                                                                slots),
            "dense": rng.randn(batch, 13).astype("float32"),
            "label": rng.randint(0, 2, (batch, 1)).astype("float32")})

    it = {"i": 0}

    # async dispatch window (fluid/async_pipeline.py): submit returns a
    # lazy loss; timed_run's float(loss) at the chunk boundary is the only
    # sync, so feed staging and dispatch overlap device compute
    from paddle_tpu.fluid.async_pipeline import AsyncStepRunner
    runner = AsyncStepRunner(exe, train_prog, [loss])

    def one_step():
        f = feeds[it["i"] % n_batches]
        it["i"] += 1
        return runner.submit(f).lazy(0)

    dt = timed_run(one_step, steps, warmup)
    runner.drain()
    fp32_chunks = list(_LAST_CHUNKS)
    # snapshot the fp32 leg's compile tax + executable size NOW: the
    # cumulative counters keep counting through the bf16 leg below, and
    # the headline row is the fp32 measurement
    fp32_cstats = _compile_stats()
    from paddle_tpu.fluid import trace as _tr
    ops_after = int(_tr.metrics().gauge("executor.ops_per_step").value)

    # bf16 leg: same program through the AMP compiler plane (amp_bf16 +
    # prune_redundant_casts on top of the fusion passes already applied) —
    # the bf16-vs-fp32 pair and the dtype mix ride the same JSON line
    bs2 = fluid.BuildStrategy()
    bs2.amp = True
    amp_prog = fluid.CompiledProgram(main, build_strategy=bs2)
    amp_runner = AsyncStepRunner(exe, amp_prog, [loss])

    def one_step_amp():
        f = feeds[it["i"] % n_batches]
        it["i"] += 1
        return amp_runner.submit(f).lazy(0)

    dt16 = timed_run(one_step_amp, steps, warmup)
    amp_runner.drain()
    bf16_ex_s = steps * batch / dt16
    del _LAST_CHUNKS[:]
    _LAST_CHUNKS.extend(fp32_chunks)

    cache_rows = box.cache_rows
    box.end_pass(global_scope().find_var("bench_box@HBMCACHE"))
    ex_s = steps * batch / dt
    print(f"# box tier: id_space=2^40 host_rows={box.host_rows()} "
          f"device_cache_rows={cache_rows}", file=sys.stderr)
    print(f"# ir passes: ops_per_step {ops_before} -> {ops_after}",
          file=sys.stderr)
    out = {
        "metric": "wide_deep_ctr_train_throughput", "value": round(ex_s, 1),
        "unit": "examples/sec/chip", **device,
        "config": {"slots": slots, "dim": dim, "batch": batch,
                   "steps": steps},
        "chunk_secs": list(_LAST_CHUNKS),
        "ops_per_step_before": ops_before,
        "bf16_value": round(bf16_ex_s, 1),
        "amp_speedup": round(bf16_ex_s / ex_s, 3) if ex_s else 0.0,
        # amp_dtype labels the HEADLINE value — the fp32 leg here; the
        # bf16 leg rides bf16_value/amp_speedup
        "amp_dtype": "float32",
    }
    out["autotune"] = _autotune_block()
    mix = dtype_mix()
    if mix:
        out["dtype_mix"] = mix
    out.update(fp32_cstats)
    print(json.dumps(out))


def main_sharding(quick):
    """Unified-SPMD-plane leg (docs/sharding.md): the fluid mlp/CTR demo
    trained single-chip vs whole-step-sharded DP over every visible
    device (8 emulated host devices on CPU — set BEFORE jax init).  The
    row records the plane's three claims: ONE executable dispatch per
    step (vs N per-gradient allreduce launches), the implied-vs-
    dispatched collective split (0 dispatched in the sharded program),
    and per-device HBM from the XLA memory analysis — the numbers the
    next accelerator round baselines multichip against."""
    if os.environ.get("JAX_PLATFORMS") == "cpu" \
            and "--xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_"
                                     "device_count=8")
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core, trace
    from paddle_tpu.fluid.core import Scope, scope_guard
    from paddle_tpu.fluid.framework import reset_unique_name
    from paddle_tpu.distributed.fleet.meta_optimizers.common import \
        insert_allreduce_ops

    device = device_info(quick)
    n_dev = device["device_count"]
    batch, steps, warmup = (256, 4, 1) if quick else (4096, 20, 3)
    core.set_flags({"FLAGS_device_cost_analysis": True})

    def build():
        m, s = fluid.Program(), fluid.Program()
        with fluid.program_guard(m, s):
            x = fluid.data("x", [-1, 64])
            y = fluid.data("y", [-1, 1], dtype="int64")
            h = fluid.layers.fc(x, 256, act="relu")
            h = fluid.layers.fc(h, 128, act="relu")
            logits = fluid.layers.fc(h, 16)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, y))
            opt = fluid.optimizer.AdamOptimizer(1e-3)
            _, pg = opt.minimize(loss)
        return m, s, loss, pg

    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(batch, 64).astype("float32"),
            "y": rng.randint(0, 16, (batch, 1)).astype("int64")}

    def run_leg(sharded):
        reset_unique_name()
        m, s, loss, pg = build()
        prog = m
        if sharded:
            insert_allreduce_ops(m.global_block(), pg)
            bs = fluid.BuildStrategy()
            bs.sharding = "dp"
            prog = fluid.CompiledProgram(m, build_strategy=bs)
        exe = fluid.Executor()
        losses = []
        with scope_guard(Scope()):
            exe.run(s)
            it = {"n": 0}

            def one_step():
                it["n"] += 1
                lv, = exe.run(prog, feed=feed, fetch_list=[loss])
                losses.append(float(np.asarray(lv).ravel()[0]))
                return lv

            dt = timed_run(one_step, steps, warmup)
            hbm = max((int(fp.get("per_device_peak_bytes",
                                  fp.get("peak_bytes", 0)) or 0)
                       for fp in exe._footprints.values()), default=0)
        plan = prog._sharding_plan if sharded else None
        return dt, losses, hbm, plan

    d0 = trace.metrics().counter("sharding.collectives_dispatched").value
    dt1, loss1, hbm1, _ = run_leg(False)
    dt8, loss8, hbm8, plan = run_leg(True)
    dispatched = trace.metrics().counter(
        "sharding.collectives_dispatched").value - d0
    implied = trace.metrics().counter("sharding.collectives_implied").value
    parity = max(abs(a - b) / max(abs(a), 1e-9)
                 for a, b in zip(loss1[-steps:], loss8[-steps:]))
    ex_s = steps * batch / dt8 / max(n_dev, 1)
    out = {
        "metric": "sharded_dp_train_throughput",
        "value": round(ex_s, 1), "unit": "examples/sec/chip", **device,
        "config": {"batch": batch, "steps": steps},
        "chunk_secs": list(_LAST_CHUNKS),
        "sharding": "dp",
        "mesh_shape": plan.mesh_shape() if plan is not None else {},
        "step_dispatches_per_step": 1,
        "collectives_implied": int(implied),
        "collectives_dispatched": int(dispatched),
        "hbm_peak_bytes_per_device": int(hbm8),
        "hbm_peak_bytes_single": int(hbm1),
        "single_chip_examples_per_sec": round(steps * batch / dt1, 1),
        "loss_parity_rel_err": round(parity, 8),
    }
    out["autotune"] = _autotune_block()
    out.update(_compile_stats())
    print(json.dumps(out))


def main_serve(quick):
    """Serving-plane row: open-loop QPS + latency percentiles through
    tools/serve_bench's single in-process engine (request-level, not
    steps/sec)."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import serve_bench
    from paddle_tpu.fluid import core as _core

    device = device_info(quick)
    qps = 200.0 if quick else 2000.0
    n = 300 if quick else 4000
    report = serve_bench.serve_bench(qps=qps, n_requests=n,
                                     sizes=(1, 2, 4, 8),
                                     max_batch=32, hidden=64,
                                     auto_tune=bool(
                                         _core.get_flag("auto_tune")))
    out = dict(report, **device)
    out["autotune"] = _autotune_block()
    out.update(_compile_stats())
    print(json.dumps(out))


def main_ps(quick):
    """Parameter-server row: sharded-embedding pull/push latency plus
    trainer steps/s with the async working-set prefetcher on vs off (the
    PR-18 scale tier).  Host-side only — the shard servers are real
    subprocesses with WAL + snapshot persistence, so the numbers include
    the RPC/dedup/durability tax a trainer actually pays.  The headline
    value is prefetch-on steps/s; the extras carry the off leg and the
    ``ps.pull_wait_seconds`` totals that show the prefetcher hiding the
    multi-shard pull behind (simulated) device compute."""
    import shutil
    import tempfile
    from paddle_tpu.distributed.ps.sharded import ShardedSparseTable
    from paddle_tpu.fluid import trace as _tr

    n_shards = 4
    dim = 16
    vocab = 200_000 if quick else 2_000_000
    batch = 256 if quick else 2048
    lat_ops = 30 if quick else 150
    steps = 20 if quick else 80
    compute_s = 0.01            # simulated device step the prefetch hides
    rng = np.random.default_rng(0)
    m = _tr.metrics()

    def batch_ids():
        # zipfish working set: 80% of ids from a hot 1/16 slice
        hot = rng.integers(0, vocab // 16, size=batch)
        cold = rng.integers(0, vocab, size=batch)
        return np.unique(np.where(rng.random(batch) < 0.8,
                                  hot, cold)).astype(np.int64)

    state = tempfile.mkdtemp(prefix="ps-bench-")
    tbl = ShardedSparseTable("bench_emb", dim=dim, n_shards=n_shards,
                             optimizer="sgd", lr=0.05, state_dir=state,
                             staleness=0, supervise=False)
    try:
        # -- per-op latency: synchronous pull / push+flush ---------------
        pull_ts, push_ts = [], []
        for _ in range(lat_ops):
            ids = batch_ids()
            t0 = time.perf_counter()
            tbl.pull(ids)
            pull_ts.append(time.perf_counter() - t0)
            g = np.full((len(ids), dim), 1e-3, np.float32)
            t0 = time.perf_counter()
            tbl.push(ids, g)
            tbl.flush()
            push_ts.append(time.perf_counter() - t0)

        def pct(ts, q):
            return round(float(np.percentile(np.asarray(ts) * 1e3, q)), 3)

        def train_leg(prefetch):
            # uniform feed: consecutive batches rarely share ids, so the
            # bit-parity patch path (re-pull of ids pushed after the
            # prefetch was issued) stays the exception, as it is at real
            # terabyte-table vocab sizes
            feed = [np.unique(rng.integers(0, vocab, size=batch))
                    .astype(np.int64) for _ in range(steps)]
            wait0 = m.histogram("ps.pull_wait_seconds").total
            it = tbl.prefetching(iter(feed), extract=lambda b: b) \
                if prefetch else iter(feed)
            t0 = time.perf_counter()
            for ids in it:
                rows = tbl.pull(ids)
                time.sleep(compute_s)               # "device" step
                tbl.push(ids, rows * 1e-4)
            tbl.flush()
            dt = time.perf_counter() - t0
            wait = m.histogram("ps.pull_wait_seconds").total - wait0
            return steps / dt, wait

        off_sps, off_wait = train_leg(prefetch=False)
        on_sps, on_wait = train_leg(prefetch=True)
        hits = m.counter("ps.prefetch_hits").value
        misses = m.counter("ps.prefetch_misses").value
        hit_rate = hits / (hits + misses) if hits + misses else 0.0
        out = {
            "metric": "ps_sharded_train_throughput",
            "value": round(on_sps, 1), "unit": "steps/sec",
            "platform": "host",     # shard servers + numpy; no device work
            "n_shards": n_shards, "batch_ids": batch, "dim": dim,
            "pull_p50_ms": pct(pull_ts, 50), "pull_p99_ms": pct(pull_ts, 99),
            "push_p50_ms": pct(push_ts, 50), "push_p99_ms": pct(push_ts, 99),
            "steps_per_sec_prefetch_on": round(on_sps, 1),
            "steps_per_sec_prefetch_off": round(off_sps, 1),
            "pull_wait_s_prefetch_on": round(on_wait, 4),
            "pull_wait_s_prefetch_off": round(off_wait, 4),
            "prefetch_hit_rate": round(hit_rate, 3),
            "prefetch_patched": m.counter("ps.prefetch_patched").value,
        }
        out["autotune"] = _autotune_block()
        print(json.dumps(out))
    finally:
        tbl.close()
        shutil.rmtree(state, ignore_errors=True)


def main_bert(quick):
    import jax.numpy as jnp

    device = device_info(quick)
    if quick:
        vocab, hidden, layers, heads, ffn = 1000, 128, 2, 4, 512
        seq, batch, steps, warmup = 128, 8, 5, 2
    else:
        vocab, hidden, layers, heads, ffn = 30522, 768, 12, 12, 3072
        seq, batch, steps, warmup = 128, 64, 20, 3

    jstep, state, n_params = build_train_step(
        vocab, hidden, layers, heads, ffn, seq, batch)

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, vocab, (batch, seq)).astype("int32"))
    mlm = jnp.asarray(rng.randint(0, vocab, (batch, seq)).astype("int32"))
    nsp = jnp.asarray(rng.randint(0, 2, (batch,)).astype("int32"))

    box = {"state": state}

    def one_step():
        box["state"], loss = jstep(box["state"], ids, mlm, nsp)
        return loss

    dt = timed_run(one_step, steps, warmup)
    tokens_per_sec = steps * batch * seq / dt
    bf16_chunks = list(_LAST_CHUNKS)

    # fp32 comparison leg (fewer steps — a ratio, not a headline): the
    # bf16-vs-fp32 pair rides the same JSON row
    fp32_steps = max(3, steps // 4)
    jstep32, state32, _ = build_train_step(
        vocab, hidden, layers, heads, ffn, seq, batch, amp=False)
    box32 = {"state": state32}

    def one_step32():
        box32["state"], loss = jstep32(box32["state"], ids, mlm, nsp)
        return loss

    dt32 = timed_run(one_step32, fp32_steps, warmup)
    fp32_tokens_per_sec = fp32_steps * batch * seq / dt32
    del _LAST_CHUNKS[:]
    _LAST_CHUNKS.extend(bf16_chunks)

    # device truth: XLA's own per-step FLOPs (cost_analysis on the grad +
    # update executables) grades the same wall clock as mfu_measured
    measured_rate = jstep.measured_flops(
        box["state"], (ids, mlm, nsp)) * steps / dt

    report("bert_base_pretrain_throughput", "tokens/sec/chip",
           tokens_per_sec,
           tokens_per_sec * flops_per_token(hidden, layers, ffn, seq, vocab),
           device,
           config={"vocab": vocab, "hidden": hidden, "layers": layers,
                   "heads": heads, "ffn": ffn, "seq": seq, "batch": batch,
                   "steps": steps},
           extras={"fp32_value": round(fp32_tokens_per_sec, 1),
                   "amp_speedup": round(
                       tokens_per_sec / fp32_tokens_per_sec, 3)
                   if fp32_tokens_per_sec else 0.0},
           measured_flops_rate=measured_rate)


LEGS = {"bert": main_bert, "resnet50": main_resnet, "nmt": main_nmt,
        "wide_deep": main_ctr, "serve": main_serve,
        "sharding": main_sharding, "ps": main_ps}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="bert", choices=sorted(LEGS))
    ap.add_argument("--quick", action="store_true",
                    help="toy shapes: a control-flow check that runs "
                         "anywhere, not a measurement")
    ap.add_argument("--layout", default="nhwc", choices=["nhwc", "nchw"],
                    help="resnet50 leg only")
    args = ap.parse_args(argv)
    import paddle_tpu
    from paddle_tpu.fluid import compile_cache
    compile_cache.enable_jax_cache()
    # one seed for weights, dropout op seeds and the dygraph tracer's base
    # key: unseeded, each process bakes different constants into its
    # programs, and the compile cache can never hit
    paddle_tpu.seed(0)
    if args.model == "resnet50":
        main_resnet(args.quick, args.layout)
    else:
        LEGS[args.model](args.quick)


if __name__ == "__main__":
    main()
