"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the training path once, through the entry
points a user calls, at BERT-base width on one TPU chip, and checks what
comes out by the repo's own means:

* **eager** — ``models.bert.BertForPretraining`` through
  ``dygraph.functional.functional_loss`` and bench.py's fused-Adam
  two-program step: loss falls, the grad program's HLO holds Mosaic calls,
  outputs live on the TPU.
* **executor** — ``models.static_graphs.build_bert_train_program`` run by
  ``fluid.Executor(fluid.TPUPlace(0))`` as a plain program, then as a
  ``CompiledProgram`` with the AMP plane, as a cell runs it: every
  attention chain must have become the fused op, and the step launches its
  kernel once forward and once backward per layer (the grad op applies the
  vjp the forward op kept: ``backward.vjp_kept``).
* **kernels** — every kernel in ``ops/pallas_kernels.__all__`` compiled by
  Mosaic and run once through its op lowering against an XLA reference.
* **cache** — compile seconds, and the files in the compile cache before and
  after (``--expect-warm``: a second run must add none).

``--chips 4`` runs, instead, the executor program data-parallel over four
real devices and one ``parallel/hybrid`` step on a pp2 x tp2 mesh.

One process, no children, no handler around a leg: any failure is a non-zero
exit.  It exits non-zero, printing no result, where JAX finds no TPU — unless
``--tiny`` is given, which runs toy sizes on any backend as a control-flow
check for the sandbox and the tests and is NOT a chip result.  The last line
of a passing run is one JSON object naming the device as JAX reports it.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import time

import numpy as np

# (vocab, hidden, layers, heads, ffn, seq, batch): published BERT-base width
# and depth; --tiny keeps the structure and shrinks every number.
FULL = dict(vocab=30522, hidden=768, layers=12, heads=12, ffn=3072, seq=128,
            batch=64)
TINY = dict(vocab=512, hidden=128, layers=2, heads=2, ffn=256, seq=128,
            batch=4)
DROPOUT = 0.1
STEPS = 5


def say(msg):
    print(f"[smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# compile accounting: jax reports every backend compile (cache hit or not)
# ---------------------------------------------------------------------------

class CompileLog:
    """Seconds jax spent in backend compiles and how many were served from
    the persistent cache, from jax's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1
            if secs >= 1.0:
                say(f"  compiled one executable in {secs:.1f}s")

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextlib.contextmanager
    def leg(self, name, results):
        s0, c0, h0 = self.seconds, self.compiles, self.cache_hits
        t0 = time.perf_counter()
        say(f"== {name}")
        out = results[name] = {}
        yield out
        out["wall_s"] = round(time.perf_counter() - t0, 1)
        out["compile_s"] = round(self.seconds - s0, 1)
        out["compiles"] = self.compiles - c0
        out["cache_hits"] = self.cache_hits - h0
        say(f"== {name} ok: {json.dumps(out)}")


def cache_files(cache_dir):
    return sum(len(files) for _, _, files in os.walk(cache_dir))


def on_device(x, device):
    """Every shard of ``x`` lives on ``device``."""
    return {s.device for s in x.addressable_shards} == {device}


def check_losses(losses, what):
    assert all(math.isfinite(v) for v in losses), (what, losses)
    assert losses[-1] < losses[0], \
        f"{what}: loss did not fall over {len(losses)} steps: {losses}"


# ---------------------------------------------------------------------------
# leg: eager front door
# ---------------------------------------------------------------------------

def leg_eager(size, device, out):
    """BertForPretraining -> functional_loss -> bench.py's two-program
    fused-Adam step, bf16 autocast, dropout on."""
    import jax
    import jax.numpy as jnp
    import bench

    jstep, state, n_params = bench.build_train_step(
        size["vocab"], size["hidden"], size["layers"], size["heads"],
        size["ffn"], size["seq"], size["batch"])
    rng = np.random.RandomState(0)
    shape = (size["batch"], size["seq"])
    ids = jnp.asarray(rng.randint(0, size["vocab"], shape).astype("int32"))
    mlm = jnp.asarray(rng.randint(0, size["vocab"], shape).astype("int32"))
    nsp = jnp.asarray(rng.randint(0, 2, shape[:1]).astype("int32"))

    t0 = time.perf_counter()
    state, loss = jstep(state, ids, mlm, nsp)           # warm-up: compiles
    jax.block_until_ready(loss)
    out["first_step_s"] = round(time.perf_counter() - t0, 1)

    losses = []
    t0 = time.perf_counter()
    for _ in range(STEPS):
        state, loss = jstep(state, ids, mlm, nsp)
        losses.append(loss)
    jax.block_until_ready((state, loss))
    out["step_ms"] = round((time.perf_counter() - t0) / STEPS * 1e3, 2)
    assert on_device(loss, device), loss.sharding
    assert all(on_device(p, device) for p in jstep.params_of(state))
    losses = [float(v) for v in losses]
    check_losses(losses, "eager")
    out["losses"] = [round(v, 4) for v in losses]
    out["params"] = n_params

    # the dropout epilogues took the Pallas kernels, not the bernoulli
    # lowering: Mosaic custom calls in the grad program's compiled HLO
    # (this lowering is a cache hit — the executable was just built)
    from paddle_tpu.fluid import device_stats
    from paddle_tpu.ops.pallas_preflight import mosaic_call_count
    args = device_stats.sds_tree((jstep.params_of(state), ids, mlm, nsp))
    n = mosaic_call_count(jstep.grad_program.lower(*args).compile())
    out["mosaic_calls"] = n
    if device.platform == "tpu":
        assert n > 0, "no Mosaic custom call in the BERT grad program"


# ---------------------------------------------------------------------------
# leg: Program -> passes -> Executor
# ---------------------------------------------------------------------------

def build_static_bert(size):
    from paddle_tpu.fluid.framework import reset_unique_name
    from paddle_tpu.models.static_graphs import build_bert_train_program
    reset_unique_name()
    return build_bert_train_program(
        vocab=size["vocab"], hidden=size["hidden"], heads=size["heads"],
        seq=size["seq"], layers=size["layers"], dropout=DROPOUT)


def static_feed(size):
    from paddle_tpu.models.static_graphs import bert_demo_feed
    return bert_demo_feed(np.random.RandomState(1), batch=size["batch"],
                          seq=size["seq"], vocab=size["vocab"])


def run_executor(program, startup, loss, feed, device, check_param=None):
    """startup + first step (compiles) + STEPS timed steps in a fresh scope,
    then ``check_param(a trained parameter)`` while the state is still live
    (default: it sits on ``device``).  Returns (losses, first_step_s,
    step_ms)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.core import Scope, scope_guard

    exe = fluid.Executor(fluid.TPUPlace(0))
    with scope_guard(Scope()):
        exe.run(startup)
        t0 = time.perf_counter()
        first, = exe.run(program, feed=feed, fetch_list=[loss])
        first_s = time.perf_counter() - t0
        losses = [float(np.asarray(first).ravel()[0])]
        t0 = time.perf_counter()
        for _ in range(STEPS):
            lv, = exe.run(program, feed=feed, fetch_list=[loss],
                          return_numpy=False)     # a lazy FetchHandle
            losses.append(lv)
        lv.block_until_ready()
        step_ms = (time.perf_counter() - t0) / STEPS * 1e3
        assert device in {s.device for s in lv.raw.addressable_shards}
        losses = [float(np.asarray(v).ravel()[0]) for v in losses]
        # TPUPlace(0).jax_device() may resolve to a CPU device where no
        # accelerator exists (fluid/core.py): check where state really is
        scope = fluid.global_scope()
        main = getattr(program, "_program", program)
        w = scope.find_var(main.all_parameters()[0].name)
        if check_param is None:
            assert on_device(w, device), w.sharding
        else:
            check_param(w)
    exe.close()
    return losses, round(first_s, 1), round(step_ms, 2)


def attention_kernel_calls(layers):
    """The custom calls of the newest remembered executable that holds the
    fused attention op, by the Program op they are charged to; asserts one
    backward kernel per layer under the grad op (a grad op that traced its
    forward again would launch the forward kernel a second time, charged to
    the grad op: two per layer there).  The forward op's count is a floor:
    under ``shard_map`` the per-chip PRNG key's ``X64Combine`` custom call
    carries its scope too."""
    from paddle_tpu.fluid import device_stats
    for entry in reversed(device_stats.op_maps()):
        calls = {}
        for v in entry["map"].values():
            if v and v["opcode"] == "custom-call" \
                    and v["label"].startswith("fused_multihead_attention"):
                calls[v["label"]] = calls.get(v["label"], 0) + 1
        if calls:
            assert calls.get("fused_multihead_attention_grad") == layers \
                and calls.get("fused_multihead_attention", 0) >= layers, calls
            return dict(calls, step_mosaic_calls=entry["mosaic_calls"])
    raise AssertionError("no remembered executable holds the attention op")


def vjp_counts():
    from paddle_tpu.fluid import trace
    return {n: trace.metrics().counter("backward.vjp_" + n).value
            for n in ("kept", "retraced")}


def executor_plain(size, device, out):
    """The plain (unrewritten, f32) program on one chip; returns its
    losses, the reference the rewritten and the sharded runs track."""
    main, startup, loss = build_static_bert(size)
    losses, first_s, step_ms = run_executor(main, startup, loss,
                                            static_feed(size), device)
    check_losses(losses, "executor plain")
    out.update({"first_step_s": first_s, "step_ms": step_ms,
                "losses": [round(v, 4) for v in losses]})
    return losses


def leg_executor(size, device, out):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.dygraph import base as dybase
    from paddle_tpu.fluid import trace
    from paddle_tpu.fluid.framework import in_dygraph_mode

    if in_dygraph_mode():           # the eager leg leaves eager mode on
        dybase.disable_dygraph()
    out["plain"] = {}
    losses = executor_plain(size, device, out["plain"])
    say(f"  plain program: {json.dumps(out['plain'])}")

    feed = static_feed(size)
    main, startup, loss = build_static_bert(size)
    bs = fluid.BuildStrategy()
    bs.amp = True                   # what a cell sets, and nothing else
    rewrites = trace.metrics().counter("kernel_tier.fuse_attention.rewrites")
    r0 = rewrites.value
    vjp0 = vjp_counts()
    amp_losses, first_s, step_ms = run_executor(
        fluid.CompiledProgram(main, build_strategy=bs), startup, loss, feed,
        device)
    check_losses(amp_losses, "executor amp")
    fused = int(rewrites.value - r0)
    assert fused == size["layers"], fused
    types = [op.type for op in main.global_block().ops]
    assert "fused_multihead_attention" in types and "softmax" not in types
    # same weights, same batch: the bf16 rewritten program starts where
    # the plain one does
    assert abs(amp_losses[0] - losses[0]) <= 0.05 * abs(losses[0]), \
        (amp_losses[0], losses[0])
    # every generic_grad but the loss's (custom_grad) applied a kept vjp
    vjp = {n: v - vjp0[n] for n, v in vjp_counts().items()}
    assert vjp["retraced"] == 1 and vjp["kept"] >= 2 * size["layers"], vjp
    out["amp"] = {
        "first_step_s": first_s, "step_ms": step_ms,
        "losses": [round(v, 4) for v in amp_losses],
        "fuse_attention_rewrites": fused, "vjp": vjp}
    if device.platform == "tpu":
        out["amp"]["kernel_calls"] = attention_kernel_calls(size["layers"])
    say(f"  amp: {json.dumps(out['amp'])}")


# ---------------------------------------------------------------------------
# leg: kernel roll-call, every kernel through its op lowering
# ---------------------------------------------------------------------------

def lower_op(op_type, attrs=None):
    """``fn(ins, key) -> outs`` for one op lowering, ins/outs as
    {slot: [arrays]} like the executor hands them over."""
    import jax
    from paddle_tpu.ops.registry import LoweringContext, get_op

    def fn(ins, key):
        return get_op(op_type).fn(ins, dict(attrs or {}),
                                  LoweringContext(base_key=key))
    return jax.jit(fn)


def run_lowered(jfn, *args, expect_mosaic):
    """Compile, count Mosaic calls, run.  ``expect_mosaic`` is None on a
    backend without the kernels, else whether the Pallas path must
    (True) or must not (False) have been taken."""
    from paddle_tpu.ops.pallas_preflight import mosaic_call_count
    compiled = jfn.lower(*args).compile()
    n = mosaic_call_count(compiled)
    if expect_mosaic is True:
        assert n > 0, "the op lowering did not take its Pallas kernel"
    if expect_mosaic is False:
        assert n == 0, "the op lowering took a kernel past its gate"
    return compiled(*args), n


def rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def rel_l2(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def roll_flash(tiny, tpu, key):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.attention import _reference_attention
    from paddle_tpu.ops.registry import LoweringContext, get_op

    b, h, t, d = (1, 2, 1024, 64) if tiny else (8, 12, 1024, 64)
    ks = jax.random.split(key, 4)
    q, k, v = (jax.random.normal(kk, (b, h, t, d), jnp.bfloat16)
               for kk in ks[:3])
    # additive padding mask, [B, 1, 1, T]: the last quarter of the keys of
    # every odd batch row is masked out
    pad = (jnp.arange(t)[None, :] >= (3 * t) // 4) \
        & (jnp.arange(b)[:, None] % 2 == 1)
    mask = jnp.where(pad, -10000.0, 0.0).astype(jnp.float32)[:, None, None]
    w = jax.random.normal(ks[3], (b, h, t, d), jnp.float32)
    scale = d ** -0.5

    def op(q, k, v):
        ins = {"Q": [q], "K": [k], "V": [v], "Mask": [mask]}
        return get_op("fused_multihead_attention").fn(
            ins, {"scale": scale}, LoweringContext(base_key=key))["Out"][0]

    def ref(q, k, v):
        f32 = jnp.float32
        return _reference_attention(q.astype(f32), k.astype(f32),
                                    v.astype(f32), mask, scale, False)

    def fwd_bwd(f):
        def g(q, k, v):
            out, vjp = jax.vjp(f, q, k, v)
            return (out,) + vjp(w.astype(out.dtype))
        return jax.jit(g)

    got, n = run_lowered(fwd_bwd(op), q, k, v, expect_mosaic=tpu)
    want = fwd_bwd(ref)(q, k, v)
    errs = [rel_err(a, b_) for a, b_ in zip(got, want)]
    assert max(errs) < 3e-2, errs            # bf16 operands, f32 reference
    return {"shape": [b, h, t, d], "mosaic": n,
            "rel_err_out_dq_dk_dv": [round(e, 4) for e in errs]}


def roll_fused_attention(tiny, tpu, key):
    """The fused attention kernel through the op's lowering at BERT's two
    lengths, padding bias as its [B, 1, 1, S] row, dropout on the
    probabilities at DROPOUT: the keep rate, the backward's regenerated mask
    against the forward's (row and column counts of the exported mask, read
    back exactly from a uniform-attention call in float32), and outputs and
    dQ/dK/dV against a float32 reference fed the exported mask."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.ops.registry import LoweringContext, get_op

    p = DROPOUT
    seed = 11
    drop_key = LoweringContext(base_key=key).key_for(seed)
    attrs = {"dropout_rate": p, "dropout_seed": seed,
             "dropout_implementation": "upscale_in_train"}
    f32 = jnp.float32

    def op(scale, mask):
        def f(q, k, v):
            ins = {"Q": [q], "K": [k], "V": [v]}
            if mask is not None:
                ins["Mask"] = [mask]
            return get_op("fused_multihead_attention").fn(
                ins, dict(attrs, scale=scale),
                LoweringContext(base_key=key))["Out"][0]
        return f

    def fwd_bwd(f, w):
        def g(q, k, v):
            out, vjp = jax.vjp(f, q, k, v)
            return (out,) + vjp(w.astype(out.dtype))
        return jax.jit(g)

    rows = []
    shapes = [(2, 2, 128, 64), (1, 2, 512, 64)] if tiny \
        else [(16, 12, 128, 64), (8, 12, 512, 64)]
    for i, (b, h, t, d) in enumerate(shapes):
        ks = jax.random.split(jax.random.fold_in(key, i), 4)
        keep = rate = None
        if tpu:
            keep = pk.fused_attention_keep_mask((b, h, t, d), t, p, drop_key)
            rate = float(jnp.mean(keep.astype(f32)))
            assert abs(rate - (1 - p)) < 1e-3, f"keep rate {rate:.5f}"
            # q = 0: every probability is 1/t, so out * t * (1 - p) counts
            # the forward mask's rows (v = 1) and dv the backward mask's
            # columns (dout = 1)
            ones = jnp.ones((b, h, t, d), f32)
            (out, _, _, dv), _ = run_lowered(
                fwd_bwd(op(1.0, None), ones), jnp.zeros_like(ones), ones,
                ones, expect_mosaic=tpu)
            count = t * (1 - p)
            kf = np.asarray(keep, np.float32)
            assert np.abs(np.asarray(out)[..., 0] * count
                          - kf.sum(-1)).max() < 0.05, "forward mask"
            assert np.abs(np.asarray(dv)[..., 0] * count
                          - kf.sum(-2)).max() < 0.05, "backward mask"

        q, k, v = (jax.random.normal(kk, (b, h, t, d), jnp.bfloat16)
                   for kk in ks[:3])
        w = jax.random.normal(ks[3], (b, h, t, d), f32)
        pad = (jnp.arange(t)[None, :] >= (3 * t) // 4) \
            & (jnp.arange(b)[:, None] % 2 == 1)
        mask = jnp.where(pad, -10000.0, 0.0).astype(f32)[:, None, None]
        scale = d ** -0.5
        got, n = run_lowered(fwd_bwd(op(scale, mask), w), q, k, v,
                             expect_mosaic=tpu)
        row = {"shape": [b, h, t, d], "mosaic": n, "keep_rate": rate}
        if tpu:
            def ref(q, k, v):
                s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(f32),
                               k.astype(f32)) * scale + mask
                pr = jnp.where(keep != 0, jax.nn.softmax(s, -1) / (1 - p),
                               0.0)
                return jnp.einsum("bhqk,bhkd->bhqd", pr, v.astype(f32))
            want = fwd_bwd(ref, w)(q, k, v)
            errs = [rel_err(a, b_) for a, b_ in zip(got, want)]
            assert max(errs) < 3e-2, errs    # bf16 operands, f32 reference
            row["rel_err_out_dq_dk_dv"] = [round(e, 4) for e in errs]
        rows.append(row)
    return {"cases": rows}


def roll_dropout(tiny, tpu, key):
    """dropout / fused_dropout_add / fused_act_dropout at both shapes and
    both dtypes: keep rate within 1% of 1-p, kept values exact, and the
    backward's regenerated mask identical to the forward's."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.registry import LoweringContext, get_op

    p = DROPOUT
    shapes = [(256, 768), (2, 2, 128, 128)] if tiny \
        else [(8192, 768), (64, 12, 128, 128)]
    attrs = {"dropout_prob": p,
             "dropout_implementation": "upscale_in_train"}

    def make_case(key, shape, dtype):
        """Inputs and the f32 gelu references.  0.5 <= |x| <= 3 keeps
        gelu(x) and its derivative away from zero (the in-kernel erf
        polynomial rounds gelu to exactly 0 below about -4), so "the
        output is non-zero" reads a keep-mask back exactly."""
        kx, kr = jax.random.split(key)
        x = jax.random.normal(kx, shape, jnp.float32)
        x = (jnp.sign(x) * (0.5 + jnp.minimum(jnp.abs(x), 2.5))
             ).astype(dtype)
        r = jax.random.normal(kr, shape, jnp.float32).astype(dtype)
        xf = x.astype(jnp.float32)
        cdf = 0.5 * (1.0 + jax.lax.erf(xf / math.sqrt(2.0)))
        pdf = jnp.exp(-0.5 * xf * xf) / math.sqrt(2.0 * math.pi)
        return x, r, xf * cdf, cdf + xf * pdf

    def fwd_bwd(op_type, extra, slots, op_key):
        """jitted (Out, Mask or None, input cotangents of ones)."""
        def f(*xs):
            outs = get_op(op_type).fn(
                {s: [a] for s, a in zip(slots, xs)},
                dict(attrs, **extra), LoweringContext(base_key=op_key))
            return outs["Out"][0], outs.get("Mask", [None])[0]

        def g(*xs):
            out, vjp, mask = jax.vjp(f, *xs, has_aux=True)
            return out, mask, vjp(jnp.ones_like(out))
        return jax.jit(g)

    def f32(a):
        return np.asarray(a, np.float32)

    def check_keep(keep, what):
        rate = float(keep.mean())
        assert abs(rate - (1 - p)) < 0.01, \
            f"{what} {shape} {dtype}: keep rate {rate:.4f}, want {1 - p}"

    rows = []
    for shape in shapes:
        for dtype in ("float32", "bfloat16"):
            tol = 1e-5 if dtype == "float32" else 2e-2
            kc, kk = jax.random.split(jax.random.fold_in(key, len(rows)))
            x, r, gelu, dgelu = jax.jit(
                make_case, static_argnums=(1, 2))(kc, shape, dtype)
            xf, rf, gelu, dgelu = f32(x), f32(r), f32(gelu), f32(dgelu)

            # -- dropout: Out, Mask, dX
            (out, mask, (dx,)), n1 = run_lowered(
                fwd_bwd("dropout", {}, ("X",), kk), x, expect_mosaic=tpu)
            keep = f32(mask) != 0
            check_keep(keep, "dropout")
            assert np.allclose(f32(out), np.where(keep, xf / (1 - p), 0),
                               rtol=tol, atol=tol)
            assert ((f32(dx) != 0) == keep).all(), "dropout bwd mask"

            # -- fused_dropout_add: Out = dropout(X) + Residual; the mask
            # is read off the backward and must explain the forward
            (out, _, (dx, dr)), n2 = run_lowered(
                fwd_bwd("fused_dropout_add", {}, ("X", "Residual"), kk),
                x, r, expect_mosaic=tpu)
            keep = f32(dx) != 0
            check_keep(keep, "fused_dropout_add")
            assert np.allclose(f32(out),
                               np.where(keep, xf / (1 - p), 0) + rf,
                               rtol=tol, atol=2 * tol)
            assert (f32(dr) == 1).all()

            # -- fused_act_dropout (gelu): Out = dropout(gelu(X))
            (out, _, (dx,)), n3 = run_lowered(
                fwd_bwd("fused_act_dropout", {"act": "gelu"}, ("X",), kk),
                x, expect_mosaic=tpu)
            keep = f32(out) != 0
            check_keep(keep, "fused_act_dropout")
            assert np.allclose(f32(out), np.where(keep, gelu / (1 - p), 0),
                               rtol=tol, atol=tol)
            assert ((f32(dx) != 0) == keep).all(), "act_dropout bwd mask"
            assert np.allclose(f32(dx), np.where(keep, dgelu / (1 - p), 0),
                               rtol=10 * tol, atol=10 * tol)
            rows.append({"shape": list(shape), "dtype": dtype,
                         "keep_rate": round(float(keep.mean()), 4),
                         "mosaic": n1 + n2 + n3})
    return {"cases": rows, "mosaic": sum(r["mosaic"] for r in rows)}


def roll_embedding(tiny, tpu, key):
    """fused_embedding_pool and its fused gradient: a table that fits the
    kernels' VMEM gate takes Pallas, one far past it takes XLA — both
    against a host reference."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.ops.registry import LoweringContext, get_op

    d = 128
    small_v = pk._EMB_VMEM_BYTES // (d * 4)               # exactly 4 MB
    cases = [("fits_vmem", 256 if tiny else small_v, 64 if tiny else 4096, 5),
             ("past_vmem", 2 * small_v if tiny else 13 * small_v,
              64 if tiny else 4096, 5)]
    opdef = get_op("fused_embedding_pool")
    attrs = {"pooltype": "SUM", "padding_idx": 0}
    out = {}
    for name, vocab, b, s in cases:
        kw, ki, kg = jax.random.split(jax.random.fold_in(key, vocab), 3)
        w = jax.random.normal(kw, (vocab, d), jnp.float32)
        ids = jax.random.randint(ki, (b, s), 0, vocab, jnp.int32)
        length = (jnp.arange(b, dtype=jnp.int32) % s) + 1
        g = jax.random.normal(kg, (b, d), jnp.float32)
        ins = {"W": [w], "Ids": [ids], "Length": [length]}
        in_gate = tpu and pk.fused_embedding_pool_supported(w, ids)
        expect = None if tpu is None else bool(in_gate)
        assert expect is None or expect == (name == "fits_vmem")

        fwd = jax.jit(lambda ins, key: opdef.fn(
            ins, attrs, LoweringContext(base_key=key)))
        bwd = jax.jit(lambda ins, g, key: opdef.custom_grad(
            ins, None, {"Out": g}, attrs, LoweringContext(base_key=key)))
        pooled, n_f = run_lowered(fwd, ins, key, expect_mosaic=expect)
        dw, n_b = run_lowered(bwd, ins, g, key, expect_mosaic=expect)

        wn, idn, gn = np.asarray(w), np.asarray(ids), np.asarray(g)
        wgt = (np.arange(s)[None, :] < np.asarray(length)[:, None]) \
            * (idn != 0)
        want = np.einsum("bsd,bs->bd", wn[idn], wgt.astype(np.float32))
        want_dw = np.zeros_like(wn)
        np.add.at(want_dw, idn.reshape(-1),
                  (gn[:, None, :] * wgt[:, :, None]).reshape(-1, d))
        e_f = rel_err(pooled["Out"][0], want)
        e_b = rel_err(dw["W"][0], want_dw)
        assert e_f < 1e-5 and e_b < 1e-5, (name, e_f, e_b)
        out[name] = {"table_mb": round(vocab * d * 4 / 2 ** 20, 1),
                     "ids": [b, s], "mosaic": n_f + n_b,
                     "path": "pallas" if n_f + n_b else "xla"}
    return out


def roll_paged(tiny, tpu, key):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.ops.attention import _paged_reference

    d, ps = 128, 16
    # both pools together just under the kernel's own VMEM gate
    rows = 256 if tiny else pk._PAGED_VMEM_BYTES // (2 * d * 4) - ps
    b, s = (4, 64) if tiny else (8, 1024)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, d), jnp.float32)
    kp = jax.random.normal(kk, (rows, d), jnp.float32)
    vp = jax.random.normal(kv, (rows, d), jnp.float32)
    rng = np.random.RandomState(3)
    pages = np.stack([rng.permutation(rows // ps)[:s // ps]
                      for _ in range(b)])
    idx = (pages[:, :, None] * ps + np.arange(ps)).reshape(b, s)
    lens = rng.randint(1, s + 1, b)
    lens[0], lens[-1] = s, 1
    valid = (np.arange(s)[None, :] < lens[:, None]).astype("float32")
    ins = {"Q": [q], "KPool": [kp], "VPool": [vp],
           "Index": [jnp.asarray(idx.reshape(-1).astype("int32"))],
           "Valid": [jnp.asarray(valid)]}
    scale = d ** -0.5
    assert tpu is None or pk.paged_attention_supported(q, kp, idx)
    got, n = run_lowered(
        lower_op("paged_attention",
                 {"scale": scale, "page_size": ps, "neg": 1e30}),
        ins, key, expect_mosaic=tpu)
    want = _paged_reference(q, kp, vp, ins["Index"][0], ins["Valid"][0],
                            scale, 1e30)
    err = rel_err(got["Out"][0], want)
    assert err < 1e-5, err
    return {"pool_rows": int(rows), "pool_mb_both": round(
        2 * rows * d * 4 / 2 ** 20, 2), "window": [b, s], "page_size": ps,
        "mosaic": n, "rel_err": err}


def roll_hyper_connection(tiny, tpu, key):
    """``hyper_connection_mix`` and ``hyper_connection_merge`` through their
    lowerings, forward and every gradient, against the ``jnp`` spelling the
    same lowerings take where the context allows no kernel."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.registry import LoweringContext, get_op

    class NoKernel(LoweringContext):
        def pallas_ok(self):
            return False

    n = 4
    k = n * n + 2 * n
    attrs = {"n": n, "epsilon": 1e-6, "sinkhorn_iters": 20, "hc_eps": 1e-6,
             "clamp_min": -30.0, "clamp_max": 30.0}

    def fwd_bwd(ctx_type, w):
        def f(x, phi, alpha, b, z):
            ctx = ctx_type(base_key=key)
            mixed = get_op("hyper_connection_mix").fn(
                {"X": [x], "Phi": [phi], "Alpha": [alpha], "B": [b]},
                attrs, ctx)
            out = get_op("hyper_connection_merge").fn(
                {"X": [x], "Z": [z], "Post": mixed["Post"],
                 "C": mixed["C"]}, {}, ctx)["Out"][0]
            # Y reaches the result in float32: through a bfloat16 branch a
            # last-bit difference in Y would read as a bfloat16 step in Out
            return (out + jnp.tile(jnp.tanh(mixed["Y"][0]), n),
                    mixed["RowSumError"][0])

        def g(*args):
            out, vjp, err = jax.vjp(f, *args, has_aux=True)
            return (out, err) + vjp(w)
        return jax.jit(g)

    names = ("out", "row_sum_error", "dx", "dphi", "dalpha", "db", "dz")
    # float32 sums in another order; dz is rounded to the branch's bfloat16
    limits = dict.fromkeys(names, 1e-4)
    limits.update(dphi=2e-3, dalpha=2e-3, db=2e-3, dz=2e-2)
    cases = []
    # the training cell's streams, then a ragged last tile
    for tokens, d in [(256, 128)] if tiny else [(4096, 3584), (128 + 40, 256)]:
        ks = jax.random.split(jax.random.fold_in(key, tokens), 5)
        x = jax.random.normal(ks[0], (1, tokens, n * d), jnp.float32)
        phi = 0.02 * jax.random.normal(ks[1], (n * d, k), jnp.float32)
        alpha = jnp.asarray([1.0, 0.7, 1.3], jnp.float32)
        b = 0.5 * jax.random.normal(ks[2], (k,), jnp.float32)
        z = jax.random.normal(ks[3], (1, tokens, d), jnp.bfloat16)
        w = jax.random.normal(ks[4], (1, tokens, n * d), jnp.float32)
        args = (x, phi, alpha, b, z)
        got, n_calls = run_lowered(fwd_bwd(LoweringContext, w), *args,
                                   expect_mosaic=tpu)
        want = fwd_bwd(NoKernel, w)(*args)
        errs = {name: rel_err(a, r) for name, a, r in zip(names, got, want)}
        assert all(errs[name] < limits[name] for name in names), errs
        if tpu:
            assert n_calls == 5, n_calls    # mix 1 + 2, merge 1 + 1
        cases.append({"streams": [tokens, n, d], "mosaic": n_calls,
                      "rel_err": {k_: float(f"{v:.2e}")
                                  for k_, v in errs.items()}})
    return {"cases": cases}


def roll_sparse_attention(tiny, tpu, key):
    """The indexer's selection and the attention over it through their
    lowerings at the training cell's shape (32 : 4 heads of 128, 16 index
    heads of 64, 16384 tokens that keep 2048 keys), forward and every
    gradient of the attention, and behind it the indexer's loss with its
    gradients of QI, KI and W (the loss's own kernels: the heads' mean
    probabilities and the two passes over the index scores), against the
    ``jnp`` path the same lowerings take where the context allows no kernel;
    and the selection's count."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.registry import LoweringContext, get_op

    class NoKernel(LoweringContext):
        def kernel_site(self, x):
            return None

        def pallas_ok(self):
            return False

    seq, topk = (2048, 128) if tiny else (16384, 2048)
    hq, hkv, d, hi, di = (4, 2, 128, 2, 64) if tiny else (32, 4, 128, 16, 64)
    ks = jax.random.split(key, 8)
    q = jax.random.normal(ks[0], (1, hq, seq, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, hkv, seq, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, hkv, seq, d), jnp.bfloat16)
    w_out = jax.random.normal(ks[3], (1, hq, seq, d), jnp.float32)
    index_in = {"QI": [jax.random.normal(ks[4], (1, hi, seq, di),
                                         jnp.bfloat16)],
                "KI": [jax.random.normal(ks[5], (1, seq, di), jnp.bfloat16)],
                "W": [jax.random.normal(ks[6], (1, seq, hi), jnp.float32)]}
    chosen, _ = run_lowered(
        jax.jit(lambda ins: get_op("sparse_attention_index").fn(
            ins, {"topk": topk}, LoweringContext(base_key=key))),
        index_in, expect_mosaic=False if tpu else None)
    sel = chosen["Selection"][0]
    mean = float(chosen["SelectedKeysMean"][0][0])
    want_mean = (topk * (topk + 1) / 2 + (seq - topk) * topk) / seq
    assert abs(mean - want_mean) < 0.01, (mean, want_mean)

    def fwd_bwd(ctx_type):
        """The attention and the indexer's loss behind it: the loss reads
        the attention's log-sum-exp."""
        def f(q, k, v, qi, ki, w):
            ctx = ctx_type(base_key=key)
            attended = get_op("fused_multihead_attention").fn(
                {"Q": [q], "K": [k], "V": [v], "Selection": [sel]},
                {"causal": True, "scale": d ** -0.5}, ctx)
            loss = get_op("sparse_attention_index_loss").fn(
                {"QI": [qi], "KI": [ki], "W": [w], "Q": [q], "K": [k],
                 "LSE": attended["LSE"], "Selection": [sel]},
                {"scale": d ** -0.5}, ctx)["Loss"][0]
            return attended["Out"][0], loss

        def g(*args):
            (out, loss), vjp = jax.vjp(f, *args)
            return (out, loss) + vjp((w_out.astype(out.dtype),
                                      jnp.ones_like(loss)))
        return jax.jit(g)

    args = (q, k, v, index_in["QI"][0], index_in["KI"][0], index_in["W"][0])
    got, n_calls = run_lowered(fwd_bwd(LoweringContext), *args,
                               expect_mosaic=tpu)
    want = fwd_bwd(NoKernel)(*args)
    names = ("out", "index_loss", "dq", "dk", "dv", "dqi", "dki", "dw")
    errs = {name: rel_err(a, r) for name, a, r in zip(names, got, want)}
    # bfloat16 operands on both sides, float32 sums in another order
    assert all(e < 2e-2 for e in errs.values()), errs
    # the loss's own kernels: the loss and its three gradients within 1 %
    # (as a whole: one bfloat16 step of the largest element is 0.8 %)
    loss_errs = {name: rel_l2(a, r) for name, a, r in zip(names, got, want)
                 if name in ("index_loss", "dqi", "dki", "dw")}
    assert all(e < 1e-2 for e in loss_errs.values()), loss_errs
    if tpu:
        # forward, dq, dk/dv, and a super block of the loss: the heads' mean
        # probabilities, the row statistics with the KL, the gradients
        assert n_calls == 3 + 3 * (seq // 2048), n_calls
    return {"shape": [hq, hkv, seq, d], "topk": topk, "mosaic": n_calls,
            "selected_keys_mean": mean,
            "tile_occupancy": float(chosen["TileOccupancy"][0][0]),
            "rel_err": {k_: float(f"{e:.2e}") for k_, e in errs.items()},
            "loss_rel_l2": {k_: float(f"{e:.2e}")
                            for k_, e in loss_errs.items()}}


def roll_expert_permutation(tiny, tpu, key):
    """``moe_dispatch`` and ``moe_combine`` through their lowerings, forward
    and every gradient, against the same lowerings where the context allows
    no kernel (the held rows regrouped alike, each token's added one ``k``
    at a time): a chip that holds a quarter of the experts at the sparse
    training cell's shape, then one that holds them all."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.registry import LoweringContext, get_op
    from paddle_tpu.parallel import moe

    class NoKernel(LoweringContext):
        def pallas_ok(self):
            return False

    tokens, top_k, width, experts = (256, 4, 128, 8) if tiny \
        else (8192, 8, 2304, 64)

    def fwd_bwd(ctx_type, plan, g):
        plan_ins = {"Order": [plan.order], "Pos": [plan.pos],
                    "GroupSizes": [plan.group_sizes]}

        def f(x, w):
            ctx = ctx_type(base_key=key)
            rows = get_op("moe_dispatch").fn({"X": [x], **plan_ins}, {},
                                             ctx)["Out"][0]
            return get_op("moe_combine").fn(
                {"X": [rows * 2], "TopKWeight": [w], **plan_ins}, {},
                ctx)["Out"][0]

        def run(x, w):
            out, vjp = jax.vjp(f, x, w)
            return (out,) + vjp(g)
        return jax.jit(run)

    names = ("out", "dx", "dw")
    cases = []
    for held in (experts // 4, experts):
        ks = jax.random.split(jax.random.fold_in(key, held), 4)
        chosen = jnp.argsort(jax.random.uniform(ks[0], (tokens, experts)),
                             axis=1)[:, :top_k].astype(jnp.int32)
        plan = moe.dispatch_plan(chosen, 0, held)
        x = jax.random.normal(ks[1], (tokens, width), jnp.bfloat16)
        w = jax.nn.softmax(jax.random.normal(ks[2], (tokens, top_k)), -1)
        g = jax.random.normal(ks[3], (tokens, width), jnp.bfloat16)
        got, n_calls = run_lowered(fwd_bwd(LoweringContext, plan, g), x, w,
                                   expect_mosaic=tpu)
        want = fwd_bwd(NoKernel, plan, g)(x, w)
        # float32 sums in another order, rounded once to bfloat16
        errs = {name: rel_err(a, r) for name, a, r in zip(names, got, want)}
        assert all(e < 2e-2 for e in errs.values()), errs
        if tpu:
            assert n_calls == 2, n_calls    # combine forward, dispatch grad
        cases.append({"held_rows": int(jnp.sum(plan.group_sizes)),
                      "rows": int(plan.order.shape[0]), "mosaic": n_calls,
                      "rel_err": {k_: float(f"{v:.2e}")
                                  for k_, v in errs.items()}})
    return {"cases": cases}


def roll_selective_scan(tiny, tpu, key):
    """``selective_scan`` through its lowering at the training cell's shape
    (4096 tokens, 5120 channels of 16 states) and with a ragged last chunk,
    forward and all six gradients, against the ``jnp`` path the same
    lowering takes where the context allows no kernel."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.registry import LoweringContext, get_op

    class NoKernel(LoweringContext):
        def pallas_ok(self):
            return False

    def fwd_bwd(ctx_type, w):
        def f(*args):
            return get_op("selective_scan").fn(
                {slot: [a] for slot, a in zip("X Dt A B C D".split(), args)},
                {}, ctx_type(base_key=key))["Y"][0]

        def g(*args):
            out, vjp = jax.vjp(f, *args)
            return (out,) + vjp(w)
        return jax.jit(g)

    names = ("y", "dx", "ddt", "da", "db", "dc", "dd")
    cases = []
    for seq, di, n in [(256 + 40, 128, 8)] if tiny \
            else [(4096, 5120, 16), (512 + 40, 256, 16)]:
        ks = jax.random.split(jax.random.fold_in(key, seq), 6)
        x = jax.random.normal(ks[0], (1, seq, di), jnp.float32)
        # steps log-uniform in [1e-3, 1e-1], Mamba's start
        dt = jnp.exp(jax.random.uniform(ks[1], (1, seq, di), jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        a = -jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32), (di, 1))
        b = jax.random.normal(ks[2], (1, seq, n), jnp.float32)
        c = jax.random.normal(ks[3], (1, seq, n), jnp.float32)
        d = jnp.ones((di,), jnp.float32)
        w = jax.random.normal(ks[4], (1, seq, di), jnp.float32)
        args = (x, dt, a, b, c, d)
        got, n_calls = run_lowered(fwd_bwd(LoweringContext, w), *args,
                                   expect_mosaic=tpu)
        want = fwd_bwd(NoKernel, w)(*args)
        errs = {name: rel_l2(g_, r) for name, g_, r in zip(names, got, want)}
        # float32 on both sides, the sums in another order
        assert all(e < 1e-4 for e in errs.values()), errs
        if tpu:
            assert n_calls == 2, n_calls        # forward, backward
        cases.append({"shape": [seq, di, n], "mosaic": n_calls,
                      "rel_l2": {k_: float(f"{v:.2e}")
                                 for k_, v in errs.items()}})
    return {"cases": cases}


def roll_differential_heads(tiny, tpu, key):
    """``fused_multihead_attention`` over a 64-wide score head with a
    128-wide value head, grouped 40 : 20 and causal, with a 512 window and
    without: the splash kernel against the banded XLA spelling the same
    lowering takes where the context allows no kernel, forward and
    gradients."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.registry import LoweringContext, get_op

    class NoKernel(LoweringContext):
        def kernel_site(self, x):
            return None

    hq, hkv, seq = (4, 2, 1024) if tiny else (40, 20, 4096)
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (1, hq, seq, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, hkv, seq, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, hkv, seq, 128), jnp.bfloat16)
    w = jax.random.normal(ks[3], (1, hq, seq, 128), jnp.float32)
    cases = []
    for window in (512, 0):
        def fwd_bwd(ctx_type):
            def f(q, k, v):
                return get_op("fused_multihead_attention").fn(
                    {"Q": [q], "K": [k], "V": [v]},
                    {"causal": True, "window": window, "scale": 0.125},
                    ctx_type(base_key=key))["Out"][0]

            def g(q, k, v):
                out, vjp = jax.vjp(f, q, k, v)
                return (out,) + vjp(w.astype(out.dtype))
            return jax.jit(g)
        got, n_calls = run_lowered(fwd_bwd(LoweringContext), q, k, v,
                                   expect_mosaic=tpu)
        want = fwd_bwd(NoKernel)(q, k, v)
        errs = [rel_l2(a, r) for a, r in zip(got, want)]
        assert got[0].shape == (1, hq, seq, 128)
        assert max(errs) < 2e-2, errs       # bf16 operands on both sides
        if tpu:
            assert n_calls == 3, n_calls    # forward, dq, dk/dv
        cases.append({"window": window, "mosaic": n_calls,
                      "rel_l2_out_dq_dk_dv": [round(e, 4) for e in errs]})
    return {"shape": [hq, hkv, seq, 64, 128], "cases": cases}


def roll_embedding_grad(tiny, tpu, key):
    """``lookup_table_v2``'s gradient through its lowering (the kernel on a
    chip) against the same lowering where the context allows no kernel
    (XLA's scatter), at the hybrid decoder's timed shape and BERT's two
    tables.  Within 1e-6 of the largest element, or, where thousands of
    duplicates add in another float32 order (BERT's two token types: 8192 a
    row), within 1e-6 of the float64 sum or no further from it than XLA."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.registry import LoweringContext, get_op

    class NoKernel(LoweringContext):
        def pallas_ok(self):
            return False

    def grad(ctx_type):
        def run(w, ids, g):
            fn = get_op("lookup_table_v2").fn
            return jax.vjp(lambda w: fn({"W": [w], "Ids": [ids]}, {},
                                        ctx_type())["Out"][0], w)[1](g)
        return jax.jit(run)

    shapes = [(256, 300, 128), (256, 2, 128)] if tiny else \
        [(4096, 25008, 2560), (16384, 30522, 768), (16384, 2, 768)]
    cases = []
    for i, (n, vocab, width) in enumerate(shapes):
        ks = jax.random.split(jax.random.fold_in(key, i), 3)
        w = jax.random.normal(ks[0], (vocab, width), jnp.float32)
        ids = jax.random.randint(ks[1], (1, n), 0, vocab, jnp.int32)
        g = jax.random.normal(ks[2], (1, n, width), jnp.float32)
        (got,), n_calls = run_lowered(grad(LoweringContext), w, ids, g,
                                      expect_mosaic=tpu)
        want, = grad(NoKernel)(w, ids, g)
        exact = np.zeros((vocab, width))
        np.add.at(exact, np.asarray(ids).reshape(-1),
                  np.asarray(g, np.float64).reshape(-1, width))
        case = {"tokens": n, "table": [vocab, width], "mosaic": n_calls,
                "rel_err": float(f"{rel_err(got, want):.2e}"),
                "from_float64": [float(f"{rel_err(a, exact):.2e}")
                                 for a in (got, want)]}
        assert case["rel_err"] < 1e-6 or case["from_float64"][0] \
            <= max(case["from_float64"][1], 1e-6), case
        cases.append(case)
    return {"cases": cases}


# which of pallas_kernels.__all__ each roll-call entry drives
ROLL_CALL = [
    ("flash", roll_flash, ["flash_attention_tpu"]),
    ("fused_attention", roll_fused_attention, ["fused_attention_tpu"]),
    ("dropout", roll_dropout, ["fused_dropout_tpu", "fused_dropout_add_tpu",
                               "fused_act_dropout_tpu"]),
    ("embedding", roll_embedding, ["fused_embedding_pool_tpu",
                                   "embedding_pool_grad_tpu"]),
    ("paged", roll_paged, ["paged_flash_attention_tpu"]),
    ("hyper_connection", roll_hyper_connection,
     ["hyper_connection_mix_tpu", "hyper_connection_merge_tpu"]),
    ("sparse_attention", roll_sparse_attention,
     ["selected_attention_tpu", "selected_probability_mean_tpu",
      "index_kl_tpu"]),
    ("expert_permutation", roll_expert_permutation, ["held_rows_sum_tpu"]),
    ("selective_scan", roll_selective_scan, ["selective_scan_tpu"]),
    ("embedding_grad", roll_embedding_grad, ["embedding_grad_tpu"]),
    # jax's splash kernel under this repo's rule for a head of two widths
    ("differential_heads", roll_differential_heads, []),
]


def leg_kernels(tiny, device, out):
    import jax
    from paddle_tpu.ops import pallas_kernels as pk

    covered = sorted(k for _, _, ks in ROLL_CALL for k in ks)
    assert covered == sorted(pk.__all__), \
        f"roll-call {covered} != pallas_kernels.__all__ {sorted(pk.__all__)}"
    # True/False: the Pallas path must / must not be taken; None: this
    # backend has no kernels, the XLA lowering is checked instead
    tpu = True if device.platform == "tpu" else None
    key = jax.random.PRNGKey(7)
    for i, (name, fn, _) in enumerate(ROLL_CALL):
        out[name] = fn(tiny, tpu, jax.random.fold_in(key, i))
        say(f"  {name}: {json.dumps(out[name])}")


# ---------------------------------------------------------------------------
# legs: four chips
# ---------------------------------------------------------------------------

def leg_dp4(size, devices, one_chip_losses, out):
    """The executor leg's program under BuildStrategy.sharding = "dp": the
    one-chip batch tiled over four devices, so the mean loss and gradient
    are the one-chip leg's up to dropout noise."""
    import jax
    import paddle_tpu.fluid as fluid

    n = len(devices)
    main, startup, loss = build_static_bert(size)
    bs = fluid.BuildStrategy()
    bs.sharding = "dp"
    prog = fluid.CompiledProgram(main, build_strategy=bs)
    plan = prog._ensure_sharding_plan()
    assert plan.mesh.devices.size == n, plan.mesh
    feed = {k: jax.device_put(
        np.concatenate([v] * n), plan.data_sharding((v.shape[0] * n,)
                                                    + v.shape[1:]))
        for k, v in static_feed(size).items()}
    for k, v in feed.items():
        shard_devs = {s.device for s in v.addressable_shards}
        assert shard_devs == set(devices), (k, shard_devs)
        assert v.addressable_shards[0].data.shape[0] == size["batch"], k

    in_use = []

    def check_param(w):
        """Called while the trained state is still in scope."""
        assert {s.device for s in w.addressable_shards} == set(devices), \
            w.sharding
        # the CPU backend (--tiny) reports no memory statistics
        if devices[0].platform == "tpu":
            in_use.extend(d.memory_stats()["bytes_in_use"] for d in devices)
            assert all(b > 0 for b in in_use), in_use

    from paddle_tpu.fluid import device_stats, trace
    m = trace.metrics()
    names = ("attention.lowering.fused_kernel", "attention.lowering.xla",
             "kernel.shard_map_calls")
    before = {k: m.counter(k).value for k in names}
    vjp0 = vjp_counts()
    losses, first_s, step_ms = run_executor(prog, startup, loss, feed,
                                            devices[0], check_param)
    check_losses(losses, "executor dp4")
    vjp = {k: v - vjp0[k] for k, v in vjp_counts().items()}
    assert vjp["retraced"] == 1 and vjp["kept"] >= 2 * size["layers"], vjp
    # the partitioned step took its Pallas kernels, once per chip under
    # shard_map (LoweringContext.kernel_site): the default pipeline fused
    # every attention chain, its lowering (once per layer: the grad op
    # applies the vjp the forward op kept) picked the kernel, and the
    # compiled step launches it once forward and once backward per layer
    lowered = {k: int(m.counter(k).value - before[k]) for k in names}
    mosaic = sum(e["mosaic_calls"] for e in device_stats.op_maps())
    kernel_calls = None
    if devices[0].platform == "tpu":
        assert lowered["attention.lowering.fused_kernel"] \
            == size["layers"] and not lowered["attention.lowering.xla"] \
            and lowered["kernel.shard_map_calls"] > 0, lowered
        assert mosaic > 0, "no Mosaic call in the data-parallel step"
        kernel_calls = attention_kernel_calls(size["layers"])
    # same weights, the same 64 rows on every chip: the first steps agree
    # to dropout noise (the masks differ — other block shapes) and the
    # last one lands in the same place; in between Adam at lr 1e-3 is
    # chaotic on this program, so the drift there is only recorded
    drifts = [abs(a - b) / abs(b) for a, b in zip(losses, one_chip_losses)]
    assert max(drifts[:2]) < 0.02 and drifts[-1] < 0.1, \
        (losses, one_chip_losses)
    drift = max(drifts)
    out.update({"first_step_s": first_s, "step_ms": step_ms,
                "global_batch": size["batch"] * n,
                "losses": [round(v, 4) for v in losses],
                "max_rel_drift_vs_one_chip": round(drift, 4),
                "bytes_in_use": in_use, "lowered": lowered, "vjp": vjp,
                "mosaic_calls": mosaic, "kernel_calls": kernel_calls})


def leg_hybrid(tiny, devices, out):
    """One step of parallel/hybrid.make_train_step on a pp2 x tp2 mesh."""
    import jax
    from paddle_tpu.parallel.hybrid import (TransformerConfig,
                                            build_hybrid_mesh, demo_batch,
                                            make_train_step)

    mesh = build_hybrid_mesh(4, devices=devices,
                             axes={"dp": 1, "pp": 2, "tp": 2, "sp": 1})
    cfg = TransformerConfig(vocab=512, d_model=128, n_heads=4, d_ff=256,
                            n_layers=2, seq_len=32, batch=4) if tiny \
        else TransformerConfig(vocab=30522, d_model=768, n_heads=12,
                               d_ff=3072, n_layers=4, seq_len=128, batch=16)
    params, opt_state, step_fn = make_train_step(mesh, cfg)
    tok, lbl = demo_batch(cfg, mesh, seed=0)
    t0 = time.perf_counter()
    params, opt_state, loss = step_fn(params, opt_state, tok, lbl)
    jax.block_until_ready((params, loss))
    loss = float(loss)
    assert math.isfinite(loss), loss
    placed = {s.device for p in params.values()
              for s in p.addressable_shards}
    assert placed == set(devices), placed
    out.update({"mesh": {a: int(mesh.shape[a]) for a in mesh.axis_names},
                "first_step_s": round(time.perf_counter() - t0, 1),
                "loss": round(loss, 4)})


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes on any backend: a control-flow check, "
                         "NOT a chip result")
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: the data-parallel and pp2 x tp2 legs over four "
                         "real devices")
    ap.add_argument("--expect-warm", action="store_true",
                    help="a second run against the same compile cache: "
                         "fail if it adds a file")
    args = ap.parse_args(argv)

    import jax
    import jaxlib
    from importlib import metadata

    devices = jax.devices()
    device = devices[0]
    info = {"platform": device.platform, "kind": device.device_kind,
            "count": len(devices)}
    say(f"platform={info['platform']} device_kind={info['kind']!r} "
        f"devices={info['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={metadata.version('libtpu')}")
    if args.tiny:
        say("--tiny: toy sizes, a control-flow check — NOT a chip result")
    elif device.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform {device.platform!r}). "
            f"This check runs on the chip; --tiny runs its control flow "
            f"on any backend.")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, JAX reports {len(devices)}")

    import paddle_tpu
    from paddle_tpu.fluid import compile_cache
    # weights, dropout seeds and the tracer's base key all come from this
    # seed: unseeded, every process bakes different constants into its
    # programs and the compile cache can never hit
    paddle_tpu.seed(0)
    cache_dir = compile_cache.enable_jax_cache()
    files0 = cache_files(cache_dir)
    say(f"compile cache {cache_dir}: {files0} files at start")

    size = TINY if args.tiny else FULL
    log = CompileLog()
    results = {}
    if args.chips == 1:
        with log.leg("eager", results) as out:
            leg_eager(size, device, out)
        with log.leg("executor", results) as out:
            leg_executor(size, device, out)
        with log.leg("kernels", results) as out:
            leg_kernels(args.tiny, device, out)
    else:
        four = devices[:4]
        with log.leg("executor_one_chip", results) as out:
            losses = executor_plain(size, device, out)
        with log.leg("executor_dp4", results) as out:
            leg_dp4(size, four, losses, out)
        with log.leg("hybrid_pp2_tp2", results) as out:
            leg_hybrid(args.tiny, four, out)

    files1 = cache_files(cache_dir)
    results["cache"] = {
        "dir": cache_dir, "files_start": files0, "files_end": files1,
        "files_added": files1 - files0,
        "compile_s": round(log.seconds, 1), "compiles": log.compiles,
        "cache_hits": log.cache_hits}
    say(f"== cache: {json.dumps(results['cache'])}")
    if args.expect_warm:
        assert files1 == files0, \
            f"a warm run added {files1 - files0} files to {cache_dir}"

    # a record for chiprun to bring back; the verdict is the last line
    os.makedirs("chiprun_out", exist_ok=True)
    name = "chip_smoke" + ("_tiny" if args.tiny else "") \
        + (f"_chips{args.chips}" if args.chips != 1 else "") \
        + ("_warm" if args.expect_warm else "") + ".json"
    with open(os.path.join("chiprun_out", name), "w") as f:
        json.dump({"device": info, "legs": results}, f, indent=1)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
