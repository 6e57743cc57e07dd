"""MFU attribution sweep for the BERT bench (run on a real TPU chip).

No sweep has been run on this code; every number earlier rounds quoted
from it is withdrawn.  The sweep ablates one suspect at a time against the
exact bench configuration:

  baseline      the exact bench configuration (fused dropout epilogues)
  unfused       fused dropout+add / act+dropout epilogues reverted to
                separate ops
  nodrop        dropout off (RNG + mask traffic cost)
  seq512        sequence 512 (attention/matmul ratio shifts, bigger tiles)
  nohead        MLM head replaced by mean pooling (vocab-matmul +
                softmax-xent cost)
  b256          batch 256 (MXU tiling at larger leading dim)
  profile       baseline + jax.profiler trace to /tmp/mfu_trace

Usage:  python tools/mfu_sweep.py [case ...]   (default: all non-profile)
Prints one JSON line per case, naming the device it ran on.  Each case runs
in a child process; the parent never touches JAX, so the chip is free for
each child in turn.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def run_case(case, steps=20, warmup=3):
    import jax
    import jax.numpy as jnp
    import bench
    from paddle_tpu.fluid import compile_cache

    compile_cache.enable_jax_cache()
    tiny = bool(os.environ.get("MFU_SWEEP_TINY"))   # CPU smoke of the harness
    device = bench.device_info(quick=tiny)

    vocab, hidden, layers, heads, ffn = 30522, 768, 12, 12, 3072
    seq, batch = (512, 16) if case == "seq512" else (128, 64)
    if case == "b256":
        batch = 256
    if tiny:
        vocab, hidden, layers, heads, ffn = 500, 64, 2, 4, 128
        seq, batch, steps, warmup = 32, 4, 2, 1

    if case == "nodrop":
        import paddle_tpu.dygraph.layers as dl
        dl.Layer.train = dl.Layer.eval          # dropout off everywhere

    if case == "unfused":
        os.environ["PADDLE_TPU_UNFUSED_EPILOGUE"] = "1"

    if case == "nohead":
        from paddle_tpu.dygraph import base as dybase
        from paddle_tpu.dygraph.functional import functional_loss
        from paddle_tpu.models.bert import BertModel
        from paddle_tpu.fluid import layers as L

        dybase.enable_dygraph()
        tracer = dybase._dygraph_tracer()
        tracer._amp_enabled = True
        model = BertModel(vocab_size=vocab, hidden_size=hidden,
                          num_layers=layers, num_heads=heads,
                          intermediate_size=ffn, max_position=seq)
        model.train()

        def loss_fn(ids):
            seq_out, _ = model(ids)
            return L.mean(seq_out)

        values, lfn = functional_loss(model, loss_fn)
        # EXACTLY the bench's fused-Adam two-program step — an unjitted
        # per-param python update here once made `nohead` SLOWER than
        # baseline and wrecked the attribution
        step2, opt_state = bench.make_two_program_step(values, lfn, 1e-6)

        def jstep(state, ids, _m, _n):
            return step2(state, ids)
        n_params = sum(int(np.prod(v.shape)) for v in values)
    else:
        jstep, opt_state, n_params = bench.build_train_step(
            vocab, hidden, layers, heads, ffn, seq, batch)

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, vocab, (batch, seq)).astype("int32"))
    mlm = jnp.asarray(rng.randint(0, vocab, (batch, seq)).astype("int32"))
    nsp = jnp.asarray(rng.randint(0, 2, (batch,)).astype("int32"))

    st = opt_state
    for _ in range(warmup):
        st, loss = jstep(st, ids, mlm, nsp)
    float(loss)

    if case == "profile":
        import jax.profiler
        jax.profiler.start_trace("/tmp/mfu_trace")
    t0 = time.perf_counter()
    for _ in range(steps):
        st, loss = jstep(st, ids, mlm, nsp)
    float(loss)
    dt = time.perf_counter() - t0
    if case == "profile":
        jax.profiler.stop_trace()

    tok_s = steps * batch * seq / dt
    fpt = bench.flops_per_token(hidden, layers, ffn, seq, vocab)
    if case == "nohead":
        fpt -= 3 * 2 * hidden * vocab      # head ablated: honest FLOPs
    row = {"case": case, "tok_s": round(tok_s, 1),
           "step_ms": round(dt / steps * 1e3, 2),
           "seq": seq, "batch": batch, **device}
    if device["platform"] != "cpu":
        row["mfu"] = round(tok_s * fpt / bench.device_peaks(
            device["device_kind"])["bf16_flops"], 4)
    print(json.dumps(row))


def main():
    cases = sys.argv[1:] or ["baseline", "unfused", "nodrop", "nohead",
                             "b256", "seq512"]
    for case in cases:
        # each case in a fresh process: monkeypatches + jit caches isolate
        if os.environ.get("MFU_SWEEP_CHILD"):
            run_case(case)
            return
        import subprocess
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__), case],
                env=dict(os.environ, MFU_SWEEP_CHILD="1"),
                capture_output=True, text=True, timeout=900)
        except subprocess.TimeoutExpired:
            # one hung case (a giant compile) must not kill the
            # remaining ablations
            print(f'{{"case": "{case}", "error": "timeout 900s"}}',
                  flush=True)
            continue
        out = [l for l in r.stdout.splitlines() if l.startswith("{")]
        print(out[-1] if out else
              f'{{"case": "{case}", "error": "rc={r.returncode}: '
              f'{r.stderr[-200:].strip()}"}}', flush=True)


if __name__ == "__main__":
    main()
