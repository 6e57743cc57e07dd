"""Per-op micro-benchmark harness — the op_tester analog.

Reference: paddle/fluid/operators/benchmark/op_tester.cc (+ op_tester.cfg):
build one op from a config, run it repeatedly, report latency.  TPU-native:
the op's lowering rule is jitted standalone (forward, and optionally its
generic-vjp backward) and timed over a synthetic batch.

Usage:
  python tools/op_bench.py --op softmax --inputs X:128x1024 --steps 200
  python tools/op_bench.py --op matmul_v2 --inputs X:256x512,Y:512x512 --grad

Prints one JSON line per benched op:
  {"op": ..., "fwd_us": ..., "bwd_us": ..., "shapes": ...}
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _parse_inputs(spec: str):
    """'X:128x1024,Y:512x512i' -> {slot: (shape, dtype)} (i suffix=int64)."""
    out = {}
    for part in spec.split(","):
        name, shape = part.split(":")
        dtype = "float32"
        if shape.endswith("i"):
            shape, dtype = shape[:-1], "int64"
        out[name] = (tuple(int(d) for d in shape.split("x")), dtype)
    return out


def bench_op(op_type, inputs, attrs=None, steps=100, warmup=10, grad=False,
             seed=0):
    """Time one op lowering (and optionally its vjp) under jit.

    inputs: {slot: (shape, dtype)} or {slot: ndarray}.
    Returns dict with fwd_us / bwd_us (per-call microseconds)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.registry import get_op, LoweringContext

    opdef = get_op(op_type)
    attrs = dict(attrs or {})
    rng = np.random.RandomState(seed)
    arrs = {}
    for slot, v in inputs.items():
        if isinstance(v, np.ndarray):
            arrs[slot] = jnp.asarray(v)
        else:
            shape, dtype = v
            if "int" in dtype:
                arrs[slot] = jnp.asarray(
                    rng.randint(0, 2, shape).astype(dtype))
            else:
                arrs[slot] = jnp.asarray(rng.randn(*shape).astype(dtype))

    ctx = LoweringContext(base_key=jax.random.PRNGKey(seed))

    def fwd(xs):
        outs = opdef.fn({k: [v] for k, v in xs.items()}, attrs, ctx)
        return {k: v for k, v in outs.items()}

    jf = jax.jit(fwd)

    def timeit(fn, *a):
        out = fn(*a)
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready()
            if hasattr(x, "block_until_ready") else x, out)
        for _ in range(warmup):
            out = fn(*a)
        jax.tree_util.tree_map(
            lambda x: np.asarray(x) if hasattr(x, "dtype") else x, out)
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*a)
        jax.tree_util.tree_map(
            lambda x: np.asarray(x) if hasattr(x, "dtype") else x, out)
        return (time.perf_counter() - t0) / steps * 1e6

    result = {"op": op_type,
              "shapes": {k: list(np.shape(v)) for k, v in arrs.items()},
              "fwd_us": round(timeit(jf, arrs), 2)}

    if grad and opdef.differentiable:
        diff = {k: v for k, v in arrs.items()
                if k not in opdef.nondiff_inputs
                and jnp.issubdtype(v.dtype, jnp.floating)}
        closed = {k: v for k, v in arrs.items() if k not in diff}

        def loss(d):
            outs = fwd({**closed, **d})
            return sum(jnp.sum(v[0]).astype(jnp.float32)
                       for v in outs.values()
                       if v and hasattr(v[0], "dtype")
                       and jnp.issubdtype(v[0].dtype, jnp.floating))

        jg = jax.jit(jax.grad(loss))
        result["bwd_us"] = round(timeit(jg, diff), 2)
    return result


def main(argv=None):
    p = argparse.ArgumentParser("op_bench")
    p.add_argument("--op", required=True)
    p.add_argument("--inputs", required=True,
                   help="slot:shape[,slot:shape...]; 'i' dtype suffix")
    p.add_argument("--attrs", default="{}", help="json op attrs")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--warmup", type=int, default=10)
    p.add_argument("--grad", action="store_true")
    args = p.parse_args(argv)
    res = bench_op(args.op, _parse_inputs(args.inputs),
                   json.loads(args.attrs), args.steps, args.warmup,
                   args.grad)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
