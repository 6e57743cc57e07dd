#!/usr/bin/env python
"""Framework-free consumer of a paddle_tpu AOT artifact.

Proves the deployment claim of inference/aot.py: serving a saved
`model.stablehlo` + `aot_meta.json` needs ONLY the pinned jax.export
deserialize interface over PJRT — not paddle_tpu, not the model's Python
code, not its op registry.  This script never imports paddle_tpu (and
asserts so); it is the capi/go-client analog (reference
paddle/fluid/inference/capi/) for the XLA deployment story: the same two
files can be served from any language with a PJRT binding, and
`--dump-mlir` shows the artifact is open compiler IR, not a framework
blob.

`--engine` flips the script to the other end of the deployment story:
it drives the full paddle_tpu ServingEngine over the (multi-bucket) AOT
artifact — bounded admission queue, continuous batching of mixed-size
requests, warmup precompilation — and prints the sustained QPS + latency
split.  That mode DOES import paddle_tpu (the framework-free assert
applies to the default consumer path only).

Usage:
    python examples/aot_serve.py MODEL_DIR --input x=INPUT.npy ...
    python examples/aot_serve.py MODEL_DIR --random     # meta-shaped RNG
    python examples/aot_serve.py MODEL_DIR --dump-mlir  # print StableHLO
    python examples/aot_serve.py MODEL_DIR --engine --requests 100
"""
import argparse
import json
import os
import sys

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("model_dir")
    ap.add_argument("--input", action="append", default=[],
                    metavar="NAME=FILE.npy",
                    help="bind a feed by name to a .npy file")
    ap.add_argument("--random", action="store_true",
                    help="feed RNG data shaped per the sidecar meta")
    ap.add_argument("--dump-mlir", action="store_true",
                    help="print the StableHLO module text and exit")
    ap.add_argument("--engine", action="store_true",
                    help="serve mixed-size requests through the "
                         "paddle_tpu ServingEngine (continuous batching)")
    ap.add_argument("--requests", type=int, default=64,
                    help="request count for --engine")
    args = ap.parse_args(argv)

    from jax import export as jexport

    with open(os.path.join(args.model_dir, "model.stablehlo"), "rb") as f:
        exported = jexport.deserialize(f.read())
    with open(os.path.join(args.model_dir, "aot_meta.json")) as f:
        meta = json.load(f)

    if args.dump_mlir:
        print(exported.mlir_module())
        return 0

    if args.engine:
        return serve_with_engine(args.model_dir, meta, args.requests)

    feeds = {}
    for spec in args.input:
        name, path = spec.split("=", 1)
        feeds[name] = np.load(path)
    if args.random:
        rng = np.random.RandomState(0)
        for name in meta["feed_names"]:
            if name not in feeds:
                shape = meta["input_shapes"][name]
                dtype = np.dtype(meta["input_dtypes"][name])
                if dtype.kind in "iu":
                    feeds[name] = rng.randint(0, 2, shape).astype(dtype)
                else:
                    feeds[name] = rng.randn(*shape).astype(dtype)
    missing = [n for n in meta["feed_names"] if n not in feeds]
    if missing:
        ap.error(f"missing inputs: {missing} (use --input or --random)")

    outs = exported.call(*[feeds[n] for n in meta["feed_names"]])
    for name, out in zip(meta["fetch_names"], outs):
        arr = np.asarray(out)
        print(f"{name}: shape={arr.shape} dtype={arr.dtype} "
              f"mean={arr.mean():.6f}")
        np.save(os.path.join(args.model_dir, f"out_{name}.npy"), arr)

    assert "paddle_tpu" not in sys.modules, \
        "consumer must stay framework-free"
    print("served without paddle_tpu")
    return 0


def serve_with_engine(model_dir, meta, n_requests):
    """End-to-end ServingEngine over the AOT artifact: mixed-size
    requests coalesce into shape-bucketed batches, every bucket
    precompiled by warmup()."""
    import time
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:        # runnable straight from a checkout
        sys.path.insert(0, root)
    from paddle_tpu.inference import load_aot_model
    from paddle_tpu.serving import ServingEngine

    pred = load_aot_model(model_dir)
    buckets = pred.buckets
    if not buckets:
        print("artifact has no bucketed modules — re-export with "
              "save_aot_model(..., bucket_edges=[...]); serving the "
              "baked shape only", file=sys.stderr)
    max_rows = max(buckets) if buckets else None
    rng = np.random.RandomState(0)

    def random_feed(rows):
        feed = {}
        for name in meta["feed_names"]:
            shape = list(meta["input_shapes"][name])
            if shape:
                shape[0] = rows
            dtype = np.dtype(meta["input_dtypes"][name])
            feed[name] = (rng.randint(0, 2, shape).astype(dtype)
                          if dtype.kind in "iu"
                          else rng.randn(*shape).astype(dtype))
        return feed

    sizes = sorted({s for s in (1, 2, 3, 4, 5, 8)
                    if max_rows is None or s <= max_rows}) or [1]
    with ServingEngine(pred, max_batch=max_rows or 8,
                       max_wait_us=2000) as eng:
        eng.warmup()
        t0 = time.perf_counter()
        futs = [eng.submit(random_feed(sizes[i % len(sizes)]))
                for i in range(n_requests)]
        outs = [f.result(timeout=120) for f in futs]
        wall = time.perf_counter() - t0
        stats = eng.stats()
    total_rows = sum(next(iter(o.values())).shape[0] for o in outs)
    lat = stats["latency_seconds"]
    print(f"served {len(outs)} requests ({total_rows} rows) in "
          f"{wall*1e3:.0f}ms -> {len(outs)/wall:.0f} req/s, "
          f"p50 {lat.get('p50', 0)*1e3:.2f}ms "
          f"p99 {lat.get('p99', 0)*1e3:.2f}ms, "
          f"{stats['batches']} batches "
          f"(avg {stats['batch_size'].get('avg', 0):.1f} rows)")
    print("served through ServingEngine")
    return 0


if __name__ == "__main__":
    sys.exit(main())
