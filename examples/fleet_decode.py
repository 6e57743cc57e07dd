"""Serving-fleet + autoregressive-decode demo (CPU-runnable).

Two acts:

1. **Fleet** — spin up a 2-replica subprocess fleet of the demo mlp
   behind the least-queue-depth router, serve a burst, SIGKILL one
   replica mid-burst and watch the router eject it on the missed
   /healthz scrapes, redispatch the in-flight requests, and (because
   ``auto_replace``) bring up a warm replacement from the shared
   persistent compile cache with zero cold compiles.  No accepted
   request is lost.

2. **Decode** — build the demo KV-cached decode model and generate a
   few sequences through the continuous decode batcher, with requests
   joining mid-flight; print the token streams and show they are
   bit-identical to decoding each request alone.

Run: python examples/fleet_decode.py
"""
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np                                        # noqa: E402
from paddle_tpu.fluid import compile_cache                # noqa: E402
from paddle_tpu.serving import decode, fleet              # noqa: E402


def fleet_act():
    print("== act 1: serving fleet (2 replicas, kill drill) ==")
    # the replicas' compile index sits beside jax's compilation cache: a
    # fixed place, so a replacement (and the next run) starts warm
    cache = compile_cache.jax_cache_dir()
    fl = fleet.ServingFleet(
        spec=fleet.demo_mlp_spec(watchdog_stall_s=1.0),
        n_replicas=2, scrape_interval_s=0.25, missed_scrape_limit=2,
        auto_replace=True, persistent_cache_dir=cache,
        rpc_timeout_s=5.0, quiet_children=True)
    try:
        rng = np.random.RandomState(0)
        pool = rng.randn(16, 16).astype("float32")
        futs = [fl.submit({"x": pool[: 1 + i % 8]}) for i in range(30)]
        [f.result(timeout=30) for f in futs]
        print(f"  burst 1: {len(futs)} requests served by "
              f"{sorted({f.replica for f in futs})}")

        fl.kill_replica("r0")
        t_kill = time.monotonic()
        futs = [fl.submit({"x": pool[: 1 + i % 8]}) for i in range(30)]
        outs = [f.result(timeout=60) for f in futs]
        deadline = time.time() + 60
        while not fl.events_of("replace") and time.time() < deadline:
            time.sleep(0.1)
        ej = [e for e in fl.events_of("eject") if e["replica"] == "r0"]
        rep = fl.events_of("replace")
        print(f"  killed r0 mid-burst: {len(outs)} requests still "
              f"served, 0 lost")
        if ej:
            print(f"  ejected ({ej[0]['reason']}) "
                  f"{ej[0]['t_mono'] - t_kill:.2f}s after the kill")
        if rep:
            w = rep[0].get("warmup") or {}
            print(f"  warm replacement {rep[0]['replica']}: "
                  f"{w.get('cold_misses')} cold compiles "
                  f"(persistent cache shared across the fleet)")
    finally:
        fl.close()


def decode_act():
    print("== act 2: autoregressive decode (join/leave batching) ==")
    model = decode.build_demo_decode_model(vocab=31, d_model=12,
                                           max_len=20, seed=11)
    prompts = [[3, 1, 4], [2, 7, 1, 8], [6], [9, 9, 2, 6, 5]]
    budgets = [6, 5, 7, 4]
    with decode.DecodeEngine(model, max_batch=4,
                             collect_logits=True) as eng:
        futs = [eng.submit(p, max_new_tokens=b)
                for p, b in zip(prompts[:2], budgets[:2])]
        time.sleep(0.2)     # join the rest mid-flight
        futs += [eng.submit(p, max_new_tokens=b)
                 for p, b in zip(prompts[2:], budgets[2:])]
        batched = [f.result(timeout=60) for f in futs]
    reference = decode.decode_sequential(model, prompts,
                                         max_new_tokens=budgets)
    for p, b, r in zip(prompts, batched, reference):
        mark = "==" if np.array_equal(b["tokens"], r["tokens"]) \
            and np.array_equal(b["logits"], r["logits"]) else "!="
        print(f"  prompt {p} -> {b['tokens'].tolist()}  "
              f"(batched {mark} sequential)")
    ok = all(np.array_equal(b["tokens"], r["tokens"])
             for b, r in zip(batched, reference))
    print(f"  join/leave batching bit-identical to sequential: {ok}")
    if not ok:
        raise SystemExit(1)


def main():
    fleet_act()
    decode_act()
    print("fleet + decode demo: loss of zero requests, saved the day")


if __name__ == "__main__":
    main()
