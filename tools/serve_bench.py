"""Open-loop serving benchmark: sustained QPS + latency percentiles.

The "millions of users" measurement (ROADMAP item 2): drive a
ServingEngine with **open-loop** synthetic load — Poisson arrivals at a
target rate with mixed request sizes, submitted on schedule whether or
not earlier requests finished — and report what the engine actually
sustained: completed QPS, p50/p95/p99 latency split into queue vs
device time, rejection/timeout counts, and the batch-size distribution
the continuous batcher achieved.  Open loop is the honest protocol: a
closed loop would slow the clients down with the server and hide the
knee.

Run:
    python tools/serve_bench.py                       # demo mlp, 200 qps
    python tools/serve_bench.py --qps 500 --seconds 5 --sizes 1,2,4,8
    python tools/serve_bench.py --metrics-port 9100   # live /metrics

Fleet mode (ROADMAP item 2's protocol — sustained fleet QPS/p99 under
open-loop Poisson load with a replica KILLED mid-run; reports ejection
latency, requests rerouted, and warm replacement spin-up as BENCH
evidence):

    python tools/serve_bench.py --fleet 3 --kill-replica-at 2.0

Topology mode (ROADMAP item 2's scaling protocol): TP-sharded replicas
over emulated devices, per-chip throughput, a 1-replica baseline for
the scaling ratio, the sharded-vs-unsharded per-device HBM compare,
and — with ``--decode`` — a routed-decode leg so one JSON line carries
examples/s/chip AND tokens/s/chip for the whole fleet:

    python tools/serve_bench.py --fleet 2 --replica-mesh tp:8 \\
        --scaling --decode

Chaos mode (docs/robustness.md — the network half of the failure
model): a seeded schedule mixing latency, drops, resets, frame
corruption, and trickle against the fleet's RPC plane; reports lost
requests (must be 0), checksum-detected corruptions, and circuit
breaker transitions.  Same seed ⇒ same injected-fault sequence:

    python tools/serve_bench.py --chaos 42 --fleet 2 --qps 60 --seconds 6

Emits one JSON line (machine-readable, bench.py-style).
``bench.py --model serve`` rides this module's single-engine leg.  The
``--fleet`` legs start subprocess replicas, which ``ServingFleet`` refuses
on a TPU host (a chip belongs to one process; docs/serving.md).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def build_demo_engine(hidden=64, features=16, classes=10, max_batch=32,
                      max_wait_us=2000, queue_depth=256):
    """A small frozen mlp + ServingEngine — the ci_smoke serving demo."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = fluid.data("x", [-1, features])
        h = fluid.layers.fc(x, hidden, act="relu")
        h = fluid.layers.fc(h, hidden, act="relu")
        logits = fluid.layers.fc(h, classes)
    exe = fluid.Executor()
    exe.run(startup)
    frozen = serving.freeze_program(main_p, ["x"], [logits])
    eng = serving.ServingEngine(frozen, executor=exe, max_batch=max_batch,
                                max_wait_us=max_wait_us,
                                queue_depth=queue_depth)
    return eng, frozen, exe, logits.name, features


def run_open_loop(engine, feed_of_rows, qps: float, n_requests: int,
                  sizes, seed=0, deadline_ms=None):
    """Submit ``n_requests`` on a Poisson schedule at ``qps`` offered
    load; returns (futures, wall_seconds, offered_seconds, rejected).
    Submission never waits for results — open loop."""
    rng = np.random.RandomState(seed)
    inter = rng.exponential(1.0 / max(qps, 1e-9), size=n_requests)
    sched = np.cumsum(inter)
    sizes = list(sizes)
    req_rows = [int(sizes[i % len(sizes)]) for i in rng.permutation(
        n_requests)]
    futures, rejected = [], 0
    rows_of = _FUTURE_ROWS
    t0 = time.perf_counter()
    for i in range(n_requests):
        lag = sched[i] - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        try:
            fut = engine.submit(feed_of_rows(req_rows[i]),
                                deadline_ms=deadline_ms)
            rows_of[id(fut)] = (fut, req_rows[i])
            futures.append(fut)
        except Exception:           # noqa: BLE001 — QueueFull counts
            rejected += 1
    wall_submit = time.perf_counter() - t0
    return futures, wall_submit, float(sched[-1]), rejected


# future -> submitted row count (futures are __slots__ classes, so the
# side table keeps the fut alive and the rows findable for the per-chip
# examples/s accounting)
_FUTURE_ROWS: dict = {}


def collect(futures, timeout=120.0):
    """Wait every future out; returns (completed, failed)."""
    done = failed = 0
    deadline = time.monotonic() + timeout
    for f in futures:
        try:
            f.result(timeout=max(deadline - time.monotonic(), 0.01))
            done += 1
        except Exception:           # noqa: BLE001 — timeouts/rejections
            failed += 1
    return done, failed


def slowest_requests(futures, top=5):
    """The slowest completed requests of this round, by the engine's own
    per-request latency (the flight recorder's wide events, keyed by
    each future's ``trace_id``) — a bad bench round links straight to
    the offending request traces (`tools/diagnose.py --request <id>` or
    grep the exported timeline).  Fleet rounds record these parent-side
    with the serving replica attached, so each offender also carries
    ``replica`` and — when the run was traced end-to-end, giving the
    router the replica's queue/device split over the propagated trace
    id — ``router_ms``, the router-side share (routing + RPC) of the
    end-to-end latency."""
    from paddle_tpu.fluid import flight_recorder

    ids = {f.trace_id for f in futures if getattr(f, "trace_id", None)}
    recs = [r for r in flight_recorder.recorder().snapshot()
            if r.get("kind") == "request" and r.get("trace_id") in ids
            and r.get("outcome") == "ok"
            and r.get("latency_us") is not None]
    recs.sort(key=lambda r: -r["latency_us"])
    out = []
    for r in recs[:top]:
        row = {"trace_id": r["trace_id"],
               "latency_ms": round(r["latency_us"] / 1e3, 3),
               "queue_ms": round(r.get("queue_us", 0) / 1e3, 3),
               "device_ms": round(r.get("device_us", 0) / 1e3, 3),
               "rows": r.get("rows"), "batch_id": r.get("batch_id")}
        if r.get("replica") is not None:
            row["replica"] = r["replica"]
            if r.get("queue_us") is not None \
                    and r.get("device_us") is not None:
                row["router_ms"] = round(max(
                    r["latency_us"] - r["queue_us"] - r["device_us"],
                    0.0) / 1e3, 3)
        out.append(row)
    return out


def serve_bench(qps=200.0, n_requests=400, sizes=(1, 2, 4, 8),
                max_batch=32, max_wait_us=2000, queue_depth=256,
                hidden=64, deadline_ms=None, metrics_port=None,
                warmup=True):
    """Build the demo engine, warm it, run the open-loop load, and
    return the report dict."""
    from paddle_tpu.fluid import trace, metrics_export

    srv = None
    if metrics_port is not None:
        srv = metrics_export.start_http(port=int(metrics_port))
        print(f"# /metrics live on port {srv.port}", file=sys.stderr)

    try:
        eng, frozen, exe, fetch_name, features = build_demo_engine(
            hidden=hidden, max_batch=max_batch, max_wait_us=max_wait_us,
            queue_depth=queue_depth)
        rng = np.random.RandomState(1)
        pool = rng.randn(max(sizes) * 4, features).astype("float32")

        def feed_of_rows(n):
            off = rng.randint(0, len(pool) - n + 1)
            return {"x": pool[off:off + n]}

        m = trace.metrics()
        with eng:
            wreport = eng.warmup() if warmup else None
            cold0 = m.counter("executor.compile_cache_cold_miss").value
            miss0 = m.counter("executor.compile_cache_miss").value
            t0 = time.perf_counter()
            futures, wall_submit, offered_s, rejected = run_open_loop(
                eng, feed_of_rows, qps, n_requests, sizes,
                deadline_ms=deadline_ms)
            done, failed = collect(futures)
            wall = time.perf_counter() - t0
            slowest = slowest_requests(futures)
            compiles_under_load = \
                m.counter("executor.compile_cache_miss").value - miss0
            cold_under_load = \
                m.counter("executor.compile_cache_cold_miss").value - cold0
        stats = eng.stats()
    finally:
        if srv is not None:
            metrics_export.stop_http()

    lat = stats["latency_seconds"]
    q = stats["queue_seconds"]
    d = stats["device_seconds"]
    report = {
        "metric": "serving_sustained_qps",
        "value": round(done / wall, 1) if wall > 0 else 0.0,
        "unit": "req/s",
        "offered_qps": round(qps, 1),
        "requests": n_requests,
        "completed": done,
        "failed": failed,
        "rejected_at_submit": rejected,
        "timeouts": stats["timeouts"],
        "latency_ms": {
            "p50": round(lat.get("p50", 0) * 1e3, 3),
            "p95": round(lat.get("p95", 0) * 1e3, 3),
            "p99": round(lat.get("p99", 0) * 1e3, 3),
            "queue_p50": round(q.get("p50", 0) * 1e3, 3),
            "queue_p99": round(q.get("p99", 0) * 1e3, 3),
            "device_p50": round(d.get("p50", 0) * 1e3, 3),
            "device_p99": round(d.get("p99", 0) * 1e3, 3),
        },
        "batch_size_avg": round(stats["batch_size"].get("avg", 0), 2),
        "batches": stats["batches"],
        "buckets": stats["buckets"],
        # the p99 offenders of THIS round, linkable to their traces
        "slowest_requests": slowest,
        "warmup": wreport,
        "compiles_under_load": compiles_under_load,
        "cold_compiles_under_load": cold_under_load,
        "config": {"max_batch": max_batch, "max_wait_us": max_wait_us,
                   "queue_depth": queue_depth, "sizes": list(sizes),
                   "hidden": hidden, "deadline_ms": deadline_ms},
    }
    return report


def decode_workload(n_requests, shared_prefix_ratio, vocab, page_size,
                    seed=0):
    """Prompt mix for the decode leg: a ``shared_prefix_ratio`` fraction
    of requests shares one page-aligned warm prefix (two full pages plus
    a unique tail token — a full prefix-cache hit), the rest are unique
    prompts of mixed length."""
    rng = np.random.RandomState(seed)
    shared = [int(t) for t in rng.randint(1, vocab, size=2 * page_size)]
    prompts = []
    for _ in range(n_requests):
        if rng.rand() < shared_prefix_ratio:
            prompts.append(shared + [int(rng.randint(1, vocab))])
        else:
            n = int(rng.randint(2, 2 * page_size + 2))
            prompts.append([int(t) for t in rng.randint(1, vocab, size=n)])
    return prompts


def _decode_leg(model, prompts, max_new, qps, name, draft=None, **eng_kw):
    """Run one engine configuration over the open-loop decode workload;
    returns the per-leg report row."""
    import jax

    from paddle_tpu.serving import decode as dec

    eng = dec.DecodeEngine(model, name=name, draft_model=draft, **eng_kw)
    rng = np.random.RandomState(7)
    sched = np.cumsum(rng.exponential(1.0 / max(qps, 1e-9),
                                      size=len(prompts)))
    futs, rejected, tokens, failed = [], 0, 0, 0
    try:
        eng.warmup()
        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            lag = sched[i] - (time.perf_counter() - t0)
            if lag > 0:
                time.sleep(lag)
            try:
                futs.append(eng.submit(p, max_new_tokens=max_new))
            except Exception:       # noqa: BLE001 — pool/queue rejections
                rejected += 1
        for f in futs:
            try:
                tokens += len(f.result(timeout=180)["tokens"])
            except Exception:       # noqa: BLE001 — timeouts count
                failed += 1
        wall = time.perf_counter() - t0
        st = eng.stats()
    finally:
        eng.close()
    ttft = st.get("ttft_seconds", {})
    row = {
        "requests": len(prompts),
        "completed": len(futs) - failed,
        "rejected_at_submit": rejected,
        "tokens": tokens,
        "tokens_per_sec_per_chip": round(
            tokens / wall / max(jax.device_count(), 1), 1)
            if wall > 0 else 0.0,
        "ttft_ms": {"p50": round(ttft.get("p50", 0) * 1e3, 3),
                    "p99": round(ttft.get("p99", 0) * 1e3, 3)},
        "peak_concurrent_sessions": st.get("peak_active", 0),
    }
    paged = st.get("paged")
    if paged:
        row["kv"] = {k: paged.get(k) for k in
                     ("page_size", "pool_pages", "prefix_hits",
                      "prefix_evictions")}
        if "spec_accept_rate" in paged:
            row["spec_proposed"] = paged["spec_proposed"]
            row["spec_accepted"] = paged["spec_accepted"]
            row["spec_accept_rate"] = paged["spec_accept_rate"]
    return row


def decode_bench(shared_prefix_ratio=0.6, n_requests=32, qps=100.0,
                 max_new=6, page_size=4, max_len=32, d_model=16,
                 vocab=29, dense_batch=3, spec=False, seed=0):
    """The --decode leg: the same open-loop workload against (a) the
    dense per-slot KV engine, (b) the block-paged engine with the SAME
    device KV-row budget (dense_batch·max_len rows), (c) paged + prefix
    cache, and optionally (d) paged + prefix + speculative.  The two
    acceptance wins ride the report: the paged pool sustains more
    concurrent sessions than dense at equal memory (occupancy-bounded
    vs max_len-bounded), and the warm prefix cache cuts TTFT p50 on a
    shared-prefix workload."""
    from paddle_tpu.serving import decode as dec

    m = dec.build_demo_decode_model(vocab=vocab, d_model=d_model,
                                    max_len=max_len, seed=seed,
                                    page_size=page_size)
    prompts = decode_workload(n_requests, shared_prefix_ratio, vocab,
                              page_size, seed=seed)
    # equal device memory: dense carries dense_batch*max_len KV rows;
    # the paged pool gets exactly the same row budget (scratch included)
    pool_pages = dense_batch * max_len // page_size
    paged_kw = dict(paged=True, page_size=page_size,
                    pool_pages=pool_pages,
                    max_batch=min(16, pool_pages), queue_depth=256)
    legs = {
        "dense": _decode_leg(m, prompts, max_new, qps, "bench_dense",
                             max_batch=dense_batch, queue_depth=256),
        "paged_nocache": _decode_leg(m, prompts, max_new, qps,
                                     "bench_paged", **paged_kw),
        "paged_cache": _decode_leg(m, prompts, max_new, qps,
                                   "bench_cache", prefix_cache=True,
                                   **paged_kw),
    }
    if spec:
        draft = dec.build_demo_decode_model(
            vocab=vocab, d_model=max(4, d_model // 2), max_len=max_len,
            seed=seed + 1, page_size=page_size)
        legs["paged_spec"] = _decode_leg(
            m, prompts, max_new, qps, "bench_spec", draft=draft,
            prefix_cache=True, **paged_kw)
    return {
        "metric": "decode_tokens_per_sec_per_chip",
        "value": legs["paged_cache"]["tokens_per_sec_per_chip"],
        "unit": "tok/s/chip",
        "legs": legs,
        "prefix_ttft_win": legs["paged_cache"]["ttft_ms"]["p50"]
            < legs["paged_nocache"]["ttft_ms"]["p50"],
        "paged_concurrency_win":
            legs["paged_nocache"]["peak_concurrent_sessions"]
            > legs["dense"]["peak_concurrent_sessions"],
        "config": {"shared_prefix_ratio": shared_prefix_ratio,
                   "requests": n_requests, "qps": qps,
                   "max_new": max_new, "page_size": page_size,
                   "max_len": max_len, "d_model": d_model,
                   "vocab": vocab, "dense_batch": dense_batch,
                   "kv_rows_budget": dense_batch * max_len,
                   "speculative": bool(spec)},
    }


def chaos_schedule(seed: int, duration_s: float):
    """Derive the --chaos fault schedule from one seed: a randomized
    mix of every fault kind, placed deterministically (same seed ⇒ same
    windows, same per-rule decision streams — the replay contract).
    Returns (parent_spec, child_spec): the parent injects on the
    router→replica request path (with a reset window aimed at one
    replica's RPC port, patched in once ports are known), the child
    spec rides FLAGS_faultline into every replica subprocess and
    injects on the reply path."""
    import random
    rng = random.Random(int(seed))
    corrupt_at = rng.uniform(0.4, max(0.8, duration_s * 0.25))
    reset_at = rng.uniform(duration_s * 0.35, duration_s * 0.55)
    parent = {"seed": int(seed), "faults": [
        {"kind": "latency", "prob": 0.3, "ms": round(rng.uniform(2, 10), 2),
         "jitter_ms": round(rng.uniform(0, 6), 2)},
        {"kind": "drop", "prob": 0.02, "max_injections": 4},
        {"kind": "trickle", "prob": 0.04, "bytes_per_s": 262144},
        {"kind": "corrupt", "prob": 1.0, "start_s": round(corrupt_at, 2),
         "end_s": round(corrupt_at + 0.3, 2)},
        {"kind": "reset", "prob": 1.0, "start_s": round(reset_at, 2),
         "end_s": round(reset_at + rng.uniform(1.2, 2.0), 2),
         "endpoint": "VICTIM"},
    ]}
    child = {"seed": int(seed) + 1, "faults": [
        {"kind": "latency", "prob": 0.2, "ms": 3, "jitter_ms": 4},
        {"kind": "corrupt", "prob": 0.01, "max_injections": 3},
    ]}
    return parent, child


def parse_mesh(s):
    """``"tp:8"`` / ``"dp:2,tp:4"`` -> ``{"tp": 8}`` / ordered dict."""
    if not s:
        return None
    out = {}
    for part in str(s).split(","):
        axis, _, n = part.partition(":")
        out[axis.strip()] = int(n)
    return out


def _mesh_chips(mesh) -> int:
    n = 1
    for v in (mesh or {}).values():
        n *= int(v)
    return max(1, n)


def _completed_examples(futures) -> int:
    """Sum the row counts of futures that actually completed (results
    are cached by now — collect() already waited them out)."""
    total = 0
    for f in futures:
        try:
            f.result(timeout=0.05)
            total += int(_FUTURE_ROWS.get(id(f), (None, 0))[1])
        except Exception:           # noqa: BLE001 — failed ones
            pass
        _FUTURE_ROWS.pop(id(f), None)
    return total


def _fleet_hbm_peak(fl):
    """Max per-device HBM peak (bytes) + device count across the
    fleet's replica ``/stats`` payloads (present when the replica ran
    with FLAGS_device_cost_analysis)."""
    peak, devices = 0, 1
    for r in fl.router.replicas:
        try:
            st = r.scrape(timeout_s=5.0) if not r.in_process \
                else (r.last_stats or {})
        except Exception:           # noqa: BLE001 — best effort
            st = r.last_stats or {}
        hbm = (st or {}).get("hbm") or {}
        if hbm.get("per_device_peak_bytes", 0) > peak:
            peak = int(hbm["per_device_peak_bytes"])
            devices = int(hbm.get("mesh_devices", 1))
    return (peak or None), devices


def _unsharded_hbm_control(spec, cache_dir, max_rows, quiet=True):
    """Spawn ONE unsharded single-device replica of the same model,
    push one max-size batch through it, and return its per-device HBM
    peak — the control leg of the sharding-reduces-per-chip-memory
    claim (same batch, no mesh)."""
    from paddle_tpu.serving import fleet as fleet_mod

    control = {k: v for k, v in spec.items()
               if k not in ("mesh", "sharding", "emulate_devices")}
    fl = fleet_mod.ServingFleet(
        spec=control, n_replicas=1, auto_replace=False,
        persistent_cache_dir=cache_dir, scrape_interval_s=0.25,
        quiet_children=quiet,
        env={"FLAGS_device_cost_analysis": "true"})
    try:
        rng = np.random.RandomState(3)
        feed = {"x": rng.randn(max_rows,
                               int(spec.get("features", 16))
                               ).astype("float32")}
        fl.submit(feed).result(timeout=60)
        peak, _ = _fleet_hbm_peak(fl)
    finally:
        fl.close()
    return peak


def fleet_decode_leg(n_replicas=2, n_requests=24, max_new=6, qps=50.0,
                     page_size=4, shared_prefix_ratio=0.5, vocab=29,
                     cache_dir=None, policy="least_queue", seed=0,
                     quiet=True):
    """Decode THROUGH the router: N subprocess decode replicas behind
    session-affinity routing, open-loop prompt arrivals, tokens/s/chip
    for the whole fleet.  The identity contract (routed == engine-
    direct, preserved across migration) is proved by the test suite;
    this leg prices the plane."""
    from paddle_tpu.fluid import compile_cache
    from paddle_tpu.serving import fleet as fleet_mod

    cache_dir = cache_dir or compile_cache.jax_cache_dir()
    spec = fleet_mod.demo_decode_spec(vocab=vocab, page_size=page_size,
                                      seed=seed)
    prompts = decode_workload(n_requests, shared_prefix_ratio, vocab,
                              page_size, seed=seed)
    rng = np.random.RandomState(11)
    sched = np.cumsum(rng.exponential(1.0 / max(qps, 1e-9),
                                      size=len(prompts)))
    fl = fleet_mod.ServingFleet(
        spec=spec, n_replicas=int(n_replicas), policy=policy,
        auto_replace=False, persistent_cache_dir=cache_dir,
        scrape_interval_s=0.25, quiet_children=quiet)
    futs, rejected, tokens, failed = [], 0, 0, 0
    try:
        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            lag = sched[i] - (time.perf_counter() - t0)
            if lag > 0:
                time.sleep(lag)
            try:
                futs.append(fl.submit_decode(p, max_new_tokens=max_new))
            except Exception:       # noqa: BLE001 — queue rejections
                rejected += 1
        by_replica = {}
        for f in futs:
            try:
                tokens += len(f.result(timeout=180)["tokens"])
                by_replica[f.replica] = by_replica.get(f.replica, 0) + 1
            except Exception:       # noqa: BLE001 — timeouts count
                failed += 1
        wall = time.perf_counter() - t0
        fstats = fl.stats()
    finally:
        fl.close()
    return {
        "replicas": int(n_replicas),
        "requests": len(prompts),
        "completed": len(futs) - failed,
        "rejected_at_submit": rejected,
        "tokens": tokens,
        "tokens_per_sec_per_chip": round(
            tokens / wall / max(int(n_replicas), 1), 1)
            if wall > 0 else 0.0,
        "requests_by_replica": by_replica,
        "decode_migrations": fstats.get("decode_migrations", 0),
        "config": {"max_new": max_new, "qps": qps,
                   "page_size": page_size,
                   "shared_prefix_ratio": shared_prefix_ratio},
    }


def fleet_bench(n_replicas=2, qps=200.0, n_requests=400, sizes=(1, 2, 4, 8),
                kill_at=None, policy="least_queue", hidden=64,
                max_batch=32, max_wait_us=2000, queue_depth=256,
                cache_dir=None, watchdog_stall_s=2.0, deadline_ms=None,
                seed=0, chaos_seed=None, replica_mesh=None,
                sharding="tp", decode=False, quiet=True):
    """The kill-mid-run fleet protocol: N subprocess replicas behind the
    router, open-loop Poisson load, SIGKILL one replica at ``kill_at``
    seconds into the run (auto_replace spawns a warm replacement from
    the shared persistent cache), wait every future out.  Reports
    sustained QPS, latency percentiles, ejection latency, requests
    rerouted, warm spin-up seconds, and (the invariant) how many
    accepted requests were lost — which must be 0."""
    from paddle_tpu.distributed import faultline
    from paddle_tpu.fluid import compile_cache, trace
    from paddle_tpu.serving import fleet as fleet_mod

    cache_dir = cache_dir or compile_cache.jax_cache_dir()
    m = trace.metrics()
    chips_per_replica = _mesh_chips(replica_mesh)
    spec = fleet_mod.demo_mlp_spec(
        hidden=hidden, features=16, max_batch=max_batch,
        max_wait_us=max_wait_us, queue_depth=queue_depth, seed=seed,
        watchdog_stall_s=watchdog_stall_s,
        mesh=replica_mesh,
        sharding=sharding if replica_mesh else None,
        emulate_devices=chips_per_replica if replica_mesh else None)
    duration_s = n_requests / max(qps, 1e-9)
    chaos_parent = chaos_child = None
    env = None
    if chaos_seed is not None:
        chaos_parent, chaos_child = chaos_schedule(chaos_seed, duration_s)
        env = {"FLAGS_faultline": json.dumps(chaos_child)}
    t_up0 = time.perf_counter()
    fl = fleet_mod.ServingFleet(
        spec=spec, n_replicas=int(n_replicas), policy=policy,
        auto_replace=True, persistent_cache_dir=cache_dir,
        scrape_interval_s=0.25, missed_scrape_limit=2,
        max_attempts=30 if chaos_seed is not None else 6,
        rpc_timeout_s=10.0, quiet_children=quiet, env=env)
    fleet_up_s = time.perf_counter() - t_up0
    fl_inject = None
    corrupt0 = m.counter("rpc.corrupt_frames").value
    bopen0 = m.counter("fleet.breaker_opens").value
    bclose0 = m.counter("fleet.breaker_closes").value
    if chaos_parent is not None:
        # aim the reset window at a live replica's RPC port, then start
        # the schedule clock — the load loop below runs inside it
        victim = fl.router.replicas[-1]
        for rule in chaos_parent["faults"]:
            if rule.get("endpoint") == "VICTIM":
                rule["endpoint"] = f"*:{victim.rpc_port}"
        fl_inject = faultline.install(chaos_parent)
    rng = np.random.RandomState(1)
    pool = rng.randn(max(sizes) * 4, 16).astype("float32")

    def feed_of_rows(n):
        off = rng.randint(0, len(pool) - n + 1)
        return {"x": pool[off:off + n]}

    kill_info = {}

    def killer():
        time.sleep(float(kill_at))
        victims = [r for r in fl.router.replicas if r.state == "up"]
        if victims:
            v = fl.kill_replica(victims[0])
            kill_info["name"] = v.name
            kill_info["t_mono"] = time.monotonic()

    redis0 = m.counter("fleet.redispatches").value
    try:
        kt = None
        if kill_at is not None:
            kt = threading.Thread(target=killer, daemon=True)
            kt.start()
        t0 = time.perf_counter()
        futures, wall_submit, offered_s, rejected = run_open_loop(
            fl, feed_of_rows, qps, n_requests, sizes,
            deadline_ms=deadline_ms)
        done, failed = collect(futures, timeout=180.0)
        wall = time.perf_counter() - t0
        examples = _completed_examples(futures)
        slowest = slowest_requests(futures)
        if kt is not None:
            kt.join(timeout=10)
        # let the ejection + replacement land in the event log
        deadline = time.time() + 90
        while kill_at is not None and not fl.events_of("replace") \
                and time.time() < deadline:
            time.sleep(0.1)
        lat = m.histogram("fleet.latency_seconds").stats()
        rerouted = m.counter("fleet.redispatches").value - redis0
        eject_latency = warm_spinup = replacement_cold = None
        if kill_info:
            ejects = [e for e in fl.events_of("eject")
                      if e["replica"] == kill_info["name"]]
            if ejects:
                eject_latency = round(
                    ejects[0]["t_mono"] - kill_info["t_mono"], 3)
            reps = fl.events_of("replace")
            if reps:
                spawns = [e for e in fl.events_of("spawn")
                          if e["replica"] == reps[0]["replica"]]
                if spawns:
                    warm_spinup = spawns[0]["spinup_s"]
                w = reps[0].get("warmup") or {}
                replacement_cold = w.get("cold_misses")
        chaos = None
        if chaos_parent is not None:
            # replica-side truth: scraped /stats carries each child's
            # checksum-caught corruptions and its own injections
            child_detected = child_injected = 0
            for r in fl.router.replicas:
                if r.in_process or not r.alive():
                    continue
                try:
                    st = r.scrape(timeout_s=3.0)
                except Exception:   # noqa: BLE001 — best effort
                    continue
                child_detected += (st.get("rpc") or {}).get(
                    "corrupt_frames", 0)
                child_injected += (st.get("faults") or {}).get(
                    "injected", 0)
            chaos = {
                "seed": int(chaos_seed),
                "injected": fl_inject.injected,
                "child_injected": child_injected,
                "corruptions_detected_by_replicas": child_detected,
                "corruptions_detected_by_router":
                    m.counter("rpc.corrupt_frames").value - corrupt0,
                "breaker_opens":
                    m.counter("fleet.breaker_opens").value - bopen0,
                "breaker_closes":
                    m.counter("fleet.breaker_closes").value - bclose0,
                "breaker_events": len(fl.events_of("breaker_open"))
                    + len(fl.events_of("breaker_close")),
            }
        hbm_peak = hbm_devices = hbm_compare = None
        if replica_mesh:
            # same-batch probe: one max_batch-row request so the peak
            # belongs to the same executable size the unsharded control
            # below will run
            probe = {"x": np.random.RandomState(3).randn(
                max_batch, 16).astype("float32")}
            try:
                fl.submit(probe).result(timeout=60)
            except Exception:       # noqa: BLE001 — probe is best-effort
                pass
            hbm_peak, hbm_devices = _fleet_hbm_peak(fl)
            un_peak = _unsharded_hbm_control(spec, cache_dir,
                                             max_rows=max_batch,
                                             quiet=quiet)
            if hbm_peak and un_peak:
                hbm_compare = {
                    "sharded_per_device_peak_bytes": hbm_peak,
                    "unsharded_per_device_peak_bytes": un_peak,
                    "sharded_below_unsharded": hbm_peak < un_peak,
                }
        fstats = fl.stats()
    finally:
        if fl_inject is not None:
            faultline.uninstall()
        fl.close()
    dec_leg = None
    if decode:
        # routed-decode leg rides the same report line: one JSON
        # object carries examples/s/chip AND tokens/s/chip
        dec_leg = fleet_decode_leg(
            n_replicas=n_replicas, policy=policy, seed=seed,
            cache_dir=cache_dir, quiet=quiet)

    report = {
        "metric": "fleet_sustained_qps",
        "value": round(done / wall, 1) if wall > 0 else 0.0,
        "unit": "req/s",
        "replicas": int(n_replicas),
        "chips_per_replica": chips_per_replica,
        "total_chips": int(n_replicas) * chips_per_replica,
        "policy": policy,
        "offered_qps": round(qps, 1),
        "requests": n_requests,
        "completed": done,
        "examples": examples,
        "examples_per_sec_per_chip": round(
            examples / wall / (int(n_replicas) * chips_per_replica), 1)
            if wall > 0 else 0.0,
        # the invariant the kill drill proves: accepted requests lost
        "lost": failed,
        "rejected_at_submit": rejected,
        "latency_ms": {
            "p50": round(lat.get("p50", 0) * 1e3, 3),
            "p95": round(lat.get("p95", 0) * 1e3, 3),
            "p99": round(lat.get("p99", 0) * 1e3, 3),
        },
        "fleet_up_s": round(fleet_up_s, 3),
        "kill_replica_at_s": kill_at,
        "killed": kill_info.get("name"),
        "ejection_latency_s": eject_latency,
        "requests_rerouted": rerouted,
        "warm_spinup_s": warm_spinup,
        "replacement_cold_compiles": replacement_cold,
        # p99 offenders with replica attribution (parent-side records)
        "slowest_requests": slowest,
        "ejections": fstats["ejections"],
        "replacements": fstats["replacements"],
        "config": {"max_batch": max_batch, "max_wait_us": max_wait_us,
                   "queue_depth": queue_depth, "sizes": list(sizes),
                   "hidden": hidden, "deadline_ms": deadline_ms,
                   "watchdog_stall_s": watchdog_stall_s,
                   "replica_mesh": replica_mesh},
    }
    if hbm_peak:
        report["hbm"] = {"per_device_peak_bytes": hbm_peak,
                         "mesh_devices": hbm_devices}
    if hbm_compare is not None:
        report["hbm_compare"] = hbm_compare
    if dec_leg is not None:
        report["decode"] = dec_leg
        report["tokens_per_sec_per_chip"] = \
            dec_leg["tokens_per_sec_per_chip"]
    if chaos is not None:
        report["metric"] = "fleet_chaos_qps"
        report["chaos"] = chaos
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--qps", type=float, default=200.0,
                    help="offered (open-loop) arrival rate")
    ap.add_argument("--requests", type=int, default=400)
    ap.add_argument("--seconds", type=float, default=None,
                    help="derive --requests as qps * seconds")
    ap.add_argument("--sizes", default="1,2,4,8",
                    help="comma list of request row counts to mix")
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--max-wait-us", type=int, default=2000)
    ap.add_argument("--queue-depth", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--deadline-ms", type=float, default=None)
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve live /metrics during the run (0=ephemeral)")
    ap.add_argument("--fleet", type=int, default=None, metavar="N",
                    help="fleet mode: N subprocess replicas behind the "
                         "router (paddle_tpu.serving.fleet)")
    ap.add_argument("--kill-replica-at", type=float, default=None,
                    metavar="T", help="fleet mode: SIGKILL one replica T "
                    "seconds into the load (reports ejection latency, "
                    "reroutes, warm spin-up)")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="fleet mode: run under a seeded fault schedule "
                         "mixing latency/drop/reset/corrupt/trickle on "
                         "the RPC plane (same seed = same schedule); "
                         "reports loss, detected corruptions, and "
                         "breaker transitions")
    ap.add_argument("--decode", action="store_true",
                    help="decode mode: open-loop autoregressive decode "
                         "traffic against dense vs block-paged KV vs "
                         "paged+prefix-cache engines at equal device "
                         "memory; reports TTFT p50/p99, tokens/sec/chip "
                         "and the concurrency/TTFT win booleans.  With "
                         "--fleet: adds a routed-decode leg so the one "
                         "JSON line carries examples/s/chip AND "
                         "tokens/s/chip")
    ap.add_argument("--replica-mesh", default=None, metavar="SPEC",
                    help="fleet mode: per-replica device mesh, e.g. "
                         "'tp:8' (emulated on CPU via "
                         "--xla_force_host_platform_device_count); "
                         "reports per-chip throughput and the sharded-"
                         "vs-unsharded per-device HBM compare")
    ap.add_argument("--scaling", action="store_true",
                    help="fleet mode: also run a 1-replica baseline at "
                         "the same offered load and report the "
                         "N-replica/1-replica throughput ratio")
    ap.add_argument("--shared-prefix-ratio", type=float, default=0.6,
                    metavar="R", help="decode mode: fraction of requests "
                    "sharing one page-aligned warm prompt prefix")
    ap.add_argument("--spec", action="store_true",
                    help="decode mode: add a speculative-decoding leg "
                         "(half-width draft model) and report "
                         "spec_accept_rate")
    ap.add_argument("--page-size", type=int, default=4,
                    help="decode mode: KV page size in tokens")
    ap.add_argument("--max-new", type=int, default=6,
                    help="decode mode: tokens to generate per request")
    ap.add_argument("--policy", default="least_queue",
                    choices=("least_queue", "round_robin"))
    ap.add_argument("--cache-dir", default=None,
                    help="fleet mode: where the replicas' program-level "
                         "compile index lives (default: beside jax's "
                         "compilation cache, compile_cache.jax_cache_dir())")
    ap.add_argument("--watchdog-stall-s", type=float, default=2.0)
    args = ap.parse_args(argv)

    from paddle_tpu.fluid import compile_cache
    compile_cache.enable_jax_cache()

    n = args.requests
    if args.seconds:
        n = max(1, int(args.qps * args.seconds))
    sizes = [int(s) for s in args.sizes.split(",") if s.strip()]
    if args.chaos is not None and not args.fleet:
        args.fleet = 2                  # chaos is a fleet drill
    if args.decode and not args.fleet:
        # decode rounds are token-budgeted, not request-budgeted: the
        # open-loop default of 400 requests would run for minutes on CPU
        n_dec = n if (args.seconds or args.requests != 400) else 32
        report = decode_bench(
            shared_prefix_ratio=args.shared_prefix_ratio,
            n_requests=n_dec, qps=args.qps, max_new=args.max_new,
            page_size=args.page_size, spec=args.spec)
    elif args.fleet:
        mesh = parse_mesh(args.replica_mesh)
        fleet_kw = dict(
            qps=args.qps, n_requests=n,
            sizes=sizes, policy=args.policy, hidden=args.hidden,
            max_batch=args.max_batch, max_wait_us=args.max_wait_us,
            queue_depth=args.queue_depth, cache_dir=args.cache_dir,
            watchdog_stall_s=args.watchdog_stall_s,
            deadline_ms=args.deadline_ms, replica_mesh=mesh)
        report = fleet_bench(
            n_replicas=args.fleet, kill_at=args.kill_replica_at,
            chaos_seed=args.chaos, decode=args.decode, **fleet_kw)
        if args.scaling and args.fleet > 1:
            base = fleet_bench(n_replicas=1, **fleet_kw)
            ratio = (round(report["value"] / base["value"], 2)
                     if base["value"] else None)
            try:
                host_cores = len(os.sched_getaffinity(0))
            except (AttributeError, OSError):
                host_cores = os.cpu_count() or 1
            report["scaling"] = {
                "baseline_replicas": 1,
                "baseline_qps": base["value"],
                "fleet_qps": report["value"],
                "ratio": ratio,
                # replica subprocesses scale with real cores; on a
                # single-core host the ratio is CPU-conserved (~1.0),
                # so the artifact carries the denominator that explains it
                "host_cpu_cores": host_cores,
            }
    else:
        report = serve_bench(
            qps=args.qps, n_requests=n, sizes=sizes,
            max_batch=args.max_batch, max_wait_us=args.max_wait_us,
            queue_depth=args.queue_depth, hidden=args.hidden,
            deadline_ms=args.deadline_ms, metrics_port=args.metrics_port)

    import bench
    report["backend"] = bench.backend_name()
    if report["backend"] not in ("cpu", "error"):
        bench.record_evidence(dict(report))
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
