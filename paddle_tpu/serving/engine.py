"""ServingEngine: admission queue + shape-bucketed continuous batching.

Reference: the request-level serving loop the paddle_tpu stack never had
— paddle/fluid/inference/ answers one AnalysisPredictor::ZeroCopyRun at
a time and leaves batching to the caller.  Orca-style continuous
batching (PAPERS.md) is the production shape: heterogeneous single
requests coalesce into device batches, admission control rejects load
the device cannot absorb (backpressure, not OOM), and the SLO surface
(p50/p99 split into queue vs device time) is first-class.

Data path (one request):

    submit(feed) --bounded queue--> batcher thread
        coalesce same-signature requests -> concatenate rows
        -> dispatch through the PR-4 AsyncStepRunner (batch k+1 forms
           while batch k runs on device; max-batch-or-max-wait trigger)
        -> collector thread waits device results, demuxes per-request
           row slices, resolves ServingFutures, records latency split

Shape discipline rides the PR-2 planes: the engine stamps the program's
``shape_bucketing``/``bucket_edges`` hints so the executor pads each
batch to a bucket edge with the true row count threaded in as
``__batch_valid__`` (masked reductions keep partial batches numerically
exact), and ``warmup()`` precompiles every bucket through the compile
cache (persistent-cache-backed: a restarted server takes zero cold
compiles).

Instruments (docs/observability.md): ``serving.requests`` /
``rejected`` / ``timeouts`` / ``batches`` counters,
``serving.batch_size`` / ``queue_seconds`` / ``device_seconds`` /
``latency_seconds`` histograms (p50/p95/p99 via the PR-7 stats plane and
the /metrics endpoint), ``serving.queue_depth`` gauge, and a
``serving::batch`` trace span per dispatch.
"""
from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..fluid import compile_cache, core, trace
from ..fluid import flight_recorder as _flight
from ..fluid.async_pipeline import AsyncStepRunner
from ..fluid.core import global_scope
from ..fluid.executor import Executor

__all__ = ["ServingEngine", "ServingFuture", "BaseFuture",
           "FamilyInstruments", "ServingError",
           "QueueFullError", "PagePoolExhaustedError",
           "DeadlineExceededError", "EngineClosedError"]


class ServingError(RuntimeError):
    """Base class for serving-plane rejections."""


class QueueFullError(ServingError):
    """Admission queue at capacity: the request was rejected at submit —
    backpressure, the open-loop overload answer that is not an OOM."""


class PagePoolExhaustedError(QueueFullError):
    """The decode KV page pool cannot hold the request (serving/decode.py
    block-paged mode): a typed queue-full rejection at admission — the
    paged answer to overload is backpressure, never a device OOM."""


class DeadlineExceededError(ServingError):
    """The request's deadline elapsed while it queued."""


class EngineClosedError(ServingError):
    """submit() after close()."""


class BaseFuture:
    """The shared pending-result machinery every serving-plane future
    rides (ServingFuture here, fleet.FleetFuture, decode.DecodeFuture):
    one event, one result-or-exception cell, timeout-raising reads."""

    __slots__ = ("_event", "_result", "_exc")

    _pending_msg = "request still pending"

    def __init__(self):
        self._event = threading.Event()
        self._result = None
        self._exc: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError(self._pending_msg)
        if self._exc is not None:
            raise self._exc
        return self._result

    def exception(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError(self._pending_msg)
        return self._exc

    def _resolve(self, result) -> None:
        self._result = result
        self._event.set()

    def _reject(self, exc: BaseException) -> None:
        self._exc = exc
        self._event.set()


class ServingFuture(BaseFuture):
    """One request's pending result: ``result(timeout)`` blocks until the
    batch containing this request completes, then returns
    ``{fetch_name: rows-sliced ndarray}``.  A rejection/timeout resolves
    the future with the corresponding :class:`ServingError`.

    ``trace_id`` is the request's causal identity: every span/wide event
    the request produces on its way through admit → queue → batch →
    device → demux carries it, so a client can hand the id to
    ``tools/diagnose.py`` (or grep the exported timeline) and get the
    request's full trajectory — allocated whether or not tracing is on
    (the flight recorder keys on it even then)."""

    __slots__ = ("rows", "trace_id", "timing")

    _pending_msg = "serving request still pending"

    def __init__(self, rows: int, trace_id: Optional[str] = None):
        super().__init__()
        self.rows = rows
        self.trace_id = trace_id
        # set by the collector at demux: {"queue_us", "device_us",
        # "latency_us"} — lets a replica server report the split back to
        # the fleet router without scanning the flight recorder
        self.timing: Optional[Dict[str, float]] = None


class _Request:
    __slots__ = ("feed", "rows", "sig", "t_enqueue", "t_ns", "deadline",
                 "future", "trace_id")

    def __init__(self, feed, rows, sig, t_enqueue, deadline, future,
                 trace_id):
        self.feed = feed
        self.rows = rows
        self.sig = sig
        self.t_enqueue = t_enqueue      # monotonic: deadline math
        self.t_ns = trace.now()         # trace clock: span windows
        self.deadline = deadline
        self.future = future
        self.trace_id = trace_id


_STOP = object()


class FamilyInstruments:
    """Per-engine instrument bundle over one metric family.

    PR 8 documented the process-global limitation: every engine in one
    process accumulated into one ``serving.*`` family.  A NAMED engine
    (``ServingEngine(..., name="r0")``, ``DecodeEngine(..., name=...)``)
    now writes its own ``<family>.<name>.*`` sub-family — per-replica
    attribution inside one test process — and ALSO bumps the plain
    ``<family>.*`` aggregate so fleet dashboards keep a single roll-up
    (the default-engine alias: an unnamed engine writes the plain
    family only, exactly the PR-8 behaviour).  Counters/histograms
    aggregate additively; plain gauges stay last-writer-wins across
    engines (read the namespaced gauge for a specific engine — the SLO
    watchdog scans both)."""

    def __init__(self, family: str, counters, histograms, gauges,
                 name: Optional[str] = None):
        m = trace.metrics()
        self.name = name or None
        self.prefix = f"{family}.{name}." if name else f"{family}."
        self._c = {}
        self._h = {}
        self._g = {}
        for b in counters:
            insts = [m.counter(f"{family}.{name}.{b}")] if name else []
            insts.append(m.counter(f"{family}.{b}"))
            self._c[b] = insts
        for b in histograms:
            insts = [m.histogram(f"{family}.{name}.{b}")] if name else []
            insts.append(m.histogram(f"{family}.{b}"))
            self._h[b] = insts
        for b in gauges:
            insts = [m.gauge(f"{family}.{name}.{b}")] if name else []
            insts.append(m.gauge(f"{family}.{b}"))
            self._g[b] = insts

    def count(self, base: str, n: int = 1) -> None:
        for inst in self._c[base]:
            inst.inc(n)

    def observe(self, base: str, v: float) -> None:
        for inst in self._h[base]:
            inst.observe(v)

    def set_gauge(self, base: str, v: float) -> None:
        for g in self._g[base]:
            g.set(v)

    # reads come from the engine's OWN family (namespaced when named)
    def counter_value(self, base: str) -> int:
        return self._c[base][0].value

    def hist_stats(self, base: str):
        return self._h[base][0].stats()


class _EngineInstruments(FamilyInstruments):
    COUNTERS = ("requests", "rejected", "timeouts", "batches",
                "dispatch_errors", "warmup_compiles")
    HISTOGRAMS = ("batch_size", "queue_seconds", "device_seconds",
                  "latency_seconds")

    def __init__(self, name: Optional[str] = None):
        super().__init__("serving", self.COUNTERS, self.HISTOGRAMS,
                         ("queue_depth",), name)

    def set_queue_depth(self, v: float) -> None:
        self.set_gauge("queue_depth", v)


# ---------------------------------------------------------------------------
# dispatch backends
# ---------------------------------------------------------------------------

class _ExecutorBackend:
    """Frozen Program + Executor, dispatched through the PR-4 async
    runner: ``dispatch`` returns immediately (window-bounded), ``wait``
    persists the FetchHandles the executor already sliced back to the
    true batch size."""

    def __init__(self, program, fetch_names, executor, scope,
                 max_inflight):
        self.program = program
        self.fetch_names = list(fetch_names)
        self.executor = executor
        self.scope = scope
        self.runner = AsyncStepRunner(executor, program, fetch_names,
                                      scope=scope,
                                      max_inflight=max_inflight,
                                      steps_per_dispatch=1)

    def dispatch(self, feed):
        return self.runner.submit(feed)

    def wait(self, fut) -> List[np.ndarray]:
        out = [h.persist() for h in fut.handles()]
        # retire materialised entries from the async window: an idle
        # engine must read executor.inflight_steps == 0, or the SLO
        # watchdog sees phantom outstanding work and flips a healthy
        # replica to `stalled` (the fleet would eject it)
        self.runner.reap()
        return out

    def warmup_run(self, feed) -> None:
        self.executor.run(self.program, feed=feed,
                          fetch_list=self.fetch_names,
                          scope=self.scope, return_numpy=True)

    def drain(self):
        self.runner.drain()

    def feed_specs(self):
        """(name, feature_shape, dtype) per feed, from the IR."""
        block = self.program.global_block()
        out = []
        for n in self.program._hints.get("feed_names", []):
            v = block._find_var_recursive(n)
            shape = list(v.shape or []) if v is not None else []
            out.append((n, [int(d) for d in shape[1:]],
                        (v.dtype if v is not None else None) or "float32"))
        return out

    def bucket_edges(self):
        return self.program._hints.get("bucket_edges")


class _AotBackend:
    """AotPredictor-backed dispatch (examples/aot_serve.py --engine):
    the multi-bucket artifact pads/slices internally; jax dispatch is
    async, so ``dispatch`` still overlaps with batch formation."""

    def __init__(self, predictor):
        self.predictor = predictor
        self.fetch_names = list(predictor.get_output_names())

    def dispatch(self, feed):
        return self.predictor.call_lazy(feed)

    def wait(self, fut) -> List[np.ndarray]:
        return [np.asarray(o) for o in fut]

    def warmup_run(self, feed) -> None:
        self.predictor.call_lazy(feed)

    def drain(self):
        pass

    def feed_specs(self):
        meta = self.predictor._meta
        out = []
        for n in meta["feed_names"]:
            shape = list(meta["input_shapes"].get(n, []))
            out.append((n, [int(d) for d in shape[1:]],
                        meta["input_dtypes"].get(n, "float32")))
        return out

    def bucket_edges(self):
        return self.predictor._meta.get("buckets")


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class ServingEngine:
    """Continuous-batching server over a frozen Program (or AOT
    artifact).

    ::

        frozen = serving.freeze_program(main_prog, ["x"], [logits])
        with serving.ServingEngine(frozen) as eng:
            eng.warmup()                       # precompile every bucket
            fut = eng.submit({"x": batch})     # -> ServingFuture
            out = fut.result(timeout=1.0)      # {"logits": rows x ...}

    Every knob defaults to its ``FLAGS_serving_*`` flag:
    ``max_batch`` rows per device batch, ``max_wait_us`` batch-formation
    deadline, ``queue_depth`` admission bound, ``default_deadline_ms``
    per-request deadline (0 = none).
    """

    def __init__(self, program,
                 fetch_names: Optional[Sequence[str]] = None,
                 feed_names: Optional[Sequence[str]] = None,
                 executor: Optional[Executor] = None,
                 scope=None,
                 max_batch: Optional[int] = None,
                 max_wait_us: Optional[int] = None,
                 queue_depth: Optional[int] = None,
                 default_deadline_ms: Optional[float] = None,
                 bucket_edges=None,
                 max_inflight: Optional[int] = None,
                 auto_start: bool = True,
                 mesh=None,
                 sharding=None,
                 name: Optional[str] = None):
        # per-engine instrument namespace (serving.<name>.* beside the
        # process aggregate; None = the plain serving.* family)
        self.name = name
        self._ins = _EngineInstruments(name)
        self.max_batch = int(max_batch
                             or core.get_flag("serving_max_batch", 32))
        self.max_wait_us = int(max_wait_us if max_wait_us is not None
                               else core.get_flag("serving_max_wait_us",
                                                  2000))
        self.queue_depth = int(queue_depth
                               or core.get_flag("serving_queue_depth", 256))
        dl = (default_deadline_ms if default_deadline_ms is not None
              else core.get_flag("serving_default_deadline_ms", 0))
        self.default_deadline_ms = float(dl or 0)

        if hasattr(program, "call_lazy"):       # AotPredictor
            if mesh is not None or sharding is not None:
                raise ValueError(
                    "ServingEngine(mesh=/sharding=) needs a frozen "
                    "Program — an AOT artifact's modules were exported "
                    "with their sharding baked in and cannot be "
                    "re-sharded; freeze with serving.freeze_program("
                    "..., mesh=) instead")
            self._backend = _AotBackend(program)
            self.feed_names = list(feed_names
                                   or program.get_input_names())
            self.fetch_names = list(fetch_names
                                    or program.get_output_names())
            edges = bucket_edges or self._backend.bucket_edges()
            if not edges:
                # legacy single-shape artifact: the ONLY servable batch
                # size is the baked one — warmup and batching target it
                # instead of pow2 edges the artifact cannot execute
                shapes = program._meta.get("input_shapes") or {}
                dims = {int(s[0]) for s in shapes.values() if s}
                if len(dims) != 1:
                    raise ValueError(
                        "this AOT artifact has no bucketed modules and "
                        "no common baked batch dim — re-export with "
                        "save_aot_model(..., bucket_edges=[...])")
                edges = [next(iter(dims))]
                self.max_batch = min(self.max_batch, edges[0])
            self.bucket_edges = compile_cache.normalize_edges(edges)
        else:
            if mesh is not None or sharding is not None:
                # serving over the SPMD plane (parallel/sharding.py): the
                # executor runs the frozen program as one sharded (pjit)
                # executable over the mesh — TP rules by default, so the
                # batch replicates and shape bucketing keeps its partial-
                # batch exactness (docs/sharding.md)
                if getattr(program, "_sharding_plan", None) is None:
                    from ..parallel import sharding as shard_plane
                    plan = shard_plane.build_plan(
                        program=program,
                        mode=sharding if sharding is not None else "tp",
                        mesh=mesh)
                    program._sharding_plan = plan
                    program._hints["sharding"] = plan.describe()
            hints = program._hints
            self.feed_names = list(feed_names or hints.get("feed_names")
                                   or [])
            self.fetch_names = list(fetch_names or hints.get("fetch_names")
                                    or [])
            if not self.fetch_names:
                raise ValueError(
                    "ServingEngine needs fetch_names — freeze the program "
                    "first (serving.freeze_program) or pass them explicitly")
            edges = compile_cache.normalize_edges(
                bucket_edges or hints.get("bucket_edges")
                or compile_cache.pow2_edges(self.max_batch))
            self.bucket_edges = edges
            # ride the PR-2 plane per-program: the hint opts THIS program
            # into executor-side bucketing without flipping the global flag
            hints["shape_bucketing"] = True
            hints["bucket_edges"] = edges
            hints["feed_names"] = list(self.feed_names)
            hints["fetch_names"] = list(self.fetch_names)
            scope = scope or global_scope()
            self._backend = _ExecutorBackend(
                program, self.fetch_names, executor or Executor(), scope,
                max_inflight or core.get_flag("max_inflight_steps", 2))

        self._auto_start = bool(auto_start)
        self._q: "queue.Queue" = queue.Queue(maxsize=self.queue_depth)
        self._completions: deque = deque()
        self._cv = threading.Condition()
        self._closed = False
        self._started = False
        # pause()/resume() chaos+maintenance hook: cleared = the batcher
        # holds every dispatch (admission keeps filling the queue, so a
        # paused engine looks exactly like a wedged device to the SLO
        # watchdog — the fleet drill's honest stall injection)
        self._resume = threading.Event()
        self._resume.set()
        self._lock = threading.Lock()
        self._batcher_t: Optional[threading.Thread] = None
        self._collector_t: Optional[threading.Thread] = None
        self.warmup_report: Optional[Dict[str, Any]] = None

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "ServingEngine":
        with self._lock:
            if self._started or self._closed:
                return self
            self._started = True
            self._batcher_t = threading.Thread(
                target=self._batcher, name="serving-batcher", daemon=True)
            self._collector_t = threading.Thread(
                target=self._collector, name="serving-collector",
                daemon=True)
            self._batcher_t.start()
            self._collector_t.start()
        return self

    def pause(self) -> None:
        """Hold every dispatch (maintenance / chaos drills): admission
        stays open, the queue fills, nothing reaches the device.  A
        paused engine under load trips the SLO watchdog's stall verdict
        — which is exactly what the fleet's ejection drill injects.
        No-op after close(): a late pause must not re-wedge the batcher
        close() is draining (nobody would be left to resume it)."""
        if not self._closed:
            self._resume.clear()

    def resume(self) -> None:
        self._resume.set()

    def paused(self) -> bool:
        return not self._resume.is_set()

    def close(self) -> None:
        """Stop admitting, drain everything in flight, join threads.
        Implies :meth:`resume` — a close must drain, never deadlock on a
        paused batcher."""
        self._resume.set()
        with self._lock:
            if self._closed:
                return
            self._closed = True
            started = self._started
        if started:
            self._q.put(_STOP)
            self._batcher_t.join()
            self._collector_t.join()
        else:
            # never started (auto_start=False): queued requests would
            # strand their clients — resolve them with the close
            while True:
                try:
                    req = self._q.get_nowait()
                except queue.Empty:
                    break
                req.future._reject(EngineClosedError(
                    "engine closed before its batcher started"))
        self._backend.drain()

    stop = close

    def __enter__(self):
        return self.start()

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False

    # -- warmup -------------------------------------------------------------
    def warmup(self, example_feed: Optional[Dict[str, Any]] = None
               ) -> Dict[str, Any]:
        """Precompile every (bucket, dtype) combination so steady-state
        serving takes zero cold compiles.  Feed shapes/dtypes come from
        the program IR (or the AOT sidecar); ``example_feed`` overrides
        when the IR has unknown feature dims.  Returns
        ``{"buckets": ..., "compiles": ..., "cold_misses": ...,
        "seconds": ...}``."""
        specs = self._backend.feed_specs()
        by_name = {n: (feat, dt) for n, feat, dt in specs}
        for n in self.feed_names:
            if n not in by_name:
                by_name[n] = ([], "float32")
        if example_feed:
            for n, v in example_feed.items():
                v = np.asarray(v)
                by_name[n] = (list(v.shape[1:]), str(v.dtype))
        bad = [n for n, (feat, _) in by_name.items()
               if any(d < 0 for d in feat)]
        if bad:
            raise ValueError(
                f"warmup cannot infer feature shapes for feeds {bad}; "
                f"pass example_feed with concretely shaped arrays")
        m = trace.metrics()
        miss0 = m.counter("executor.compile_cache_miss").value
        cold0 = m.counter("executor.compile_cache_cold_miss").value
        t0 = time.perf_counter()
        for edge in self.bucket_edges:
            feed = {}
            for n in self.feed_names:
                feat, dt = by_name[n]
                feed[n] = np.zeros([int(edge)] + [int(d) for d in feat],
                                   dtype=np.dtype(str(dt)))
            self._backend.warmup_run(feed)
        report = {
            "buckets": list(self.bucket_edges),
            "compiles": m.counter("executor.compile_cache_miss").value
            - miss0,
            "cold_misses": m.counter(
                "executor.compile_cache_cold_miss").value - cold0,
            "seconds": round(time.perf_counter() - t0, 4),
        }
        self._ins.count("warmup_compiles", report["compiles"])
        self.warmup_report = report
        return report

    # -- request admission ---------------------------------------------------
    def submit(self, feed: Dict[str, Any],
               deadline_ms: Optional[float] = None,
               trace_id: Optional[str] = None) -> ServingFuture:
        """Admit one request.  Every feed array must share the same
        leading (row) dim; raises :class:`QueueFullError` when the
        admission queue is at capacity and :class:`EngineClosedError`
        after close().

        ``trace_id`` (or, failing that, the ambient
        ``trace.current_trace_id()`` a fleet replica server installs
        around dispatch) overrides the freshly allocated id, so a
        request propagated across a process boundary keeps its CALLER's
        causal identity end to end."""
        if self._closed:
            raise EngineClosedError("ServingEngine is closed")
        if not self._started and self._auto_start:
            self.start()
        missing = [n for n in self.feed_names if n not in (feed or {})]
        if missing:
            raise ValueError(f"request missing feeds: {missing}")
        arrs = {n: np.asarray(feed[n]) for n in self.feed_names}
        rows = {a.shape[0] for a in arrs.values() if a.ndim >= 1}
        if len(rows) != 1:
            raise ValueError(
                f"request feeds must share one leading batch dim, got "
                f"{ {n: a.shape for n, a in arrs.items()} }")
        n_rows = int(next(iter(rows)))
        # non-batch feeds (scalars/0-d knobs) cannot be concatenated —
        # their VALUE is part of the coalescing signature, so requests
        # with different knob values never share a batch
        sig = tuple(sorted(
            (n, a.shape[1:], str(a.dtype))
            if a.ndim >= 1 else (n, a.tobytes(), str(a.dtype))
            for n, a in arrs.items()))
        now = time.monotonic()
        dl_ms = (deadline_ms if deadline_ms is not None
                 else self.default_deadline_ms)
        deadline = now + dl_ms / 1e3 if dl_ms and dl_ms > 0 else None
        # the request's causal identity — allocated with tracing ON or
        # OFF (the flight recorder's wide events key on it either way);
        # a propagated/ambient id wins so cross-process stories join
        trace_id = (trace_id or trace.current_trace_id()
                    or trace.new_trace_id("req"))
        fut = ServingFuture(n_rows, trace_id=trace_id)
        req = _Request(arrs, n_rows, sig, now, deadline, fut, trace_id)
        # closed-check + enqueue under the lock: close() takes the same
        # lock to flip _closed BEFORE it enqueues _STOP, so a request can
        # never land behind the departing batcher and strand its future
        with self._lock:
            if self._closed:
                raise EngineClosedError("ServingEngine is closed")
            try:
                self._q.put_nowait(req)
            except queue.Full:
                self._ins.count("rejected")
                if _flight.enabled():
                    _flight.record_request(trace_id, n_rows,
                                           outcome="rejected")
                exc = QueueFullError(
                    f"admission queue full ({self.queue_depth} requests)"
                    f" — the device is saturated; shed load or raise "
                    f"FLAGS_serving_queue_depth")
                fut._reject(exc)
                raise exc
        # admitted only (docs/observability.md): rejections don't count
        self._ins.count("requests")
        self._ins.set_queue_depth(self._q.qsize())
        if trace.enabled():
            trace.instant("serving::admit", cat="serving",
                          args={"trace_id": trace_id, "rows": n_rows,
                                "deadline_ms": dl_ms or 0})
        return fut

    def infer(self, feed: Dict[str, Any],
              timeout: Optional[float] = None,
              deadline_ms: Optional[float] = None) -> Dict[str, np.ndarray]:
        """Blocking convenience: submit + result."""
        return self.submit(feed, deadline_ms=deadline_ms).result(timeout)

    # -- batcher thread ------------------------------------------------------
    def _timeout_request(self, req: _Request) -> None:
        self._ins.count("timeouts")
        waited_ms = (time.monotonic() - req.t_enqueue) * 1e3
        if trace.enabled():
            trace.complete("serving::queue", req.t_ns, cat="serving",
                           args={"trace_id": req.trace_id,
                                 "outcome": "timeout"})
        if _flight.enabled():
            _flight.record_request(req.trace_id, req.rows,
                                   outcome="timeout",
                                   queue_us=waited_ms * 1e3,
                                   latency_us=waited_ms * 1e3)
        req.future._reject(DeadlineExceededError(
            f"deadline elapsed after {waited_ms:.1f}ms in queue"))

    def _batcher(self) -> None:
        pending: Dict[tuple, List[_Request]] = {}
        stopping = False
        while True:
            # read the formation deadline EVERY round, not once at thread
            # start: max_wait_us may be set on a live engine
            max_wait_s = self.max_wait_us / 1e6
            timeout = 0.05
            if pending:
                now = time.monotonic()
                oldest = min(rs[0].t_enqueue for rs in pending.values())
                timeout = max(0.0, oldest + max_wait_s - now)
            items = []
            if not stopping:
                try:
                    items.append(self._q.get(timeout=timeout))
                except queue.Empty:
                    pass
                # greedy drain: everything already queued joins this
                # formation round — a slow dispatch must not leave the
                # backlog to be aged out one item per iteration.  Bounded
                # at ~2 full batches of rows so overload backs up into
                # the bounded admission queue (where it REJECTS) instead
                # of pooling unbounded host-side.
                drained = sum(sum(r.rows for r in rs)
                              for rs in pending.values())
                try:
                    while drained < 2 * self.max_batch:
                        it = self._q.get_nowait()
                        items.append(it)
                        if it is not _STOP:
                            drained += it.rows
                except queue.Empty:
                    pass
                self._ins.set_queue_depth(self._q.qsize())
            now = time.monotonic()
            for item in items:
                if item is _STOP:
                    stopping = True
                elif item.deadline is not None and now > item.deadline:
                    self._timeout_request(item)
                else:
                    pending.setdefault(item.sig, []).append(item)
            # dispatch every signature that is full or has waited out
            now = time.monotonic()
            for sig in list(pending):
                reqs = pending[sig]
                total = sum(r.rows for r in reqs)
                aged = (now - reqs[0].t_enqueue) >= max_wait_s
                while reqs and (total >= self.max_batch or aged
                                or stopping):
                    take, taken_rows = [], 0
                    while reqs:
                        r = reqs[0]
                        if take and taken_rows + r.rows > self.max_batch:
                            break
                        take.append(reqs.pop(0))
                        taken_rows += r.rows
                    self._dispatch(take)
                    total = sum(r.rows for r in reqs)
                    if total < self.max_batch and not stopping:
                        break         # leftovers wait for their own age
                if not reqs:
                    del pending[sig]
            if stopping and not pending:
                # everything dispatched; let the collector finish
                with self._cv:
                    self._completions.append(_STOP)
                    self._cv.notify()
                return

    def _dispatch(self, reqs: List[_Request]) -> None:
        now = time.monotonic()
        live = []
        for r in reqs:
            if r.deadline is not None and now > r.deadline:
                self._timeout_request(r)
            else:
                live.append(r)
        if not live:
            return
        rows = sum(r.rows for r in live)
        feed = {n: (np.concatenate([r.feed[n] for r in live])
                    if np.ndim(live[0].feed[n]) >= 1 else live[0].feed[n])
                for n in self.feed_names}
        tr_on = trace.enabled()
        # paused (maintenance / chaos drill): hold the dispatch until
        # resume() — close() resumes first, and the timed re-check makes
        # a pause that races past close()'s resume unable to wedge the
        # drain forever
        while not self._resume.wait(0.1):
            if self._closed:
                self._resume.set()
                break
        # the batch's causal identity: member request spans name it, the
        # executor::step span dispatched below inherits it through the
        # ambient trace context, and tools/timeline.py draws flow arrows
        # from each request lane into the batch span
        batch_id = trace.new_trace_id("batch")
        bucket = compile_cache.bucket_for(rows, self.bucket_edges)
        _t0 = trace.now() if tr_on else 0
        try:
            # may block on the async window (backpressure) — that wait is
            # exactly the device saturating, and it throttles formation
            with trace.trace_context(batch_id):
                fut = self._backend.dispatch(feed)
        except BaseException as exc:   # noqa: BLE001 — resolved, not lost
            for r in live:
                r.future._reject(exc)
                if _flight.enabled():
                    _flight.record_request(r.trace_id, r.rows,
                                           outcome="error",
                                           batch_id=batch_id)
            self._ins.count("dispatch_errors")
            return
        t_dispatch = time.monotonic()
        t_dispatch_ns = trace.now()
        if tr_on:
            # per-request queue span: admit -> this dispatch (the queue
            # half of the latency split, anchored on the trace clock)
            for r in live:
                trace.complete("serving::queue", r.t_ns, cat="serving",
                               args={"trace_id": r.trace_id,
                                     "batch_id": batch_id},
                               end_ns=_t0)
            trace.complete(
                "serving::batch", _t0, cat="serving",
                args={"rows": rows, "n_requests": len(live),
                      "batch_id": batch_id, "bucket": bucket,
                      "request_ids": [r.trace_id for r in live]})
        self._ins.count("batches")
        self._ins.observe("batch_size", float(rows))
        with self._cv:
            self._completions.append(
                (fut, live, rows, t_dispatch, batch_id, t_dispatch_ns,
                 bucket))
            self._cv.notify()

    # -- collector thread ----------------------------------------------------
    def _collector(self) -> None:
        while True:
            with self._cv:
                while not self._completions:
                    self._cv.wait(timeout=0.5)
                item = self._completions.popleft()
            if item is _STOP:
                return
            fut, reqs, rows, t_dispatch, batch_id, t_dispatch_ns, \
                bucket = item
            try:
                arrays = self._backend.wait(fut)
            except BaseException as exc:  # noqa: BLE001 — per-request
                for r in reqs:
                    r.future._reject(exc)
                    if _flight.enabled():
                        _flight.record_request(r.trace_id, r.rows,
                                               outcome="error",
                                               batch_id=batch_id)
                self._ins.count("dispatch_errors")
                continue
            t_done = time.monotonic()
            t_done_ns = trace.now()
            tr_on = trace.enabled()
            device_s = max(t_done - t_dispatch, 0.0)
            self._ins.observe("device_seconds", device_s)
            if tr_on:
                trace.complete("serving::device", t_dispatch_ns,
                               cat="serving",
                               args={"batch_id": batch_id, "rows": rows},
                               end_ns=t_done_ns)
            off = 0
            for r in reqs:
                res = {}
                for name, arr in zip(self.fetch_names, arrays):
                    if getattr(arr, "ndim", 0) >= 1 \
                            and arr.shape[0] == rows:
                        res[name] = arr[off:off + r.rows]
                    else:
                        res[name] = arr
                off += r.rows
                queue_s = max(t_dispatch - r.t_enqueue, 0.0)
                latency_s = max(t_done - r.t_enqueue, 0.0)
                self._ins.observe("queue_seconds", queue_s)
                self._ins.observe("latency_seconds", latency_s)
                if tr_on:
                    # the request's full span, closed at demux: the
                    # causal chain a trace_id reconstructs is
                    # admit(i) -> serving::queue -> serving::batch
                    # -> serving::device -> serving::request (this)
                    trace.complete(
                        "serving::request", r.t_ns, cat="serving",
                        args={"trace_id": r.trace_id,
                              "batch_id": batch_id, "rows": r.rows,
                              "bucket": bucket,
                              "queue_us": round(queue_s * 1e6, 1),
                              "device_us": round(device_s * 1e6, 1)},
                        end_ns=t_done_ns)
                if _flight.enabled():
                    _flight.record_request(
                        r.trace_id, r.rows, outcome="ok",
                        batch_id=batch_id, batch_rows=rows,
                        bucket=bucket, queue_us=queue_s * 1e6,
                        device_us=device_s * 1e6,
                        latency_us=latency_s * 1e6)
                r.future.timing = {"queue_us": queue_s * 1e6,
                                   "device_us": device_s * 1e6,
                                   "latency_us": latency_s * 1e6}
                r.future._resolve(res)

    # -- introspection -------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        """Point-in-time SLO snapshot (counters + latency percentiles).

        Reads the engine's OWN instrument family: a named engine
        (``name="r0"``) reads ``serving.r0.*`` — per-engine attribution
        with several engines in one process (the fleet's in-process
        replica shape) — while the unnamed default engine reads the
        process-wide ``serving.*`` family (several UNNAMED engines in
        one process still share it, the documented PR-8 limitation)."""
        out = {
            "name": self.name,
            "requests": self._ins.counter_value("requests"),
            "rejected": self._ins.counter_value("rejected"),
            "timeouts": self._ins.counter_value("timeouts"),
            "batches": self._ins.counter_value("batches"),
            "dispatch_errors": self._ins.counter_value("dispatch_errors"),
            "queue_depth": self._q.qsize(),
            "paused": self.paused(),
            "buckets": list(self.bucket_edges),
        }
        for h in ("batch_size", "queue_seconds", "device_seconds",
                  "latency_seconds"):
            st = self._ins.hist_stats(h)
            out[h] = {k: st[k] for k in
                      ("count", "avg", "p50", "p95", "p99") if k in st}
        plan = getattr(getattr(self._backend, "program", None),
                       "_sharding_plan", None)
        if plan is not None:
            out["sharding"] = plan.describe()
        return out
