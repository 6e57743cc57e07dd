"""Async step pipeline: lazy fetches + bounded in-flight dispatch window.

Reference: operators/reader/buffered_reader.cc keeps a double-buffer thread
between the host feed path and the device, and ParallelExecutor's async
SSA-graph executors (fast_threaded_ssa_graph_executor.cc) keep the host out
of the device's critical path.  TPU-native: XLA dispatch is ALREADY async —
``jax.jit``'d calls return device arrays immediately — so the framework's
job is to stop forcing synchronisation.  Three pieces live here:

* :class:`FetchHandle` — the lazy fetch wrapper ``Executor.run`` returns
  under ``return_numpy=False``: a live device array that materialises on
  ``.numpy()`` / ``np.asarray`` / ``float()``; NaN scans and deferred
  checkify errors surface at materialisation, not at dispatch.
* :class:`AsyncStepRunner` — ``submit(feed)`` dispatches steps while
  keeping at most ``FLAGS_max_inflight_steps`` dispatches outstanding;
  backpressure blocks on the OLDEST step's handles (the framework.channel.h
  bounded-queue analog).  With ``steps_per_dispatch=K`` it groups K feeds
  and drives them through one ``lax.scan``-compiled executable
  (``Executor.run_scan``) — one Python dispatch, K device steps.
* :func:`batch_stack` / :func:`group_steps` — the loader-side staging
  hooks: group K feeds and ``jax.device_put`` them on the Prefetcher's
  producer thread (sharded along the data-parallel axis when a mesh is
  active) so H2D transfer overlaps device compute.

Observability (docs/observability.md): ``executor.inflight_steps`` /
``executor.inflight_peak`` gauges, ``executor.dispatch_seconds`` vs
``executor.host_wait_seconds`` histograms — the overlap is visible, not
inferred.

Donation safety: with ``donate_buffers`` active the NEXT dispatch donates
the scope's state arrays to XLA.  A still-live older fetch that aliases
that state (``FetchHandle.aliases_state``) would then read a deleted
buffer — the Executor registers every aliasing lazy fetch
(``Executor._alias_live``) and persists (host-copies) them before any
donating dispatch, across runners, programs, and sync runs.  The runner's
``donate_guard=True`` replicates that guard locally for duck-typed /
fake executors (tests simulating donation on CPU).

Single-threaded contract: one runner is driven from one thread (the train
loop); the device-side overlap comes from XLA's async dispatch, not from
host threads.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

import numpy as np

from . import core
from . import trace


class ScanUnsupportedError(ValueError):
    """Raised by Executor.run_scan when the program cannot be scan-fused
    (mesh / pipeline / recompute / PS hints, checkify debug mode,
    non-uniform feed shapes).  AsyncStepRunner catches it and degrades to
    sequential dispatches — permanently for structural causes
    (``permanent=True``, the default), per-group for transient ones like
    a ragged tail batch or a debug flag that may be turned off."""

    def __init__(self, msg, permanent=True):
        super().__init__(msg)
        self.permanent = permanent


def _once(fn: Callable[[], None]) -> Callable[[], None]:
    """Idempotent wrapper: shared across one step's handles so a deferred
    checkify throw fires exactly once no matter which handle materialises
    first."""
    done = [False]

    def call():
        if not done[0]:
            done[0] = True
            fn()
    return call


class FetchHandle:
    """A lazy fetch: wraps the live device array of one fetched var.

    Materialisation (``numpy()`` / ``__array__`` / ``float()``) is the
    ONLY point that forces a D2H transfer; until then the array stays
    device-resident and the host keeps dispatching.  Deferred per-op
    checkify errors (``pre_check``) and the ``FLAGS_check_nan_inf`` fetch
    scan run at materialisation — an error raised at dispatch N surfaces
    when handle N is read, never earlier and never lost by the runner
    (``AsyncStepRunner.drain`` re-raises unconsumed dispatch errors).
    """

    __slots__ = ("name", "aliases_state", "_raw", "_np", "_pre_check",
                 "_check_nan", "_waiter", "__weakref__")

    def __init__(self, value, name: Optional[str] = None,
                 aliases_state: bool = False, check_nan: bool = False,
                 pre_check: Optional[Callable[[], None]] = None,
                 waiter: Optional[Callable[[], None]] = None):
        self.name = name
        self.aliases_state = bool(aliases_state)
        self._raw = value
        self._np: Optional[np.ndarray] = None
        self._pre_check = pre_check
        self._check_nan = bool(check_nan)
        self._waiter = waiter          # test seam: fake-device completion

    # -- introspection (no sync) -------------------------------------------
    @property
    def raw(self):
        """The underlying device array (no host copy, no sync)."""
        return self._raw if self._np is None else self._np

    @property
    def shape(self):
        return tuple(np.shape(self.raw))

    @property
    def dtype(self):
        return np.dtype(getattr(self.raw, "dtype", type(self.raw)))

    @property
    def ndim(self):
        return len(self.shape)

    def is_materialized(self) -> bool:
        return self._np is not None

    # -- synchronisation ----------------------------------------------------
    def block_until_ready(self) -> "FetchHandle":
        """Wait for the device value (no host copy).  Deferred dispatch
        checks fire here too — blocking on handle N surfaces step N's
        error."""
        self._run_pre_check()
        if self._waiter is not None:
            self._waiter()
        elif self._np is None:
            import jax
            jax.block_until_ready(self._raw)
        return self

    def persist(self) -> np.ndarray:
        """Materialise to host and cache — after this the handle survives
        donation of the underlying device buffer.  Safe under concurrent
        callers (the serving plane persists from its collector thread
        while the runner's backpressure path may persist the same
        handle): the loser of the race re-reads the winner's cached
        value instead of converting an already-dropped reference."""
        if self._np is None:
            self._run_pre_check()
            if self._waiter is not None:
                self._waiter()
            raw = self._raw            # local ref: survives a concurrent
            if raw is None:            # winner clearing the attribute
                return self._np
            v = np.asarray(raw)
            if self._check_nan and np.issubdtype(v.dtype, np.floating) \
                    and not np.all(np.isfinite(v)):
                raise FloatingPointError(
                    f"NaN/Inf in fetched var '{self.name}'")
            self._np = v               # publish BEFORE dropping the ref
            self._raw = None
        return self._np

    def _run_pre_check(self):
        if self._pre_check is not None:
            check, self._pre_check = self._pre_check, None
            check()

    # -- materialisation protocols -----------------------------------------
    def numpy(self) -> np.ndarray:
        return self.persist()

    def __array__(self, dtype=None, copy=None):
        v = self.persist()
        return v.astype(dtype) if dtype is not None else v

    def __float__(self):
        return float(np.ravel(self.persist())[0])

    def __int__(self):
        return int(np.ravel(self.persist())[0])

    def __repr__(self):
        state = "np" if self._np is not None else "device"
        return (f"FetchHandle({self.name!r}, shape={self.shape}, "
                f"dtype={self.dtype}, {state})")


class _LazyFetch:
    """A fetch bound to a not-yet-dispatched step (scan grouping buffers
    feeds).  Reading it numerically flushes the runner's partial group and
    materialises — which is why per-step host-side logging caps the
    effective ``steps_per_dispatch`` (docs/performance.md)."""

    __slots__ = ("_future", "_index")

    def __init__(self, future: "StepFuture", index: int):
        self._future = future
        self._index = index

    def handle(self) -> FetchHandle:
        return self._future.handles()[self._index]

    def numpy(self) -> np.ndarray:
        return self.handle().persist()

    def __array__(self, dtype=None, copy=None):
        return self.handle().__array__(dtype)

    def __float__(self):
        return float(self.handle())

    def __int__(self):
        return int(self.handle())

    def __repr__(self):
        return f"_LazyFetch(step fetch #{self._index})"


class StepFuture:
    """One submitted step's result: resolves to a list of FetchHandles.

    A dispatch error is stored and raised when THIS step's handles are
    requested; ``AsyncStepRunner.drain`` raises any error nobody consumed.
    """

    __slots__ = ("_runner", "_handles", "_error", "_consumed")

    def __init__(self, runner: "AsyncStepRunner"):
        self._runner = runner
        self._handles: Optional[List[FetchHandle]] = None
        self._error: Optional[BaseException] = None
        self._consumed = False

    def _set_handles(self, handles: List[FetchHandle]):
        self._handles = list(handles)

    def _set_error(self, exc: BaseException):
        self._error = exc

    @property
    def dispatched(self) -> bool:
        return self._handles is not None or self._error is not None

    def handles(self) -> List[FetchHandle]:
        """The step's FetchHandles; forces dispatch of a buffered partial
        scan group, and raises the step's dispatch error if it had one."""
        if not self.dispatched:
            self._runner.flush()
        if self._error is not None:
            self._consumed = True
            raise self._error
        return self._handles

    def lazy(self, index: int = 0) -> _LazyFetch:
        """A deferred view of fetch ``index`` that does NOT force dispatch
        until read numerically — what hapi.Model.fit hands to callbacks."""
        return _LazyFetch(self, index)

    def result(self) -> List[np.ndarray]:
        """Materialise every fetch to numpy (the blocking read)."""
        return [h.persist() for h in self.handles()]

    def __len__(self):
        return len(self.handles())

    def __iter__(self):
        return iter(self.handles())

    def __getitem__(self, i):
        return self.handles()[i]


class AsyncStepRunner:
    """Bounded in-flight dispatch window over one (program, fetch set).

    ``submit(feed)`` returns a :class:`StepFuture` immediately; at most
    ``max_inflight`` dispatches stay outstanding — the window applies
    backpressure by blocking on the oldest dispatch's handles, and the
    blocked time lands in ``executor.host_wait_seconds`` (vs
    ``executor.dispatch_seconds`` for time spent dispatching), so the
    host/device overlap is measurable.  ``steps_per_dispatch=K`` buffers K
    feeds and drives them through ``Executor.run_scan`` (one lax.scan
    executable, K device steps per Python dispatch); programs the scan path
    cannot fuse (mesh/pipeline/recompute/PS) degrade to sequential
    dispatches transparently.
    """

    def __init__(self, executor, program, fetch_list: Sequence,
                 scope=None, max_inflight: Optional[int] = None,
                 steps_per_dispatch: Optional[int] = None,
                 donate_guard: Optional[bool] = None):
        self._exe = executor
        self._program = program
        self._fetch_list = list(fetch_list or [])
        self._scope = scope
        if max_inflight is None:
            max_inflight = core.get_flag("max_inflight_steps", 2)
        self.max_inflight = max(1, int(max_inflight or 1))
        prog = getattr(program, "_program", program)
        hints = getattr(prog, "_hints", {}) or {}
        if steps_per_dispatch is None:
            steps_per_dispatch = (hints.get("steps_per_dispatch")
                                  or core.get_flag("steps_per_dispatch", 1))
        self.steps_per_dispatch = max(1, int(steps_per_dispatch or 1))
        if (getattr(program, "_mesh", None) is not None
                or hints.get("pipeline_microbatches")
                or hints.get("recompute_checkpoints")
                or hints.get("ps_plan") or hints.get("ps_server")):
            # these step builders do their own batch surgery / host loops —
            # no scan fusion, plain async window only
            self.steps_per_dispatch = 1
        self._donate_guard = donate_guard
        self._pending: List[tuple] = []    # (feed, future, trace ctx)
        self._inflight: "deque[List[FetchHandle]]" = deque()
        # serialises the window's FRONT pops: _wait_oldest (batcher /
        # drain thread) vs reap() (serving collector) — never held
        # across a device wait
        self._pop_lock = threading.Lock()
        self._error_futures: List[StepFuture] = []
        # every not-yet-persisted state-aliasing handle issued while
        # donation is active — the guard persists THESE before a dispatch
        # donates, so handles the window already waited out (or that the
        # caller holds across drain()) are covered too, not just the ones
        # still sitting in _inflight
        self._alias_handles: List[FetchHandle] = []
        self._scan_ok = self.steps_per_dispatch > 1
        # elastic-runtime accounting (distributed/elastic.py): after a
        # drain() every submitted step has completed, so `submitted` IS
        # the exact resume cursor a preemption checkpoint records
        self.submitted = 0

    # -- public -------------------------------------------------------------
    def submit(self, feed: Dict[str, Any]) -> StepFuture:
        fut = StepFuture(self)
        self.submitted += 1
        # the submitter's ambient trace context (a serving batch id)
        # rides with the feed: a buffered scan group dispatches LATER,
        # possibly under a different request's context — the step must
        # still attribute to the one that submitted it
        self._pending.append((dict(feed or {}), fut,
                              trace.current_trace_id()))
        if len(self._pending) >= self.steps_per_dispatch:
            self._dispatch_group()
        return fut

    def flush(self):
        """Dispatch a buffered partial scan group now (epoch tails,
        eager metric reads)."""
        self._dispatch_group()

    def drain(self):
        """Dispatch everything, wait for every in-flight step, and raise
        the first dispatch error nobody consumed — an error at dispatch N
        is never lost, even if handle N was never read."""
        self.flush()
        while self._inflight:
            self._wait_oldest()
        self._publish_device_counters()
        for fut in self._error_futures:
            if not fut._consumed:
                fut._consumed = True
                raise fut._error
        self._error_futures = [f for f in self._error_futures
                               if not f._consumed]

    def _publish_device_counters(self):
        """Counts the program keeps on the device (``program._hints
        ["device_counters"]``: persistable variable -> metric name, e.g. the
        expert layer's tokens per held expert) become gauges of
        ``trace.metrics()``: ``<metric>`` for a scalar, ``<metric>.<i>`` for
        element ``i`` of a vector.  An entry ``(metric, total, steps, rows)``
        is a share of two of them: ``sum(total) / (steps x rows)``, ``rows``
        a gauge the lowering set from its shapes, e.g. the part of the
        expert layer's buffers that held a row.  Read here,
        after the window has emptied and the device is idle, so no step
        carries a fetch or a host callback for them."""
        prog = getattr(self._program, "_program", self._program)
        counters = (getattr(prog, "_hints", None) or {}).get(
            "device_counters")
        if not counters:
            return
        scope = self._scope or core.global_scope()
        metrics = trace.metrics()
        for var, metric in counters.items():
            if isinstance(metric, tuple):
                metric, total, steps, rows = metric
                total, steps = scope.find_var(total), scope.find_var(steps)
                rows = trace.gauge_value(rows)
                if total is not None and steps is not None and rows:
                    calls = float(np.asarray(steps).sum())
                    metrics.gauge(metric).set(
                        float(np.asarray(total).sum()) / (calls * rows)
                        if calls else 0.0)
                continue
            value = scope.find_var(var)
            if value is None:
                continue
            flat = np.asarray(value).ravel()
            if flat.size == 1:
                metrics.gauge(metric).set(float(flat[0]))
            else:
                for i, v in enumerate(flat):
                    metrics.gauge(f"{metric}.{i}").set(float(v))

    def abort(self):
        """Error-path cleanup: DROP buffered feeds (their futures resolve
        to an error, never dispatch stale batches later), wait out
        in-flight dispatches, and clear stored errors — without raising,
        so the primary exception in the driving loop stays primary."""
        aborted = RuntimeError(
            "AsyncStepRunner.abort(): step was buffered when the driving "
            "loop aborted — it was never dispatched")
        for _, fut, _ctx in self._pending:
            fut._set_error(aborted)
        self.submitted -= len(self._pending)    # never ran: not resumable
        self._pending = []
        while self._inflight:
            try:
                self._wait_oldest()
            except Exception:       # noqa: BLE001 — cleanup never raises
                pass
        self._error_futures = []

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            self.drain()
        return False

    @property
    def inflight(self) -> int:
        return len(self._inflight)

    def reap(self) -> None:
        """Pop fully-materialised entries off the front of the window —
        for consumers (the serving collector) that wait results OUT of
        band instead of through drain()/backpressure.  Without this, the
        last dispatched batch sits in the window forever once traffic
        stops, and ``executor.inflight_steps`` reads >0 on an idle
        engine — which the SLO watchdog must interpret as outstanding
        work (a false ``stalled`` verdict that would get a healthy idle
        replica ejected from a fleet).  The front-pop is serialised with
        ``_wait_oldest`` through ``_pop_lock`` (check-then-pop on the
        bare deque would race the batcher's backpressure pop); the lock
        never spans a device wait, so contention is a few instructions."""
        with self._pop_lock:
            popped = False
            while self._inflight and all(h.is_materialized()
                                         for h in self._inflight[0]):
                self._inflight.popleft()
                popped = True
            if popped:
                # gauge set INSIDE the lock: outside it, a stale 0 from
                # this thread could overwrite the count of a batch the
                # batcher dispatched in between — and the watchdog would
                # miss that batch wedging
                trace.metrics().gauge("executor.inflight_steps").set(
                    len(self._inflight))

    @property
    def pending(self) -> int:
        """Buffered submits not yet dispatched (a partial scan group).
        Their updates are NOT in the scope yet, so a point-in-time
        checkpoint cursor is ``submitted - pending`` until a
        flush()/drain() empties the buffer."""
        return len(self._pending)

    # -- internals ----------------------------------------------------------
    def _dispatch_feeds(self, feeds: List[Dict[str, Any]]
                        ) -> List[List[FetchHandle]]:
        """One group -> per-step handle lists.  Overridable test seam."""
        if len(feeds) > 1 and self._scan_ok:
            try:
                return self._exe.run_scan(
                    self._program, feeds, self._fetch_list,
                    scope=self._scope, return_handles=True)
            except ScanUnsupportedError as e:
                if getattr(e, "permanent", True):
                    self._scan_ok = False   # structural: dispatch 1:1
                else:
                    # transient (ragged tail group, debug flag): THIS
                    # group runs sequentially, the next uniform group
                    # scans again — counted, never silent
                    trace.metrics().counter(
                        "executor.scan_fallback_groups").inc()
        return [self._exe.run(self._program, feed=f,
                              fetch_list=self._fetch_list,
                              scope=self._scope, return_numpy=False)
                for f in feeds]

    def _dispatch_group(self):
        group, self._pending = self._pending, []
        if not group:
            return
        # donation safety for REAL Executors lives executor-side
        # (Executor._alias_live: run() registers aliasing handles, every
        # donating dispatch persists them first).  The runner-local guard
        # below runs only on explicit donate_guard=True — duck-typed /
        # fake executors and tests that simulate donation on CPU.
        donate = self._donate_guard is True
        try:
            # backpressure BEFORE dispatching: never more than
            # max_inflight dispatches outstanding
            while len(self._inflight) >= self.max_inflight:
                self._wait_oldest()
            if donate:
                # the dispatch below would donate the scope's state
                # buffers — host-copy every still-live fetch that aliases
                # them first (in-flight or already waited out)
                for h in self._alias_handles:
                    h.persist()
                del self._alias_handles[:]
        except BaseException:
            # an OLDER step's deferred error (NaN scan, checkify) — the
            # new group was never dispatched: put it back so its futures
            # aren't stranded without handles or error, then surface
            self._pending = group + self._pending
            raise
        m = trace.metrics()
        t0 = time.perf_counter()
        # restore the SUBMITTER's trace context around the real dispatch:
        # a buffered group dispatches later (flush/next submit), possibly
        # under another request's ambient context — the executor::step
        # span and step wide event must attribute to the context that
        # submitted the group (its head; a scan group shares one span)
        token = trace.set_context(group[0][2])
        try:
            per_step = self._dispatch_feeds([f for f, _, _ in group])
        except BaseException as exc:    # noqa: BLE001 — stored, not lost
            for _, fut, _ctx in group:
                fut._set_error(exc)
                self._error_futures.append(fut)
            m.counter("executor.async_dispatch_errors").inc()
            return
        finally:
            trace.restore_context(token)
        m.histogram("executor.dispatch_seconds").observe(
            time.perf_counter() - t0)
        m.counter("executor.async_steps").inc(len(group))
        # PS-wrapped programs and duck-typed executors may hand back raw
        # arrays — normalise so futures always resolve to FetchHandles
        per_step = [[h if isinstance(h, FetchHandle) else FetchHandle(h)
                     for h in hs] for hs in per_step]
        flat: List[FetchHandle] = []
        for (_, fut, _ctx), handles in zip(group, per_step):
            fut._set_handles(handles)
            flat.extend(handles)
        if donate:
            self._alias_handles.extend(h for h in flat if h.aliases_state)
        self._inflight.append(flat)
        depth = len(self._inflight)
        m.gauge("executor.inflight_steps").set(depth)
        peak = m.gauge("executor.inflight_peak")
        if depth > peak.value:
            peak.set(depth)

    def _wait_oldest(self):
        with self._pop_lock:
            if not self._inflight:
                return
            handles = self._inflight.popleft()
        _sp = trace.now() if trace.enabled() else 0
        t0 = time.perf_counter()
        for h in handles:
            if h._check_nan:
                # FLAGS_check_nan_inf contract: the per-fetch scan must
                # fire even for fetches nobody reads — persist (host
                # copy) instead of just waiting, like the sync path did
                h.persist()
            else:
                h.block_until_ready()
        if _sp:
            # goodput plane: host blocked on device results = the device
            # was the bottleneck doing productive work — this span is
            # what charges backpressure to the device_compute bucket
            trace.complete("executor::host_wait", _sp, cat="step",
                           args={"n_handles": len(handles)})
        m = trace.metrics()
        m.histogram("executor.host_wait_seconds").observe(
            time.perf_counter() - t0)
        m.gauge("executor.inflight_steps").set(len(self._inflight))


# ---------------------------------------------------------------------------
# loader-side staging hooks
# ---------------------------------------------------------------------------

def group_steps(source: Iterable, k: int) -> Iterable[list]:
    """Group a feed stream into lists of up to ``k`` consecutive feeds —
    the unit `steps_per_dispatch=k` consumes.  The tail group may be
    short (scan == sequential numerics, so a short group is just less
    fusion, never different math)."""
    k = max(1, int(k))
    group: list = []
    for item in source:
        group.append(item)
        if len(group) >= k:
            yield group
            group = []
    if group:
        yield group


def _stage_one(feed, sharding):
    import jax
    if isinstance(feed, dict):
        return {k: jax.device_put(v, sharding) if sharding is not None
                else jax.device_put(v) for k, v in feed.items()}
    if isinstance(feed, (list, tuple)):
        return type(feed)(jax.device_put(v, sharding) if sharding is not None
                          else jax.device_put(v) for v in feed)
    return jax.device_put(feed, sharding) if sharding is not None \
        else jax.device_put(feed)


def batch_stack(k: int, mesh=None) -> Callable:
    """Prefetcher ``stage=`` hook for K-step groups: ``jax.device_put``
    every array of every feed in the group on the PRODUCER thread, so the
    H2D transfer of group t+1 overlaps the device steps of group t.  With
    a data-parallel mesh the batch axis is sharded across the mesh's first
    axis (the ``with_data_parallel`` layout)."""
    del k                               # the group is already formed
    sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        sharding = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))

    def stage(group):
        if isinstance(group, list):
            return [_stage_one(feed, sharding) for feed in group]
        return _stage_one(group, sharding)
    return stage
