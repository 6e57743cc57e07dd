"""Always-on flight recorder: a bounded ring of structured wide events.

Reference: the reference stack keeps failure forensics next to the
profiler (``PADDLE_ENFORCE`` error stacks annotate what the process was
doing when it died); aviation flight recorders are the cleaner model —
a small, always-on ring of high-signal records that survives to the
post-mortem.  The trace plane (fluid/trace.py) is the opposite design
point: rich but opt-in and unbounded-ish.  This module is the third
leg: **one wide event per executor step and per served request**,
recorded even with ``FLAGS_enable_trace`` off, cheap enough that the
ci_smoke gate holds a recorder-on demo loop within 5% of recorder-off.

A wide event is one flat dict carrying everything an incident
responder asks first:

* step records — ``{"kind": "step", "seq", "ts_us", "step", "dur_us",
  "t0_us", "run_us", "phases_us", "bucket", "batch_valid",
  "compile_miss", "fp", "n_fetch", "scan", "inflight", "goodput_ratio",
  "rss_bytes", "hbm_peak_bytes", "trace_id"}`` (trace_id present when
  the step ran under a serving batch's context).  ``t0_us`` is the start
  of ``Executor.run`` on the trace epoch (``ts_us`` stays the time of
  recording), ``run_us`` the whole of ``run`` and ``phases_us`` its
  parts by name (``executor.PHASES``; they add up to ``run_us``);
  ``dur_us`` is the jitted call, with a plan's placing before it;
* compile records — ``{"kind": "compile", "fp", "n_ops", "t0_us",
  "total_us", "prepare_us", "backend_us", "cache_hit", "xla_compiles",
  "scan"}``, one per Executor compile miss: ``total_us`` is what
  ``executor.compile_seconds`` observes, ``prepare_us`` Program -> step
  function, ``backend_us`` the ``xla_compile`` records inside the miss,
  and the rest the Python side (jaxpr trace, MLIR lowering);
* ``{"kind": "xla_compile", "t0_us", "backend_us", "cache_hit",
  "retrieval_us"}``, one per executable XLA built or the persistent
  cache loaded, anywhere in the process (:func:`watch_xla_compiles`);
* request records — ``{"kind": "request", "seq", "ts_us", "trace_id",
  "batch_id", "rows", "batch_rows", "bucket", "queue_us", "device_us",
  "latency_us", "outcome"}`` (outcome ``ok`` / ``timeout`` /
  ``rejected`` / ``error``);
* marker records — ``kind`` ``"preempt"`` / ``"incident"`` / ... from
  the elastic plane and the SLO watchdog.

Design for the hot path: ``record()`` costs one enabled-boolean, one
dict build, and one lock-guarded ring-slot store — no serialization, no
allocation proportional to history.  Gauge sampling (goodput ratio,
HBM, rss) happens in :func:`record_step` through cached instrument
references; rss is re-read from ``/proc`` at most once per second.

Gating: ``FLAGS_flight_recorder`` (default ON — the whole point is
being there when nobody armed anything) and
``FLAGS_flight_recorder_events`` (ring capacity, default 4096).  The
SLO watchdog (fluid/watchdog.py) reads ``completions`` (steps + ok
requests only — a rejection storm is not liveness) as its progress
signal and embeds ``snapshot()`` into diagnostic bundles.
"""
from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Dict, List, Optional

from . import trace

__all__ = [
    "FlightRecorder", "recorder", "enabled", "record", "record_step",
    "record_request", "record_compile", "watch_xla_compiles",
    "configure", "reset", "rss_bytes",
]

class FlightRecorder:
    """Fixed-capacity ring of wide-event dicts.  ``total`` counts every
    record ever written; ``completions`` counts only records that mean
    WORK COMPLETED (steps, ok requests) — the watchdog's progress
    signal, so a storm of rejections/timeouts from a wedged device
    never masquerades as liveness.  The ring keeps the last
    ``capacity`` records in arrival order."""

    def __init__(self, capacity: int = 4096, enabled: bool = True):
        self.capacity = max(16, int(capacity))
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._buf: List[Optional[Dict[str, Any]]] = [None] * self.capacity
        self._n = 0                     # total records ever written
        self._done = 0                  # completion records only

    @property
    def total(self) -> int:
        """Records written since construction (ring bookkeeping)."""
        with self._lock:
            return self._n

    @property
    def completions(self) -> int:
        """Monotonic count of completed-work records (steps + ok
        requests) — what the SLO watchdog reads as progress."""
        with self._lock:
            return self._done

    def record(self, rec: Dict[str, Any],
               progress: Optional[bool] = None) -> None:
        """Store one wide event (adds ``seq``/``ts_us``).  No-op when
        disabled; never raises into the caller's step path.
        ``progress`` marks the record as completed work (default:
        steps and ok-outcome requests)."""
        if not self.enabled:
            return
        if progress is None:
            progress = rec.get("kind") == "step" or (
                rec.get("kind") == "request"
                and rec.get("outcome") == "ok")
        rec["ts_us"] = trace.elapsed_us()
        with self._lock:
            rec["seq"] = self._n
            self._buf[self._n % self.capacity] = rec
            self._n += 1
            if progress:
                self._done += 1

    def snapshot(self, last: Optional[int] = None) -> List[Dict[str, Any]]:
        """The retained records oldest→newest (``last`` caps the count).
        Each record is copied, so a bundle serializer can't race a
        writer mutating a live dict."""
        with self._lock:
            n, cap = self._n, self.capacity
            start = max(0, n - cap)
            if last is not None:
                start = max(start, n - int(last))
            out = [dict(r) for r in
                   (self._buf[i % cap] for i in range(start, n))
                   if r is not None]
        return out

    def resize(self, capacity: int) -> None:
        keep = self.snapshot()
        with self._lock:
            self.capacity = max(16, int(capacity))
            self._buf = [None] * self.capacity
            # re-lay the retained tail so the ring stays consistent with
            # the (unchanged, monotonic) total count
            keep = keep[-self.capacity:]
            for i, rec in enumerate(keep):
                self._buf[(self._n - len(keep) + i) % self.capacity] = rec

    def clear(self) -> None:
        with self._lock:
            self._buf = [None] * self.capacity
            self._n = 0
            self._done = 0


_recorder = FlightRecorder(
    capacity=int(os.environ.get("FLAGS_flight_recorder_events", "4096")
                 or 4096),
    enabled=os.environ.get("FLAGS_flight_recorder", "1").strip().lower()
    in trace._TRUE_STRINGS)


def recorder() -> FlightRecorder:
    return _recorder


def enabled() -> bool:
    """The single-boolean hot-path guard (mirrors trace.enabled())."""
    return _recorder.enabled


def record(kind: str, **fields) -> None:
    """Generic wide event — markers from the elastic plane / watchdog."""
    if _recorder.enabled:
        fields["kind"] = kind
        _recorder.record(fields)


def configure(capacity: Optional[int] = None,
              enabled: Optional[bool] = None) -> None:
    """Apply FLAGS_flight_recorder / FLAGS_flight_recorder_events at
    runtime (called from core.set_flags)."""
    if enabled is not None:
        _recorder.enabled = bool(enabled)
    if capacity is not None and int(capacity) != _recorder.capacity:
        _recorder.resize(int(capacity))


def reset() -> None:
    """Clear the ring (test isolation)."""
    _recorder.clear()


# ---------------------------------------------------------------------------
# cheap gauge sampling for step records
# ---------------------------------------------------------------------------

# cached instrument references: record_step must not pay a registry
# dict lookup per step
_m = trace.metrics()
_g_inflight = _m.gauge("executor.inflight_steps")
_g_goodput = _m.gauge("goodput.ratio")
_g_hbm = _m.gauge("xla.mem.lru_total_peak_bytes")

_rss_cache = [0.0, 0]                   # (monotonic stamp, bytes)
_PAGE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


def rss_bytes(max_age_s: float = 1.0) -> int:
    """Process resident set size, re-read from /proc at most once per
    ``max_age_s`` (a syscall per step would show up on the 5% gate)."""
    t = time.monotonic()
    if t - _rss_cache[0] > max_age_s:
        _rss_cache[0] = t
        try:
            with open("/proc/self/statm", "rb") as f:
                _rss_cache[1] = int(f.read().split()[1]) * _PAGE
        except (OSError, ValueError, IndexError):
            pass                        # non-linux: keep the last value
    return _rss_cache[1]


def record_step(step: int, dur_us: float, bucket=None, batch_valid=None,
                compile_miss: bool = False, fp: Optional[str] = None,
                n_fetch: int = 0, scan: Optional[int] = None,
                t0_ns: Optional[int] = None, run_us: Optional[float] = None,
                phases_us: Optional[Dict[str, float]] = None) -> None:
    """One wide event per completed executor step.  Callers guard with
    :func:`enabled` so a disabled recorder costs one boolean.  ``t0_ns``
    is the ``trace.now()`` stamp at the start of ``Executor.run``."""
    rec: Dict[str, Any] = {
        "kind": "step", "step": int(step), "dur_us": round(dur_us, 1),
        "compile_miss": bool(compile_miss), "n_fetch": int(n_fetch),
        "inflight": _g_inflight.value,
        "goodput_ratio": round(_g_goodput.value, 4),
        "rss_bytes": rss_bytes(),
        "hbm_peak_bytes": _g_hbm.value,
    }
    if bucket is not None:
        rec["bucket"] = int(bucket)
    if batch_valid is not None:
        rec["batch_valid"] = int(batch_valid)
    if fp:
        rec["fp"] = fp
    if scan:
        rec["scan"] = int(scan)
    if phases_us is not None:
        rec["t0_us"] = trace._ts_us(t0_ns)
        rec["run_us"] = run_us
        rec["phases_us"] = phases_us
    tid = trace.current_trace_id()
    if tid is not None:
        rec["trace_id"] = tid
    _recorder.record(rec)


# ---------------------------------------------------------------------------
# where a compile's time went: XLA's share against the Python side
# ---------------------------------------------------------------------------

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

_h_backend = _m.histogram("xla.backend_compile_seconds")
_c_cache_hits = _m.counter("xla.persistent_cache_hits")


class _CompileLocal(threading.local):
    def __init__(self):
        self.hit = False                # a cache hit since the last record
        self.retrieval_us = None
        # (t0_us, backend_us, cache_hit) of this thread's last compiles:
        # an Executor compile miss finds its own among them
        self.recent = collections.deque(maxlen=256)


_compiling = _CompileLocal()
_watching = []                          # the two listeners, once registered


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        _compiling.hit = True


def _on_duration(event: str, secs: float, **_) -> None:
    if event == _CACHE_RETRIEVAL:
        _compiling.retrieval_us = round(secs * 1e6, 1)
    elif event == _BACKEND_COMPILE:
        # jax times compile_or_get_cached as one block and reports as it
        # ends (pxla._cached_compilation), the hit and its retrieval time
        # from inside that block: they belong to this executable
        tl = _compiling
        hit, retrieval_us = tl.hit, tl.retrieval_us
        tl.hit, tl.retrieval_us = False, None
        backend_us = round(secs * 1e6, 1)
        _h_backend.observe(secs)
        if hit:
            _c_cache_hits.inc()
        t0_us = trace.elapsed_us() - backend_us
        tl.recent.append((t0_us, backend_us, hit))
        if _recorder.enabled:
            rec = {"kind": "xla_compile", "t0_us": t0_us,
                   "backend_us": backend_us, "cache_hit": hit}
            if retrieval_us is not None:
                rec["retrieval_us"] = retrieval_us
            _recorder.record(rec)


def watch_xla_compiles() -> None:
    """Register the two ``jax.monitoring`` listeners behind the
    ``xla_compile`` records and the ``xla.*`` instruments, once per
    process however often this is called (the Executor's module calls it
    as it is imported).  Process-wide on purpose: a reference's plain
    ``jax.jit`` and a loader's staging programs start a job late too."""
    if _watching:
        return
    import jax.monitoring
    _watching.extend((_on_event, _on_duration))
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def record_compile(fp: str, n_ops: Optional[int], t0_ns: int,
                   total_us: float, prepare_us: float,
                   scan: Optional[int] = None) -> Dict[str, Any]:
    """One wide event per Executor compile miss (not progress), written
    by the thread the miss ran on as it ends: the ``xla_compile`` events
    of this thread since ``t0_ns`` are the miss's own.  Returns the fields
    a span's ``args`` repeat."""
    t0_us = trace._ts_us(t0_ns)
    xla = [(us, hit) for t, us, hit in _compiling.recent if t >= t0_us]
    fields: Dict[str, Any] = {
        "fp": fp, "n_ops": n_ops, "total_us": round(total_us, 1),
        "prepare_us": round(prepare_us, 1),
        "backend_us": round(sum(us for us, _ in xla), 1),
        # of the executables inside, the step's own is the largest; the
        # small eager programs beside it are never written to the cache
        "cache_hit": bool(xla) and max(xla)[1],
        "xla_compiles": len(xla),
    }
    if scan:
        fields["scan"] = int(scan)
    if _recorder.enabled:
        _recorder.record(dict(fields, kind="compile", t0_us=t0_us))
    return fields


def record_request(trace_id: str, rows: int, outcome: str = "ok",
                   batch_id: Optional[str] = None,
                   batch_rows: Optional[int] = None,
                   bucket=None, queue_us: Optional[float] = None,
                   device_us: Optional[float] = None,
                   latency_us: Optional[float] = None,
                   replica: Optional[str] = None) -> None:
    """One wide event per served (or rejected/timed-out) request.
    ``replica`` attributes a fleet-routed request to the replica that
    served it (the router records these parent-side)."""
    rec: Dict[str, Any] = {
        "kind": "request", "trace_id": trace_id, "rows": int(rows),
        "outcome": outcome,
    }
    if replica is not None:
        rec["replica"] = replica
    if batch_id is not None:
        rec["batch_id"] = batch_id
    if batch_rows is not None:
        rec["batch_rows"] = int(batch_rows)
    if bucket is not None:
        rec["bucket"] = int(bucket)
    if queue_us is not None:
        rec["queue_us"] = round(queue_us, 1)
    if device_us is not None:
        rec["device_us"] = round(device_us, 1)
    if latency_us is not None:
        rec["latency_us"] = round(latency_us, 1)
    _recorder.record(rec)
