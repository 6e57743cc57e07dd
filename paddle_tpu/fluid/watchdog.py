"""SLO watchdog + post-mortem diagnostic bundles.

Reference: the reference stack's failure story is forensic —
``PADDLE_ENFORCE`` error stacks tell you what died and why, after the
fact.  A serving/training process needs the same property for the
failures that DON'T raise: a wedged device call (inflight > 0, nothing
completing), a sustained p99 breach, an OOM, an uncaught crash.  This
module watches for all four and, on any trigger, freezes the evidence
into one **atomic diagnostic bundle** a responder can open on a
different machine with ``tools/diagnose.py``.

Detection (one daemon thread, ``FLAGS_watchdog_interval_s`` ticks;
``tick()`` is also callable directly for deterministic tests):

* **stall** — work is outstanding (``executor.inflight_steps`` /
  ``executor.steps_in_progress`` / ``serving.queue_depth`` > 0) and the
  completion counters (flight-recorder ``completions`` +
  ``executor.steps_completed`` + ``serving.batches``) have not moved
  for ``FLAGS_watchdog_stall_s``.  A live compile
  (``executor.compiles_in_progress`` > 0) or an elastic drain
  (``elastic.drain_in_progress`` > 0) counts as liveness, so a long
  legitimate XLA compile or a preemption drain never false-positives.
  The stall latches: exactly one bundle per incident, cleared when
  progress resumes.
* **breach** — the p99 of completed-request latency (from the flight
  recorder's request records, per tick window) stays at or above
  ``FLAGS_watchdog_p99_ms`` for ``FLAGS_watchdog_breach_windows``
  CONSECUTIVE windows (0 ms disables).  A below-threshold or empty
  window resets the count; one bundle per breach incident.
* **crash** — ``install_crash_hook()`` chains ``sys.excepthook``: an
  uncaught exception dumps a ``crash`` bundle (with the traceback)
  before the previous hook runs.
* **oom** — ``fluid/device_stats.attach_oom_report`` calls
  :func:`notify_oom` on every RESOURCE_EXHAUSTED error; a running
  watchdog dumps an ``oom`` bundle (rate-limited, one per incident
  window).

The health state (``ok`` / ``stalled`` / ``breached``) is what
``GET /healthz`` on the metrics plane serves, so a router/fleet
controller can eject a wedged replica (ROADMAP item 2's ejection
signal).

Bundles are single JSON files written tmp+fsync+rename (the checkpoint
plane's ``atomic_write_bytes``): a crash mid-dump leaves the previous
bundle (or nothing), never a torn one.  Contents: trace tail, the
flight recorder's wide events, the goodput report, device footprints,
a full metrics snapshot, flags, program fingerprints, watchdog state,
and (crash/oom) the exception + traceback.  ``tools/diagnose.py``
renders a bundle into a human report and a Chrome trace with
request↔batch flow arrows — no live process required.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback as _tbmod
from typing import Any, Dict, List, Optional

from . import flight_recorder
from . import trace

__all__ = [
    "SloWatchdog", "start", "stop", "get", "health", "apply_flags",
    "dump_bundle", "list_bundles", "load_bundle", "notify_oom",
    "build_bundle_doc", "dump_fleet_bundle", "list_fleet_bundles",
    "install_crash_hook", "uninstall_crash_hook",
    "DEFAULT_DIAGNOSTIC_DIR", "BUNDLE_SCHEMA", "FLEET_BUNDLE_SCHEMA",
]

DEFAULT_DIAGNOSTIC_DIR = "/tmp/paddle_tpu_diagnostics"
BUNDLE_SCHEMA = "paddle_tpu.diagnostic_bundle.v1"
FLEET_BUNDLE_SCHEMA = "paddle_tpu.fleet_bundle.v1"


def _flag(name, default):
    try:
        from . import core
        v = core.get_flag(name, default)
        return default if v is None else v
    except Exception:               # noqa: BLE001 — flags are advisory
        return default


# ---------------------------------------------------------------------------
# bundle writer
# ---------------------------------------------------------------------------

def _json_safe(obj):
    """Flags / args may carry Paths, numpy scalars, sets — a bundle dump
    must degrade to strings, never throw."""
    try:
        json.dumps(obj)
        return obj
    except (TypeError, ValueError):
        if isinstance(obj, dict):
            return {str(k): _json_safe(v) for k, v in obj.items()}
        if isinstance(obj, (list, tuple, set)):
            return [_json_safe(v) for v in obj]
        return str(obj)


def _goodput_report() -> Dict[str, Any]:
    from . import goodput
    try:
        if trace.enabled():
            return goodput.snapshot()
        return goodput.from_metrics(trace.elapsed_us() / 1e6)
    except Exception as e:          # noqa: BLE001 — forensics degrade
        return {"error": f"{type(e).__name__}: {e}"}


def _device_footprints() -> List[Dict[str, Any]]:
    try:
        from . import device_stats
        return device_stats.live_footprints()
    except Exception:               # noqa: BLE001
        return []


def _faultline_state() -> Optional[Dict[str, Any]]:
    """The active fault-injection schedule, if any — an incident bundle
    must say what chaos was deliberately being injected when it fired,
    or a responder debugs the fault plane as a production failure.
    Only reads an ALREADY-imported faultline module: a bundle dump
    never pulls in the distributed package."""
    mod = sys.modules.get("paddle_tpu.distributed.faultline")
    if mod is None:
        return None
    try:
        fl = mod.get()
        return fl.describe() if fl is not None else None
    except Exception:               # noqa: BLE001 — forensics degrade
        return None


def _program_fingerprints(wide_events) -> List[str]:
    return sorted({r["fp"] for r in wide_events
                   if r.get("kind") == "step" and r.get("fp")})


def dump_bundle(reason: str, diagnostic_dir: Optional[str] = None,
                exc: Optional[BaseException] = None,
                extra: Optional[Dict[str, Any]] = None,
                trace_tail: Optional[int] = None,
                watchdog_state: Optional[Dict[str, Any]] = None) -> str:
    """Freeze the process's forensic state into one atomic JSON bundle
    and return its path.  Never raises into a crashing process: a
    failed dump prints one stderr line and returns ''."""
    try:
        return _dump_bundle(reason, diagnostic_dir, exc, extra, trace_tail,
                            watchdog_state)
    except Exception as e:          # noqa: BLE001 — a dying process's
        print(f"paddle_tpu.watchdog: bundle dump failed: "  # last words
              f"{type(e).__name__}: {e}", file=sys.stderr)
        trace.metrics().counter("watchdog.bundle_errors").inc()
        return ""


def build_bundle_doc(reason: str, exc: Optional[BaseException] = None,
                     extra: Optional[Dict[str, Any]] = None,
                     trace_tail: Optional[int] = None,
                     watchdog_state: Optional[Dict[str, Any]] = None
                     ) -> Dict[str, Any]:
    """The diagnostic-bundle document WITHOUT writing it anywhere —
    what a fleet parent fetches over the replica's /bundle endpoint to
    embed in a fleet incident bundle."""
    tail_n = int(trace_tail if trace_tail is not None
                 else _flag("diagnostic_trace_tail", 5000))
    wide = flight_recorder.recorder().snapshot()
    try:
        from . import core
        flags = _json_safe(dict(core._FLAGS))
    except Exception:               # noqa: BLE001
        flags = {}
    doc: Dict[str, Any] = {
        "schema": BUNDLE_SCHEMA,
        "reason": reason,
        "ts": time.time(),
        "time": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "pid": os.getpid(),
        "uptime_s": round(trace.elapsed_us() / 1e6, 3),
        "watchdog": watchdog_state if watchdog_state is not None
        else health(),
        "flags": flags,
        "trace_enabled": trace.enabled(),
        "trace_dropped_events": trace.dropped_count(),
        "trace_tail": trace.tail_events(tail_n),
        "wide_events": wide,
        "goodput": _goodput_report(),
        "metrics": _json_safe(trace.metrics().snapshot()),
        "device_footprints": _device_footprints(),
        "program_fingerprints": _program_fingerprints(wide),
        "faultline": _faultline_state(),
    }
    if exc is not None:
        doc["exception"] = {
            "type": type(exc).__name__,
            "message": str(exc),
            "traceback": "".join(_tbmod.format_exception(
                type(exc), exc, exc.__traceback__)),
            "device_footprints": _json_safe(
                getattr(exc, "device_footprints", None)),
        }
    if extra:
        doc["extra"] = _json_safe(extra)
    return doc


def _dump_bundle(reason, diagnostic_dir, exc, extra, trace_tail,
                 watchdog_state=None) -> str:
    root = os.path.abspath(diagnostic_dir
                           or _flag("diagnostic_dir", None)
                           or DEFAULT_DIAGNOSTIC_DIR)
    os.makedirs(root, exist_ok=True)
    doc = build_bundle_doc(reason, exc=exc, extra=extra,
                           trace_tail=trace_tail,
                           watchdog_state=watchdog_state)
    stamp = time.strftime("%Y%m%d-%H%M%S")
    path = os.path.join(
        root, f"bundle-{stamp}-{reason}-{os.getpid()}-{trace.new_id()}"
              f".json")
    from .checkpoint import atomic_write_bytes
    atomic_write_bytes(path, json.dumps(doc, default=str).encode())
    trace.metrics().counter("watchdog.bundles").inc()
    flight_recorder.record("incident", reason=reason, bundle=path)
    if trace.enabled():
        trace.instant("watchdog::bundle", cat="step",
                      args={"reason": reason, "path": path})
    print(f"paddle_tpu.watchdog: {reason} — diagnostic bundle written to "
          f"{path} (render with: python tools/diagnose.py {path})",
          file=sys.stderr)
    return path


def list_bundles(diagnostic_dir: Optional[str] = None) -> List[str]:
    root = os.path.abspath(diagnostic_dir
                           or _flag("diagnostic_dir", None)
                           or DEFAULT_DIAGNOSTIC_DIR)
    try:
        return sorted(os.path.join(root, f) for f in os.listdir(root)
                      if f.startswith("bundle-") and f.endswith(".json"))
    except OSError:
        return []


def load_bundle(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def dump_fleet_bundle(reason: str, replica: str,
                      router_view: Dict[str, Any],
                      replica_bundles: Dict[str, Any],
                      diagnostic_dir: Optional[str] = None) -> str:
    """Freeze one FLEET incident bundle: the router's own view of the
    incident window (``router_view``: events, breaker states, scrape
    history, in-flight count) plus each involved replica's embedded
    diagnostic-bundle document (``replica_bundles``, name → doc or
    ``{"error": ...}`` when the replica couldn't answer).  Same
    never-raises contract as :func:`dump_bundle`."""
    try:
        root = os.path.abspath(diagnostic_dir
                               or _flag("diagnostic_dir", None)
                               or DEFAULT_DIAGNOSTIC_DIR)
        os.makedirs(root, exist_ok=True)
        doc = {
            "schema": FLEET_BUNDLE_SCHEMA,
            "reason": reason,
            "replica": replica,
            "ts": time.time(),
            "time": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "pid": os.getpid(),
            "router": _json_safe(router_view),
            "replicas": replica_bundles,
        }
        stamp = time.strftime("%Y%m%d-%H%M%S")
        path = os.path.join(
            root, f"fleet-bundle-{stamp}-{reason}-{os.getpid()}-"
                  f"{trace.new_id()}.json")
        from .checkpoint import atomic_write_bytes
        atomic_write_bytes(path, json.dumps(doc, default=str).encode())
        trace.metrics().counter("watchdog.fleet_bundles").inc()
        flight_recorder.record("fleet_incident", reason=reason,
                               replica=replica, bundle=path)
        print(f"paddle_tpu.watchdog: fleet {reason} ({replica}) — "
              f"incident bundle written to {path} (render with: python "
              f"tools/diagnose.py --fleet {path})", file=sys.stderr)
        return path
    except Exception as e:          # noqa: BLE001 — diagnostics never
        print(f"paddle_tpu.watchdog: fleet bundle dump failed: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        trace.metrics().counter("watchdog.bundle_errors").inc()
        return ""


def list_fleet_bundles(diagnostic_dir: Optional[str] = None) -> List[str]:
    root = os.path.abspath(diagnostic_dir
                           or _flag("diagnostic_dir", None)
                           or DEFAULT_DIAGNOSTIC_DIR)
    try:
        return sorted(os.path.join(root, f) for f in os.listdir(root)
                      if f.startswith("fleet-bundle-")
                      and f.endswith(".json"))
    except OSError:
        return []


# ---------------------------------------------------------------------------
# the watchdog
# ---------------------------------------------------------------------------

class SloWatchdog:
    """Stall / p99-breach detector with one-bundle-per-incident latching.

    ``start()`` spawns the daemon poll thread; ``tick()`` runs one
    detection pass synchronously (what the tests drive, with
    ``now_fn`` injected for deterministic clocks)."""

    def __init__(self, interval_s: Optional[float] = None,
                 stall_s: Optional[float] = None,
                 p99_ms: Optional[float] = None,
                 breach_windows: Optional[int] = None,
                 diagnostic_dir: Optional[str] = None,
                 now_fn=time.monotonic):
        self.interval_s = float(interval_s if interval_s is not None
                                else _flag("watchdog_interval_s", 1.0))
        self.stall_s = float(stall_s if stall_s is not None
                             else _flag("watchdog_stall_s", 30.0))
        self.p99_ms = float(p99_ms if p99_ms is not None
                            else _flag("watchdog_p99_ms", 0.0) or 0.0)
        self.breach_windows = max(1, int(
            breach_windows if breach_windows is not None
            else _flag("watchdog_breach_windows", 3)))
        self.diagnostic_dir = diagnostic_dir
        self._now = now_fn
        self.state = "ok"
        # RLock: tick() holds it while dumping a bundle, and the dump
        # embeds health() — which takes this very lock when the module
        # health() routes to this instance
        self._lock = threading.RLock()
        self._stop_ev = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # stall tracking
        self._last_progress = self._progress()
        self._last_progress_t = self._now()
        self._stall_latched = False
        # breach tracking
        self._breach_count = 0
        self._breach_latched = False
        self._last_seen_seq = flight_recorder.recorder().total
        self.bundles: List[str] = []

    # -- signals ------------------------------------------------------------
    _gauge = staticmethod(trace.gauge_value)
    _counter = staticmethod(trace.counter_value)

    def _progress(self) -> tuple:
        """Anything that moves when the process COMPLETES work.  Only
        completion signals count — recorder ``completions`` (steps + ok
        requests), never ``total``: a wedged device under open-loop
        load keeps writing rejected/timeout wide events, and those must
        not read as liveness.  ``serving.batches`` aggregates every
        engine (named engines dual-write the plain family); the decode
        plane's step counter rides alongside."""
        return (flight_recorder.recorder().completions,
                self._counter("executor.steps_completed"),
                self._counter("serving.batches"),
                self._counter("decode.steps"))

    def _outstanding(self) -> bool:
        if (self._gauge("executor.inflight_steps") > 0
                or self._gauge("executor.steps_in_progress") > 0
                or self._gauge("serving.queue_depth") > 0
                or self._gauge("decode.queue_depth") > 0
                or self._gauge("decode.active_slots") > 0):
            return True
        # NAMED serving engines (serving.<name>.queue_depth): the plain
        # aggregate gauge is last-writer-wins across engines, so a named
        # engine's backlog can hide behind another's zero — scan the
        # namespaced gauges too (fleet replicas run one unnamed engine
        # per process; this covers the in-process multi-engine shape)
        for name, inst in trace.metrics().items():
            parts = name.split(".")
            if len(parts) == 3 and parts[0] in ("serving", "decode") \
                    and parts[2] in ("queue_depth", "active_slots"):
                try:
                    if float(inst.value) > 0:
                        return True
                except (TypeError, AttributeError):
                    pass
        return False

    def _alive_anyway(self) -> bool:
        """Live compiles and elastic drains are legitimate long pauses."""
        return (self._gauge("executor.compiles_in_progress") > 0
                or self._gauge("elastic.drain_in_progress") > 0)

    # -- one detection pass --------------------------------------------------
    def tick(self) -> str:
        """Run one detection pass; returns the resulting health state."""
        now = self._now()
        with self._lock:
            progress = self._progress()
            outstanding = self._outstanding()
            if progress != self._last_progress or self._alive_anyway() \
                    or (not outstanding and self._stall_latched):
                # unlatch on real progress — or when the outstanding
                # work itself went away (an aborted/closed engine must
                # not leave a healthy idle process reporting `stalled`
                # forever)
                self._last_progress = progress
                self._last_progress_t = now
                if self._stall_latched:
                    self._stall_latched = False
                    trace.metrics().counter(
                        "watchdog.stall_recoveries").inc()
            stalled = (outstanding
                       and not self._alive_anyway()
                       and (now - self._last_progress_t) >= self.stall_s)
            dump_stall = stalled and not self._stall_latched
            if dump_stall:
                # exactly once per incident: latch until progress resumes
                self._stall_latched = True
                trace.metrics().counter("watchdog.stalls").inc()
            breach_info = self._tick_breach()
            # the state is settled BEFORE the dumps so the bundles record
            # the incident verdict, not the pre-incident one
            if self._stall_latched:
                self.state = "stalled"
            elif self._breach_latched:
                self.state = "breached"
            else:
                self.state = "ok"
            trace.metrics().gauge("watchdog.state").set(
                {"ok": 0, "breached": 1, "stalled": 2}[self.state])
            if dump_stall:
                self.bundles.append(dump_bundle(
                    "stall", diagnostic_dir=self.diagnostic_dir,
                    watchdog_state=self.health(),
                    extra={"no_progress_s":
                           round(now - self._last_progress_t, 3)}))
            if breach_info is not None:
                self.bundles.append(dump_bundle(
                    "breach", diagnostic_dir=self.diagnostic_dir,
                    watchdog_state=self.health(), extra=breach_info))
            # (the dump's own `incident` wide event is not a completion
            # record, so it can never read as progress and unlatch the
            # very stall it reported)
            return self.state

    def _tick_breach(self) -> Optional[Dict[str, Any]]:
        """Advance breach detection one window; returns the incident
        info dict when THIS window crossed into a breach (the caller
        dumps the bundle after settling the state), else None."""
        if self.p99_ms <= 0:
            return None
        rec = flight_recorder.recorder()
        total = rec.total
        # copy only the tail written since the last window — a full
        # snapshot would copy up to the whole ring under the recorder's
        # lock every tick, contending with the step path
        recs = rec.snapshot(last=max(0, total - self._last_seen_seq))
        fresh = [r for r in recs
                 if r.get("kind") == "request"
                 and r.get("outcome") == "ok"
                 and r.get("seq", -1) >= self._last_seen_seq
                 and r.get("latency_us") is not None]
        self._last_seen_seq = total
        if not fresh:
            # traffic stopped: a breach cannot be sustained with no
            # samples — reset the streak and clear the state
            self._breach_count = 0
            self._breach_latched = False
            return None
        lats = sorted(r["latency_us"] for r in fresh)
        p99 = lats[min(len(lats) - 1, int(0.99 * len(lats)))] / 1e3
        trace.metrics().gauge("watchdog.window_p99_ms").set(p99)
        if p99 >= self.p99_ms:
            self._breach_count += 1
            if self._breach_count >= self.breach_windows \
                    and not self._breach_latched:
                self._breach_latched = True
                trace.metrics().counter("watchdog.breaches").inc()
                return {"window_p99_ms": round(p99, 3),
                        "threshold_ms": self.p99_ms,
                        "windows": self._breach_count}
        else:
            self._breach_count = 0
            self._breach_latched = False
        return None

    # -- health --------------------------------------------------------------
    def health(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "status": self.state,
                "running": self._thread is not None
                and self._thread.is_alive(),
                "stall_latched": self._stall_latched,
                "last_progress_age_s": round(
                    self._now() - self._last_progress_t, 3),
                "breach_windows": self._breach_count,
                "bundles": len(self.bundles),
            }

    # -- lifecycle ------------------------------------------------------------
    def start(self) -> "SloWatchdog":
        if self._thread is None or not self._thread.is_alive():
            self._stop_ev.clear()
            self._thread = threading.Thread(
                target=self._run, name="slo-watchdog", daemon=True)
            self._thread.start()
        return self

    def _run(self):
        while not self._stop_ev.wait(self.interval_s):
            try:
                self.tick()
            except Exception:       # noqa: BLE001 — the watchdog must
                trace.metrics().counter(  # outlive its own bugs
                    "watchdog.tick_errors").inc()

    def stop(self) -> None:
        self._stop_ev.set()
        t = self._thread
        if t is not None:
            t.join(timeout=10)
        self._thread = None


# ---------------------------------------------------------------------------
# module-level lifecycle (flag-driven, one watchdog per process)
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_watchdog: Optional[SloWatchdog] = None
_prev_excepthook = None
_last_oom_bundle_t = [0.0]


def get() -> Optional[SloWatchdog]:
    return _watchdog


def start(**kwargs) -> SloWatchdog:
    """Start (or return) the process watchdog; installs the crash
    excepthook alongside."""
    global _watchdog
    with _lock:
        if _watchdog is None:
            _watchdog = SloWatchdog(**kwargs)
            _watchdog.start()
            install_crash_hook()
        return _watchdog


def stop() -> None:
    global _watchdog
    with _lock:
        wd, _watchdog = _watchdog, None
    if wd is not None:
        wd.stop()
    uninstall_crash_hook()


def health() -> Dict[str, Any]:
    """The /healthz payload — ``{"status": "ok"}`` when no watchdog
    runs (liveness alone), the watchdog's full state otherwise."""
    wd = _watchdog
    if wd is None:
        return {"status": "ok", "running": False}
    return wd.health()


def apply_flags() -> None:
    """Reconcile with FLAGS_watchdog* (called from core.set_flags and
    the fluid import when the env opts in)."""
    if _flag("watchdog", False):
        if _watchdog is None:
            start()
    elif _watchdog is not None:
        stop()


# -- crash / OOM hooks --------------------------------------------------------

def _crash_hook(exc_type, exc, tb):
    if not issubclass(exc_type, (KeyboardInterrupt, SystemExit)):
        if exc is not None and exc.__traceback__ is None:
            exc = exc.with_traceback(tb)
        dump_bundle("crash", exc=exc,
                    diagnostic_dir=getattr(_watchdog, "diagnostic_dir",
                                           None))
    prev = _prev_excepthook or sys.__excepthook__
    prev(exc_type, exc, tb)


def install_crash_hook() -> None:
    """Chain ``sys.excepthook``: an uncaught exception dumps a crash
    bundle before the previous hook reports it.  Idempotent."""
    global _prev_excepthook
    if sys.excepthook is not _crash_hook:
        _prev_excepthook = sys.excepthook
        sys.excepthook = _crash_hook


def uninstall_crash_hook() -> None:
    global _prev_excepthook
    if sys.excepthook is _crash_hook:
        sys.excepthook = _prev_excepthook or sys.__excepthook__
        _prev_excepthook = None


def notify_oom(exc: BaseException, min_interval_s: float = 30.0) -> str:
    """RESOURCE_EXHAUSTED hook (called by device_stats.attach_oom_report
    on every OOM): a running watchdog dumps an ``oom`` bundle, rate
    limited so an OOM retry loop produces one bundle per incident
    window, not one per attempt.  Returns the bundle path ('' when not
    armed or rate-limited)."""
    if _watchdog is None:
        return ""
    now = time.monotonic()
    if now - _last_oom_bundle_t[0] < min_interval_s:
        return ""
    _last_oom_bundle_t[0] = now
    path = dump_bundle("oom", exc=exc,
                       diagnostic_dir=_watchdog.diagnostic_dir)
    if path:
        _watchdog.bundles.append(path)
    return path
