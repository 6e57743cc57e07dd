"""Profiler facade: host observability plane + optional JAX/XLA profiler.

Reference: python/paddle/fluid/profiler.py context manager ->
platform/profiler.cc RAII spans + CUPTI device tracer (SURVEY §5 tracing).
TPU-native, two tiers:

* the framework-native host plane (fluid/trace.py) — always available:
  per-op dispatch spans, compile-cache events, step timing, the sorted
  calls/total/min/max/ave summary, Chrome-trace export;
* ``jax.profiler`` XPlane traces (TensorBoard / Perfetto) for device-side
  op time — best effort: on backends/headless setups where
  ``start_trace`` raises, the profiler DEGRADES to host-only tracing
  instead of crashing the training run.  Only the profiler's DEVICE half
  is started: with its host half on, the TPU runtime logs every tile it
  transposes while staging a batch (4.3 M events for one 154 MB image
  batch, PERF.md section 3) and the traced steps slow tenfold.

``stop_profiler`` prints two tables.  Where the device trace holds ``XLA
Ops``: **device time by Program op** (``device_stats.device_time_by_op``:
every device instruction charged to the Program op whose scope its HLO
metadata carries), per step.  Below it, always: **host time by span**, the host plane's
summary; its ``cat="op"`` rows are each op's lowering time, taken once per
compile and not per step: what explains a long trace + compile, never
device time.

``RecordEvent`` spans land in the host plane; the exported host timeline's
metadata carries the wall clock of its epoch (``epoch_unix_ns``), and the
device trace that of its session start (``profile_start_time``), so the two
lie on one clock.
"""
from __future__ import annotations

import contextlib
import glob
import os
import sys
import time

import jax

from . import device_stats, trace

_DEFAULT_PATH = "/tmp/paddle_tpu_profile"

# what the host plane's table is, said in its heading: not device time
HOST_TABLE_TITLE = ("Host time by span; an op's row is its lowering time, "
                    "once per compile, not per step")

# whether a jax.profiler trace session is live (start/stop must pair)
_jax_trace_active = False


def _start_jax_trace(profile_path: str) -> bool:
    """Best-effort device trace.  Headless/CPU-CI/odd backends can make
    ``start_trace`` raise — degrade to the host plane, never propagate."""
    global _jax_trace_active
    if _jax_trace_active:
        return True
    try:
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 0       # the device's half only: see
        options.python_tracer_level = 0     # the module docstring
        jax.profiler.start_trace(profile_path, profiler_options=options)
        _jax_trace_active = True
        return True
    except Exception as e:          # noqa: BLE001 — degrade by contract
        print(f"paddle_tpu.profiler: device trace unavailable "
              f"({type(e).__name__}: {e}); continuing with host-only "
              f"tracing", file=sys.stderr)
        return False


def _stop_jax_trace() -> bool:
    """True where a live device trace was stopped (and so written)."""
    global _jax_trace_active
    if not _jax_trace_active:
        return False
    _jax_trace_active = False
    try:
        jax.profiler.stop_trace()
        return True
    except Exception:               # noqa: BLE001 — stop must not raise
        return False


def device_op_table(profile_path: str, sorted_key=None):
    """The device-time-by-Program-op table of the newest device trace under
    ``profile_path``, or None where there is none or it holds no device
    plane with ``XLA Ops`` (the CPU's)."""
    files = glob.glob(os.path.join(profile_path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        return None
    try:
        table = device_stats.device_time_by_op(
            max(files, key=os.path.getmtime))
    except Exception as e:          # noqa: BLE001 — a report, not the run
        print(f"paddle_tpu.profiler: device trace unreadable "
              f"({type(e).__name__}: {e})", file=sys.stderr)
        return None
    if table is None:
        return None
    return device_stats.format_device_ops(table, sorted_key)


def start_profiler(state="All", tracer_option="Default",
                   profile_path=_DEFAULT_PATH):
    """Begin profiling: host plane on, device trace if the backend
    supports it (reference start_profiler semantics, no-crash)."""
    trace.enable()
    _start_jax_trace(profile_path)


def stop_profiler(sorted_key=None, profile_path=_DEFAULT_PATH):
    """Stop profiling; print device time by Program op (where the device
    trace holds any), then the host plane's sorted summary, and export the
    host timeline next to the device trace."""
    if _stop_jax_trace():
        table = device_op_table(profile_path, sorted_key)
        if table:
            print(table)
    if trace.get_events():
        out = os.path.join(profile_path, "paddle_tpu_timeline.json")
        trace.export_chrome_trace(out)
        print(trace.summary_table(sorted_key or "total",
                                  title=HOST_TABLE_TITLE))
        print(f"[profiler] host timeline: {out} "
              f"(chrome://tracing / ui.perfetto.dev)")


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path=_DEFAULT_PATH):
    was_enabled = trace.enabled()
    start_profiler(state, profile_path=profile_path)
    t0 = time.time()
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)
        print(f"[profiler] trace under {profile_path} "
              f"(wall {time.time() - t0:.3f}s); device view: tensorboard "
              f"--logdir {profile_path}")
        if not was_enabled:
            trace.disable()         # restore caller's gating


class RecordEvent:
    """platform/profiler.h:127 RecordEvent analog — host span annotation.
    Emits into the host plane always (when enabled) and into the device
    trace when one is live; TraceAnnotation failures never propagate."""

    def __init__(self, name):
        self.name = name
        self._t0 = None
        self._ann = None

    def __enter__(self):
        if trace.enabled():
            self._t0 = trace.now()
        try:
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        except Exception:           # noqa: BLE001 — annotation best-effort
            self._ann = None
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            except Exception:       # noqa: BLE001
                pass
        if self._t0 is not None:
            trace.complete(self.name, self._t0, cat="annotation")
            self._t0 = None
        return False


record_event = RecordEvent


@contextlib.contextmanager
def cuda_profiler(*a, **k):  # API parity; no CUDA on TPU
    yield


def reset_profiler():
    """Clear accumulated profile events (profiler.py reset_profiler):
    stops any live device trace and empties the host event buffer."""
    _stop_jax_trace()
    trace.reset()


def start_gperf_profiler():
    """dygraph/profiler.py analog — gperftools has no TPU role; the JAX
    trace profiler (start_profiler) is the supported path."""
    start_profiler()


def stop_gperf_profiler():
    stop_profiler()
