"""Platform layer: Place/device identity + global flags.

Reference: paddle/fluid/platform/place.h:26-62 (CPUPlace/CUDAPlace variants),
device_context.h:60-568 (per-device handle bundles), flags.cc (runtime gflags).
TPU-native: a Place names a JAX device; there is no per-place stream/handle
bundle because XLA/PJRT owns streams and HBM — the DeviceContext analog is
just the resolved `jax.Device` plus the process-wide compilation cache that
executor.py maintains.
"""
from __future__ import annotations

import os as _os
from typing import Dict, Optional

# stdlib-only module; single source of truth for trace env parsing and the
# default timeline path (import order with this package is cycle-safe:
# trace only touches core lazily, inside functions)
from . import trace as _trace


class Place:
    device_kind = "cpu"
    device_id = 0

    def jax_device(self):
        import jax
        devs = [d for d in jax.devices() if self._match(d)]
        if not devs:
            # fall back to whatever the default backend offers (e.g. running
            # TPU-targeted code on the CPU backend in tests)
            devs = jax.devices()
        return devs[min(self.device_id, len(devs) - 1)]

    def _match(self, d) -> bool:
        return True

    def __eq__(self, other):
        return (type(self) is type(other)
                and self.device_id == getattr(other, "device_id", 0))

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"


class CPUPlace(Place):
    device_kind = "cpu"

    def _match(self, d):
        return d.platform == "cpu"


class TPUPlace(Place):
    """The CUDAPlace analog (place.h:62): names one accelerator chip."""
    device_kind = "tpu"

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def _match(self, d):
        return d.platform != "cpu"


# fluid alias: code written against the reference uses CUDAPlace; on this
# framework it resolves to the accelerator (TPU) as well.
CUDAPlace = TPUPlace


class TPUPinnedPlace(CPUPlace):
    """Host staging buffers; XLA handles pinning internally."""


def is_compiled_with_tpu() -> bool:
    import jax
    return any(d.platform != "cpu" for d in jax.devices())


def is_compiled_with_cuda() -> bool:
    return False


def get_device_count() -> int:
    import jax
    return jax.device_count()


# ---------------------------------------------------------------------------
# global flags (platform/flags.cc analog; settable from Python like
# global_value_getter_setter.cc). Only flags meaningful on TPU are kept.
# ---------------------------------------------------------------------------
_FLAGS: Dict[str, object] = {
    "check_nan_inf": False,          # per-fetch NaN scan (operator.cc:1149 analog)
    "benchmark": False,
    "paddle_num_threads": 1,
    "use_donated_buffers": True,     # buffer donation == inplace/GC knobs
    "jit_cache_size": 128,
    "deterministic": False,
    # TPU hardware RNG (XLA RngBitGenerator) instead of threefry for dropout
    # and *_random ops.  The reference uses curand Philox per device
    # (platform/ *generator*); counter-based threefry spends vector-unit
    # work per mask element, so hardware RNG is the default (the cost
    # difference is not measured on this code).  Set
    # FLAGS_deterministic_rng=True for threefry (bit-reproducible across
    # backends, like cudnn_deterministic in platform/flags.cc:98).
    "deterministic_rng": False,
    # 64-bit integer feeds on device.  Off by default (jax x64 mode also
    # promotes float64, hurting TPU perf); the framework's CTR paths keep
    # full-width uint64 feasigns HOST-side (PS/Box tiers translate ids to
    # indices in numpy), so device programs rarely need 64-bit ints.  The
    # executor raises on silently-truncating feeds instead of corrupting.
    "enable_x64": False,
    # observability plane (fluid/trace.py): host-side structured tracing.
    # Env defaults let `FLAGS_enable_trace=1 python train.py` produce a
    # chrome://tracing timeline at FLAGS_trace_path with no code changes;
    # trace.enable()/disable()/set_path() keep these mirror values in sync.
    "enable_trace": _trace.enabled(),
    "trace_path": _trace.get_path(),
    # recompile hygiene (fluid/compile_cache.py).  shape_bucketing pads
    # ragged leading batch dims up to a bucket edge so a tail batch reuses
    # a cached executable; bucket_edges=None means powers of two.  The
    # persistent cache dir survives process restarts (jax compilation
    # cache + program-level index).  Env defaults let
    # `FLAGS_shape_bucketing=1 python train.py` opt in with no code change.
    "shape_bucketing": _os.environ.get(
        "FLAGS_shape_bucketing", "").strip().lower() in _trace._TRUE_STRINGS,
    "shape_bucket_edges": _os.environ.get("FLAGS_shape_bucket_edges") or None,
    "persistent_cache_dir": _os.environ.get(
        "FLAGS_persistent_cache_dir") or None,
    # in-memory executable cache bound (executor LRU; 0 disables eviction)
    "executor_cache_capacity": int(_os.environ.get(
        "FLAGS_executor_cache_capacity", "128")),
    # recompile-storm warning: N compile misses within the window (seconds)
    # emit a trace event with shape/bucket attribution; 0 disables
    "recompile_warn_threshold": int(_os.environ.get(
        "FLAGS_recompile_warn_threshold", "8")),
    "recompile_warn_window": float(_os.environ.get(
        "FLAGS_recompile_warn_window", "60")),
    # async step pipeline (fluid/async_pipeline.py, docs/performance.md).
    # max_inflight_steps bounds how many dispatched steps may be
    # outstanding before the runner blocks on the oldest one's fetches
    # (also caps the Prefetcher's device-staged queue at inflight+1);
    # steps_per_dispatch=K compiles a lax.scan over K stacked microbatches
    # so one Python dispatch drives K device steps.
    "max_inflight_steps": int(_os.environ.get(
        "FLAGS_max_inflight_steps", "2")),
    "steps_per_dispatch": int(_os.environ.get(
        "FLAGS_steps_per_dispatch", "1")),
    # elastic checkpoint plane (fluid/checkpoint.py, docs/checkpointing.md).
    # keep_last bounds retention (newest K checkpoints); keep_every
    # additionally pins every Nth step (0 = off); async routes snapshot
    # writes to a background thread so the step window never blocks;
    # shard_bytes caps per-shard file size.
    "checkpoint_keep_last": int(_os.environ.get(
        "FLAGS_checkpoint_keep_last", "3")),
    "checkpoint_keep_every": int(_os.environ.get(
        "FLAGS_checkpoint_keep_every", "0")),
    "checkpoint_async": _os.environ.get(
        "FLAGS_checkpoint_async", "1").strip().lower()
        in _trace._TRUE_STRINGS,
    "checkpoint_shard_bytes": int(_os.environ.get(
        "FLAGS_checkpoint_shard_bytes", str(64 << 20))),
    # live metrics export plane (fluid/metrics_export.py,
    # docs/observability.md "Goodput & device memory").  metrics_port
    # serves /metrics (Prometheus text) + /goodput (JSON) on a daemon
    # thread (0 = off); the snapshot path/interval append periodic JSONL
    # metrics rows for headless runs.  Both are exact no-ops when unset.
    "metrics_port": int(_os.environ.get("FLAGS_metrics_port", "0") or 0),
    # bind address for the export server.  Localhost by default: the
    # registry names executables/checkpoints — serving beyond the host
    # is an explicit opt-in (FLAGS_metrics_host=0.0.0.0 for fleet
    # scrapers).
    "metrics_host": _os.environ.get("FLAGS_metrics_host", "127.0.0.1"),
    "metrics_snapshot_path": _os.environ.get(
        "FLAGS_metrics_snapshot_path") or None,
    "metrics_snapshot_interval_s": float(_os.environ.get(
        "FLAGS_metrics_snapshot_interval_s", "60") or 60),
    # device truth (fluid/device_stats.py): AOT cost/memory analysis of
    # every freshly compiled executable.  "auto" = follows tracing;
    # True/False force it.  The capture pays a second (only partially
    # cached) XLA compile per compile MISS and nothing per step — which
    # is why serving /metrics alone does NOT opt a run in.
    "device_cost_analysis": _os.environ.get(
        "FLAGS_device_cost_analysis", "auto"),
    # serving plane (paddle_tpu/serving/, docs/serving.md).  max_batch
    # caps the rows per coalesced device batch; max_wait_us is the
    # batch-formation deadline (dispatch a partial batch rather than
    # hold a request longer); queue_depth bounds the admission queue
    # (a full queue REJECTS at submit — backpressure, not OOM);
    # default_deadline_ms rejects requests that queue longer than their
    # deadline (0 = no deadline unless the request carries one).
    "serving_max_batch": int(_os.environ.get(
        "FLAGS_serving_max_batch", "32")),
    "serving_max_wait_us": int(_os.environ.get(
        "FLAGS_serving_max_wait_us", "2000")),
    "serving_queue_depth": int(_os.environ.get(
        "FLAGS_serving_queue_depth", "256")),
    "serving_default_deadline_ms": float(_os.environ.get(
        "FLAGS_serving_default_deadline_ms", "0") or 0),
    # serving fleet (paddle_tpu/serving/fleet.py, docs/serving.md
    # "Serving fleet"): the router polls each replica's compact /stats
    # every scrape_interval_s; missed_scrapes consecutive failed polls
    # eject an unreachable replica (a stalled/breached /healthz verdict
    # ejects on the FIRST scrape that carries it)
    "fleet_scrape_interval_s": float(_os.environ.get(
        "FLAGS_fleet_scrape_interval_s", "1.0") or 1.0),
    "fleet_missed_scrapes": int(_os.environ.get(
        "FLAGS_fleet_missed_scrapes", "3") or 3),
    # rolling window for the goodput.ratio gauge and /goodput (seconds;
    # 0 = whole run).  A bounded default keeps scrape cost O(window) on
    # long traced runs: the live accumulator prunes intervals that can
    # no longer enter a window, so attribution never re-sweeps hours of
    # history per scrape.  Whole-run attribution stays available
    # explicitly (goodput.snapshot(window_s=0) / attribute_events on an
    # exported timeline).
    "goodput_window_s": float(_os.environ.get(
        "FLAGS_goodput_window_s", "600") or 600),
    # forensic plane (fluid/flight_recorder.py + fluid/watchdog.py,
    # docs/observability.md "Flight recorder & post-mortems").  The
    # flight recorder is a bounded ring of wide events (one per step /
    # served request) that runs even with tracing OFF; the watchdog is
    # a daemon that detects stalled progress / sustained p99 breach /
    # crash+OOM and dumps one atomic diagnostic bundle per incident
    # into diagnostic_dir (tools/diagnose.py renders them).
    "flight_recorder": _os.environ.get(
        "FLAGS_flight_recorder", "1").strip().lower()
        in _trace._TRUE_STRINGS,
    "flight_recorder_events": int(_os.environ.get(
        "FLAGS_flight_recorder_events", "4096") or 4096),
    "watchdog": _os.environ.get(
        "FLAGS_watchdog", "").strip().lower() in _trace._TRUE_STRINGS,
    "watchdog_interval_s": float(_os.environ.get(
        "FLAGS_watchdog_interval_s", "1.0") or 1.0),
    # stalled = work outstanding (inflight / step-in-progress / serving
    # queue) with zero completions for this long; live compiles and
    # elastic drains count as liveness so a long legit XLA compile
    # never false-positives
    "watchdog_stall_s": float(_os.environ.get(
        "FLAGS_watchdog_stall_s", "30") or 30),
    # sustained-p99 breach: threshold in ms (0 = off) held for N
    # consecutive watchdog windows
    "watchdog_p99_ms": float(_os.environ.get(
        "FLAGS_watchdog_p99_ms", "0") or 0),
    "watchdog_breach_windows": int(_os.environ.get(
        "FLAGS_watchdog_breach_windows", "3") or 3),
    "diagnostic_dir": _os.environ.get("FLAGS_diagnostic_dir") or None,
    # how many trailing trace events a bundle embeds
    "diagnostic_trace_tail": int(_os.environ.get(
        "FLAGS_diagnostic_trace_tail", "5000") or 5000),
    # chaos/robustness plane (distributed/faultline.py + ps/rpc.py +
    # serving/fleet.py, docs/robustness.md).  faultline installs a
    # seeded socket-level fault-injection schedule (JSON spec or @path;
    # replica subprocesses inherit it via the env var).  The rpc_* knobs
    # bound the hardened framing: max_frame_bytes rejects garbage/
    # hostile length prefixes before allocation, retries/backoff_ms
    # shape the client retry policy (exponential + jitter), and
    # dedup_window sizes the server's req_id window that makes retried
    # non-idempotent pushes exactly-once.  fleet_breaker_* shape the
    # per-replica circuit breaker (consecutive transport failures to
    # open; cooldown before the half-open probe; 0 failures disables).
    "faultline": _os.environ.get("FLAGS_faultline") or None,
    "rpc_max_frame_bytes": int(_os.environ.get(
        "FLAGS_rpc_max_frame_bytes", str(1 << 30))),
    "rpc_retries": int(_os.environ.get("FLAGS_rpc_retries", "3")),
    "rpc_backoff_ms": float(_os.environ.get(
        "FLAGS_rpc_backoff_ms", "25")),
    "rpc_dedup_window": int(_os.environ.get(
        "FLAGS_rpc_dedup_window", "1024")),
    "fleet_breaker_failures": int(_os.environ.get(
        "FLAGS_fleet_breaker_failures", "5") or 5),
    "fleet_breaker_cooldown_s": float(_os.environ.get(
        "FLAGS_fleet_breaker_cooldown_s", "3.0") or 3.0),
    # sharded parameter server (distributed/ps/sharded.py,
    # docs/parameter_server.md).  ps_staleness bounds how many async
    # pushes may be outstanding before a pull fences (0 = fully
    # synchronous = bit-parity with the single-table baseline);
    # ps_hot_rows caps each shard's hot RAM tier (0 = untired);
    # ps_snapshot_every takes an incremental snapshot after every N
    # logged mutations (0 = manual snapshots only); ps_wal_fsync forces
    # fsync per WAL record (off: flush to the OS, which survives process
    # SIGKILL — the restart drill — but not machine loss);
    # ps_shard_vnodes sets virtual nodes per shard on the hash ring.
    "ps_staleness": int(_os.environ.get("FLAGS_ps_staleness", "0") or 0),
    "ps_hot_rows": int(_os.environ.get("FLAGS_ps_hot_rows", "0") or 0),
    "ps_snapshot_every": int(_os.environ.get(
        "FLAGS_ps_snapshot_every", "0") or 0),
    "ps_wal_fsync": _os.environ.get(
        "FLAGS_ps_wal_fsync", "0") not in ("0", "", "false", "False"),
    "ps_shard_vnodes": int(_os.environ.get(
        "FLAGS_ps_shard_vnodes", "64") or 64),
    # profile-guided self-tuning runtime (fluid/autotune.py,
    # docs/performance.md "Auto-tuning"): auto_tune arms BOTH surfaces
    # (executor programs tune once per fingerprint on first run; serving
    # engines get a flag-started online tuner, reconciled by
    # autotune.apply_flags on mid-run flips); auto_tune_probe_steps is
    # the probe-window length in real steps; auto_tune_dir re-roots the
    # persisted-config store away from FLAGS_persistent_cache_dir;
    # auto_tune_hbm_budget_mb pins the OOM-rejection budget (0 = ask the
    # backend for bytes_limit); auto_tune_max_candidates bounds the
    # proposal stream per search.
    "auto_tune": _os.environ.get(
        "FLAGS_auto_tune", "0") not in ("0", "", "false", "False"),
    "auto_tune_probe_steps": int(_os.environ.get(
        "FLAGS_auto_tune_probe_steps", "8") or 8),
    "auto_tune_dir": _os.environ.get("FLAGS_auto_tune_dir") or None,
    "auto_tune_hbm_budget_mb": float(_os.environ.get(
        "FLAGS_auto_tune_hbm_budget_mb", "0") or 0),
    "auto_tune_max_candidates": int(_os.environ.get(
        "FLAGS_auto_tune_max_candidates", "16") or 16),
}


def _apply_prng_impl(deterministic):
    """Apply the PRNG choice.  `deterministic=None` (import-time default)
    defers to a JAX_DEFAULT_PRNG_IMPL env override; an explicit set_flags
    call always wins."""
    import os
    if deterministic is None and os.environ.get("JAX_DEFAULT_PRNG_IMPL"):
        return
    impl = "threefry2x32" if deterministic else "rbg"
    try:
        import jax
        jax.config.update("jax_default_prng_impl", impl)
    except Exception as e:                   # noqa: BLE001 — never block import,
        # but NEVER silently: a swallowed error here once left dropout on
        # threefry for a full round
        import sys
        print(f"paddle_tpu: WARNING: could not set PRNG impl {impl!r}: "
              f"{type(e).__name__}: {e} — dropout/random ops will use the "
              f"jax default (threefry)",
              file=sys.stderr)


_apply_prng_impl(None)


def set_flags(flags: Dict[str, object]):
    for k, v in flags.items():
        k = k.removeprefix("FLAGS_")
        _FLAGS[k] = v
        if k == "deterministic_rng":
            _apply_prng_impl(bool(v))
        elif k == "enable_x64":
            import jax
            jax.config.update("jax_enable_x64", bool(v))
        elif k == "enable_trace":
            from . import trace
            (trace.enable if v else trace.disable)()
        elif k == "trace_path":
            from . import trace
            trace.set_path(str(v))
        elif k == "shape_bucket_edges":
            from . import compile_cache
            _FLAGS[k] = compile_cache.normalize_edges(v)
        elif k == "persistent_cache_dir" and v:
            # eagerly wire jax's compilation cache so compiles between this
            # call and the first executor run also persist
            from . import compile_cache
            compile_cache.persistent_cache()
        elif k in ("metrics_port", "metrics_host", "metrics_snapshot_path",
                   "metrics_snapshot_interval_s"):
            # reconcile the export surfaces with the new flag values
            # (start, restart on a changed port/path, or stop on unset)
            from . import metrics_export
            metrics_export.apply_flags()
        elif k in ("flight_recorder", "flight_recorder_events"):
            from . import flight_recorder
            flight_recorder.configure(
                capacity=int(_FLAGS.get("flight_recorder_events", 4096)
                             or 4096),
                enabled=bool(_FLAGS.get("flight_recorder", True)))
        elif k == "watchdog":
            from . import watchdog
            watchdog.apply_flags()
        elif k == "faultline":
            # install/replace/uninstall the fault-injection schedule
            from ..distributed import faultline
            faultline.apply_flags()
        elif k in ("auto_tune", "auto_tune_probe_steps", "auto_tune_dir"):
            # reconcile the self-tuning runtime with the new flag values
            # (start flag-started serving tuners / stop ONLY flag-started
            # ones — the metrics-export reconciliation contract)
            from . import autotune
            autotune.apply_flags()


def get_flags(names):
    if isinstance(names, str):
        names = [names]
    return {n: _FLAGS.get(n.removeprefix("FLAGS_")) for n in names}


def get_flag(name: str, default=None):
    return _FLAGS.get(name, default)


# ---------------------------------------------------------------------------
# crash/stuck diagnostics (platform/init.cc:257 InitGLOG signal-handler
# analog).  The reference installs glog's FailureSignalHandler to dump C++
# stacks on SIGSEGV/SIGABRT; here faulthandler dumps every thread's Python
# stack on fatal signals, and SIGUSR1 gives a live dump for hung runs
# (stuck collective, wedged device) without killing the process.
# ---------------------------------------------------------------------------
_signal_handlers_installed = False


def init_signal_handlers():
    global _signal_handlers_installed
    if _signal_handlers_installed:
        return
    import faulthandler
    import signal
    import sys
    try:
        faulthandler.enable(file=sys.stderr, all_threads=True)
        if hasattr(signal, "SIGUSR1") and hasattr(faulthandler, "register"):
            faulthandler.register(signal.SIGUSR1, file=sys.stderr,
                                  all_threads=True, chain=True)
        _signal_handlers_installed = True
    except (ValueError, OSError, RuntimeError):
        pass        # non-main thread or exotic embedding: run without dumps


class Scope:
    """name -> device array map (framework/scope.h analog, flat)."""

    def __init__(self, parent: Optional["Scope"] = None):
        self._vars: Dict[str, object] = {}
        self.parent = parent

    def var(self, name):
        return self._vars.get(name)

    def find_var(self, name):
        s = self
        while s is not None:
            if name in s._vars:
                return s._vars[name]
            s = s.parent
        return None

    def set_var(self, name, value):
        self._vars[name] = value

    def erase(self, name):
        self._vars.pop(name, None)

    def local_var_names(self):
        return list(self._vars)

    def new_scope(self) -> "Scope":
        return Scope(parent=self)


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


import contextlib


@contextlib.contextmanager
def scope_guard(scope: Scope):
    global _global_scope
    prev = _global_scope
    _global_scope = scope
    try:
        yield
    finally:
        _global_scope = prev


# ---------------------------------------------------------------------------
# custom-op loading (framework.py:5517 load_op_library + op_function_generator
# analog).  TPU-native: a custom op is a lowering-rule plugin —
#   * .py module: calls ops.registry.register_op directly (the first-class
#     path; pallas kernels plug in here too)
#   * .so library: C ABI kernels exposed through jax.pure_callback (host
#     execution — arbitrary native code cannot run ON the TPU; the
#     reference's custom CUDA kernels map to host callbacks or pallas)
# ---------------------------------------------------------------------------

def load_op_library(path: str):
    """Load a custom-op plugin; returns the list of newly registered ops."""
    import importlib.util
    import os as _os
    from ..ops import registry as _registry

    before = set(_registry.all_ops())
    if str(path).endswith(".py"):
        name = f"paddle_tpu_custom_{_os.path.basename(path)[:-3]}"
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    elif str(path).endswith(".so"):
        _load_native_op_library(path)
    else:
        raise ValueError(f"op library must be .py or .so, got {path}")
    new = sorted(set(_registry.all_ops()) - before)
    for t in new:                      # plugin ops sit outside the
        _registry.get_op(t).custom = True   # catalog/grad-audit contract
    return new


def _load_native_op_library(path: str):
    """C-ABI convention: the .so exports `pt_op_names()` returning a
    comma-separated op list, and per op `void <name>_run(const float* in,
    float* out, int64_t n)` — an elementwise f32 kernel wrapped into a
    lowering via jax.pure_callback."""
    import ctypes
    import jax
    import numpy as _np
    from ..ops.registry import register_op, has_op

    lib = ctypes.CDLL(path)
    lib.pt_op_names.restype = ctypes.c_char_p
    names = lib.pt_op_names().decode().split(",")
    for name in [n for n in names if n]:
        fn = getattr(lib, f"{name}_run")
        fn.argtypes = [ctypes.POINTER(ctypes.c_float),
                       ctypes.POINTER(ctypes.c_float), ctypes.c_int64]

        def _host_kernel(x, _fn=fn):
            x = _np.ascontiguousarray(x, _np.float32)
            out = _np.empty_like(x)
            _fn(x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                x.size)
            return out

        if has_op(name):
            continue

        def _lowering(ins, attrs, ctx, _k=_host_kernel):
            import jax.numpy as jnp
            x = ins["X"][0]
            out = jax.pure_callback(
                _k, jax.ShapeDtypeStruct(x.shape, jnp.float32),
                x.astype(jnp.float32))
            return {"Out": [out]}

        # pure_callback has no JVP/transpose rule — never differentiate
        register_op(name, _lowering, differentiable=False)
