"""Device truth for compiled executables: measured FLOPs + HBM footprint.

Reference: the reference stack's per-kernel stats and
memory/allocation/ accounting give device-side answers the host plane
cannot (PAPER.md layers 1-2): how many FLOPs does this executable
*actually* issue, and how much device memory does it *actually* need?
TPU-native, the same truth comes from XLA itself — an AOT
``jitted.lower(...).compile()`` yields ``cost_analysis()`` (measured
FLOPs / bytes accessed, the denominator-free half of MFU) and
``memory_analysis()`` (argument / output / temp / generated-code bytes:
the executable's peak HBM footprint).

What lives here:

* :func:`capture` — lower + compile a jitted callable against example
  avals (``jax.ShapeDtypeStruct`` trees, so donated/deleted buffers are
  never touched) and normalise both analyses into one flat dict.  The
  AOT compile is a real SECOND compile of the program (the jit call's
  executable is not reused; only the persistent compilation cache or a
  repeated capture shortcut it), so its cost — observed in
  ``xla.analysis_seconds`` — is why capture is opt-in.
* :func:`capture_enabled` — the gate.  ``FLAGS_device_cost_analysis``:
  ``auto`` (default: follows tracing), or an explicit true/false —
  serving /metrics alone never opts a run into the extra compile.
  When off, the executor pays one flag read per compile MISS — nothing
  per step.
* :func:`publish` / :func:`unpublish` — per-executable
  ``xla.mem.exe.<label>.*`` / ``xla.cost.exe.<label>.*`` gauges, removed
  again when the executor's LRU evicts the executable.
* :func:`attach_oom_report` — on a RESOURCE_EXHAUSTED compile/run error
  the executor attaches the top footprints (structured, on
  ``exc.device_footprints``, plus a stderr table) so OOM forensics can
  name the biggest executables instead of guessing.
* :func:`sds_tree` — pytree -> ShapeDtypeStruct twin (shared with
  bench.py's ``mfu_measured`` capture of its raw jitted step fns).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import trace

__all__ = [
    "capture_enabled", "capture", "sds_tree", "publish", "unpublish",
    "peak_bytes_of", "flops_of", "is_oom", "attach_oom_report",
    "format_footprints", "live_footprints",
]

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


def capture_enabled() -> bool:
    """FLAGS_device_cost_analysis gate: explicit bool wins; ``auto``
    follows TRACING only.  The capture pays a second (only partially
    cached) XLA compile per compile miss, so merely serving /metrics
    must not opt a production run into it — runs that want footprint
    gauges on the scrape without tracing set the flag to True
    explicitly."""
    from . import core
    v = core.get_flag("device_cost_analysis", "auto")
    if isinstance(v, bool):
        return v
    s = str(v).strip().lower()
    if s in _TRUE:
        return True
    if s in _FALSE:
        return False
    return trace.enabled()


def sds_tree(tree):
    """ShapeDtypeStruct twin of a pytree of arrays — safe to lower
    against even when the originals were donated (shape/dtype survive
    deletion; buffer contents are never read)."""
    import jax

    def _sds(a):
        if isinstance(a, jax.ShapeDtypeStruct):
            return a
        dt = getattr(a, "dtype", None)
        if dt is None:
            a = np.asarray(a)
            dt = a.dtype
        return jax.ShapeDtypeStruct(tuple(np.shape(a)), dt)

    return jax.tree_util.tree_map(_sds, tree)


def _cost_dict(cost) -> Dict[str, Any]:
    """cost_analysis() returns a dict on new jax, a 1-list of dicts on
    older ones, or None on backends without the query."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    return dict(cost) if isinstance(cost, dict) else {}


def _tree_bytes(tree) -> int:
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        try:
            total += int(np.prod(np.shape(leaf)) or 1) \
                * np.dtype(getattr(leaf, "dtype", "f4")).itemsize
        except (TypeError, ValueError):
            pass
    return total


def capture(jitted, example_args: Sequence,
            label: Optional[str] = None,
            n_devices: int = 1) -> Optional[Dict[str, Any]]:
    """Lower + compile ``jitted`` at ``example_args`` (arrays or
    ShapeDtypeStruct trees) and return the merged device-truth record::

        {"flops", "bytes_accessed",
         "argument_bytes", "output_bytes", "temp_bytes", "alias_bytes",
         "generated_code_bytes", "peak_bytes", "per_device_peak_bytes",
         "mesh_devices", "analysis_seconds"}

    Under a sharded (SPMD) compile, XLA's analyses describe the
    PER-DEVICE program — pass ``n_devices`` (the plan's mesh size) so the
    record says both what one device holds (``per_device_peak_bytes``,
    the HBM-fit question) and how wide the executable runs
    (``mesh_devices``).

    Returns None when the callable has no ``lower`` (checkify wrappers,
    custom step builders) or the backend refuses the analysis — capture
    degrades, never raises into the training loop."""
    if not hasattr(jitted, "lower"):
        return None
    m = trace.metrics()
    t0 = time.perf_counter()
    try:
        examples = [sds_tree(a) for a in example_args]
        compiled = jitted.lower(*examples).compile()
    except Exception:                   # noqa: BLE001 — capture degrades
        m.counter("xla.analysis_errors").inc()
        return None
    cost = {}
    try:
        cost = _cost_dict(compiled.cost_analysis())
    except Exception:                   # noqa: BLE001
        m.counter("xla.analysis_errors").inc()
    mem = None
    try:
        mem = compiled.memory_analysis()
    except Exception:                   # noqa: BLE001
        m.counter("xla.analysis_errors").inc()
    info: Dict[str, Any] = {
        "flops": float(cost.get("flops", 0.0) or 0.0),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0) or 0.0),
    }
    if mem is not None:
        for field, key in (("argument_size_in_bytes", "argument_bytes"),
                           ("output_size_in_bytes", "output_bytes"),
                           ("temp_size_in_bytes", "temp_bytes"),
                           ("alias_size_in_bytes", "alias_bytes"),
                           ("generated_code_size_in_bytes",
                            "generated_code_bytes")):
            info[key] = int(getattr(mem, field, 0) or 0)
    else:
        # backend without CompiledMemoryStats: argument bytes from the
        # example avals is still real truth; temp/code are unknowable
        info["argument_bytes"] = sum(_tree_bytes(a) for a in example_args)
        info["output_bytes"] = 0
        info["temp_bytes"] = 0
        info["alias_bytes"] = 0
        info["generated_code_bytes"] = 0
    info["peak_bytes"] = max(
        0,
        info["argument_bytes"] + info["output_bytes"] + info["temp_bytes"]
        + info["generated_code_bytes"] - info["alias_bytes"])
    # per-shard HBM truth: the analysis above is already per-device (one
    # SPMD program per chip); record it under the explicit name the
    # sharding plane's consumers (bench --sharding, OOM
    # forensics) read, beside the mesh width
    info["mesh_devices"] = max(1, int(n_devices or 1))
    info["per_device_peak_bytes"] = info["peak_bytes"]
    dt = time.perf_counter() - t0
    info["analysis_seconds"] = round(dt, 4)
    m.histogram("xla.analysis_seconds").observe(dt)
    if label:
        info["label"] = str(label)
    return info


def flops_of(jitted, example_args: Sequence) -> float:
    """Measured FLOPs of one executable (0.0 when unavailable) — what
    bench.py sums across its step's programs for ``mfu_measured``."""
    info = capture(jitted, example_args)
    return float(info["flops"]) if info else 0.0


def peak_bytes_of(info: Dict[str, Any]) -> int:
    return int(info.get("peak_bytes", 0) or 0)


# ---------------------------------------------------------------------------
# gauge surface
# ---------------------------------------------------------------------------

_MEM_FIELDS = ("peak_bytes", "argument_bytes", "output_bytes", "temp_bytes",
               "per_device_peak_bytes", "mesh_devices")
_COST_FIELDS = ("flops", "bytes_accessed")

# process-wide label -> peak bytes of every published executable.  The
# xla.mem.lru_* aggregate gauges derive from THIS map, not from any one
# Executor's private footprint dict — two executors (hapi's internal one
# plus a user's) would otherwise last-writer-win each other's totals,
# and closing a scratch executor would zero the aggregates while the
# main one still holds resident executables.
_agg_lock = threading.Lock()
_agg: Dict[str, float] = {}


def publish(label: str, info: Dict[str, Any]) -> None:
    """Per-executable gauges (``xla.mem.exe.<label>.<field>`` /
    ``xla.cost.exe.<label>.<field>``) + the process-wide aggregates."""
    m = trace.metrics()
    for f in _MEM_FIELDS:
        m.gauge(f"xla.mem.exe.{label}.{f}").set(float(info.get(f, 0) or 0))
    for f in _COST_FIELDS:
        m.gauge(f"xla.cost.exe.{label}.{f}").set(float(info.get(f, 0) or 0))
    with _agg_lock:
        _agg[label] = float(info.get("peak_bytes", 0) or 0)
    _refresh_aggregates()


def unpublish(label: str) -> None:
    m = trace.metrics()
    for f in _MEM_FIELDS:
        m.remove(f"xla.mem.exe.{label}.{f}")
    for f in _COST_FIELDS:
        m.remove(f"xla.cost.exe.{label}.{f}")
    with _agg_lock:
        _agg.pop(label, None)
    _refresh_aggregates()


def live_footprints() -> List[Dict[str, Any]]:
    """Every published (still-resident) executable as
    ``{"label", "peak_bytes"}`` rows, biggest first — what a diagnostic
    bundle embeds as the device-memory picture at incident time."""
    with _agg_lock:
        items = sorted(_agg.items(), key=lambda kv: kv[1], reverse=True)
    return [{"label": k, "peak_bytes": int(v)} for k, v in items]


def _refresh_aggregates() -> None:
    """Aggregate footprint across every live executable in the process:
    how much HBM the resident executables claim in total and at worst —
    the signal OOM forensics and eviction tuning read."""
    with _agg_lock:
        peaks = list(_agg.values())
    m = trace.metrics()
    m.gauge("xla.mem.lru_executables").set(len(peaks))
    m.gauge("xla.mem.lru_total_peak_bytes").set(float(sum(peaks)))
    m.gauge("xla.mem.largest_peak_bytes").set(float(max(peaks, default=0)))


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

def is_oom(exc: BaseException) -> bool:
    text = f"{type(exc).__name__}: {exc}"
    return ("RESOURCE_EXHAUSTED" in text
            or "out of memory" in text.lower()
            or "hbm" in text.lower() and "exceed" in text.lower())


def format_footprints(footprints: Sequence[Dict[str, Any]],
                      top: int = 5) -> str:
    rows = sorted(footprints, key=peak_bytes_of, reverse=True)[:top]
    lines = [f"{'executable':<24s} {'peak':>10s} {'args':>10s} "
             f"{'temp':>10s} {'out':>10s}"]
    for r in rows:
        lines.append(
            f"{str(r.get('label', '?'))[:24]:<24s} "
            f"{_fmt_bytes(r.get('peak_bytes', 0)):>10s} "
            f"{_fmt_bytes(r.get('argument_bytes', 0)):>10s} "
            f"{_fmt_bytes(r.get('temp_bytes', 0)):>10s} "
            f"{_fmt_bytes(r.get('output_bytes', 0)):>10s}")
    return "\n".join(lines)


def _fmt_bytes(n) -> str:
    n = float(n or 0)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}GiB"                # pragma: no cover - loop returns


def attach_oom_report(exc: BaseException,
                      footprints: Sequence[Dict[str, Any]],
                      top: int = 5) -> BaseException:
    """Attach OOM forensics to a RESOURCE_EXHAUSTED error: the
    structured top footprints land on ``exc.device_footprints`` (OOM
    handlers can act on them) and a rendered table goes to stderr (on
    py3.11+ it would ride ``add_note``; 3.10 gets the attribute + print).
    The exception object is returned, never replaced — the original
    traceback and type survive."""
    rows = sorted(footprints, key=peak_bytes_of, reverse=True)[:top]
    try:
        exc.device_footprints = rows
    except Exception:                   # noqa: BLE001 — slotted exc types
        pass
    report = ("paddle_tpu: device OOM — largest live executables by "
              "XLA-reported footprint:\n" + format_footprints(rows, top))
    note = getattr(exc, "add_note", None)
    if callable(note):                  # pragma: no cover - py3.11+
        try:
            note(report)
        except Exception:               # noqa: BLE001
            pass
    import sys
    print(report, file=sys.stderr)
    trace.metrics().counter("xla.oom_errors").inc()
    if trace.enabled():
        trace.instant("device_oom", cat="compile",
                      args={"top": [
                          {"label": r.get("label"),
                           "peak_bytes": r.get("peak_bytes")}
                          for r in rows]})
    try:
        # RESOURCE_EXHAUSTED hook for the SLO watchdog: a running
        # watchdog freezes the evidence (footprints now ride on exc)
        # into an `oom` diagnostic bundle — rate-limited there
        from . import watchdog
        watchdog.notify_oom(exc)
    except Exception:                   # noqa: BLE001 — forensics never
        pass                            # worsen the primary error
    return exc
