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
* Device time by Program op — :func:`op_scope` names the scope
  ``run_block_ops`` opens around every op (label, role, instance; the one
  format, with :func:`parse_scope`), :func:`hlo_op_map` reads an
  executable's optimized HLO text back into instruction -> Program op,
  :func:`remember` / :func:`op_maps` keep what is needed to ask an
  executable for that text on demand (no switch: a compile miss pays one
  :func:`sds_tree`), and :func:`device_time_by_op` joins a profiler
  trace's ``XLA Ops`` with the map.  ``fluid.profiler`` prints the table;
  the benchmark's per-layer metrics read the same rows.
"""
from __future__ import annotations

import os
import re
import threading
import time
from collections import Counter, OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import trace

__all__ = [
    "capture_enabled", "capture", "sds_tree", "publish", "unpublish",
    "peak_bytes_of", "flops_of", "is_oom", "attach_oom_report",
    "format_footprints", "live_footprints",
    "op_scope", "scope_name", "parse_scope", "hlo_op_map", "remember",
    "forget", "op_maps", "device_time_by_op", "format_device_ops",
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


def sds_tree(tree, shardings=True):
    """ShapeDtypeStruct twin of a pytree of arrays — safe to lower
    against even when the originals were donated (shape/dtype/sharding
    survive deletion; buffer contents are never read).

    ``shardings``: True — a COMMITTED array keeps its own ``sharding``, so
    the AOT lowering of a partitioned program is the program that ran, not
    an unsharded one; False — none; or a pytree of shardings shaped like
    ``tree`` (a plan-wrapped step's ``in_shardings``: what the jitted step
    really receives, after the wrapper's ``device_put``s) — then the
    structs are those of the call that ran, to the trace cache's key."""
    import jax

    def _sds(a, sh=None):
        if isinstance(a, jax.ShapeDtypeStruct):
            if shardings is True or (sh is None and a.sharding is None):
                return a
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
        dt = getattr(a, "dtype", None)
        if dt is None:
            a = np.asarray(a)
            dt = a.dtype
        if shardings is True and getattr(a, "committed", False):
            sh = getattr(a, "sharding", None)
        return jax.ShapeDtypeStruct(tuple(np.shape(a)), dt, sharding=sh)

    if shardings is True or shardings is False:
        return jax.tree_util.tree_map(_sds, tree)
    return jax.tree_util.tree_map(_sds, tree, shardings)


def _aot_compile(jitted, examples):
    """``jitted.lower(*examples).compile()``.  A jit that names its own
    ``in_shardings`` (``wrap_with_plan``) refuses an argument committed
    elsewhere (state the startup program left on one device, before the
    step's first ``device_put``): its own shardings decide then."""
    try:
        return jitted.lower(*examples).compile()
    except ValueError:
        return jitted.lower(*sds_tree(list(examples),
                                      shardings=False)).compile()


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
            n_devices: int = 1, op_map_key=None) -> Optional[Dict[str, Any]]:
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
    (``mesh_devices``).  ``op_map_key`` names a :func:`remember` entry
    whose op map is read from the same compiled object (no third
    compile when :func:`op_maps` is asked later).

    Returns None when the callable has no ``lower`` (checkify wrappers,
    custom step builders) or the backend refuses the analysis — capture
    degrades, never raises into the training loop."""
    if not hasattr(jitted, "lower"):
        return None
    m = trace.metrics()
    t0 = time.perf_counter()
    try:
        examples = [sds_tree(a) for a in example_args]
        compiled = _aot_compile(jitted, examples)
        entry = _remembered.get(op_map_key)
        if entry is not None and entry["map"] is None:
            _fill_op_map(entry, compiled)
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


# ---------------------------------------------------------------------------
# device time by Program op: the scope, the op map, the join with a trace
# ---------------------------------------------------------------------------
#
# The scope run_block_ops opens around each op: ``pd:<role>:<label>:<instance>``
#   role      f (forward), b (backward: op_role 1) or o (optimizer: an op
#             that takes Param and Grad, and what follows the backward pass
#             with no role of its own: regularisation, clipping)
#   label     the op type; ``<fwd_type>_grad`` for a generic_grad
#   instance  the op's first output variable, cleaned (``@`` cuts an HLO
#             op_name short, ``/`` separates its components)
# One regular expression finds it anywhere in an instruction's ``op_name``
# (under ``jit(fn)``, a partitioned program's wrappers, ``jvp()`` and
# ``transpose(jvp())``, the frames of a kernel that runs once per chip:
# ``pd:b:x_grad:i/transpose(jvp(jit(body)))/shard_map/pallas_call``); the
# innermost (last) scope of the path is the op's.  What a transformation
# wraps in its parentheses is the name stack of the function it transformed,
# not a frame of the path: the backward rule of a ``custom_vjp`` reads
# ``pd:b:dropout_grad:g/transpose(pd:f:dropout:o)/jvp()/pallas_call`` when the
# grad op applies the vjp the forward op kept, and is the grad op's.
#
# The scopes are metadata, and jax's compile-cache key leaves metadata out:
# renaming them changes no key, and a cache directory warmed by a tree with
# another format serves executables that carry ITS names (every map entry
# None, the attributed share 0).  Clear the cache after changing the format.

ROLES = {"f": "forward", "b": "backward", "o": "optimizer"}
_SCOPE = re.compile(r"pd:([fbo]):([A-Za-z0-9_]+):([A-Za-z0-9_.\-]*)")
_UNCLEAN = re.compile(r"[^A-Za-z0-9_.\-]")
_WRAPPED = re.compile(r"\([^()]*\)")


def scope_name(label: str, role: str, instance: str = "") -> str:
    return (f"pd:{role[0]}:{re.sub(r'[^A-Za-z0-9_]', '_', label)}:"
            f"{_UNCLEAN.sub('.', instance)}")


def op_scope(op, after_backward: bool = False) -> str:
    """The scope name of one Program op (trace time only)."""
    label = op.type
    if label == "generic_grad":
        label = f"{op.attrs.get('fwd_type', 'generic')}_grad"
    if "Param" in op.inputs and "Grad" in op.inputs:
        role = "o"
    elif op.attrs.get("op_role") == 1:
        role = "b"
    else:
        role = "o" if after_backward and not op.attrs.get("op_role") else "f"
    outs = op.output_arg_names
    return scope_name(label, role, outs[0] if outs else "")


def parse_scope(op_name: str) -> Optional[Tuple[str, str, str]]:
    """``(label, role, instance)`` of the innermost Program-op scope in an
    HLO ``op_name`` path (outside any transformation's parentheses), or
    None where it holds none."""
    path, n = _WRAPPED.subn("", op_name or "")
    while n:
        path, n = _WRAPPED.subn("", path)
    found = _SCOPE.findall(path)
    if not found:
        return None
    role, label, instance = found[-1]
    return label, ROLES[role], instance


_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_CONTROL = re.compile(r"\b(?:body|condition|to_apply|true_computation|"
                      r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_CONTROL_FLOW = ("while", "conditional", "call")
_MXU = ("convolution", "dot")


def hlo_module_name(hlo_text: str) -> str:
    m = re.match(r"\s*HloModule\s+([^\s,]+)", hlo_text)
    return m.group(1) if m else ""


def _hlo_computations(hlo_text):
    """``({computation: [(instruction, opcode, rest of its line)]}, entry)``
    of an HLO module in text form."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = comps[m.group(2)] = []
                if m.group(1):
                    entry = m.group(2)
        elif line.rstrip() == "}":
            # the computation's own closing brace: a constant printed over
            # several lines ends in "}}, metadata=..." at column 0 too
            cur = None
        else:
            m = _INSTRUCTION.match(line)
            if m:
                rest = m.group(2)
                op = _OPCODE.search(" " + rest)
                cur.append((m.group(1), op.group(1) if op else "", rest))
            elif cur and "metadata=" in line:
                # an instruction printed over several lines (a custom call
                # with a literal among its attributes): its metadata is on
                # the last of them
                name, opcode, rest = cur[-1]
                cur[-1] = (name, opcode, rest + " " + line.strip())
    return comps, entry


def hlo_op_map(hlo_text: str) -> Dict[str, Optional[Dict[str, Any]]]:
    """``{instruction: {"label", "role", "instance", "opcode", "mxu",
    "also"}}`` for every instruction of the entry computation and of the
    computations it calls as control flow (``while`` bodies and
    conditions, conditionals' branches, calls).

    A plain instruction takes the scope in its own metadata.  A fusion is
    charged whole, never split, to one hero: the ``convolution``/``dot``
    inside its called computation if there is one with a scope, else its
    own metadata, else the scope most instructions inside carry; ``also``
    lists the other labels inside (XLA fuses the Adam update into the dW
    matmul: that fusion reads ``mul_grad`` with ``also: ["adam"]``).
    ``mxu`` is true where the instruction or its body holds a
    ``convolution`` or ``dot``.  An instruction with no scope anywhere
    (copies of parameters, the PRNG key's programs) maps to None."""
    comps, entry = _hlo_computations(hlo_text)
    out: Dict[str, Optional[Dict[str, Any]]] = {}
    todo, seen = [entry], set()
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for name, opcode, rest in comps[comp]:
            own = _OP_NAME.search(rest)
            own = parse_scope(own.group(1)) if own else None
            if opcode in _CONTROL_FLOW:
                todo.extend(_CONTROL.findall(rest))
                for group in _BRANCHES.findall(rest):
                    todo.extend(c.strip().lstrip("%")
                                for c in group.split(","))
            body = [i for c in _CALLS.findall(rest) for i in comps.get(c, ())]
            inside = Counter()
            hero = None
            mxu = opcode in _MXU
            for _, b_opcode, b_rest in body:
                if b_opcode == "parameter":
                    continue
                scope = _OP_NAME.search(b_rest)
                scope = parse_scope(scope.group(1)) if scope else None
                if b_opcode in _MXU:
                    mxu = True
                    hero = hero or scope
                if scope:
                    inside[scope] += 1
            hero = hero or own or (inside.most_common(1)[0][0]
                                   if inside else None)
            if hero is None:
                out[name] = None
                continue
            also = sorted({s[0] for s in inside} - {hero[0]})
            out[name] = {"label": hero[0], "role": hero[1],
                         "instance": hero[2], "opcode": opcode, "mxu": mxu,
                         "also": also}
    return out


# -- the remembered executables ----------------------------------------------
# process-wide, beside the footprints above: on every compile miss the
# Executor leaves the jitted callable and the ShapeDtypeStruct twin of its
# arguments (no buffer is held), so that a reader can ask AFTER a traced
# window (and after Executor.close()) which Program op each instruction of
# the executable came from.  Newest _REMEMBERED_MAX; an entry is retired
# when the Executor's LRU evicts its executable.

_REMEMBERED_MAX = 8
_remembered: "OrderedDict[Any, Dict[str, Any]]" = OrderedDict()


def remember(key, jitted, example_args: Sequence,
             label: Optional[str] = None) -> None:
    """Keep what :func:`op_maps` needs to read this executable's op map.
    Costs one :func:`sds_tree` of the arguments; nothing is lowered or
    compiled until someone asks."""
    if not hasattr(jitted, "lower"):
        return
    entry = {"label": label or str(key), "jitted": jitted,
             "examples": [sds_tree(a) for a in example_args],
             "module": None, "map": None, "mosaic_calls": None}
    with _agg_lock:
        _remembered.pop(key, None)
        _remembered[key] = entry
        while len(_remembered) > _REMEMBERED_MAX:
            _remembered.popitem(last=False)


def forget(key) -> None:
    with _agg_lock:
        _remembered.pop(key, None)


def _fill_op_map(entry, compiled) -> None:
    """Parse ``compiled``'s optimized HLO into a remembered entry; the
    callable and the structs are dropped with it (the parsed map is all a
    reader needs)."""
    from ..ops.pallas_preflight import MOSAIC_CALL
    text = compiled.as_text()
    entry["module"] = hlo_module_name(text)
    entry["map"] = hlo_op_map(text)
    entry["mosaic_calls"] = text.count(MOSAIC_CALL)
    entry["jitted"] = entry["examples"] = None


def op_maps() -> List[Dict[str, Any]]:
    """``[{"label", "module", "map", "mosaic_calls"}]`` of every remembered
    executable, newest last (``mosaic_calls``: the Pallas kernels in it).
    An entry nobody asked about yet is lowered and compiled
    here, once (``jitted.lower(*structs).compile().as_text()``: a
    persistent-cache load where that cache is on); one the backend refuses
    is left out."""
    with _agg_lock:
        entries = list(_remembered.values())
    out = []
    for entry in entries:
        if entry["map"] is None:
            t0 = time.perf_counter()
            try:
                _fill_op_map(entry, _aot_compile(entry["jitted"],
                                                 entry["examples"]))
            except Exception:           # noqa: BLE001 — a reader degrades
                trace.metrics().counter("xla.analysis_errors").inc()
                continue
            trace.metrics().histogram("xla.op_map_seconds").observe(
                time.perf_counter() - t0)
        out.append({k: entry[k]
                    for k in ("label", "module", "map", "mosaic_calls")})
    return out


# -- the join with a profiler trace -------------------------------------------

_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
_RUN_ID = re.compile(r"\(\d+\)$")
_NUMBER = re.compile(r"(?:\.\d+)+$")


def _self_times(events):
    """``[(key, self seconds)]`` of one line's ``(key, start, end)`` events:
    an event's time less that of the events nested inside it."""
    out, stack = [], []              # stack: [end, key, self]
    for key, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        # an event that outlasts the one it starts in is its successor, not
        # its child (the device stamps a successor a little early now and
        # then): the overlap stays the predecessor's.  So the self times
        # always add up to the union of the intervals, the busy time.
        while stack and stack[-1][0] < e:
            top = stack.pop()
            out.append((top[1], top[2]))
            s = min(e, max(s, top[0]))
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, key, e - s])
    out.extend((top[1], top[2]) for top in stack)
    return out


def _instruction_times(xplane, lo, hi):
    """``({module: {instruction: [seconds, calls, min, max]}}, {module:
    runs}, devices)`` of a trace's device planes inside ``[lo, hi)``, summed
    over the devices: each ``XLA Ops`` event's self time, under the program
    run on the ``XLA Modules`` line that holds it (two programs may both
    hold a ``%fusion.1``)."""
    times: Dict[str, Dict[str, List[float]]] = {}
    runs: Counter = Counter()
    n_devices = 0
    for plane in xplane.planes:
        lines = {line.name: line for line in plane.lines}
        if _OPS_LINE not in lines:
            continue
        modules = sorted(
            (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
             _RUN_ID.sub("", e.name))
            for e in lines[_MODULES_LINE].events) \
            if _MODULES_LINE in lines else []
        events, j = [], 0
        for ev in sorted(lines[_OPS_LINE].events, key=lambda ev: ev.start_ns):
            s = ev.start_ns * 1e-9
            e = s + ev.duration_ns * 1e-9
            if min(e, hi) <= max(s, lo):
                continue
            while j < len(modules) and modules[j][1] <= s:
                j += 1
            module = modules[j][2] if j < len(modules) \
                and modules[j][0] <= s else ""
            instruction = ev.name.partition(" = ")[0].strip().lstrip("%")
            events.append(((module, instruction), max(s, lo), min(e, hi)))
        if not events:
            continue
        n_devices += 1
        runs.update(name for s, e, name in modules
                    if min(e, hi) > max(s, lo))
        for (module, instruction), t in _self_times(events):
            row = times.setdefault(module, {}).setdefault(
                instruction, [0.0, 0, float("inf"), 0.0])
            row[0] += t
            row[1] += 1
            row[2] = min(row[2], t)
            row[3] = max(row[3], t)
    return times, runs, n_devices


def device_time_by_op(xplane, window: Optional[Tuple[float, float]] = None,
                      maps: Optional[List[Dict[str, Any]]] = None
                      ) -> Optional[Dict[str, Any]]:
    """Charge the device time of a profiler trace to Program ops.

    ``xplane`` is a path to an ``.xplane.pb`` (or a ``ProfileData``);
    ``window`` is ``(start, end)`` in seconds since the profiler's session
    started (default: everything); ``maps`` default to :func:`op_maps`.
    Reads every device plane's ``XLA Ops`` line (instruction = the event's
    name up to `` = ``, without ``%``), takes each instruction's self time
    inside the window, and joins it with the remembered map that fits the
    program it ran in: the map of that module's name, and among several of
    one name the one whose instruction names cover most of its busy time.

    Returns None where the trace has no device plane with operations, else
    seconds as means over the devices::

        {"devices", "busy_s", "attributed_s", "mxu_s", "steps",
         "roles": {role: seconds},
         "labels": [{"label", "role", "seconds", "calls", "min_s", "max_s",
                     "instructions", "mxu_s", "also"}],      # by seconds;
                    # ``also`` by the time of the instructions that hold it
         "instances": [{"label", "role", "instance", "seconds",
                        "instructions"}],
         "unattributed": [{"module", "instruction", "seconds",
                           "instructions"}],   # by class: ``copy-done``
         "matched": [{"module", "executable", "runs", "busy_s",
                      "covered_s"}]}

    ``steps`` is the runs of the busiest matched module (per device)."""
    if isinstance(xplane, (str, os.PathLike)):
        from jax.profiler import ProfileData
        xplane = ProfileData.from_file(os.fspath(xplane))
    lo, hi = window if window is not None else (-float("inf"), float("inf"))
    by_module, runs, n = _instruction_times(xplane, lo, hi)
    if not n:
        return None
    if maps is None:
        maps = op_maps()
    # a runtime that names its programs otherwise than the executable does:
    # then every map is a candidate for every program
    named = any(m["module"] in by_module for m in maps)
    labels: Dict[Tuple[str, str], Dict[str, Any]] = {}
    instances: Dict[Tuple[str, str, str], Dict[str, Any]] = {}
    roles = {r: 0.0 for r in ROLES.values()}
    unattributed: Dict[Tuple[str, str], Dict[str, Any]] = {}
    matched = []
    busy = attributed = mxu = 0.0
    for module, instrs in by_module.items():
        module_busy = sum(r[0] for r in instrs.values())
        busy += module_busy
        candidates = [m for m in maps if m["module"] == module] \
            or ([] if named else maps)
        best, covered = None, 0.0
        for m in candidates:
            c = sum(r[0] for i, r in instrs.items() if i in m["map"])
            if c > covered:
                best, covered = m, c
        matched.append({"module": module,
                        "executable": best["label"] if best else None,
                        "runs": runs[module] / n,
                        "busy_s": module_busy / n,
                        "covered_s": covered / n})
        for instruction, (t, calls, t_min, t_max) in instrs.items():
            op = best["map"].get(instruction) if best else None
            if op is None:
                # by class (the name without its number): a few rows say
                # what thousands of copies and prefetch waits are
                row = unattributed.setdefault(
                    (module, _NUMBER.sub("", instruction)),
                    {"module": module,
                     "instruction": _NUMBER.sub("", instruction),
                     "seconds": 0.0, "instructions": 0})
                row["seconds"] += t / n
                row["instructions"] += 1
                continue
            attributed += t
            roles[op["role"]] += t
            if op["mxu"]:
                mxu += t
            row = labels.setdefault((op["label"], op["role"]), {
                "label": op["label"], "role": op["role"], "seconds": 0.0,
                "calls": 0.0, "min_s": float("inf"), "max_s": 0.0,
                "instructions": 0, "mxu_s": 0.0, "also": Counter()})
            row["seconds"] += t / n
            row["calls"] += calls / n
            row["min_s"] = min(row["min_s"], t_min)
            row["max_s"] = max(row["max_s"], t_max)
            row["instructions"] += 1
            row["mxu_s"] += t / n if op["mxu"] else 0.0
            row["also"].update({a: t for a in op["also"]})
            inst = instances.setdefault(
                (op["label"], op["role"], op["instance"]), {
                    "label": op["label"], "role": op["role"],
                    "instance": op["instance"], "seconds": 0.0,
                    "instructions": 0})
            inst["seconds"] += t / n
            inst["instructions"] += 1
    for row in labels.values():         # most time first: `adam` in dW
        row["also"] = [a for a, _ in row["also"].most_common()]
    main = max((m for m in matched if m["executable"]),
               key=lambda m: m["busy_s"], default=None)
    by_seconds = lambda r: -r["seconds"]             # noqa: E731
    return {
        "devices": n,
        "busy_s": busy / n,
        "attributed_s": attributed / n,
        "mxu_s": mxu / n,
        "steps": main["runs"] if main else 0.0,
        "roles": {r: t / n for r, t in roles.items()},
        "labels": sorted(labels.values(), key=by_seconds),
        "instances": sorted(instances.values(), key=by_seconds),
        "unattributed": sorted(unattributed.values(), key=by_seconds),
        "matched": sorted(matched, key=lambda m: -m["busy_s"]),
    }


_DEVICE_SORT = {"calls": "calls", "max": "max_s", "min": "min_s"}


def format_device_ops(table: Dict[str, Any], sorted_key: Optional[str] = None,
                      top: int = 40) -> str:
    """The profiler's table of :func:`device_time_by_op`: Program op, role,
    calls, total ms, ms per step, share of busy, ``also``; sorted by
    ``sorted_key`` (``total`` by default; ``ave`` is total over calls)."""
    steps = table["steps"] or 1.0
    busy = table["busy_s"] or 1.0
    key = sorted_key if sorted_key not in (None, "default") else "total"
    if key == "ave":
        rank = lambda r: -r["seconds"] / max(r["calls"], 1)   # noqa: E731
    else:
        field = _DEVICE_SORT.get(key, "seconds")
        rank = lambda r: -r[field]                            # noqa: E731
    lines = [
        "-" * 22 + f"  Device time by Program op (sorted by {key})  "
        + "-" * 22,
        f"{table['steps']:g} steps on {table['devices']} device(s); busy "
        f"{1e3 * table['busy_s'] / steps:.3f} ms/step, "
        f"{100.0 * table['attributed_s'] / busy:.1f}% of it charged to a "
        f"Program op, {100.0 * table['mxu_s'] / busy:.1f}% in matmul or "
        f"convolution instructions",
        f"{'Program op':<36s} {'Role':<9s} {'Calls':>8s} {'Total(ms)':>11s} "
        f"{'ms/step':>9s} {'Busy%':>6s}  also"]
    for r in sorted(table["labels"], key=rank)[:top]:
        lines.append(
            f"{r['label'][:36]:<36s} {r['role']:<9s} {r['calls']:>8g} "
            f"{1e3 * r['seconds']:>11.3f} {1e3 * r['seconds'] / steps:>9.3f} "
            f"{100.0 * r['seconds'] / busy:>6.1f}  {','.join(r['also'])}")
    rest = table["busy_s"] - table["attributed_s"]
    if rest > 0:
        lines.append(
            f"{'(no Program op)':<36s} {'-':<9s} {'':>8s} "
            f"{1e3 * rest:>11.3f} {1e3 * rest / steps:>9.3f} "
            f"{100.0 * rest / busy:>6.1f}")
    return "\n".join(lines)
