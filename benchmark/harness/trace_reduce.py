"""From the profiler's trace (an ``.xplane.pb``) to numbers.

One reduction, kept with the benchmark so that every PR computes the same
number in the same way: per device the union of the intervals in which an
operation ran (busy), the gaps in it (idle) with what the host was doing in
each, the time per operation (self time: a parent's time less its children's),
the time inside collectives and the part of it during which nothing else ran
on that device (exposed), and the time inside Mosaic (Pallas) kernels.

The profiler writes one plane per device (``/device:TPU:<n>``), with one line
of operations (``XLA Ops``; an event's name is the whole HLO instruction,
``%fusion.12 = f32[..] fusion(..), kind=kLoop, ..``), one of asynchronous
operations (``Async XLA Ops``: copies, slices and collectives that run beside
the operations, from their ``-start`` to their ``-done``), one of whole
programs (``XLA Modules``).  Event times are seconds since the profiler's
session started; the ``Task Environment`` plane says when that was on the wall
clock (``profile_start_time``), which is how the benchmark's own spans
(``spans.py``) are laid beside them.  ``jax.profiler.ProfileData`` reads the
file with nothing but JAX.

Operations are counted by class: the instruction's name without its number
(``divide_subtract_fusion``), a plain ``fusion`` with its kind
(``fusion(kOutput)``).  A collective is known by its opcode, a Mosaic kernel
by ``custom_call_target="tpu_custom_call"``.

All times are seconds.  Intervals are ``(start, end)`` tuples.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "window"
_COLLECTIVES = (r"(?:all-reduce|all-gather|reduce-scatter|all-to-all|"
                r"collective-permute|collective-broadcast)")
# the instruction's own name, or its opcode (an opcode is followed by "(";
# an operand that is a collective's result is not)
COLLECTIVE = re.compile(rf"^%?{_COLLECTIVES}|\s{_COLLECTIVES}(?:-start|-done)?\(")
MOSAIC = 'custom_call_target="tpu_custom_call"'
_CLASS = re.compile(r"^%?(.*?)(?:\.\d+)*$")
_KIND = re.compile(r"\bkind=(k\w+)")
IN_PROGRAM = "in_program"          # a gap while a program was on the device
NO_SPAN = "host_other"             # a gap under none of the spans


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------

def newest_xplane(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(profile):
    """``profile`` is a path to an ``.xplane.pb`` or a ``ProfileData``.
    Returns ``{"devices": {plane: {"ops": [...], "async": [...], "modules":
    [...]}}, "session_start_ns": wall-clock ns}`` with events as ``(name,
    start, end, kind)``; an operation's ``name`` is its class and its
    ``kind`` is "collective", "mosaic" or ""."""
    if isinstance(profile, (str, os.PathLike)):
        from jax.profiler import ProfileData
        profile = ProfileData.from_file(os.fspath(profile))
    devices, session_start = {}, None
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = devices[plane.name] = {"ops": [], "async": [],
                                         "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [_op_event(e) for e in line.events]
                elif line.name == ASYNC_LINE:
                    dev["async"] = [_op_event(e) for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] = [_event(e, "") for e in line.events]
        else:
            for key, value in plane.stats:
                if key == "profile_start_time":
                    session_start = int(value)
    return {"devices": devices, "session_start_ns": session_start}


def _event(e, kind):
    start = e.start_ns * 1e-9
    return (e.name, start, start + e.duration_ns * 1e-9, kind)


def _op_event(e):
    text = e.name
    kind = "collective" if COLLECTIVE.search(text) else \
        "mosaic" if MOSAIC in text else ""
    return (op_class(text),) + _event(e, kind)[1:]


def op_class(text):
    """``%divide_subtract_fusion.20 = (f32[..]) fusion(..), kind=kOutput``
    -> ``divide_subtract_fusion``; ``%fusion.3 = .. kind=kLoop`` ->
    ``fusion(kLoop)``."""
    head, _, rest = text.partition(" = ")
    name = _CLASS.match(head).group(1)
    if name == "fusion":
        kind = _KIND.search(rest)
        if kind:
            name = f"fusion({kind.group(1)})"
    return name


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals):
    """Disjoint sorted intervals covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The part of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events):
    """name -> seconds not covered by events nested inside (same line:
    events nest or are disjoint)."""
    totals = {}
    stack = []                       # (end, name) of the open parents
    for name, s, e, _ in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:                    # a child: its time is not the parent's
            totals[stack[-1][1]] -= e - s
        totals[name] = totals.get(name, 0.0) + (e - s)
        stack.append((e, name))
    return totals


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def reduce(trace, spans=()):
    """Per-device and overall numbers of a loaded trace.  ``spans`` are the
    host's ``(name, start, end)`` on the trace's timeline.

    The window is the span named ``window`` where there is one (the
    benchmark puts it around the measured slice) and else the extent of the
    device operations.  Raises where no operation ran on any device."""
    window = next(((s, e) for n, s, e in spans if n == WINDOW_SPAN), None)
    labelled = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    if window is None:
        every = [ev for d in trace["devices"].values() for ev in d["ops"]]
        if not every:
            raise ValueError("the trace holds no device operation")
        window = (min(ev[1] for ev in every), max(ev[2] for ev in every))
    lo, hi = window
    per_device = {}
    for plane, dev in sorted(trace["devices"].items()):
        ops = sorted(((n, max(s, lo), min(e, hi), k)
                      for n, s, e, k in dev["ops"]
                      if min(e, hi) > max(s, lo)),
                     key=lambda ev: (ev[1], -ev[2]))
        busy = union((s, e) for _, s, e, _ in ops)
        # a parent (while, conditional, call) spans its children and does
        # no work of its own: what overlaps what is asked of the leaves
        leaves = [ev for i, ev in enumerate(ops)
                  if i + 1 == len(ops) or ops[i + 1][1] >= ev[2]]
        # a collective is on the device from its start to its done, be it
        # an operation of its own or an asynchronous one beside them
        coll = union(clip([(s, e) for _, s, e, k in leaves + dev["async"]
                           if k == "collective"], lo, hi))
        other = union((s, e) for _, s, e, k in leaves if k != "collective")
        runs = clip([(s, e) for _, s, e, _ in dev["modules"]], lo, hi)
        programs = union(runs)
        gaps = subtract([(lo, hi)], busy)
        per_device[plane] = {
            "busy_s": length(busy),
            "ops": self_times(ops),
            "collective_s": length(coll),
            "collective_exposed_s": length(subtract(coll, other)),
            "mosaic_s": length(union((s, e) for _, s, e, k in leaves
                                     if k == "mosaic")),
            "program_runs": len(runs),
            "gaps": _charge(gaps, programs, labelled),
        }
    if not any(d["busy_s"] > 0 for d in per_device.values()):
        raise ValueError("no operation ran on a device inside the window")
    n = len(per_device)
    ops_mean = {}
    for d in per_device.values():
        for name, t in d["ops"].items():
            ops_mean[name] = ops_mean.get(name, 0.0) + t / n
    return {
        "window_s": hi - lo,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / n,
        "busy_s_min": min(d["busy_s"] for d in per_device.values()),
        "collective_s": sum(d["collective_s"]
                            for d in per_device.values()) / n,
        "collective_exposed_s": sum(d["collective_exposed_s"]
                                    for d in per_device.values()) / n,
        "mosaic_s": sum(d["mosaic_s"] for d in per_device.values()) / n,
        "ops": ops_mean,
        "devices": per_device,
    }


def _charge(gaps, programs, spans):
    """Cut the device's idle gaps into pieces ``(start, end, label)``: the
    part while a program was on the device is the device's own schedule
    (``in_program``); the rest goes to the span the host was in, and what no
    span covers to ``host_other``."""
    pieces = [(s, e, IN_PROGRAM) for s, e in subtract(
        gaps, subtract(gaps, programs))]
    outside = subtract(gaps, programs)
    covered = []
    for name, ss, se in spans:
        for s, e in clip(outside, ss, se):
            pieces.append((s, e, name))
            covered.append((s, e))
    pieces.extend((s, e, NO_SPAN)
                  for s, e in subtract(outside, union(covered)))
    # spans come from another clock reading: no slivers of rounding
    return sorted(p for p in pieces if p[1] - p[0] > 1e-9)


def breakdown(reduced, top=10):
    """The result line's ``breakdown``: the operations that took most device
    time (mean over devices) and the idle time of the fullest-idle device by
    what the host was doing."""
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:top]
    idlest = max(reduced["devices"].values(),
                 key=lambda d: sum(e - s for s, e, _ in d["gaps"]))
    by_label = {}
    for s, e, label in idlest["gaps"]:
        by_label[label] = by_label.get(label, 0.0) + (e - s)
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in gaps]}
