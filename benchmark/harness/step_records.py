"""Where the host's time went, from inside the program: the flight
recorder's records of the run, for the per-layer metrics that read them.

The program writes one wide record per ``Executor.run`` into an always-on
ring (``paddle_tpu.fluid.flight_recorder``): since the tracing PR that added
these files a step record carries the start of ``run`` (``t0_us``, on the
program's own epoch), the whole of it (``run_us``) and its parts by name
(``phases_us``: ``resolve``, ``gather``, ``stage``, ``persist``, ``place``,
``call``, ``scatter``, ``fetch``), a ``compile`` record per Executor compile
miss splits it into the Python side and XLA's (``total_us``, ``backend_us``),
and an ``xla_compile`` record per executable XLA built or the persistent
cache loaded says which of the two it was (``cache_hit``).  The program
keeps its epoch's wall clock (``trace.epoch_unix_ns()``), so a record goes
onto the wall clock, where the loop's spans already are (``spans.py``), and
from there onto the profiler's timeline.

This file takes the ring once per run (the readers share it through
``ctx``), keeps the step records that began between the first and the last
``executor.submit`` span of the measured window and the compile records
that began before it, and in a traced run prints an ``[executor_phases]``
table to standard error: per phase the median and the mean of a step, its
share of ``run_us``, and the seconds of the traced slice's idle time that
fell inside it; and, for the idle time of the loop's span that no phase
holds, whether it lay before ``run`` began (the runner's wait for its
window) or after it ended.

A program without these fields (an older commit) gives ``None``, and every
reader built on this returns ``None`` then: the line leaves those metrics
out.  The ring holds 4096 records: where more were written than it holds,
this file says so on standard error and the readers whose records may be
gone return ``None`` rather than a part of a sum.
"""
from __future__ import annotations

import statistics
import sys

from benchmark.harness import program_ops, trace_reduce

SUBMIT_SPAN = "executor.submit"
# the phases a reader adds up under one name
GROUPS = {
    "state": ("gather", "scatter"),          # the scope walk, both ways
    "stage": ("stage", "persist", "place"),  # feeds, key, a plan's puts
    "call": ("call",),
}
# an executable the persistent cache is asked for: small eager programs
# compile in milliseconds and are never written to it
LARGE_COMPILE_US = 1e6
_KEY = "step_records"


def view(ctx):
    """``{"steps", "compiles", "xla", "epoch_ns", "setup_whole"}`` of this
    run, or None on a program without the fields: the window's step
    records, the ``compile`` and ``xla_compile`` records that began before
    the window, the wall clock of the records' zero, and whether set-up's
    records are all still in the ring."""
    if _KEY not in ctx:
        ctx[_KEY] = _load(ctx)
        if ctx[_KEY] is not None and ctx.get("trace") is not None:
            _print(ctx, ctx[_KEY])
    return ctx[_KEY]


def _load(ctx):
    try:
        from paddle_tpu.fluid import flight_recorder, trace
        epoch_ns = trace.epoch_unix_ns()
    except (ImportError, AttributeError):
        return None                  # a program without the instrument
    rec = flight_recorder.recorder()
    return select(rec.snapshot(), ctx["spans"].records, epoch_ns,
                  lost=max(0, rec.total - rec.capacity))


def select(records, span_records, epoch_ns, lost=0):
    """The view of ``records`` (a ring's snapshot, oldest first) for a
    window given by the loop's ``span_records`` (``(name, wall_ns at start,
    seconds)``); ``lost`` records were overwritten before the oldest."""
    submits = [(wall, wall + seconds * 1e9)
               for name, wall, seconds in span_records
               if name == SUBMIT_SPAN]
    if not submits:
        return None
    lo = min(s for s, _ in submits)
    hi = max(e for _, e in submits)

    def wall(r):
        return epoch_ns + r["t0_us"] * 1e3

    steps = [r for r in records if r.get("kind") == "step"
             and "phases_us" in r and lo <= wall(r) <= hi]
    if not steps:
        return None
    before = [r for r in records if "t0_us" in r and wall(r) < lo]
    first = min((wall(r) for r in records if "t0_us" in r), default=lo)
    if lost:
        print(f"[executor_phases] the flight recorder's ring lost its "
              f"oldest {lost} records: the set-up readers return nothing"
              + ("" if first < lo else ", nor do the phase readers (the "
                 "window's first records are among them)"),
              file=sys.stderr, flush=True)
        if first >= lo:
            return None
    return {
        "steps": steps,
        "compiles": [r for r in before if r.get("kind") == "compile"],
        "xla": [r for r in before if r.get("kind") == "xla_compile"],
        "epoch_ns": epoch_ns,
        "setup_whole": not lost,
    }


# ---------------------------------------------------------------------------
# what the readers under layer_metrics/ return
# ---------------------------------------------------------------------------

def run_ms_p50(ctx):
    v = view(ctx)
    if v is None:
        return None
    return statistics.median(r["run_us"] for r in v["steps"]) / 1e3


def group_ms_p50(ctx, group):
    """Median over the window's steps of the phases of one group, ms."""
    v = view(ctx)
    if v is None:
        return None
    names = GROUPS[group]
    return statistics.median(
        sum(r["phases_us"].get(n, 0.0) for n in names)
        for r in v["steps"]) / 1e3


def setup_trace_s(ctx):
    """Seconds of set-up inside Executor compile misses and outside XLA:
    Program -> step function, the jaxpr trace, the MLIR lowering."""
    v = view(ctx)
    if v is None or not v["setup_whole"]:
        return None
    return sum(r["total_us"] - r["backend_us"] for r in v["compiles"]) / 1e6


def setup_compile_s(ctx):
    """Seconds of set-up inside XLA: compiling, or loading from the
    persistent cache; every executable of the process."""
    v = view(ctx)
    if v is None or not v["setup_whole"]:
        return None
    return sum(r["backend_us"] for r in v["xla"]) / 1e6


def setup_cache_misses(ctx):
    """Large executables of set-up that the persistent cache did not
    serve."""
    v = view(ctx)
    if v is None or not v["setup_whole"]:
        return None
    return sum(1 for r in v["xla"] if not r["cache_hit"]
               and r["backend_us"] >= LARGE_COMPILE_US)


def idle_named_share(ctx):
    """Of the traced slice's idle time that ``trace_reduce`` charged to the
    loop's ``executor.submit`` span on the idlest device, the share inside
    a step record's ``run``, in percent."""
    idle = _idle(ctx)
    if idle is None or not idle["submit_s"]:
        return None
    return 100.0 * sum(idle["by_phase"].values()) / idle["submit_s"]


# ---------------------------------------------------------------------------
# the records on the profiler's timeline
# ---------------------------------------------------------------------------

def phase_intervals(steps, epoch_ns, session_start_ns):
    """``(name, start, end)`` in seconds since the profiler's session
    started, for every phase of every step record.  A step's phases follow
    each other in the record's own order (a compile miss, whose ``prepare``
    is counted in two places, has no such order and is left out)."""
    out = []
    for r in steps:
        if r.get("compile_miss"):
            continue
        t = (epoch_ns - session_start_ns) * 1e-9 + r["t0_us"] * 1e-6
        for name, us in r["phases_us"].items():
            out.append((name, t, t + us * 1e-6))
            t += us * 1e-6
    return out


BEFORE_RUN = "(before run)"      # in the loop's span, not yet in run():
AFTER_RUN = "(after run)"        # the runner's window wait, its bookkeeping


def around_run(steps, submits, epoch_ns, session_start_ns):
    """What of each ``executor.submit`` span (``(start, end)`` on the
    profiler's timeline) lies before and after the ``run`` of the step
    record that began inside it, as ``(name, start, end)``: the idle time
    there is the loop's and the runner's, not a phase's."""
    out = []
    shift = (epoch_ns - session_start_ns) * 1e-9
    runs = sorted((shift + r["t0_us"] * 1e-6,
                   shift + (r["t0_us"] + r["run_us"]) * 1e-6)
                  for r in steps)
    for s, e in submits:
        inside = [(a, b) for a, b in runs if s <= a <= e]
        if inside:
            out.append((BEFORE_RUN, s, inside[0][0]))
            out.append((AFTER_RUN, min(inside[-1][1], e), e))
    return out


def idle_by_phase(gaps, intervals):
    """``gaps`` are a device's idle pieces ``(start, end, label)``; returns
    the seconds of the ``executor.submit`` pieces and of their parts inside
    each phase."""
    pieces = [(s, e) for s, e, label in gaps if label == SUBMIT_SPAN]
    by_phase = {}
    for name, s, e in intervals:
        inside = trace_reduce.length(trace_reduce.clip(pieces, s, e))
        if inside:
            by_phase[name] = by_phase.get(name, 0.0) + inside
    return {"submit_s": trace_reduce.length(pieces), "by_phase": by_phase}


def _idle(ctx):
    key = _KEY + ".idle"
    if key not in ctx:
        ctx[key] = _load_idle(ctx)
    return ctx[key]


def _load_idle(ctx):
    v = view(ctx)
    if v is None or ctx.get("trace") is None:
        return None
    try:
        path = trace_reduce.newest_xplane(program_ops.trace_dir(ctx))
    except FileNotFoundError:
        return None
    from jax.profiler import ProfileData
    start = next((int(value) for plane in ProfileData.from_file(path).planes
                  for key, value in plane.stats
                  if key == "profile_start_time"), None)
    if start is None:
        return None
    idlest = max(ctx["trace"]["devices"].values(),
                 key=lambda d: sum(e - s for s, e, _ in d["gaps"]))
    idle = idle_by_phase(idlest["gaps"],
                         phase_intervals(v["steps"], v["epoch_ns"], start))
    # what no phase holds, by where in the loop's span it lies: printed,
    # and not part of the share
    submits = [(s, e) for n, s, e in ctx["spans"].on_timeline(start)
               if n == SUBMIT_SPAN]
    idle["around"] = idle_by_phase(idlest["gaps"], around_run(
        v["steps"], submits, v["epoch_ns"], start))["by_phase"]
    return idle


def _print(ctx, v):
    steps = v["steps"]
    run = sum(r["run_us"] for r in steps) or 1.0
    idle = _idle(ctx) or {"submit_s": 0.0, "by_phase": {}}
    names = list(dict.fromkeys(n for r in steps for n in r["phases_us"]))
    off = sum(abs(sum(r["phases_us"].values()) - r["run_us"])
              > 0.02 * r["run_us"] for r in steps)
    p50 = statistics.median(r["run_us"] for r in steps)
    out = [f"[executor_phases] {len(steps)} step records in the window, "
           f"run p50 {p50 / 1e3:.3f} ms, mean {run / len(steps) / 1e3:.3f}; "
           f"{off} records whose phases do not add up to run_us within "
           f"2 %; idle charged to {SUBMIT_SPAN} in the traced slice "
           f"{idle['submit_s']:.4f} s, inside a phase "
           f"{sum(idle['by_phase'].values()):.4f}",
           "[executor_phases]   phase       p50 ms    mean ms  % of run  "
           "idle s"]
    for n in names:
        us = [r["phases_us"].get(n, 0.0) for r in steps]
        out.append(f"[executor_phases]   {n:<9s} "
                   f"{statistics.median(us) / 1e3:>8.3f} "
                   f"{sum(us) / len(us) / 1e3:>10.3f} "
                   f"{100.0 * sum(us) / run:>9.2f}  "
                   f"{idle['by_phase'].get(n, 0.0):.4f}")
    for n, seconds in idle.get("around", {}).items():
        out.append(f"[executor_phases]   {n:<43s} {seconds:.4f}")
    if v["setup_whole"]:
        out.append("[executor_phases] set-up, Executor compile misses: "
                   + "; ".join(
                       f"{r.get('fp')} ({r.get('n_ops')} ops) python "
                       f"{(r['total_us'] - r['backend_us']) / 1e6:.2f} s, "
                       f"xla {r['backend_us'] / 1e6:.2f} s "
                       f"{'loaded' if r['cache_hit'] else 'compiled'}"
                       for r in v["compiles"]))
        large = [r for r in v["xla"] if r["backend_us"] >= LARGE_COMPILE_US]
        total = sum(r["backend_us"] for r in v["xla"]) / 1e6
        out.append(f"[executor_phases] set-up, {len(v['xla'])} executables "
                   f"through XLA in {total:.2f} s; of a second or more: "
                   + (", ".join(f"{r['backend_us'] / 1e6:.1f} s "
                                f"{'hit' if r['cache_hit'] else 'miss'}"
                                for r in large) or "none"))
    print("\n".join(out), file=sys.stderr, flush=True)
