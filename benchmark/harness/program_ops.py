"""The traced slice's device time by Program op, for the per-layer metrics
that read it.

The program names every device instruction after the Program op that emitted
it (``paddle_tpu.fluid.device_stats``: a scope ``pd:<role>:<label>:<instance>``
around every op, read back from the executable's optimized HLO into an op
map, joined with the trace's ``XLA Ops`` by ``device_time_by_op``).  This
file finds the traced slice's trace where the loop put it
(``.bench_out/trace/<cell>`` under the checkout), takes the slice's window
from the loop's ``window`` span and the session's start from the trace, asks
the program for the table once per run (the readers share it through
``ctx``), and prints the top of it to standard error.

A program without the instrument (an older commit), a run without a trace or
a trace without a device plane gives ``None``, and every reader built on
this returns ``None`` then: the line leaves those metrics out.

The op types behind each family, from what the two configurations' Programs
hold (``bert_base_pretrain``, ``resnet50``; a backward op is named
``<forward type>_grad``):
"""
from __future__ import annotations

import os
import sys
import time

from benchmark.harness import registry, trace_reduce

FAMILIES = {
    # Mosaic kernels on one chip, XLA's rng-bit-generator in a partitioned
    # program: the label is the op's either way
    "dropout": ("dropout", "dropout_grad"),
    # the attention softmax; softmax_with_cross_entropy is the loss's
    "softmax": ("softmax", "softmax_grad"),
    "norm": ("layer_norm", "layer_norm_grad", "batch_norm",
             "batch_norm_grad"),
}
_KEY = "program_ops"


def trace_dir(ctx):
    return os.path.join(registry.ROOT, ".bench_out", "trace",
                        ctx["cell"]["name"])


def table(ctx):
    """``device_stats.device_time_by_op`` of the traced slice, or None."""
    if _KEY not in ctx:
        ctx[_KEY] = _load(ctx)
    return ctx[_KEY]


def _load(ctx):
    if ctx.get("trace") is None or not ctx.get("traced_steps"):
        return None
    try:
        from paddle_tpu.fluid import device_stats
        by_op = device_stats.device_time_by_op
    except (ImportError, AttributeError):
        return None                  # a program without the instrument
    try:
        path = trace_reduce.newest_xplane(trace_dir(ctx))
    except FileNotFoundError:
        return None
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    start = next((int(value) for plane in profile.planes
                  for key, value in plane.stats
                  if key == "profile_start_time"), None)
    window = None
    if start is not None:
        window = next(((s, e) for n, s, e in ctx["spans"].on_timeline(start)
                       if n == trace_reduce.WINDOW_SPAN), None)
    t0 = time.perf_counter()
    t = by_op(profile, window=window)
    if t is not None:
        _print(t, ctx["traced_steps"], time.perf_counter() - t0)
    return t


def _print(t, steps, seconds):
    per_step = 1e3 / steps
    busy = t["busy_s"] or 1.0
    out = [f"[program_ops] op maps and join took {seconds:.2f}s; "
           f"{steps} steps, {t['devices']} device(s), busy "
           f"{t['busy_s'] * per_step:.3f} ms/step, attributed "
           f"{100.0 * t['attributed_s'] / busy:.2f}%, mxu ops "
           f"{100.0 * t['mxu_s'] / busy:.2f}%; " + ", ".join(
               f"{r} {s * per_step:.3f}" for r, s in t["roles"].items())
           + " ms/step"]
    if t["attributed_s"] < 0.05 * busy and t["matched"][0]["executable"]:
        out.append("[program_ops] WARNING: the program's executable was "
                   "matched but its instructions carry no Program-op scope: "
                   "it came out of a compile cache warmed by a tree without "
                   "these scopes (the cache key leaves metadata out).  Clear "
                   "the compile cache and run again.")
    for m in t["matched"][:4]:
        out.append(f"[program_ops]   program {m['module']}: executable "
                   f"{m['executable']}, {m['runs']:g} runs, busy "
                   f"{m['busy_s'] * per_step:.3f} ms/step, covered "
                   f"{m['covered_s'] * per_step:.3f}")
    out.append("[program_ops] top Program ops (ms/step, % of busy):")
    for r in t["labels"][:15]:
        out.append(f"[program_ops]   {r['label']:<34s} {r['role']:<9s} "
                   f"{r['seconds'] * per_step:>9.3f} "
                   f"{100.0 * r['seconds'] / busy:>5.1f}%  mxu "
                   f"{r['mxu_s'] * per_step:.3f}  "
                   f"also {','.join(r['also']) or '-'}")
    out.append("[program_ops] top instances (ms/step):")
    for r in t["instances"][:10]:
        out.append(f"[program_ops]   {r['label']:<26s} {r['role']:<9s} "
                   f"{r['instance']:<44s} {r['seconds'] * per_step:>9.3f}")
    if t["unattributed"]:
        out.append("[program_ops] without a Program op, by class (ms/step, "
                   "instructions): "
                   + ", ".join(f"{u['module']}/{u['instruction']} "
                               f"{u['seconds'] * per_step:.3f} "
                               f"({u['instructions']})"
                               for u in t["unattributed"][:10]))
    print("\n".join(out), file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# what the readers under layer_metrics/ return
# ---------------------------------------------------------------------------

def share(ctx, field):
    """``field`` (``attributed_s``, ``mxu_s``) over the busy time, in
    percent."""
    t = table(ctx)
    if t is None or not t["busy_s"]:
        return None
    return 100.0 * t[field] / t["busy_s"]


def role_ms(ctx, role):
    """Milliseconds per traced step of the ops of one role."""
    t = table(ctx)
    if t is None:
        return None
    return 1e3 * t["roles"][role] / ctx["traced_steps"]


def family_ms(ctx, family):
    """Milliseconds per traced step of one family of op types, forward and
    backward."""
    t = table(ctx)
    if t is None:
        return None
    types = FAMILIES[family]
    return 1e3 * sum(r["seconds"] for r in t["labels"]
                     if r["label"] in types) / ctx["traced_steps"]
