"""Run one cell and make its result line.

Looks the cell up in ``BENCHMARK.json``, hands it to the loop its
configuration names, and reduces what comes back to the contract's line:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``trace`` its per-layer metrics, each read by the file of
its name under ``layer_metrics/``), ``device`` and, traced, ``breakdown``.
"""
from __future__ import annotations

import math
import os

from benchmark.harness import trace_reduce
from benchmark.harness.registry import Registry


def run_cell(name, seed, seconds, trace, t_start, reg=None, override=None):
    """``override`` is for ``benchmark/tests`` alone: ``{"config": {...},
    "mix": {...}}`` shrink a cell to a size the CPU can run and allow the CPU
    as a device.  ``run.py`` has no way to pass it."""
    reg = reg or Registry()
    cell = reg.cell(name)
    cfg, cfg_dir = reg.config(cell["config"])
    mix = reg.mix(cell["traffic"])
    if override:
        cfg.update(override.get("config", {}))
        mix.update(override.get("mix", {}))
    loop = reg.module("loops", cfg["loop"] + ".py")
    res = loop.run(cell, cfg, cfg_dir, mix, reg, seed, seconds, trace,
                   t_start, allow_cpu=bool(override),
                   out_dir=os.path.join(reg.root, ".bench_out"))

    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": {}, "device": res["device"]}
    if not trace:
        for m in reg.metrics_of("end_to_end", name):
            value = res["end_to_end"].get(m["name"])
            if value is None and override:
                continue             # no peak for the tests' CPU: no mfu
            if value is None or not math.isfinite(value):
                raise RuntimeError(f"cell {name} did not produce the "
                                   f"end-to-end metric {m['name']}")
            line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        return line

    ctx = res["layer_ctx"]
    reported = {m["name"] for m in reg.metrics_of("end_to_end", name)}
    for m in reg.metrics_of("per_layer", name):
        if m["moves"] not in reported:
            continue                 # reported only where what it moves is
        value = reg.module("layer_metrics", m["name"] + ".py").read(ctx)
        if value is not None:        # a reader that finds nothing: left out
            line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    reduced = ctx["trace"]
    line["device"]["busy_s"] = reduced["busy_s"]
    line["device"]["window_s"] = reduced["window_s"]
    line["breakdown"] = trace_reduce.breakdown(reduced)
    return line
