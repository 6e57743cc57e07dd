"""Measure cells as the driver does, in one call to the chip.

    python3 benchmark/tools/measure.py --workload <cell> [--workload <cell> ...]
        [--sets 2] [--runs 6] [--traced 1] [--seconds <run_seconds>]

For each cell: ``--sets`` sets of ``--runs`` runs of ``benchmark/run.py``, each
run a new process with another ``--seed``, then ``--traced`` runs with
``--trace 1``.  Prints, per end-to-end metric, each set's median and spread
(the distance between the quartiles over the median) and how far the second
set's median lies from the first's, and writes every result line to
``chiprun_out/measure_<cell>.json``.  This process never touches JAX (a
parent that did would hold the chip), and runs one child at a time.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_once(cell, seed, seconds, trace, tolerate=False):
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", cell, "--seed", str(seed), "--seconds",
           str(seconds), "--trace", str(trace)]
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    wall = time.perf_counter() - t0
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        sys.stderr.write(proc.stderr[-4000:])
        if tolerate:
            return {"error": proc.returncode, "seed": seed, "wall_s": wall,
                    "log_tail": proc.stderr.strip().splitlines()[-30:]}
        raise SystemExit(f"measure: {' '.join(cmd)} exited "
                         f"{proc.returncode}")
    result = json.loads(lines[-1])
    result["wall_s"] = wall
    result["seed"] = seed
    result["log_tail"] = proc.stderr.strip().splitlines()[-12:]
    result["cache_files"] = cache_files()
    return result


def cache_files():
    """Files in the compile cache: a run that finds every program there
    adds none."""
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".jax_cache")
    return sum(len(files) for _, _, files in os.walk(cache))


def keep_trace(cell):
    files = glob.glob(os.path.join(ROOT, ".bench_out", "trace", cell,
                                   "plugins", "profile", "*", "*.xplane.pb"))
    if files:
        src = max(files, key=os.path.getmtime)
        dst = os.path.join(ROOT, "chiprun_out", f"{cell}.xplane.pb.gz")
        with open(src, "rb") as fi, gzip.open(dst, "wb") as fo:
            shutil.copyfileobj(fi, fo)


def spread(values):
    """Distance between the quartiles over the median."""
    if len(values) < 2:
        return 0.0
    q = statistics.quantiles(values, n=4, method="inclusive")
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--traced", type=int, default=1)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--keep-trace", action="store_true",
                    help="copy the traced run's xplane, gzipped, to "
                         "chiprun_out/")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    seconds = args.seconds or spec["run_seconds"]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)

    for cell in args.workload:
        record = {"cell": cell, "seconds": seconds, "sets": [], "traced": []}
        seed = args.seed0
        for s in range(args.sets):
            runs = []
            for _ in range(args.runs):
                seed += 1
                r = run_once(cell, seed, seconds, 0)
                runs.append(r)
                print(f"{cell} set {s} seed {seed}: correct={r['correct']} "
                      f"failed={r['failed']}/{r['attempted']} wall "
                      f"{r['wall_s']:.1f}s cache_files={r['cache_files']} "
                      + " ".join(
                          f"{k}={v['value']:.4f}"
                          for k, v in r["metrics"].items()), flush=True)
            record["sets"].append(runs)
        for _ in range(args.traced):
            seed += 1
            # a traced run that fails still leaves its trace to look at
            r = run_once(cell, seed, seconds, 1, tolerate=True)
            record["traced"].append(r)
            print(f"{cell} traced seed {seed}: wall {r['wall_s']:.1f}s "
                  + json.dumps({k: r.get(k) for k in
                                ("error", "correct", "metrics", "device",
                                 "breakdown")}), flush=True)
            if args.keep_trace:
                keep_trace(cell)
        summary = {}
        names = list(record["sets"][0][0]["metrics"]) if record["sets"] \
            else []
        for name in names:
            per_set = []
            for i, runs in enumerate(record["sets"]):
                # the first run of the first set compiles: its set-up is
                # recorded apart, as the driver does
                vals = [r["metrics"][name]["value"] for r in runs]
                if name == "setup_s" and i == 0:
                    summary["setup_s_first_run"] = vals[0]
                    vals = vals[1:]
                per_set.append({"median": statistics.median(vals),
                                "spread": spread(vals), "values": vals})
            entry = {"sets": per_set}
            if len(per_set) > 1:
                entry["second_vs_first"] = (per_set[1]["median"]
                                            / per_set[0]["median"] - 1.0)
            summary[name] = entry
            print(f"{cell} {name}: " + "; ".join(
                f"set {i} median {p['median']:.4f} spread "
                f"{100 * p['spread']:.3f}%" for i, p in enumerate(per_set))
                + (f"; second/first {100 * entry['second_vs_first']:+.3f}%"
                   if "second_vs_first" in entry else ""), flush=True)
        record["summary"] = summary
        with open(os.path.join(ROOT, "chiprun_out",
                               f"measure_{cell}.json"), "w") as f:
            json.dump(record, f, indent=1)


if __name__ == "__main__":
    main()
