#!/usr/bin/env python
"""Render a paddle_tpu diagnostic bundle into a human post-mortem.

A bundle is the single atomic JSON file the SLO watchdog
(paddle_tpu/fluid/watchdog.py) dumps on a stall / p99 breach / crash /
OOM: trace tail, flight-recorder wide events, goodput report, device
footprints, metrics snapshot, flags, program fingerprints.  This tool
needs NOTHING from the process that produced it — stdlib only, plus
fluid/goodput.py and tools/timeline.py loaded by file path — so a
responder can run it anywhere the bundle landed.

Usage:
    python tools/diagnose.py bundle.json                # report to stdout
    python tools/diagnose.py bundle.json --trace out.json   # + chrome trace
    python tools/diagnose.py bundle.json --request req-1a2b-3c  # one request
    python tools/diagnose.py --list [/diag/dir]         # newest bundles
    python tools/diagnose.py --fleet fleet-bundle.json  # cross-process story

A FLEET bundle (fleet-bundle-*.json, frozen by ServingFleet on
ejection) embeds the router's view of the incident window — routing
decisions with replica attribution, breaker states, scrape history —
plus the ejected replica's own watchdog bundle; ``--fleet`` (or schema
auto-detection) renders which requests were in flight, where each
one's time went, and on which replica.

The Chrome trace carries the bundle's trace tail, a per-request lane +
request↔batch flow arrows (timeline.request_flows; --no-flows skips),
the goodput attribution track, and the wide events rendered as their
own "flight recorder" row — open in chrome://tracing or ui.perfetto.dev.
"""
import argparse
import importlib.util
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))


def _load_by_path(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _timeline():
    return _load_by_path("paddle_tpu_timeline",
                         os.path.join(_HERE, "timeline.py"))


BUNDLE_SCHEMA = "paddle_tpu.diagnostic_bundle.v1"
FLEET_SCHEMA = "paddle_tpu.fleet_bundle.v1"


def load_bundle(path):
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") not in (BUNDLE_SCHEMA, FLEET_SCHEMA):
        raise ValueError(f"{path}: not a paddle_tpu diagnostic bundle "
                         f"(schema={doc.get('schema')!r})")
    return doc


def is_fleet_bundle(doc):
    return doc.get("schema") == FLEET_SCHEMA


def _fmt_bytes(n):
    n = float(n or 0)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{int(n)}B" if unit == "B" else f"{n:.1f}{unit}"
        n /= 1024
    return f"{n:.1f}GiB"


def _percentile(values, q):
    if not values:
        return 0.0
    vs = sorted(values)
    return vs[min(len(vs) - 1, int(q * len(vs)))]


# ---------------------------------------------------------------------------
# report sections
# ---------------------------------------------------------------------------

def _header(doc):
    lines = [
        "=" * 72,
        f"paddle_tpu post-mortem — {doc['reason'].upper()}",
        "=" * 72,
        f"time      : {doc.get('time')}  (pid {doc.get('pid')}, "
        f"uptime {doc.get('uptime_s', 0):.1f}s)",
        f"watchdog  : {json.dumps(doc.get('watchdog', {}), default=str)}",
        f"tracing   : {'on' if doc.get('trace_enabled') else 'off'}"
        f" ({len(doc.get('trace_tail') or [])} tail events,"
        f" {doc.get('trace_dropped_events', 0)} dropped)",
    ]
    exc = doc.get("exception")
    if exc:
        lines += ["", f"exception : {exc.get('type')}: "
                      f"{exc.get('message')}"]
        tb = (exc.get("traceback") or "").strip().splitlines()
        lines += ["  " + ln for ln in tb[-12:]]
    if doc.get("extra"):
        lines.append(f"detail    : {json.dumps(doc['extra'], default=str)}")
    return lines


def _goodput_section(doc):
    gp = doc.get("goodput") or {}
    if "buckets" not in gp:
        return [f"goodput   : unavailable ({gp.get('error', 'no data')})"]
    lines = [f"goodput   : ratio {gp.get('ratio', 0):.1%} over "
             f"{gp.get('wall_seconds', 0):.1f}s "
             f"(source={gp.get('source')}"
             + (", DEGRADED — trace buffer dropped events"
                if gp.get("degraded") else "") + ")"]
    for b, v in sorted((gp.get("buckets") or {}).items(),
                       key=lambda kv: -kv[1]):
        if v > 0:
            lines.append(f"    {b:<18s} {v:10.3f}s")
    return lines


def _wide_event_section(doc, last=8):
    wide = doc.get("wide_events") or []
    steps = [r for r in wide if r.get("kind") == "step"]
    reqs = [r for r in wide if r.get("kind") == "request"]
    lines = [f"recorder  : {len(wide)} wide events retained "
             f"({len(steps)} steps, {len(reqs)} requests)"]
    if steps:
        misses = sum(1 for r in steps if r.get("compile_miss"))
        last_step = steps[-1]
        lines.append(
            f"    last step: #{last_step.get('step')} at "
            f"{last_step.get('ts_us', 0) / 1e6:.2f}s, "
            f"{last_step.get('dur_us', 0) / 1e3:.1f}ms, "
            f"goodput {last_step.get('goodput_ratio', 0):.0%}, "
            f"rss {_fmt_bytes(last_step.get('rss_bytes'))}, "
            f"{misses} compile misses across the ring")
        phases = last_step.get("phases_us")
        if phases:
            # where that step's host time went (Executor.run's own stamps)
            top = max(phases, key=phases.get)
            lines.append(
                f"    its run  : {last_step.get('run_us', 0) / 1e3:.1f}ms "
                f"on the host, most of it in '{top}' "
                f"({phases[top] / 1e3:.1f}ms)")
    for r in wide:
        if r.get("kind") == "compile":
            # one line per Executor compile miss: the Python side (trace,
            # lowering) against XLA's (compiled, or loaded from the cache)
            lines.append(
                f"    compile  : {r.get('fp')} at "
                f"{r.get('t0_us', 0) / 1e6:.2f}s, python "
                f"{(r.get('total_us', 0) - r.get('backend_us', 0)) / 1e6:.2f}"
                f"s, xla {r.get('backend_us', 0) / 1e6:.2f}s "
                f"(persistent cache "
                f"{'hit' if r.get('cache_hit') else 'miss'})")
    bad = [r for r in reqs if r.get("outcome") not in (None, "ok")]
    if bad:
        by = {}
        for r in bad:
            by[r["outcome"]] = by.get(r["outcome"], 0) + 1
        lines.append(f"    non-ok requests: {by}")
    for r in wide[-last:]:
        lines.append("    " + json.dumps(r, default=str)[:160])
    return lines


def _slow_request_section(doc, top=5):
    reqs = [r for r in (doc.get("wide_events") or [])
            if r.get("kind") == "request"
            and r.get("latency_us") is not None]
    if not reqs:
        return []
    lats = [r["latency_us"] for r in reqs]
    p99 = _percentile(lats, 0.99)
    slow = sorted(reqs, key=lambda r: -r["latency_us"])[:top]
    lines = [f"requests  : {len(reqs)} completed in ring, p50 "
             f"{_percentile(lats, 0.5) / 1e3:.1f}ms / p99 "
             f"{p99 / 1e3:.1f}ms; slowest:"]
    for r in slow:
        lines.append(
            f"    {r.get('trace_id'):<20s} {r['latency_us'] / 1e3:8.1f}ms "
            f"(queue {r.get('queue_us', 0) / 1e3:.1f}ms / device "
            f"{r.get('device_us', 0) / 1e3:.1f}ms, rows "
            f"{r.get('rows')}, batch {r.get('batch_id')})")
    return lines


def _device_section(doc, top=5):
    fps = doc.get("device_footprints") or []
    if not fps:
        return []
    lines = [f"device    : {len(fps)} resident executables by XLA peak:"]
    for r in fps[:top]:
        lines.append(f"    {str(r.get('label', '?')):<24s} "
                     f"{_fmt_bytes(r.get('peak_bytes'))}")
    return lines


def _metrics_section(doc):
    m = doc.get("metrics") or {}

    def _v(name):
        v = m.get(name)
        return v.get("count") if isinstance(v, dict) else v

    interesting = [
        ("executor.steps_completed", "steps completed"),
        ("executor.compile_cache_miss", "compile misses"),
        ("executor.compile_cache_hit", "compile hits"),
        ("serving.requests", "requests admitted"),
        ("serving.rejected", "requests rejected"),
        ("serving.timeouts", "request timeouts"),
        ("serving.dispatch_errors", "dispatch errors"),
        ("xla.oom_errors", "device OOMs"),
        ("ckpt.saves", "checkpoints saved"),
        ("elastic.preemptions", "preemptions"),
        ("watchdog.stalls", "stalls detected"),
        ("watchdog.breaches", "p99 breaches"),
    ]
    rows = [(label, _v(name)) for name, label in interesting
            if _v(name)]
    if not rows:
        return []
    return ["metrics   : " + ", ".join(f"{label} {v}"
                                       for label, v in rows)]


def _ps_section(doc):
    """Sharded parameter-server tier: tier occupancy, prefetch
    effectiveness, staleness fences, and per-shard availability — the
    ps.* instruments the sharded table and ShardServer publish."""
    m = doc.get("metrics") or {}

    def _v(name):
        v = m.get(name)
        return v.get("count") if isinstance(v, dict) else v

    if not any(_v(f"ps.{k}") for k in (
            "shards_up", "hot_rows", "cold_rows", "prefetch_hits",
            "wal_records", "shard_restarts", "dead_workers")):
        return []
    lines = ["ps tier   :"]
    hot, cold = _v("ps.hot_rows") or 0, _v("ps.cold_rows") or 0
    if hot or cold:
        lines.append(f"    tiers      hot {hot} rows / cold {cold} rows; "
                     f"evictions {_v('ps.evictions') or 0}, "
                     f"promotions {_v('ps.promotions') or 0}")
    pf_h = _v("ps.prefetch_hits") or 0
    pf_m = _v("ps.prefetch_misses") or 0
    if pf_h or pf_m:
        rate = pf_h / max(1, pf_h + pf_m)
        lines.append(f"    prefetch   {pf_h} hits / {pf_m} misses "
                     f"({rate:.0%} hit rate), "
                     f"{_v('ps.prefetch_patched') or 0} patched stale")
    stalls = _v("ps.fence_stalls") or 0
    outst = _v("ps.outstanding_pushes") or 0
    if stalls or outst:
        lines.append(f"    staleness  {stalls} fence stalls, "
                     f"{outst} pushes outstanding")
    up = _v("ps.shards_up")
    if up is not None and (up or _v("ps.breaker_open")
                           or _v("ps.shard_restarts")):
        lines.append(f"    shards     {up} up, "
                     f"{_v('ps.breaker_open') or 0} breakers open, "
                     f"{_v('ps.shard_restarts') or 0} restarts")
    wal = _v("ps.wal_records") or 0
    if wal or _v("ps.snapshots"):
        lines.append(f"    durability {wal} WAL records, "
                     f"{_v('ps.snapshots') or 0} snapshots, "
                     f"{_v('ps.restores') or 0} restores")
    return lines


def _request_story(doc, trace_id):
    """Everything the bundle knows about one trace id — the per-request
    forensic view."""
    lines = [f"request {trace_id}:"]
    for r in (doc.get("wide_events") or []):
        if r.get("trace_id") == trace_id \
                or r.get("batch_id") == trace_id:
            lines.append("  wide  " + json.dumps(r, default=str))
    for e in (doc.get("trace_tail") or []):
        args = e.get("args") or {}
        if args.get("trace_id") == trace_id \
                or args.get("batch_id") == trace_id \
                or trace_id in (args.get("request_ids") or []):
            lines.append(
                f"  span  {e.get('name'):<20s} ts={e.get('ts', 0):.1f}us "
                f"dur={e.get('dur', 0):.1f}us args="
                + json.dumps(args, default=str)[:120])
    if len(lines) == 1:
        lines.append("  (nothing retained for this id — it may have "
                     "aged out of the ring / trace tail)")
    return lines


def report(doc, request=None):
    lines = _header(doc)
    lines.append("")
    lines += _goodput_section(doc)
    lines.append("")
    lines += _wide_event_section(doc)
    sec = _slow_request_section(doc)
    if sec:
        lines.append("")
        lines += sec
    sec = _device_section(doc)
    if sec:
        lines.append("")
        lines += sec
    sec = _metrics_section(doc)
    if sec:
        lines.append("")
        lines += sec
    sec = _ps_section(doc)
    if sec:
        lines.append("")
        lines += sec
    fps = doc.get("program_fingerprints") or []
    if fps:
        lines.append(f"programs  : {', '.join(fps)}")
    if request:
        lines.append("")
        lines += _request_story(doc, request)
    lines.append("=" * 72)
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# fleet bundles — the cross-process story
# ---------------------------------------------------------------------------

def _fleet_header(doc):
    return [
        "=" * 72,
        f"paddle_tpu FLEET post-mortem — {doc['reason'].upper()} "
        f"(replica {doc.get('replica')})",
        "=" * 72,
        f"time      : {doc.get('time')}  (router pid {doc.get('pid')})",
    ]


def _fleet_router_section(doc, last_events=8):
    rv = doc.get("router") or {}
    st = rv.get("stats") or {}
    lat = st.get("latency") or {}
    lines = [
        f"router    : {st.get('dispatches', 0)} dispatches "
        f"({st.get('redispatches', 0)} redispatched, "
        f"{st.get('failures', 0)} failures), "
        f"{rv.get('in_flight', 0)} in flight at freeze, "
        f"p99 {(lat.get('p99') or 0) * 1e3:.1f}ms; "
        f"{st.get('ejections', 0)} ejections / "
        f"{st.get('readmissions', 0)} readmissions / "
        f"{st.get('replacements', 0)} replacements"
    ]
    for r in st.get("replicas") or []:
        br = r.get("breaker") or {}
        lines.append(
            f"    {str(r.get('name')):<6s} {str(r.get('state')):<9s} "
            f"breaker={br.get('state')} "
            f"(fails {br.get('consecutive_failures', 0)}, "
            f"opens {br.get('opens', 0)}) "
            f"outstanding={r.get('outstanding')} "
            f"queue={r.get('queue_depth')}"
            + (f" reason={r['reason']}" if r.get("reason") else ""))
    evs = rv.get("events") or []
    if evs:
        lines.append(f"    last {min(len(evs), last_events)} of "
                     f"{len(evs)} fleet events in the "
                     f"{rv.get('window_s', 0):.0f}s window:")
        for e in evs[-last_events:]:
            extra = {k: v for k, v in e.items()
                     if k not in ("t_mono", "ts", "kind", "replica")}
            lines.append(
                f"      {str(e.get('kind')):<16s} "
                f"{str(e.get('replica')):<6s} "
                + (json.dumps(extra, default=str)[:90] if extra else ""))
    return lines


def _fleet_requests_section(doc, top=5):
    """Which requests were in flight and where each one's time went, on
    which replica — from the router's parent-side flight records."""
    reqs = [r for r in (doc.get("router") or {}).get("requests") or []
            if r.get("kind") == "request"]
    if not reqs:
        return []
    by_replica = {}
    for r in reqs:
        key = (r.get("replica") or "?", r.get("outcome") or "?")
        by_replica[key] = by_replica.get(key, 0) + 1
    lines = [f"requests  : {len(reqs)} routed requests in the router's "
             "ring: "
             + ", ".join(f"{rep}:{out}={n}" for (rep, out), n in
                         sorted(by_replica.items()))]
    timed = [r for r in reqs if r.get("latency_us") is not None]
    for r in sorted(timed, key=lambda r: -r["latency_us"])[:top]:
        q, d = r.get("queue_us"), r.get("device_us")
        split = (f"queue {q / 1e3:.1f}ms / device {d / 1e3:.1f}ms"
                 if q is not None and d is not None
                 else "no replica split (untraced)")
        lines.append(
            f"    {str(r.get('trace_id')):<20s} "
            f"{r['latency_us'] / 1e3:8.1f}ms on "
            f"{str(r.get('replica')):<5s} ({split}, "
            f"rows {r.get('rows')}, {r.get('outcome')})")
    return lines


def _fleet_scrape_section(doc):
    hist = (doc.get("router") or {}).get("scrape_history") or {}
    lines = []
    for name, entries in sorted(hist.items()):
        if not entries:
            continue
        last = entries[-1].get("stats") or {}
        lines.append(f"    {str(name):<6s} {len(entries)} scrapes in "
                     "window; last: "
                     + json.dumps(last, default=str)[:140])
    return ["scrapes   :"] + lines if lines else []


def fleet_report(doc, request=None):
    """The cross-process incident story: the router's view of the
    ejection window, then each embedded replica bundle rendered with
    the single-process report."""
    lines = _fleet_header(doc)
    lines.append("")
    lines += _fleet_router_section(doc)
    sec = _fleet_requests_section(doc)
    if sec:
        lines.append("")
        lines += sec
    sec = _fleet_scrape_section(doc)
    if sec:
        lines.append("")
        lines += sec
    for name, sub in sorted((doc.get("replicas") or {}).items()):
        lines.append("")
        if isinstance(sub, dict) and sub.get("schema") == BUNDLE_SCHEMA:
            lines.append(f"replica {name} — its own watchdog bundle, "
                         "frozen at ejection:")
            lines += ["  " + ln for ln in
                      report(sub, request=request).splitlines()]
        else:
            err = (sub or {}).get("error") if isinstance(sub, dict) else sub
            lines.append(f"replica {name}: bundle unavailable ({err})")
    lines.append("=" * 72)
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# chrome-trace rendering
# ---------------------------------------------------------------------------

def _recorder_track(doc, base_pid):
    """The flight recorder's wide events as their own timeline row:
    steps as slices (ts - dur .. ts), requests/markers as instants."""
    out = [{"name": "process_name", "ph": "M", "pid": base_pid, "tid": 0,
            "args": {"name": "flight recorder (wide events)"}}]
    for r in doc.get("wide_events") or []:
        kind = r.get("kind", "event")
        ts = float(r.get("ts_us", 0.0))
        if kind == "step" and r.get("dur_us"):
            dur = float(r["dur_us"])
            out.append({"name": f"step#{r.get('step')}", "cat": "wide",
                        "ph": "X", "ts": max(ts - dur, 0.0), "dur": dur,
                        "pid": base_pid, "tid": 1, "args": r})
        else:
            out.append({"name": f"{kind}:{r.get('trace_id', r.get('seq'))}",
                        "cat": "wide", "ph": "i", "s": "p", "ts": ts,
                        "pid": base_pid, "tid": 2, "args": r})
    return out


def write_trace(doc, out_path, flows=True):
    tl = _timeline()
    events = list(doc.get("trace_tail") or [])
    extra = []
    if flows:
        extra += tl.request_flows(events)
    extra += tl.goodput_track(events)
    base_pid = max((e.get("pid", 0) for e in events + extra
                    if isinstance(e.get("pid"), (int, float))),
                   default=0) + 2
    extra += _recorder_track(doc, base_pid)
    events = events + extra
    events.sort(key=lambda e: (e.get("ph") != "M", e.get("ts", 0.0)))
    if events:
        tl.validate_timeline(events)
    with open(out_path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms",
                   "metadata": {"producer": "tools/diagnose.py",
                                "bundle_reason": doc.get("reason")}}, f)
    return len(events)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("bundle", nargs="?",
                    help="path to a bundle-*.json diagnostic bundle")
    ap.add_argument("--list", nargs="?", const="", default=None,
                    metavar="DIR",
                    help="list bundles in DIR (default: the standard "
                         "diagnostic dir) and exit")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="additionally render the bundle's trace tail + "
                         "wide events as a chrome trace")
    ap.add_argument("--no-flows", action="store_true",
                    help="skip request↔batch flow arrows in --trace")
    ap.add_argument("--request", default=None, metavar="TRACE_ID",
                    help="append everything known about one request id")
    ap.add_argument("--fleet", action="store_true",
                    help="expect a fleet incident bundle "
                         "(fleet-bundle-*.json) and render the "
                         "cross-process story; fleet bundles are also "
                         "auto-detected by schema")
    a = ap.parse_args(argv)

    if a.list is not None:
        root = a.list or "/tmp/paddle_tpu_diagnostics"
        found = sorted(
            os.path.join(root, f) for f in
            (os.listdir(root) if os.path.isdir(root) else [])
            if (f.startswith("bundle-") or f.startswith("fleet-bundle-"))
            and f.endswith(".json"))
        for p in found:
            print(p)
        if not found:
            print(f"no bundles under {root}", file=sys.stderr)
            return 1
        return 0

    if not a.bundle:
        print("diagnose.py: a bundle path (or --list) is required",
              file=sys.stderr)
        return 2
    doc = load_bundle(a.bundle)
    if a.fleet and not is_fleet_bundle(doc):
        print(f"diagnose.py: {a.bundle} is a single-process bundle "
              f"(schema={doc.get('schema')!r}), not a fleet bundle",
              file=sys.stderr)
        return 2
    if is_fleet_bundle(doc):
        print(fleet_report(doc, request=a.request))
        if a.trace:
            # render the ejected replica's embedded trace tail — its
            # device-side story around the incident
            sub = (doc.get("replicas") or {}).get(doc.get("replica"))
            if isinstance(sub, dict) and sub.get("schema") == \
                    BUNDLE_SCHEMA:
                n = write_trace(sub, a.trace, flows=not a.no_flows)
                print(f"\n{n} events (replica {doc.get('replica')}) -> "
                      f"{a.trace}; open in chrome://tracing or "
                      f"ui.perfetto.dev")
            else:
                print(f"\nno embedded replica bundle to render as a "
                      f"trace (replica {doc.get('replica')} "
                      f"unreachable at freeze)", file=sys.stderr)
        return 0
    print(report(doc, request=a.request))
    if a.trace:
        n = write_trace(doc, a.trace, flows=not a.no_flows)
        print(f"\n{n} events -> {a.trace}; open in chrome://tracing or "
              f"ui.perfetto.dev")
    return 0


if __name__ == "__main__":
    sys.exit(main())
