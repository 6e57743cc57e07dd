"""paddle_tpu.fluid.autotune — the profile-guided self-tuning runtime.

The reference fork ships ~50 runtime gflags plus BuildStrategy /
ExecutionStrategy knobs and leaves their values to operator folklore;
this repro grew an even larger surface (bucket edges, inflight depth,
``steps_per_dispatch``, allreduce bucket size, serving ``max_batch`` /
``max_wait``) while PRs 1/2/9/16 built exactly
the measurement plane needed to set them automatically.  This module
closes that loop (ROADMAP item 4):

* **propose** — candidate configs over a declared :class:`KnobSpace`
  (deterministic given a seed: a seeded run replays the same search).
* **price** — each candidate is costed FOR FREE via the AOT
  ``device_stats`` analysis (the SNIPPETS pjit idiom:
  ``lower().compile()`` then ``cost_analysis``/``memory_analysis``
  without ever executing a step).  Candidates whose predicted
  per-device peak exceeds the HBM budget are rejected outright —
  ``memory_analysis`` says OOM before the device does — and survivors
  are ranked by a FLOPs/HBM-bytes roofline model so the cheapest-looking
  configs probe first.
* **probe** — survivors run short flight-recorder-instrumented windows
  (``FLAGS_auto_tune_probe_steps`` real steps under an
  ``autotune::probe`` span) scored by the recorder's step durations and
  the goodput ratio; the serving tuner scores the live window-p99 the
  SLO watchdog computes.
* **commit / revert** — the winner is applied (program hints + flags,
  or live engine knobs); a serving candidate whose probe window
  breached the p99 SLO is ALWAYS reverted, never committed.

Winning configs persist in the PR-2 persistent cache keyed by
``(program fingerprint, jax version, backend, device count)`` so a
restarted process starts tuned with ZERO probe cost, and every decision
is observable: ``autotune.probes/accepts/rejects/reverts`` instruments,
``autotune.speedup`` gauge, decisions in ``/stats`` and in watchdog
diagnostic bundles.  See docs/performance.md "Auto-tuning".

Two surfaces:

* training — ``BuildStrategy.auto_tune = True`` (or ``FLAGS_auto_tune``)
  tunes a program ONCE per fingerprint on its first ``Executor.run``:
  bucket edges, ``steps_per_dispatch`` and inflight depth.
* serving — ``ServingEngine(auto_tune=True)`` (or the flag, reconciled
  by :func:`apply_flags` exactly like the PR-9 metrics-export pattern)
  hill-climbs ``max_batch``/``max_wait_us`` online against the live
  windowed p99.

Everything here degrades, never raises into the training loop or the
batcher: a failed price, probe, or store read falls back to the
untuned defaults and counts itself.
"""
from __future__ import annotations

import hashlib
import itertools
import json
import random
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Sequence

from . import core, trace, compile_cache, flight_recorder

SCHEMA = 1                       # persisted-config schema; bump = stale
MAX_DECISIONS = 256              # bounded in-process decision log
DEFAULT_PROBE_STEPS = 8
DEFAULT_INTERVAL_S = 2.0         # serving tuner tick period
MIN_TRAIN_GAIN = 1.02            # commit a non-baseline only if >=2% faster
MIN_SERVE_GAIN = 1.02            # commit only if >=2% more throughput
SERVE_P99_GUARD = 1.25           # no-SLO fallback: p99 may grow <=25%

# roofline constants for the pricing model (ranking only — relative
# order is what matters, so one generic accelerator profile is enough)
_PEAK_FLOPS = 100e12
_PEAK_BYTES = 1e12

__all__ = [
    "Knob", "KnobSpace", "training_space", "serving_space", "candidates",
    "config_key", "save_config", "load_config",
    "maybe_tune_executor", "ServingAutoTuner", "attach_engine",
    "register_engine", "apply_flags", "enabled",
    "decisions", "state", "bench_block", "hbm_budget_bytes",
    "reset_for_tests",
]

_lock = threading.Lock()
_decisions: List[Dict[str, Any]] = []
_tuned: set = set()              # (fingerprint, fetch_names) memo
_engines: "weakref.WeakSet" = weakref.WeakSet()


def enabled() -> bool:
    return bool(core.get_flag("auto_tune"))


def probe_steps() -> int:
    return int(core.get_flag("auto_tune_probe_steps",
                             DEFAULT_PROBE_STEPS) or DEFAULT_PROBE_STEPS)


# ---------------------------------------------------------------------------
# knob space
# ---------------------------------------------------------------------------

class Knob:
    """One tunable: a name, where it lives (``kind``), and the candidate
    values the search may propose.  Kinds:

    * ``"flag"``   — a ``FLAGS_*`` value applied via :func:`core.set_flags`
    * ``"hint"``   — a ``program._hints`` entry (per-program)
    * ``"engine"`` — a live :class:`ServingEngine` attribute
    """

    def __init__(self, name: str, values: Sequence, kind: str = "flag"):
        if kind not in ("flag", "hint", "engine"):
            raise ValueError(f"unknown knob kind {kind!r}")
        self.name = name
        self.kind = kind
        # dedup preserving order; the FIRST value is the baseline
        seen, vals = set(), []
        for v in values:
            k = repr(v)
            if k not in seen:
                seen.add(k)
                vals.append(v)
        self.values = vals

    def current(self, program=None, engine=None):
        if self.kind == "hint":
            return (program._hints.get(self.name)
                    if program is not None else None)
        if self.kind == "engine":
            return getattr(engine, self.name, None) \
                if engine is not None else None
        return core.get_flag(self.name)

    def apply(self, value, program=None, engine=None) -> None:
        if self.kind == "hint":
            if program is None:
                return
            if value is None:
                program._hints.pop(self.name, None)
            else:
                program._hints[self.name] = value
        elif self.kind == "engine":
            if engine is not None:
                setattr(engine, self.name, value)
        else:
            # plain flag write — NOT core.set_flags: the reconciliation
            # dispatch there may restart surfaces, which a probe loop
            # must never do
            core._FLAGS[self.name] = value

    def __repr__(self):
        return f"Knob({self.name}, {self.kind}, {self.values})"


class KnobSpace:
    """An ordered set of :class:`Knob`\\ s.  ``candidates()`` is the
    deterministic proposal stream: the full cartesian product when it is
    small, otherwise a seeded sample — either way the baseline (every
    knob at its first value) is candidate 0 and the same seed replays
    the same sequence."""

    def __init__(self, knobs: Sequence[Knob]):
        self.knobs = [k for k in knobs if k.values]

    def baseline(self) -> Dict[str, Any]:
        return {k.name: k.values[0] for k in self.knobs}

    def candidates(self, seed: int = 0,
                   limit: Optional[int] = None) -> List[Dict[str, Any]]:
        if not self.knobs:
            return []
        prod = 1
        for k in self.knobs:
            prod *= len(k.values)
        cap = int(limit or core.get_flag("auto_tune_max_candidates", 16)
                  or 16)
        names = [k.name for k in self.knobs]
        if prod <= cap:
            out = [dict(zip(names, vals)) for vals in
                   itertools.product(*(k.values for k in self.knobs))]
        else:
            rng = random.Random(int(seed))
            seen = {repr(sorted(self.baseline().items()))}
            out = [self.baseline()]
            while len(out) < cap:
                cand = {k.name: rng.choice(k.values) for k in self.knobs}
                key = repr(sorted(cand.items()))
                if key not in seen:
                    seen.add(key)
                    out.append(cand)
        base = self.baseline()
        out.sort(key=lambda c: (c != base,
                                repr(sorted(c.items()))))
        return out[:cap]

    def apply(self, config: Dict[str, Any], program=None,
              engine=None) -> None:
        for k in self.knobs:
            if k.name in config:
                k.apply(config[k.name], program=program, engine=engine)

    def snapshot(self, program=None, engine=None) -> Dict[str, Any]:
        return {k.name: k.current(program=program, engine=engine)
                for k in self.knobs}


def candidates(space: KnobSpace, seed: int = 0,
               limit: Optional[int] = None) -> List[Dict[str, Any]]:
    return space.candidates(seed=seed, limit=limit)


def training_space(program=None, feed=None) -> KnobSpace:
    """The executor-side knob space for one program: bucket edges (when
    bucketing is active) and ``steps_per_dispatch`` + inflight depth (the
    async-pipeline pair, probed through ``run_async``)."""
    knobs: List[Knob] = []
    hints = getattr(program, "_hints", {}) if program is not None else {}
    want_bucketing = hints.get("shape_bucketing")
    if want_bucketing is None:
        want_bucketing = core.get_flag("shape_bucketing")
    n = 0
    if feed:
        try:
            import numpy as np
            dims = {np.shape(v)[0] for v in feed.values()
                    if np.ndim(v) >= 1}
            n = int(next(iter(dims))) if len(dims) == 1 else 0
        except Exception:               # noqa: BLE001
            n = 0
    if want_bucketing and n:
        cur = compile_cache.normalize_edges(
            hints.get("bucket_edges")
            or core.get_flag("shape_bucket_edges"))
        vals: List[Any] = [cur]
        vals.append(compile_cache.pow2_edges(max(n, 2)))
        # exact-fit single edge: zero padding waste for a stable loader
        vals.append((compile_cache.bucket_for(
            n, compile_cache.pow2_edges(max(n, 2))),))
        if cur:
            # coarser variant: half the edges -> fewer executables
            vals.append(tuple(cur[1::2]) or cur)
        knobs.append(Knob("bucket_edges",
                          [compile_cache.normalize_edges(v) for v in vals],
                          kind="hint"))
    cur_k = int(hints.get("steps_per_dispatch") or 1)
    knobs.append(Knob("steps_per_dispatch",
                      [cur_k] + [k for k in (1, 2, 4) if k != cur_k],
                      kind="hint"))
    cur_in = int(core.get_flag("max_inflight_steps", 2) or 2)
    knobs.append(Knob("max_inflight_steps",
                      [cur_in] + [d for d in (1, 2, 4) if d != cur_in]))
    if program is not None and (
            getattr(program, "_sharding_plan", None) is not None
            or hints.get("sharding")):
        # gradient-coalescing bucket width only matters once a sharding
        # plan makes the all-reduce ring real — without one the knob is
        # dead weight in the cartesian product
        cur_fg = int(hints.get("fuse_grad_size_in_num") or 32)
        knobs.append(Knob("fuse_grad_size_in_num",
                          [cur_fg] + [v for v in (8, 32, 128)
                                      if v != cur_fg],
                          kind="hint"))
    return KnobSpace(knobs)


def serving_space(engine) -> KnobSpace:
    """The live serving pair: ``max_batch`` (clamped to the engine's
    largest declared bucket) and ``max_wait_us``."""
    mb = int(engine.max_batch)
    cap = int(engine.bucket_edges[-1]) if engine.bucket_edges else mb * 4
    mb_vals = [mb] + [v for v in (mb * 2, max(1, mb // 2))
                      if 1 <= v <= cap and v != mb]
    mw = int(engine.max_wait_us)
    mw_vals = [mw] + [v for v in (mw * 2, max(200, mw // 2))
                      if v != mw and 200 <= v <= 100_000]
    return KnobSpace([Knob("max_batch", mb_vals, kind="engine"),
                      Knob("max_wait_us", mw_vals, kind="engine")])


# ---------------------------------------------------------------------------
# persisted-config store (the PR-2 PersistentCache, new key namespace)
# ---------------------------------------------------------------------------

def config_key(fingerprint: str, surface: str = "train") -> str:
    """Stable store key: program fingerprint + jax version + backend +
    device count + surface.  A different backend, device topology, or
    schema never reuses a tuned config that was measured elsewhere."""
    import jax
    raw = "|".join(["autotune", str(SCHEMA), str(fingerprint),
                    jax.__version__, jax.default_backend(),
                    str(jax.device_count()), surface])
    return "at-" + hashlib.sha256(raw.encode()).hexdigest()


def save_config(fingerprint: str, config: Dict[str, Any],
                surface: str = "train",
                extra: Optional[Dict[str, Any]] = None) -> Optional[str]:
    """Persist a winning config; returns the store key (None when no
    store is configured).  Never raises — persistence is an optimisation,
    not a correctness dependency."""
    store = compile_cache.config_store()
    if store is None:
        return None
    import jax
    key = config_key(fingerprint, surface)
    meta = {"schema": SCHEMA, "fingerprint": str(fingerprint),
            "backend": jax.default_backend(),
            "n_devices": jax.device_count(),
            "surface": surface, "config": dict(config),
            "ts": time.time()}
    if extra:
        meta.update(extra)
    try:
        store.record(key, meta)
    except Exception:                   # noqa: BLE001
        trace.metrics().counter("autotune.store_errors").inc()
        return None
    return key


def load_config(fingerprint: str,
                surface: str = "train") -> Optional[Dict[str, Any]]:
    """Load + validate a persisted config.  A corrupt, stale-schema, or
    mismatched entry (fingerprint/backend/device count) returns None —
    the executor falls back to untuned defaults, never crashes."""
    store = compile_cache.config_store()
    if store is None:
        return None
    import jax
    meta = store.get(config_key(fingerprint, surface))
    if meta is None:
        return None
    try:
        ok = (int(meta.get("schema", -1)) == SCHEMA
              and meta.get("fingerprint") == str(fingerprint)
              and meta.get("backend") == jax.default_backend()
              and int(meta.get("n_devices", -1)) == jax.device_count()
              and meta.get("surface") == surface
              and isinstance(meta.get("config"), dict))
    except Exception:                   # noqa: BLE001
        ok = False
    if not ok:
        trace.metrics().counter("autotune.stale_configs").inc()
        return None
    return meta


# ---------------------------------------------------------------------------
# decision log + observability
# ---------------------------------------------------------------------------

def _record_decision(d: Dict[str, Any]) -> Dict[str, Any]:
    d = dict(d)
    d.setdefault("ts", time.time())
    with _lock:
        _decisions.append(d)
        del _decisions[:-MAX_DECISIONS]
    if trace.enabled():
        trace.instant("autotune_decision", cat="autotune",
                      args={k: d.get(k) for k in
                            ("surface", "action", "reason", "config",
                             "speedup", "source")})
    return d


def decisions(n: Optional[int] = None) -> List[Dict[str, Any]]:
    with _lock:
        out = list(_decisions)
    return out[-int(n):] if n else out


def state() -> Dict[str, Any]:
    """Compact tuner state for ``/stats`` and diagnostic bundles:
    instrument totals plus the last few decisions."""
    out = {
        "enabled": enabled(),
        "probes": trace.counter_value("autotune.probes"),
        "accepts": trace.counter_value("autotune.accepts"),
        "rejects": trace.counter_value("autotune.rejects"),
        "reverts": trace.counter_value("autotune.reverts"),
        "warm_starts": trace.counter_value("autotune.warm_starts"),
        "speedup": round(trace.gauge_value("autotune.speedup"), 4),
    }
    last = decisions(3)
    if last:
        out["last_decisions"] = [
            {k: d.get(k) for k in ("surface", "action", "reason",
                                   "config", "speedup", "source",
                                   "probe_steps", "mesh")}
            for d in last]
    return out


def bench_block() -> Dict[str, Any]:
    """The ``autotune`` block every bench leg reports: the chosen
    config, what the search cost in probe steps, and the tuned-vs-
    untuned delta.  ``{"enabled": False}`` when the tuner never ran in
    this process — the block is always present so BENCH rounds carry
    the evidence either way."""
    commits = [d for d in decisions()
               if d.get("action") == "accept"]
    if not commits:
        return {"enabled": enabled(), "decisions": len(decisions())}
    last = commits[-1]
    probes = sum(int(d.get("probe_steps") or 0) for d in decisions())
    return {
        "enabled": True,
        "surface": last.get("surface"),
        "chosen": last.get("config"),
        "source": last.get("source", "probe"),
        "probe_cost_steps": probes,
        "speedup": round(float(last.get("speedup") or 1.0), 4),
        "decisions": len(decisions()),
    }


def hbm_budget_bytes() -> Optional[int]:
    """Per-device memory budget the OOM filter prices against:
    ``FLAGS_auto_tune_hbm_budget_mb`` when set (tests pin it), else the
    backend's reported ``bytes_limit``, else None (no rejection)."""
    mb = float(core.get_flag("auto_tune_hbm_budget_mb", 0) or 0)
    if mb > 0:
        # float-valued: a test can pin a sub-MB budget to discriminate
        # between demo-scale candidates deterministically
        return int(mb * (1 << 20))
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
        limit = int(stats.get("bytes_limit") or 0)
        return limit or None
    except Exception:                   # noqa: BLE001
        return None


# ---------------------------------------------------------------------------
# training surface — tune once per (fingerprint, fetch set) on first run
# ---------------------------------------------------------------------------

def maybe_tune_executor(exe, program, feed, fetch_names, scope) -> None:
    """Called by ``Executor.run`` when a program opted in
    (``BuildStrategy.auto_tune`` hint or ``FLAGS_auto_tune``).  Tunes at
    most once per (fingerprint, fetch set): a persisted winner applies
    with ZERO probe windows; otherwise the propose→price→probe→commit
    search runs here, re-entering ``run``/``run_async`` under the
    ``_in_autotune`` guard.  Never raises into the training loop."""
    try:
        from .executor import _fingerprint
        fp = _fingerprint(program)
        memo = (fp, tuple(fetch_names))
        with _lock:
            if memo in _tuned:
                return
            _tuned.add(memo)            # claim even on failure: a broken
            # search must not retry (and re-pay probes) on every step
        persisted = load_config(fp, "train")
        space = training_space(program, feed)
        if persisted is not None:
            space.apply(persisted["config"], program=program)
            trace.metrics().counter("autotune.warm_starts").inc()
            _record_decision({
                "surface": "train", "action": "accept",
                "source": "persisted", "fingerprint": fp[:12],
                "config": persisted["config"], "probe_steps": 0,
                "speedup": persisted.get("speedup")})
            return
        _tune_training(exe, program, feed, fetch_names, scope, fp, space)
    except Exception as e:              # noqa: BLE001 — degrade, count
        trace.metrics().counter("autotune.errors").inc()
        import sys
        print(f"paddle_tpu.autotune: WARNING: tuning skipped: "
              f"{type(e).__name__}: {e} — running untuned",
              file=sys.stderr)


def _price_training(exe, program, feed, fetch_names, scope, space, cands):
    """AOT-price every candidate WITHOUT executing: apply, lower+compile,
    read ``memory_analysis``/``cost_analysis`` (Executor.analyze), and
    restore.  Returns ``[(config, info|None, est_seconds|None)]`` with
    OOM candidates dropped (counted + logged, ``executed: False``).
    Prices are memoized on the compile-relevant knob values — candidates
    that only differ in dispatch knobs share one analysis."""
    budget = hbm_budget_bytes()
    memo: Dict[str, Any] = {}
    orig = space.snapshot(program=program)
    priced = []
    try:
        for cand in cands:
            sig = repr(cand.get("bucket_edges"))
            if sig not in memo:
                space.apply(cand, program=program)
                memo[sig] = exe.analyze(program, feed=feed,
                                        fetch_list=list(fetch_names),
                                        scope=scope)
            info = memo[sig]
            peak = int(info.get("per_device_peak_bytes") or 0) \
                if info else 0
            if budget and info and peak > budget:
                trace.metrics().counter("autotune.rejects").inc()
                _record_decision({
                    "surface": "train", "action": "reject",
                    "reason": "oom_predicted", "config": cand,
                    "executed": False, "probe_steps": 0,
                    "peak_bytes": peak, "budget_bytes": budget})
                continue
            est = None
            if info:
                est = max(float(info.get("flops") or 0) / _PEAK_FLOPS,
                          float(info.get("bytes_accessed") or 0)
                          / _PEAK_BYTES)
            priced.append((cand, info, est))
    finally:
        space.apply(orig, program=program)
    # cheapest predicted cost probes first; un-analysable candidates last
    priced.sort(key=lambda t: (t[2] is None, t[2] or 0.0))
    return priced


def _probe_training(exe, program, feed, fetch_names, scope, space,
                    cand) -> Optional[float]:
    """One probe window: apply the candidate and run
    ``FLAGS_auto_tune_probe_steps`` REAL steps through the async runner
    (which exercises ``steps_per_dispatch``/inflight exactly as a tuned
    run would), under an ``autotune::probe`` span.  Scored by the flight
    recorder's step durations (median ``dur_us``) with wall clock as the
    fallback.  Returns per-step seconds, or None when the window failed
    (the candidate is rejected, the loop continues)."""
    steps = max(1, probe_steps())
    space.apply(cand, program=program)
    rec = flight_recorder.recorder()
    mark = rec.total
    try:
        with trace.span("autotune::probe", cat="autotune",
                        args={"surface": "train", "config": repr(cand),
                              "steps": steps}):
            t0 = time.perf_counter()
            k = int(cand.get("steps_per_dispatch") or 1)
            depth = int(cand.get("max_inflight_steps") or 1)
            if k > 1 or depth > 1:
                for _ in range(steps):
                    exe.run_async(program, feed=feed,
                                  fetch_list=list(fetch_names),
                                  scope=scope, max_inflight=depth,
                                  steps_per_dispatch=k)
                exe.drain_async()
            else:
                for _ in range(steps):
                    exe.run(program, feed=feed,
                            fetch_list=list(fetch_names), scope=scope,
                            return_numpy=False)
            wall = time.perf_counter() - t0
    except Exception:                   # noqa: BLE001 — a candidate that
        # cannot execute is a rejection, not a crash
        trace.metrics().counter("autotune.rejects").inc()
        _record_decision({"surface": "train", "action": "reject",
                          "reason": "probe_error", "config": cand,
                          "probe_steps": steps})
        return None
    trace.metrics().counter("autotune.probes").inc()
    # recorder truth: median in-executor step time of this window (the
    # first step of a window carries the candidate's compile; median is
    # robust to it, wall/steps is not)
    durs = sorted(e["dur_us"] for e in rec.snapshot(rec.total - mark)
                  if e.get("kind") == "step" and e.get("dur_us"))
    if durs:
        return durs[len(durs) // 2] / 1e6
    return wall / steps


def _tune_training(exe, program, feed, fetch_names, scope, fp,
                   space) -> None:
    cands = space.candidates(
        seed=int(getattr(program, "random_seed", 0) or 0))
    if len(cands) < 2:
        return
    gp0 = trace.elapsed_us()
    priced = _price_training(exe, program, feed, fetch_names, scope,
                             space, cands)
    if not priced:
        return                          # everything predicted OOM: keep
        # the baseline the user configured — it is their explicit choice
    baseline = space.baseline()
    exe._in_autotune = True
    scores: List[Dict[str, Any]] = []
    try:
        for cand, info, est in priced:
            s = _probe_training(exe, program, feed, fetch_names, scope,
                                space, cand)
            if s is not None:
                scores.append({"config": cand, "step_seconds": s,
                               "est_seconds": est,
                               "analysis": {k: info.get(k) for k in
                                            ("flops", "bytes_accessed",
                                             "per_device_peak_bytes")}
                               if info else None})
    finally:
        exe._in_autotune = False
    if not scores:
        space.apply(baseline, program=program)
        return
    base_s = next((s["step_seconds"] for s in scores
                   if s["config"] == baseline), None)
    best = min(scores, key=lambda s: s["step_seconds"])
    # commit guard: the tuned loop must never end below the untuned
    # baseline — a non-baseline winner needs a real margin, anything
    # less keeps the measured status quo
    if (base_s is not None and best["config"] != baseline
            and base_s / best["step_seconds"] < MIN_TRAIN_GAIN):
        best = next(s for s in scores if s["config"] == baseline)
    space.apply(best["config"], program=program)
    speedup = (base_s / best["step_seconds"]
               if base_s else 1.0)
    trace.metrics().counter("autotune.accepts").inc()
    trace.metrics().gauge("autotune.speedup").set(round(speedup, 4))
    gp_ratio = None
    try:
        from . import goodput
        rep = goodput.snapshot(t0_us=gp0) if gp0 is not None else None
        gp_ratio = rep.get("ratio") if rep else None
    except Exception:                   # noqa: BLE001
        pass
    d = _record_decision({
        "surface": "train", "action": "accept", "source": "probe",
        "fingerprint": fp[:12], "config": best["config"],
        "baseline": baseline,
        "baseline_step_seconds": base_s,
        "step_seconds": best["step_seconds"],
        "speedup": round(speedup, 4),
        "probe_steps": probe_steps() * len(scores),
        "candidates": [{"config": s["config"],
                        "step_seconds": round(s["step_seconds"], 6)}
                       for s in scores],
        "goodput_ratio": gp_ratio})
    save_config(fp, best["config"], "train",
                extra={"speedup": d["speedup"],
                       "probe_steps": d["probe_steps"]})


# ---------------------------------------------------------------------------
# serving surface — online hill climbing against the live window p99
# ---------------------------------------------------------------------------

class ServingAutoTuner:
    """Online tuner for one :class:`ServingEngine`: every tick it either
    (a) observes the current committed config's window, proposes a
    neighbour of ``(max_batch, max_wait_us)`` and applies it, or (b)
    judges the pending candidate's probe window and commits or reverts.
    The windowed stats come from the flight recorder's request records
    (completions + p99 latency); the SLO guard reverts ANY candidate
    whose probe window breached p99 — a breaching config is never
    committed.  ``tick()`` is public so tests (and the fleet drill)
    drive the state machine deterministically; ``start()`` wraps it in
    an interval thread for production."""

    def __init__(self, engine, slo_ms: Optional[float] = None,
                 interval_s: Optional[float] = None, seed: int = 0,
                 flag_started: bool = False, persist: bool = True):
        self.engine = engine
        self._slo_ms = slo_ms
        self.interval_s = float(interval_s or DEFAULT_INTERVAL_S)
        self.seed = int(seed)
        self.flag_started = bool(flag_started)
        self.persist = bool(persist)
        self._rng = random.Random(self.seed)
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._pending: Optional[Dict[str, Any]] = None
        self._cursor = 0
        self._baseline_window: Optional[Dict[str, Any]] = None
        self._fp = _engine_fingerprint(engine)
        self.committed = {"max_batch": int(engine.max_batch),
                          "max_wait_us": int(engine.max_wait_us)}
        self.warm_started = False
        if self.persist:
            self._warm_start()

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "ServingAutoTuner":
        with self._lock:
            if self._thread is not None:
                return self
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="autotune-serving", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        with self._lock:
            t, self._thread = self._thread, None
        self._stop.set()
        if t is not None:
            t.join(timeout=5.0)

    def running(self) -> bool:
        return self._thread is not None

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.tick()
            except Exception:           # noqa: BLE001 — the batcher must
                trace.metrics().counter("autotune.errors").inc()

    # -- signals -------------------------------------------------------------
    def slo_ms(self) -> float:
        if self._slo_ms is not None:
            return float(self._slo_ms)
        return float(core.get_flag("watchdog_p99_ms", 0) or 0)

    def _window(self) -> Dict[str, Any]:
        """Stats since the last cursor: completed requests + windowed
        p99 from the flight recorder's request records, falling back to
        the watchdog's live ``window_p99_ms`` gauge when the ring holds
        no requests (recorder disabled)."""
        rec = flight_recorder.recorder()
        total = rec.total
        new = rec.snapshot(max(0, total - self._cursor)) \
            if total > self._cursor else []
        self._cursor = total
        lats = sorted(e["latency_us"] for e in new
                      if e.get("kind") == "request"
                      and e.get("outcome") == "ok"
                      and e.get("latency_us"))
        if lats:
            p99 = lats[min(len(lats) - 1, int(0.99 * len(lats)))] / 1e3
            return {"completed": len(lats), "p99_ms": round(p99, 3)}
        wd_p99 = trace.gauge_value("watchdog.window_p99_ms")
        done = self.engine._ins.hist_stats("latency_seconds").get(
            "count", 0)
        prev = getattr(self, "_done_prev", 0)
        self._done_prev = done
        return {"completed": max(0, done - prev),
                "p99_ms": round(wd_p99, 3)}

    # -- the state machine ---------------------------------------------------
    def _neighbours(self) -> List[Dict[str, Any]]:
        space = serving_space(self.engine)
        base = {"max_batch": int(self.engine.max_batch),
                "max_wait_us": int(self.engine.max_wait_us)}
        out = []
        for k in space.knobs:
            for v in k.values:
                cand = dict(base)
                if cand.get(k.name) != v:
                    cand[k.name] = v
                    out.append(cand)
        # deterministic given the seed: the decision log replays
        out.sort(key=lambda c: repr(sorted(c.items())))
        self._rng.shuffle(out)
        return out

    def _apply(self, cfg: Dict[str, Any]) -> None:
        eng = self.engine
        eng.max_batch = int(cfg["max_batch"])
        eng.max_wait_us = int(cfg["max_wait_us"])

    def tick(self) -> Optional[Dict[str, Any]]:
        """One transition.  Returns the decision recorded this tick (a
        judge tick), or None (an observe/propose tick)."""
        eng = self.engine
        if getattr(eng, "_closed", False) or eng.paused():
            return None
        if self._pending is None:
            self._baseline_window = self._window()
            neigh = self._neighbours()
            if not neigh:
                return None
            cand = neigh[0]
            self._apply(cand)
            self._pending = {"config": cand, "t0_ns": trace.now()}
            trace.metrics().counter("autotune.probes").inc()
            return None
        pend, self._pending = self._pending, None
        win = self._window()
        base = self._baseline_window or {"completed": 0, "p99_ms": 0.0}
        slo = self.slo_ms()
        breached = bool(slo and win["p99_ms"] > slo)
        trace.complete("autotune::probe", pend["t0_ns"], cat="autotune",
                       args={"surface": "serving",
                             "engine": eng.name,
                             "config": repr(pend["config"]),
                             "completed": win["completed"],
                             "p99_ms": win["p99_ms"],
                             "breached": breached})
        better = (not breached
                  and win["completed"] > 0
                  and win["completed"]
                  >= base.get("completed", 0) * MIN_SERVE_GAIN
                  and (slo or base.get("p99_ms", 0) <= 0
                       or win["p99_ms"]
                       <= base["p99_ms"] * SERVE_P99_GUARD))
        if breached or not better:
            # the guard: a probe window that breached the SLO (or just
            # failed to win) is rolled back — the engine never keeps a
            # config it could not defend in its own window
            self._apply(self.committed)
            name = "reverts" if breached else "rejects"
            trace.metrics().counter(f"autotune.{name}").inc()
            return _record_decision({
                "surface": "serving", "engine": eng.name,
                "action": "revert" if breached else "reject",
                "reason": "slo_breach" if breached else "no_gain",
                "config": pend["config"], "window": win,
                "baseline_window": base, "slo_ms": slo,
                "mesh": _engine_mesh(eng)})
        self.committed = dict(pend["config"])
        speedup = (win["completed"] / base["completed"]
                   if base.get("completed") else 1.0)
        trace.metrics().counter("autotune.accepts").inc()
        trace.metrics().gauge("autotune.speedup").set(round(speedup, 4))
        d = _record_decision({
            "surface": "serving", "engine": eng.name,
            "action": "accept", "source": "probe",
            "config": dict(self.committed), "window": win,
            "baseline_window": base, "slo_ms": slo,
            "speedup": round(speedup, 4),
            "mesh": _engine_mesh(eng)})
        if self.persist and self._fp:
            save_config(self._fp, self.committed, "serving",
                        extra={"speedup": d["speedup"]})
        return d

    # -- persistence ---------------------------------------------------------
    def _warm_start(self) -> None:
        if not self._fp:
            return
        meta = load_config(self._fp, "serving")
        if meta is None:
            return
        cfg = meta["config"]
        try:
            self._apply({"max_batch": int(cfg["max_batch"]),
                         "max_wait_us": int(cfg["max_wait_us"])})
        except Exception:               # noqa: BLE001 — stale shape
            trace.metrics().counter("autotune.stale_configs").inc()
            return
        self.committed = dict(cfg)
        self.warm_started = True
        trace.metrics().counter("autotune.warm_starts").inc()
        _record_decision({"surface": "serving", "engine": self.engine.name,
                          "action": "accept", "source": "persisted",
                          "config": dict(cfg), "probe_steps": 0,
                          "speedup": meta.get("speedup"),
                          "mesh": _engine_mesh(self.engine)})

    def state(self) -> Dict[str, Any]:
        return {"running": self.running(),
                "flag_started": self.flag_started,
                "committed": dict(self.committed),
                "pending": dict(self._pending["config"])
                if self._pending else None,
                "warm_started": self.warm_started,
                "slo_ms": self.slo_ms()}


def _engine_mesh(engine) -> Optional[str]:
    """The replica's mesh shape (``"tp:4"``-style) when its frozen
    program carries a sharding plan — lets fleet rollups attribute
    tuner decisions per topology instead of flattening 1-chip and
    8-chip replicas into one bucket."""
    try:
        plan = getattr(getattr(getattr(engine, "_backend", None),
                               "program", None), "_sharding_plan", None)
        if plan is None:
            return None
        shape = plan.describe().get("mesh_shape")
        if isinstance(shape, dict):
            return ",".join(f"{k}:{v}" for k, v in sorted(shape.items()))
        return str(shape) if shape else None
    except Exception:                   # noqa: BLE001
        return None


def _engine_fingerprint(engine) -> Optional[str]:
    """Program identity for the serving store: the executor fingerprint
    of the frozen program when the engine runs one, else a hash of the
    AOT artifact's IO signature."""
    try:
        prog = getattr(engine._backend, "program", None)
        if prog is not None and hasattr(prog, "blocks"):
            from .executor import _fingerprint
            return _fingerprint(prog)
        raw = repr((sorted(engine.feed_names), sorted(engine.fetch_names),
                    tuple(engine.bucket_edges or ())))
        return hashlib.sha1(raw.encode()).hexdigest()
    except Exception:                   # noqa: BLE001
        return None


# ---------------------------------------------------------------------------
# engine registry + flag reconciliation (the PR-9 metrics-export pattern)
# ---------------------------------------------------------------------------

def register_engine(engine) -> None:
    _engines.add(engine)


def attach_engine(engine, programmatic: bool = False,
                  slo_ms: Optional[float] = None,
                  seed: int = 0) -> Optional[ServingAutoTuner]:
    """Called from ``ServingEngine.__init__``: build the engine's tuner.
    ``programmatic=True`` (the ``auto_tune=True`` ctor arg) always gets
    one; otherwise only when ``FLAGS_auto_tune`` is set — and that one
    is marked flag-started so :func:`apply_flags` may stop it later."""
    register_engine(engine)
    if programmatic:
        return ServingAutoTuner(engine, slo_ms=slo_ms, seed=seed)
    if enabled():
        return ServingAutoTuner(engine, slo_ms=slo_ms, seed=seed,
                                flag_started=True)
    return None


def apply_flags() -> None:
    """Reconcile running tuners with the current ``FLAGS_auto_tune*``
    values (mirrors ``metrics_export.apply_flags``): flipping the flag
    on mid-run starts a flag-started tuner on every live registered
    engine that lacks one; flipping it off stops ONLY flag-started
    tuners — a tuner the caller created with ``auto_tune=True`` belongs
    to its engine and is never stopped from here.
    ``FLAGS_auto_tune_dir`` re-roots the config store lazily (the next
    load/save reads the flag); ``FLAGS_auto_tune_probe_steps`` is read
    at probe time, so a new value applies to the next window."""
    on = enabled()
    for eng in list(_engines):
        tuner = getattr(eng, "_autotuner", None)
        if on:
            if tuner is None and not getattr(eng, "_closed", False):
                tuner = ServingAutoTuner(eng, flag_started=True)
                eng._autotuner = tuner
                if getattr(eng, "_started", False):
                    tuner.start()
        else:
            if tuner is not None and tuner.flag_started:
                tuner.stop()
                eng._autotuner = None


def reset_for_tests() -> None:
    """Forget every in-process tuning memo and decision (NOT the
    persisted store): the 'second process' half of a warm-restart test
    without actually forking one."""
    with _lock:
        _decisions.clear()
        _tuned.clear()
