"""Shape bucketing + process-surviving compile cache + recompile hygiene.

Reference: the fluid executor amortises preparation per (program, scope)
via _ExecutorCache (python/paddle/fluid/executor.py:1110) but never sees a
recompile problem — per-op kernel dispatch is shape-polymorphic.  Under
whole-block XLA compilation (executor.py here) every distinct feed shape is
a full recompile: multi-second cold compiles versus microsecond dispatch,
paid again for every ragged tail batch (`drop_last=False` loaders, eval
epoch ends, variable-length NLP batches) and again after every process
restart (preemption recovery).  This module owns
the three defenses, all gated by flags in fluid.core:

* **Shape bucketing** (`FLAGS_shape_bucketing`, `FLAGS_shape_bucket_edges`)
  — pad the leading batch dim up to a bucket edge (powers of two by
  default) so a ragged epoch compiles at most ``len(edges)`` executables.
  The executor threads the true batch size into the compiled step as a
  traced ``__batch_valid__`` scalar; mask-aware batch reductions
  (ops/reduction.py, ops/nn_ops.py batch-norm stats) keep padded-step
  numerics equal to the unpadded step within fp tolerance.
* **Persistent compile cache** — jax's own compilation cache persists the
  compiled XLA executables at :func:`jax_cache_dir`
  (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``); the
  :class:`PersistentCache` index under `FLAGS_persistent_cache_dir` records
  which (program fingerprint, bucketed feed sig, jax/backend version) keys
  have compiled before, so a restarted trainer reports a persistent-warm
  start (zero *cold* misses) and tooling can inspect what lives in the
  cache.
* **Recompile-storm detection** (`FLAGS_recompile_warn_threshold` /
  `FLAGS_recompile_warn_window`) — a sliding-window miss counter that
  fires a trace-plane event with shape/bucket attribution when the miss
  rate says something upstream is feeding unstable shapes.
"""
from __future__ import annotations

import collections
import hashlib
import json
import os
import tempfile
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np


# ---------------------------------------------------------------------------
# bucket-edge algebra
# ---------------------------------------------------------------------------

def next_pow2(n: int) -> int:
    n = int(n)
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def pow2_edges(max_size: int) -> Tuple[int, ...]:
    """Power-of-two edges up to ``max_size``, plus ``max_size`` itself —
    what a loader with a known batch size but unknown tail advertises."""
    max_size = int(max_size)
    edges = {max_size}
    e = 1
    while e < max_size:
        edges.add(e)
        e <<= 1
    return tuple(sorted(edges))


_edges_memo: Dict[Any, Tuple[int, ...]] = {}


def normalize_edges(edges) -> Optional[Tuple[int, ...]]:
    """Canonicalise a user edge spec: ``"8,16,32"`` / list / tuple ->
    sorted tuple of positive ints; None stays None (powers of two).
    Memoised — the executor calls this per run with the same env string /
    hint tuple, which must not cost a re-parse per training step."""
    if edges is None:
        return None
    key = edges if isinstance(edges, (str, tuple)) else tuple(edges)
    hit = _edges_memo.get(key)
    if hit is not None:
        return hit
    parts = [p for p in key.replace(";", ",").split(",") if p.strip()] \
        if isinstance(key, str) else key
    out = tuple(sorted({int(e) for e in parts}))
    if not out or out[0] <= 0:
        raise ValueError(
            f"FLAGS_shape_bucket_edges needs positive ints, got {edges!r}")
    if len(_edges_memo) < 256:      # bound: specs are few in practice
        _edges_memo[key] = out
    return out


def bucket_for(n: int, edges: Optional[Sequence[int]] = None) -> int:
    """Smallest bucket edge >= n (powers of two when ``edges`` is None).
    A batch above the largest explicit edge is its own bucket — no padding,
    one executable per such shape, exactly the pre-bucketing behaviour."""
    n = int(n)
    if edges:
        cands = [int(e) for e in edges if int(e) >= n]
        return min(cands) if cands else n
    return next_pow2(n)


def pad_dim0(v, target: int):
    """Zero-pad the leading dim up to ``target``.  numpy feeds pad on the
    host; device arrays pad with jnp (no D2H sync — the prefetch-pipeline
    rule from the executor's feed-sig path applies here too)."""
    if np.ndim(v) == 0:
        return v
    pad = int(target) - int(np.shape(v)[0])
    if pad <= 0:
        return v
    widths = [(0, pad)] + [(0, 0)] * (np.ndim(v) - 1)
    if isinstance(v, np.ndarray):
        return np.pad(v, widths)
    import jax.numpy as jnp
    return jnp.pad(jnp.asarray(v), widths)


# ---------------------------------------------------------------------------
# persistent program-level cache index
# ---------------------------------------------------------------------------

def persistent_key(fingerprint: str, feed_sig, fetch_names,
                   extras: Sequence = ()) -> str:
    """Content key for one compiled executable, stable across processes:
    program fingerprint + bucketed feed signature + fetch set + the
    compile-relevant hints, salted with the jax version and backend (an
    upgraded jax or a different platform must cold-compile)."""
    import jax
    payload = (fingerprint, tuple(feed_sig), tuple(fetch_names),
               tuple(extras), jax.__version__, jax.default_backend())
    return hashlib.sha256(repr(payload).encode()).hexdigest()


# ---------------------------------------------------------------------------
# where jax's own compilation cache lives
# ---------------------------------------------------------------------------

_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def jax_cache_dir() -> str:
    """The one place compiled XLA executables persist: wherever
    ``JAX_COMPILATION_CACHE_DIR`` says when it is set, otherwise the fixed
    ``<checkout>/.jax_cache`` (git-ignored).  Never a temp name, pid or
    time — the path is part of the cache key, so a directory that moves
    never hits."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE_DIR


def enable_jax_cache() -> str:
    """Switch jax's persistent compilation cache on at
    :func:`jax_cache_dir` and return that directory.  With
    ``JAX_COMPILATION_CACHE_DIR`` set, jax has already read it and the
    directory is never set in code.  Size/time thresholds are zeroed: a
    whole-block compile dwarfs a dispatch, so every entry is worth
    persisting.  Call before the process's first compile (entry points do:
    ``chip_smoke.py``, ``bench.py``, ``tools/serve_bench.py``, the fleet's
    replica child); idempotent."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax_cache_dir()


class PersistentCache:
    """On-disk key -> executable-metadata index under
    ``FLAGS_persistent_cache_dir``.

    One JSON file per key (``index/<sha256>.json``) written via
    tempfile + atomic rename: no locks, safe for concurrent trainers
    sharing the directory (restarts, multi-host launches on a
    shared filesystem).  Existence of the file IS the hit predicate."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.index_dir = os.path.join(self.root, "index")
        os.makedirs(self.index_dir, exist_ok=True)
        # the executables themselves persist where jax_cache_dir() says,
        # not under this index's root
        enable_jax_cache()

    def path_for(self, key: str) -> str:
        return os.path.join(self.index_dir, key + ".json")

    def has(self, key: str) -> bool:
        return os.path.exists(self.path_for(key))

    def get(self, key: str) -> Optional[Dict[str, Any]]:
        try:
            with open(self.path_for(key)) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def record(self, key: str, meta: Dict[str, Any]) -> None:
        fd, tmp = tempfile.mkstemp(dir=self.index_dir, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(meta, f, default=str)
            os.replace(tmp, self.path_for(key))
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def keys(self) -> List[str]:
        try:
            return sorted(f[:-5] for f in os.listdir(self.index_dir)
                          if f.endswith(".json"))
        except OSError:
            return []

    def entries(self) -> List[Dict[str, Any]]:
        """Every readable index record (unreadable/corrupt ones are
        skipped — the index degrades, it never throws at inspectors)."""
        out = []
        for k in self.keys():
            meta = self.get(k)
            if meta is not None:
                out.append(dict(meta, key=k))
        return out

    def device_footprints(self) -> List[Dict[str, Any]]:
        """Executables in the index that carry device-truth meta
        (``device.flops`` / ``device.peak_bytes``, recorded by the
        executor when FLAGS_device_cost_analysis captured them), sorted
        by peak HBM bytes descending — what "which executable is
        biggest?" tooling reads after the fact, without a live
        process."""
        rows = []
        for meta in self.entries():
            dev = meta.get("device") or {}
            if dev.get("peak_bytes") or dev.get("flops"):
                rows.append({"key": meta.get("key"),
                             "fingerprint": str(
                                 meta.get("fingerprint", ""))[:12],
                             "bucket": meta.get("bucket"),
                             "n_ops": meta.get("n_ops"),
                             "flops": dev.get("flops"),
                             "peak_bytes": dev.get("peak_bytes"),
                             "argument_bytes": dev.get("argument_bytes")})
        rows.sort(key=lambda r: float(r.get("peak_bytes") or 0),
                  reverse=True)
        return rows


_instance: Optional[PersistentCache] = None


def persistent_cache() -> Optional[PersistentCache]:
    """The process PersistentCache for FLAGS_persistent_cache_dir, or None
    when the flag is unset.  Re-reads the flag each call so tests (and
    set_flags at runtime) can repoint or disable it."""
    global _instance
    from . import core
    root = core.get_flag("persistent_cache_dir")
    if not root:
        return None
    root = os.path.abspath(str(root))
    if _instance is None or _instance.root != root:
        _instance = PersistentCache(root)
    return _instance


# ---------------------------------------------------------------------------
# recompile-storm detection
# ---------------------------------------------------------------------------

class RecompileStormDetector:
    """Sliding-window compile-miss monitor.  ``note_miss`` returns the
    attributed misses (shape/bucket info) exactly once when the in-window
    count crosses the threshold, then disarms until the window drains
    below half the threshold — one warning per storm, not per miss."""

    def __init__(self):
        self._misses: collections.deque = collections.deque()
        self._armed = True

    def note_miss(self, info: Dict[str, Any], threshold: int,
                  window: float, now: Optional[float] = None):
        t = time.monotonic() if now is None else now
        while self._misses and t - self._misses[0][0] > window:
            self._misses.popleft()
        # re-arm check BEFORE appending, so small thresholds (1-3, where
        # half rounds to <= 1) can re-arm once the window drains
        if len(self._misses) < max(int(threshold) // 2, 1):
            self._armed = True
        self._misses.append((t, info))
        if self._armed and len(self._misses) >= int(threshold):
            self._armed = False
            return [i for _, i in self._misses]
        return None
