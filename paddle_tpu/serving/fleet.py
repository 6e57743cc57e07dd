"""Distributed serving fleet: replica router, health-based ejection,
warm replica spin-up.

PR 8 proved ONE ServingEngine on one chip; production traffic means a
fleet.  The reference system's heterogeneous multi-trainer serving tier
(PAPER.md layer 6, PaddleBox's multi-worker dispatch) maps here onto a
router/replica plane built from the planes the stack already ships:

* **Replicas** — N engine processes (``python -m
  paddle_tpu.serving.fleet --serve-replica``), each owning a frozen
  program (or AOT artifact), its own ``/metrics``+``/healthz``+``/stats``
  HTTP surface (PR 7/9), its own SLO watchdog, and a tiny stdlib RPC
  endpoint riding the ``distributed/ps/rpc.py`` framing (raw ndarray
  bytes behind a JSON header — one memcpy per array each way).
  In-process replicas (tests, single-host canaries) wrap a local
  engine behind the same handle API.
* **Router** — least-queue-depth (default) or round-robin dispatch
  with session affinity, fed by each replica's live ``/stats`` (the
  PR 7/9 export plane is the CONTROL signal, not just a dashboard).
  Accepted requests are owned by the router until a replica answers:
  a transport error or attempt timeout redispatches the same payload
  to a healthy replica, so a killed or wedged replica loses nothing.
* **Ejection / readmission** — the health monitor polls ``/stats``;
  a ``stalled``/``breached`` verdict (PR 9's watchdog, served on
  ``/healthz``) or ``missed_scrape_limit`` consecutive missed scrapes
  ejects the replica from rotation; a recovered ``ok`` verdict readmits
  it; a dead process is replaced (``auto_replace``) by a fresh replica
  that warm-starts from the shared persistent compile cache (PR 2) and
  per-bucket AOT artifacts (PR 8) — the restart-to-serving SLO,
  measured by ``tools/serve_bench.py --fleet``.

See docs/serving.md "Serving fleet".
"""
from __future__ import annotations

import json
import os
import socket
import socketserver
import subprocess
import sys
import threading
import time
import urllib.request
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from ..fluid import flight_recorder as _flight
from ..fluid import trace
from .engine import (BaseFuture, DeadlineExceededError, EngineClosedError,
                     QueueFullError, ServingEngine, ServingError)

__all__ = [
    "ServingFleet", "Router", "ReplicaHandle", "FleetFuture",
    "FleetMetricsAggregator", "DecodeSession",
    "ReplicaServer", "serve_replica", "build_engine_from_spec",
    "demo_mlp_spec", "demo_decode_spec", "NoReplicaError",
    "ReplicaTransportError", "CircuitBreaker",
]


class NoReplicaError(ServingError):
    """No healthy replica could serve the request within the attempt
    budget."""


class ReplicaTransportError(ServingError):
    """The RPC to a replica failed (connection refused/reset/timeout) —
    retryable on another replica."""


# ---------------------------------------------------------------------------
# replica spec -> engine (runs inside the replica process)
# ---------------------------------------------------------------------------

def demo_mlp_spec(hidden: int = 32, features: int = 16, classes: int = 10,
                  max_batch: int = 16, max_wait_us: int = 2000,
                  queue_depth: int = 256, seed: int = 0,
                  warmup: bool = True, watchdog_stall_s: float = 0.0,
                  auto_tune: bool = False,
                  mesh: Optional[Dict[str, int]] = None,
                  sharding: Optional[str] = None,
                  emulate_devices: Optional[int] = None) -> Dict[str, Any]:
    """The built-in demo replica spec (a small frozen mlp) — what
    serve_bench --fleet and the ci_smoke fleet gate serve.
    ``auto_tune=True`` arms the per-replica online tuner
    (fluid/autotune.py): each replica hill-climbs max_batch/max_wait
    against its own window p99, and the decisions surface in the
    replica's /stats payload the fleet monitor scrapes.

    ``mesh`` (axis→size, e.g. ``{"tp": 8}``) makes the replica itself a
    pjit mesh: its subprocess builds the engine over a TP-sharded
    ``freeze_program`` (``sharding`` picks the plan mode, default
    ``"tp"``) and reports per-device HBM peak in /stats.
    ``emulate_devices`` asks the parent to set
    ``--xla_force_host_platform_device_count`` in the child's env — the
    CPU-emulated multi-chip host every sharding test uses."""
    spec = {"kind": "demo_mlp", "hidden": hidden, "features": features,
            "classes": classes, "max_batch": max_batch,
            "max_wait_us": max_wait_us, "queue_depth": queue_depth,
            "seed": seed, "warmup": warmup,
            "watchdog_stall_s": watchdog_stall_s,
            "auto_tune": bool(auto_tune)}
    if mesh:
        spec["mesh"] = {str(k): int(v) for k, v in dict(mesh).items()}
        spec["sharding"] = sharding or "tp"
    if emulate_devices:
        spec["emulate_devices"] = int(emulate_devices)
    return spec


def demo_decode_spec(vocab: int = 32, d_model: int = 16, max_len: int = 24,
                     seed: int = 0, page_size: int = 4,
                     pool_pages: Optional[int] = None, max_batch: int = 8,
                     queue_depth: int = 64, prefix_cache: bool = True,
                     warmup: bool = True,
                     watchdog_stall_s: float = 0.0) -> Dict[str, Any]:
    """A replica spec that hosts the DECODE plane: the replica
    subprocess builds the PR-12 demo decode transformer and serves it
    through a paged :class:`~paddle_tpu.serving.decode.DecodeEngine`
    behind the same ReplicaServer RPC surface (ops ``decode`` /
    ``decode_drop``).  Same-``seed`` replicas share bit-identical
    weights — what makes router-level session migration exact: the new
    replica re-prefills the session's history and continues the
    identical greedy stream."""
    return {"kind": "demo_decode", "vocab": int(vocab),
            "d_model": int(d_model), "max_len": int(max_len),
            "seed": int(seed), "page_size": int(page_size),
            "pool_pages": pool_pages, "max_batch": int(max_batch),
            "queue_depth": int(queue_depth),
            "prefix_cache": bool(prefix_cache), "warmup": warmup,
            "watchdog_stall_s": watchdog_stall_s}


def build_engine_from_spec(spec: Dict[str, Any]) -> ServingEngine:
    """Materialise a ServingEngine from a JSON-able replica spec.

    Kinds: ``demo_mlp`` (built-in demo net, optionally sharded over a
    ``mesh`` spec), ``demo_decode`` (the paged decode plane),
    ``inference_model`` (a ``save_inference_model`` directory), ``aot``
    (a ``save_aot_model`` multi-bucket StableHLO artifact — the PR-8
    warm-start path)."""
    kind = spec.get("kind", "demo_mlp")
    kwargs = {k: spec[k] for k in ("max_batch", "max_wait_us",
                                   "queue_depth", "default_deadline_ms",
                                   "auto_tune")
              if spec.get(k) is not None}
    if kwargs.get("auto_tune") and spec.get("watchdog_p99_ms"):
        # the tuner's revert guard judges against the same p99 the
        # replica's SLO watchdog enforces
        kwargs["slo_ms"] = float(spec["watchdog_p99_ms"])
    shard_kw: Dict[str, Any] = {}
    if spec.get("mesh"):
        # the replica IS a pjit mesh: build it here (inside the child,
        # over however many devices its env exposes) and let the engine
        # run the frozen program as one sharded executable
        from ..parallel.mesh import build_mesh
        shard_kw["mesh"] = build_mesh(
            {str(k): int(v) for k, v in spec["mesh"].items()})
        shard_kw["sharding"] = spec.get("sharding") or "tp"
    if kind == "demo_mlp":
        import paddle_tpu.fluid as fluid
        from .freeze import freeze_program
        main_p, startup = fluid.Program(), fluid.Program()
        main_p.random_seed = startup.random_seed = int(spec.get("seed", 0))
        with fluid.program_guard(main_p, startup):
            x = fluid.data("x", [-1, int(spec.get("features", 16))])
            h = fluid.layers.fc(x, int(spec.get("hidden", 32)), act="relu")
            h = fluid.layers.fc(h, int(spec.get("hidden", 32)), act="relu")
            logits = fluid.layers.fc(h, int(spec.get("classes", 10)))
        exe = fluid.Executor()
        exe.run(startup)
        frozen = freeze_program(main_p, ["x"], [logits])
        return ServingEngine(frozen, executor=exe, **shard_kw, **kwargs)
    if kind == "demo_decode":
        from .decode import DecodeEngine, build_demo_decode_model
        model = build_demo_decode_model(
            vocab=int(spec.get("vocab", 32)),
            d_model=int(spec.get("d_model", 16)),
            max_len=int(spec.get("max_len", 24)),
            seed=int(spec.get("seed", 0)),
            page_size=int(spec.get("page_size", 4)))
        return DecodeEngine(
            model, max_batch=int(spec.get("max_batch", 8)),
            queue_depth=int(spec.get("queue_depth", 64)),
            paged=True, page_size=int(spec.get("page_size", 4)),
            pool_pages=spec.get("pool_pages"),
            prefix_cache=bool(spec.get("prefix_cache", True)),
            auto_start=False)
    if kind == "inference_model":
        import paddle_tpu.fluid as fluid
        from ..fluid import io as fio
        from .freeze import freeze_program
        exe = fluid.Executor()
        prog, feeds, fetches = fio.load_inference_model(spec["dir"], exe)
        frozen = freeze_program(prog, feeds, fetches)
        return ServingEngine(frozen, executor=exe, **shard_kw, **kwargs)
    if kind == "aot":
        from ..inference.aot import load_aot_model
        return ServingEngine(load_aot_model(spec["dir"]), **kwargs)
    raise ValueError(f"unknown replica spec kind {kind!r}")


# ---------------------------------------------------------------------------
# replica process: RPC server + export plane (child side)
# ---------------------------------------------------------------------------

class ReplicaServer:
    """One replica's RPC endpoint (the brpc-server shape of
    ``distributed/ps/rpc.py``, serving inference instead of tables).

    Ops: ``hello`` (warmup report + ports), ``infer`` (feed arrays in,
    fetch arrays out, served through the engine's continuous batcher —
    concurrent handler threads coalesce into device batches),
    ``decode``/``decode_drop`` (a replica hosting the decode plane:
    prompt tokens in, generated tokens out, plus the session-migration
    hook that drops a departed session's warm prefix pages), ``stats``,
    ``pause``/``resume`` (chaos/maintenance: a paused replica genuinely
    stalls — its watchdog flips ``/healthz`` to ``stalled``, which is
    the fleet's verdict-driven ejection drill), ``drain`` (finish
    everything in flight, stop admitting), ``stop``."""

    def __init__(self, engine: ServingEngine, host: str = "127.0.0.1",
                 port: int = 0, info: Optional[Dict[str, Any]] = None):
        from ..distributed.ps.rpc import (CorruptFrameError,
                                          begin_server_trace,
                                          end_server_trace, recv_msg,
                                          send_msg)
        self.engine = engine
        # engine-kind discriminator: the decode plane's engine carries
        # prefill buckets, the batch plane's carries bucket_edges
        self.is_decode = hasattr(engine, "prefill_edges")
        self.info = dict(info or {})
        self._stop = threading.Event()
        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                sock = self.request
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                try:
                    while True:
                        try:
                            header, arrays = recv_msg(sock)
                        except CorruptFrameError:
                            # checksum caught a torn/flipped frame (the
                            # rpc.corrupt_frames counter has it); the
                            # stream is desynchronized — drop the
                            # connection, the router redispatches
                            return
                        # propagated trace context (if any) wraps the
                        # dispatch so engine spans + flight records
                        # inherit the ROUTER's trace id
                        reply = out = None
                        scope = begin_server_trace(header)
                        try:
                            reply, out = outer._dispatch(header, arrays)
                        except Exception as e:  # noqa: BLE001 — report
                            reply, out = {
                                "ok": False,
                                "error": type(e).__name__,
                                "message": str(e),
                                # a still-pending future at the RPC
                                # timeout means THIS replica is wedged
                                # or overloaded — the router must
                                # redispatch, not fail the request
                                "retryable": isinstance(
                                    e, (QueueFullError,
                                        EngineClosedError,
                                        TimeoutError)),
                            }, []
                        finally:
                            end_server_trace(scope, reply)
                        send_msg(sock, reply, out)
                        if header.get("op") == "stop":
                            break
                except (ConnectionError, OSError):
                    pass

        class Srv(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Srv((host, port), Handler)
        self.port = self._server.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def _dispatch(self, header, arrays):
        op = header["op"]
        if op == "infer":
            if self.is_decode:
                return {"ok": False, "error": "ServingError",
                        "message": "this replica hosts the decode "
                                   "plane; use op=decode"}, []
            names = header["feeds"]
            feed = dict(zip(names, arrays))
            dl = header.get("deadline_ms") or None
            dl_ts = header.get("deadline_ts")
            if dl_ts is not None:
                # the router's absolute deadline (same-host wall clock):
                # shed already-expired work before it costs a batch slot,
                # and hand the engine's admission queue only the budget
                # that actually remains
                rem_ms = (float(dl_ts) - time.time()) * 1e3
                if rem_ms <= 0:
                    trace.metrics().counter("rpc.deadline_shed").inc()
                    return {"ok": False, "shed": True,
                            "error": "DeadlineExceededError",
                            "message": "deadline expired before "
                                       "admission"}, []
                dl = min(dl, rem_ms) if dl else rem_ms
            fut = self.engine.submit(feed, deadline_ms=dl)
            timeout_s = float(header.get("timeout_s", 60.0))
            if dl:
                timeout_s = min(timeout_s, dl / 1e3 + 5.0)
            res = fut.result(timeout=timeout_s)
            fetch_names = list(res)
            reply = {"ok": True, "fetches": fetch_names,
                     "trace_id": fut.trace_id}
            if "trace_id" in header and fut.timing:
                # queue/device split for the router's attribution —
                # only on traced requests, so the tracing-off wire
                # stays byte-identical
                reply.update(fut.timing)
            return (reply, [np.asarray(res[n]) for n in fetch_names])
        if op == "decode":
            if not self.is_decode:
                return {"ok": False, "error": "ServingError",
                        "message": "this replica hosts the batch plane;"
                                   " use op=infer"}, []
            prompt = np.asarray(arrays[0], dtype=np.int64).reshape(-1)
            fut = self.engine.submit(
                prompt, max_new_tokens=int(header.get("max_new", 16)),
                eos_id=header.get("eos_id"))
            res = fut.result(timeout=float(header.get("timeout_s", 60.0)))
            reply = {"ok": True, "prompt_len": int(res["prompt_len"]),
                     "finish_reason": res["finish_reason"],
                     "trace_id": fut.trace_id}
            return reply, [np.asarray(res["tokens"], dtype=np.int64)]
        if op == "decode_drop":
            # session-migration hook: the router tells the OLD replica a
            # migrated session's history pages have no future reader
            fn = getattr(self.engine, "release_prefix", None)
            tokens = np.asarray(arrays[0], dtype=np.int64).reshape(-1)
            freed = int(fn(tokens)) if fn is not None else 0
            return {"ok": True, "pages_freed": freed}, []
        if op == "hello":
            return {"ok": True, "pid": os.getpid(), **self.info}, []
        if op == "stats":
            st = self.engine.stats()
            try:
                from ..fluid import watchdog
                st["status"] = watchdog.health().get("status", "ok")
            except Exception:       # noqa: BLE001
                st["status"] = "ok"
            return {"ok": True, "stats": st}, []
        if op == "pause":
            self.engine.pause()
            return {"ok": True}, []
        if op == "resume":
            self.engine.resume()
            return {"ok": True}, []
        if op == "drain":
            self.engine.close()
            return {"ok": True}, []
        if op == "stop":
            self._stop.set()
            return {"ok": True}, []
        return {"ok": False, "error": "ValueError",
                "message": f"unknown op {op}"}, []

    def start(self) -> "ReplicaServer":
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self

    def wait(self) -> None:
        self._stop.wait()
        self._server.shutdown()

    def stop(self) -> None:
        self._stop.set()
        self._server.shutdown()


def serve_replica(spec: Dict[str, Any], ready_stream=None) -> None:
    """Child-process entry: build the engine from ``spec``, warm it,
    bring up the export plane (/metrics /healthz /stats) + SLO watchdog,
    serve RPC until ``stop``.  Prints ONE ready line (JSON) so the
    parent learns the ports and the warmup report."""
    from ..fluid import metrics_export
    from ..fluid import watchdog as wdog

    ready_stream = ready_stream or sys.stdout
    engine = build_engine_from_spec(spec)
    warmup_report = engine.warmup() if spec.get("warmup", True) else None
    stall_s = float(spec.get("watchdog_stall_s") or 0)
    if stall_s > 0:
        wdog.start(stall_s=stall_s,
                   interval_s=min(0.2, stall_s / 2),
                   p99_ms=float(spec.get("watchdog_p99_ms") or 0))
    msrv = metrics_export.start_http(port=0)
    engine.start()
    rpc = ReplicaServer(engine, info={"warmup": warmup_report,
                                      "metrics_port": msrv.port}).start()
    ready_stream.write(json.dumps({
        "ready": True, "pid": os.getpid(), "rpc_port": rpc.port,
        "metrics_port": msrv.port, "warmup": warmup_report}) + "\n")
    ready_stream.flush()
    rpc.wait()
    engine.close()
    if trace.enabled():
        # per-process trace file (FLAGS_trace_path, templated per
        # replica by the fleet) — written deterministically at graceful
        # stop so `tools/timeline.py stitch` can merge it; the atexit
        # hook still covers other exits
        try:
            trace.export_chrome_trace()
        except OSError:
            pass
    metrics_export.stop_http()


# ---------------------------------------------------------------------------
# parent side: circuit breaker + replica handles
# ---------------------------------------------------------------------------

class CircuitBreaker:
    """Per-replica transport circuit breaker (docs/robustness.md).

    ``closed`` → (``failures`` CONSECUTIVE transport failures) →
    ``open`` → (after ``cooldown_s``) one ``half_open`` probe →
    success closes, failure reopens and restarts the cooldown.

    Transport failures only (connection refused/reset/timeout/corrupt
    frame): QueueFull is a healthy replica saying no, and application
    errors are the request's problem — neither trips the breaker.
    ``failures <= 0`` disables the breaker entirely.

    ``on_open``/``on_close`` callbacks (invoked OUTSIDE the breaker
    lock) feed the fleet's ejection/readmission lifecycle."""

    def __init__(self, failures: Optional[int] = None,
                 cooldown_s: Optional[float] = None, name: str = "",
                 now_fn=time.monotonic,
                 on_open: Optional[Callable] = None,
                 on_close: Optional[Callable] = None):
        from ..fluid import core
        self.threshold = int(
            failures if failures is not None
            else core.get_flag("fleet_breaker_failures", 5))
        self.cooldown_s = float(
            cooldown_s if cooldown_s is not None
            else core.get_flag("fleet_breaker_cooldown_s", 3.0))
        self.name = name
        self._now = now_fn
        self.state = "closed"
        self.consecutive_failures = 0
        self.opened_at: Optional[float] = None
        self._probing = False
        self.on_open = on_open
        self.on_close = on_close
        self.opens = 0
        self.closes = 0
        self._lock = threading.Lock()
        m = trace.metrics()
        self._c_opens = m.counter("fleet.breaker_opens")
        self._c_closes = m.counter("fleet.breaker_closes")
        self._c_probes = m.counter("fleet.breaker_probes")

    def probe_ready(self) -> bool:
        """An open breaker past its cooldown with no probe in flight."""
        with self._lock:
            return (self.state == "open" and not self._probing
                    and self._now() - self.opened_at >= self.cooldown_s)

    def available(self) -> bool:
        """May a request be dispatched through this breaker right now?
        Closed: yes.  Open past cooldown with no probe in flight: yes —
        that request IS the half-open probe (callers follow up with
        :meth:`begin_probe`)."""
        with self._lock:
            if self.state == "closed":
                return True
            return (self.state == "open" and not self._probing
                    and self._now() - self.opened_at >= self.cooldown_s)

    def begin_probe(self) -> None:
        with self._lock:
            if self.state in ("open", "half_open"):
                self.state = "half_open"
                self._probing = True
                self._c_probes.inc()

    def try_acquire_probe(self) -> bool:
        """Atomic check-and-begin: True for a closed breaker (no token
        needed) or for exactly ONE caller of an open-past-cooldown
        breaker — two racing dispatchers can't both become the
        half-open probe."""
        with self._lock:
            if self.state == "closed":
                return True
            if (self.state == "open" and not self._probing
                    and self._now() - self.opened_at >= self.cooldown_s):
                self.state = "half_open"
                self._probing = True
                self._c_probes.inc()
                return True
            return False

    def record_success(self) -> None:
        cb = None
        with self._lock:
            if self.state == "half_open":
                # the probe's own outcome: recovery confirmed
                self.state = "closed"
                self.closes += 1
                self._c_closes.inc()
                self.consecutive_failures = 0
                self._probing = False
                self.opened_at = None
                cb = self.on_close
            elif self.state == "closed":
                self.consecutive_failures = 0
            # state "open": a straggler dispatched BEFORE the open
            # completed late — ignored; only the half-open probe may
            # close the circuit (no zero-cooldown readmission storms)
        if cb is not None:
            cb()

    def record_failure(self) -> None:
        cb = None
        with self._lock:
            self.consecutive_failures += 1
            if self.state == "half_open":
                # failed probe: reopen, restart the cooldown
                self.state = "open"
                self.opened_at = self._now()
                self._probing = False
            elif (self.state == "closed" and self.threshold > 0
                    and self.consecutive_failures >= self.threshold):
                self.state = "open"
                self.opened_at = self._now()
                self.opens += 1
                self._c_opens.inc()
                cb = self.on_open
        if cb is not None:
            cb()

    def describe(self) -> Dict[str, Any]:
        with self._lock:
            return {"state": self.state,
                    "consecutive_failures": self.consecutive_failures,
                    "opens": self.opens, "closes": self.closes}


class _SockPool:
    """Per-replica blocking-socket pool: checkout/checkin gives the
    router concurrent in-flight RPCs (the replica's continuous batcher
    needs overlapping requests to coalesce) over the simple framed
    protocol."""

    def __init__(self, host: str, port: int, timeout_s: float):
        self.host, self.port = host, int(port)
        self.timeout_s = float(timeout_s)
        self._idle: List[socket.socket] = []
        self._lock = threading.Lock()

    def checkout(self) -> socket.socket:
        with self._lock:
            if self._idle:
                return self._idle.pop()
        from ..distributed.ps.rpc import connect_endpoint
        return connect_endpoint(self.host, self.port,
                                timeout=self.timeout_s)

    def checkin(self, s: socket.socket) -> None:
        with self._lock:
            self._idle.append(s)

    def close_all(self) -> None:
        with self._lock:
            socks, self._idle = self._idle, []
        for s in socks:
            try:
                s.close()
            except OSError:
                pass


class ReplicaHandle:
    """One replica as the router sees it: dispatch target + health
    subject.  Two kinds share the API:

    * subprocess (``spawn=True`` path of :class:`ServingFleet`): RPC
      over the socket pool, health over HTTP ``GET /stats``;
    * in-process (``ServingFleet(replicas=[...])`` / tests): a local
      engine or injected ``infer_fn``/``health_fn`` — same states, no
      processes.

    States: ``up`` → (``ejected`` ⇄ readmitted) / ``draining`` →
    ``stopped`` / ``dead``."""

    def __init__(self, name: str,
                 proc: Optional[subprocess.Popen] = None,
                 rpc_port: Optional[int] = None,
                 metrics_port: Optional[int] = None,
                 engine: Optional[ServingEngine] = None,
                 infer_fn: Optional[Callable] = None,
                 health_fn: Optional[Callable] = None,
                 probe_fn: Optional[Callable] = None,
                 rpc_timeout_s: float = 15.0,
                 warmup_report: Optional[Dict[str, Any]] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 host: str = "127.0.0.1",
                 agent: Optional[Any] = None):
        self.name = name
        self.proc = proc
        self.host = host or "127.0.0.1"
        # host-agent placement (distributed/launch.py): the replica
        # process lives on a (possibly remote) agent — teardown goes
        # through it, liveness comes from its heartbeat
        self.agent = agent
        self.rpc_port = rpc_port
        self.metrics_port = metrics_port
        self.engine = engine
        self._infer_fn = infer_fn
        self._health_fn = health_fn
        self._probe_fn = probe_fn
        self._infer_takes_deadline = False
        if infer_fn is not None:
            try:
                import inspect
                self._infer_takes_deadline = "deadline_ms" in \
                    inspect.signature(infer_fn).parameters
            except (TypeError, ValueError):
                pass
        self.rpc_timeout_s = float(rpc_timeout_s)
        self.warmup_report = warmup_report
        self.breaker = breaker if breaker is not None \
            else CircuitBreaker(name=name)
        self.state = "up"
        self.ejected_reason: Optional[str] = None
        self.missed_scrapes = 0
        self.last_stats: Dict[str, Any] = {}
        self.outstanding = 0            # router-local in-flight count
        self._out_lock = threading.Lock()
        self.spawned_at = time.monotonic()
        self.ready_at: Optional[float] = None
        self._pool = (_SockPool(self.host, rpc_port, rpc_timeout_s)
                      if rpc_port else None)

    # -- bookkeeping ---------------------------------------------------------
    @property
    def in_process(self) -> bool:
        return self._pool is None

    def _inc(self):
        with self._out_lock:
            self.outstanding += 1

    def _dec(self):
        with self._out_lock:
            self.outstanding -= 1

    def load_score(self) -> float:
        """Least-queue-depth signal: router-local in-flight + the
        replica's last-scraped engine queue depth."""
        return self.outstanding + float(
            self.last_stats.get("queue_depth", 0) or 0)

    def alive(self) -> bool:
        if self.proc is not None:
            return self.proc.poll() is None
        return self.state not in ("dead", "stopped")

    # -- RPC -----------------------------------------------------------------
    def call(self, header: Dict[str, Any], arrays: Sequence = (),
             timeout_s: Optional[float] = None):
        """One framed RPC round-trip; raises ReplicaTransportError on any
        socket-level failure — including a checksum-caught corrupt frame
        (retryable elsewhere; a torn reply never reaches the caller)."""
        if self.in_process:
            raise ReplicaTransportError(
                f"replica {self.name} is in-process: no RPC endpoint")
        from ..distributed.ps.rpc import recv_msg, send_msg
        try:
            s = self._pool.checkout()
        except OSError as e:
            raise ReplicaTransportError(
                f"connect to {self.name}: {e}") from e
        t0_ns = None
        try:
            # per-call socket deadline, with headroom over the replica's
            # own wait so its typed TimeoutError reply (retryable) wins
            # the race against a raw socket timeout
            s.settimeout((timeout_s + 2.0) if timeout_s
                         else self.rpc_timeout_s)
            if "trace_id" in header and trace.enabled():
                # wall-clock send stamp: the client half of the
                # clock-offset pair the timeline stitcher estimates
                # from (only present on traced requests)
                header["send_ts"] = time.time()
                t0_ns = trace.now()
            send_msg(s, header, arrays)
            reply, out = recv_msg(s)
        except (OSError, ConnectionError) as e:
            try:
                s.close()
            except OSError:
                pass
            raise ReplicaTransportError(
                f"rpc {header.get('op')} to {self.name}: "
                f"{type(e).__name__}: {e}") from e
        self._pool.checkin(s)
        if t0_ns is not None:
            trace.complete(
                "rpc::client", t0_ns, cat="rpc",
                args={"op": header.get("op"), "replica": self.name,
                      "trace_id": header["trace_id"],
                      "send_ts": header["send_ts"],
                      "recv_ts": time.time(),
                      "srv_recv_ts": reply.get("srv_recv_ts"),
                      "srv_send_ts": reply.get("srv_send_ts")})
        return reply, out

    def infer(self, feed: Dict[str, np.ndarray],
              deadline_ms: Optional[float] = None,
              timeout_s: Optional[float] = None,
              info: Optional[Dict[str, Any]] = None
              ) -> Dict[str, np.ndarray]:
        """Serve one request on THIS replica.  Raises
        ReplicaTransportError (retryable), QueueFullError (retryable
        elsewhere), or the replica's terminal error.

        When tracing is on, the outgoing header carries the ambient
        ``trace_id``/``parent_span`` (the router installs its request id
        around this call) so the replica's spans inherit the caller's
        causal identity; with tracing off the header is byte-identical
        to a build without propagation.  ``info``, if given a dict, is
        filled with reply metadata: the served ``trace_id`` and — on
        traced requests — the replica's ``queue_us``/``device_us``
        split."""
        if self.in_process:
            if self._infer_fn is not None:
                if self._infer_takes_deadline:
                    return self._infer_fn(feed, deadline_ms=deadline_ms)
                return self._infer_fn(feed)
            fut = self.engine.submit(feed, deadline_ms=deadline_ms)
            res = fut.result(timeout=timeout_s or self.rpc_timeout_s)
            if info is not None:
                info["trace_id"] = fut.trace_id
                if fut.timing:
                    info.update(fut.timing)
            return res
        names = sorted(feed)
        hdr = {"op": "infer", "feeds": names, "deadline_ms": deadline_ms,
               "timeout_s": timeout_s or self.rpc_timeout_s}
        if deadline_ms and deadline_ms > 0:
            # absolute deadline for server-side shedding (same host /
            # NTP-synced clocks — docs/robustness.md)
            hdr["deadline_ts"] = time.time() + deadline_ms / 1e3
        # empty with tracing off: zero extra bytes on the wire
        hdr.update(trace.propagation_fields("req"))
        reply, arrays = self.call(
            hdr, [np.asarray(feed[n]) for n in names],
            timeout_s=timeout_s or self.rpc_timeout_s)
        if not reply.get("ok"):
            err = reply.get("error", "ServingError")
            msg = f"{self.name}: {reply.get('message', err)}"
            if err == "QueueFullError":
                raise QueueFullError(msg)
            if err == "DeadlineExceededError":
                raise DeadlineExceededError(msg)
            if reply.get("retryable") or err == "TimeoutError":
                raise ReplicaTransportError(msg)
            raise ServingError(msg)
        if info is not None:
            for k in ("trace_id", "queue_us", "device_us", "latency_us"):
                if k in reply:
                    info[k] = reply[k]
        return dict(zip(reply["fetches"], arrays))

    def decode(self, prompt, max_new_tokens: int = 16,
               eos_id: Optional[int] = None,
               timeout_s: Optional[float] = None
               ) -> Dict[str, Any]:
        """Serve one decode request on THIS replica — the decode-plane
        sibling of :meth:`infer`, with the same error mapping (transport
        failures retryable elsewhere, typed engine rejections
        terminal).  Returns ``{"tokens", "prompt_len",
        "finish_reason"}`` (generated tokens only, as plain ints)."""
        if self.in_process:
            fut = self.engine.submit(prompt,
                                     max_new_tokens=max_new_tokens,
                                     eos_id=eos_id)
            res = fut.result(timeout=timeout_s or self.rpc_timeout_s)
            return {"tokens": [int(t) for t in res["tokens"]],
                    "prompt_len": int(res["prompt_len"]),
                    "finish_reason": res["finish_reason"]}
        hdr = {"op": "decode", "max_new": int(max_new_tokens),
               "eos_id": (None if eos_id is None else int(eos_id)),
               "timeout_s": timeout_s or self.rpc_timeout_s}
        hdr.update(trace.propagation_fields("dec"))
        reply, arrays = self.call(
            hdr, [np.asarray(prompt, dtype=np.int64)],
            timeout_s=timeout_s or self.rpc_timeout_s)
        if not reply.get("ok"):
            err = reply.get("error", "ServingError")
            msg = f"{self.name}: {reply.get('message', err)}"
            if err == "QueueFullError":
                raise QueueFullError(msg)
            if reply.get("retryable") or err == "TimeoutError":
                raise ReplicaTransportError(msg)
            raise ServingError(msg)
        return {"tokens": [int(t) for t in arrays[0]],
                "prompt_len": int(reply["prompt_len"]),
                "finish_reason": reply["finish_reason"]}

    def release_prefix(self, tokens) -> int:
        """Tell the replica a migrated session's history has no future
        reader here (drops its warm prefix-cache pages); returns pages
        freed.  Best-effort: 0 on any shape of refusal."""
        if self.in_process:
            fn = getattr(self.engine, "release_prefix", None)
            return int(fn(tokens)) if fn is not None else 0
        reply, _ = self.call({"op": "decode_drop"},
                             [np.asarray(tokens, dtype=np.int64)])
        return int(reply.get("pages_freed", 0)) if reply.get("ok") else 0

    # -- health --------------------------------------------------------------
    def scrape(self, timeout_s: float = 2.0) -> Dict[str, Any]:
        """The replica's compact /stats payload (verdict + queue depth
        + window p99) — the router's control signal."""
        if self.in_process:
            if self._health_fn is not None:
                return dict(self._health_fn())
            st = self.engine.stats()
            # same verdict source as the subprocess path (ReplicaServer
            # "stats"): the process watchdog — an in-process engine
            # replica must be ejectable on `stalled` too
            try:
                from ..fluid import watchdog
                st["status"] = watchdog.health().get("status", "ok")
            except Exception:       # noqa: BLE001 — verdict is advisory
                st["status"] = "ok"
            return st
        body = urllib.request.urlopen(
            f"http://{self.host}:{self.metrics_port}/stats",
            timeout=timeout_s).read()
        return json.loads(body)

    def fetch_bundle(self, timeout_s: float = 5.0,
                     reason: str = "fleet") -> Dict[str, Any]:
        """The replica's own diagnostic-bundle document (watchdog
        schema), fetched over its HTTP export plane — the fleet monitor
        pulls this at ejection time, BEFORE any teardown, to embed in
        the fleet incident bundle.  A wedged replica still answers (the
        HTTP plane lives on its own threads); a dead one raises."""
        if self.in_process:
            from ..fluid import watchdog
            return watchdog.build_bundle_doc(reason)
        body = urllib.request.urlopen(
            f"http://{self.host}:{self.metrics_port}/bundle?reason="
            f"{reason}", timeout=timeout_s).read()
        return json.loads(body)

    def probe(self) -> bool:
        """Half-open breaker probe: one cheap transport round-trip (the
        monitor drives this for breaker-ejected replicas, so a closed
        breaker — not live traffic — is what readmits them)."""
        if self.in_process:
            if self._probe_fn is not None:
                return bool(self._probe_fn())
            return self.state != "dead"
        reply, _ = self.call({"op": "hello"})
        return bool(reply.get("ok"))

    # -- control -------------------------------------------------------------
    def pause(self) -> None:
        if self.in_process:
            self.engine.pause()
        else:
            self.call({"op": "pause"})

    def resume(self) -> None:
        if self.in_process:
            self.engine.resume()
        else:
            self.call({"op": "resume"})

    def drain(self) -> None:
        if self.in_process:
            if self.engine is not None:
                self.engine.close()
        else:
            self.call({"op": "drain"})

    def stop(self, timeout_s: float = 30.0) -> None:
        self.state = "stopped"
        if self.in_process:
            if self.engine is not None:
                self.engine.close()
            return
        try:
            self.call({"op": "stop"})
        except ServingError:
            pass
        if self.proc is not None:
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        elif self.agent is not None:
            # agent-placed replica: the process is the AGENT's child —
            # it reaps (and if needed kills) on our behalf
            try:
                self.agent.stop(self.name, timeout_s=timeout_s)
            except Exception:           # noqa: BLE001 — a partitioned
                pass                    # agent can't help teardown
        self._pool.close_all()

    def kill(self) -> None:
        """SIGKILL the replica process (chaos drills / bench)."""
        if self.proc is not None:
            self.proc.kill()
        elif self.agent is not None:
            try:
                self.agent.kill(self.name)
            except Exception:           # noqa: BLE001
                pass


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------

class FleetFuture(BaseFuture):
    """One routed request's pending result (same result/exception shape
    as ServingFuture); ``replica`` names who finally served it.

    ``trace_id`` is the fleet-wide causal identity, allocated by the
    router at submit and STABLE across redispatch attempts — every
    replica that touches the request (including a second one after a
    corrupt-frame redispatch) emits its spans under this one id.
    ``server_timing`` carries the serving replica's queue/device split
    on traced requests."""

    __slots__ = ("replica", "attempts", "trace_id", "server_timing")

    _pending_msg = "fleet request still pending"

    def __init__(self):
        super().__init__()
        self.replica: Optional[str] = None
        self.attempts = 0
        self.trace_id: Optional[str] = None
        self.server_timing: Optional[Dict[str, float]] = None

    def _resolve(self, result, replica: str) -> None:  # noqa: D401
        self.replica = replica
        super()._resolve(result)


class Router:
    """Front dispatch over a set of :class:`ReplicaHandle`.

    Policies: ``least_queue`` (default — router-local in-flight + the
    replica's last-scraped queue depth) or ``round_robin``.  ``session``
    keys stick to their replica while it stays admitted (affinity); an
    ejection re-pins on the next request.  The router OWNS every
    accepted request until a replica answers: transport errors and
    attempt timeouts redispatch the same payload elsewhere
    (``fleet.redispatches``), so replica death mid-request loses
    nothing."""

    def __init__(self, replicas: Sequence[ReplicaHandle],
                 policy: str = "least_queue",
                 max_workers: int = 32,
                 max_attempts: int = 6,
                 attempt_timeout_s: float = 15.0,
                 request_timeout_s: float = 120.0):
        if policy not in ("least_queue", "round_robin"):
            raise ValueError(f"unknown router policy {policy!r}")
        from concurrent.futures import ThreadPoolExecutor
        self.policy = policy
        self.replicas: List[ReplicaHandle] = list(replicas)
        self.max_attempts = int(max_attempts)
        self.attempt_timeout_s = float(attempt_timeout_s)
        self.request_timeout_s = float(request_timeout_s)
        self._affinity: Dict[str, str] = {}
        self._rr = 0
        self._lock = threading.Lock()
        self._pool = ThreadPoolExecutor(max_workers=int(max_workers),
                                        thread_name_prefix="fleet-worker")
        self._closed = False
        m = trace.metrics()
        self._c_dispatch = m.counter("fleet.dispatches")
        self._c_redispatch = m.counter("fleet.redispatches")
        self._c_failures = m.counter("fleet.failures")
        self._c_affinity = m.counter("fleet.affinity_rebinds")
        self._h_latency = m.histogram("fleet.latency_seconds")
        # decode-through-the-router state: which replica last served
        # each decode session (the KV-locality pin) + the migration
        # count when an ejection forces a re-pin
        self._decode_pin: Dict[str, str] = {}
        self._c_migrations = m.counter("decode.migrations")
        self.on_decode_migration: Optional[Callable] = None

    # -- membership ----------------------------------------------------------
    def admitted(self) -> List[ReplicaHandle]:
        return [r for r in self.replicas
                if r.state in ("up",) and r.alive()]

    def add_replica(self, handle: ReplicaHandle) -> None:
        with self._lock:
            self.replicas.append(handle)
        trace.metrics().gauge("fleet.replicas_up").set(
            len(self.admitted()))

    def remove(self, handle: ReplicaHandle) -> None:
        with self._lock:
            if handle in self.replicas:
                self.replicas.remove(handle)

    # -- pick ----------------------------------------------------------------
    def _pick(self, session: Optional[str],
              exclude: set) -> Optional[ReplicaHandle]:
        # an open breaker gates dispatch even while the replica is still
        # formally admitted (transport failure is faster news than the
        # next health scrape); a cooled-down breaker admits exactly one
        # request as its half-open probe
        candidates = [r for r in self.admitted()
                      if r.name not in exclude
                      and r.breaker.available()]
        if not candidates:
            return None
        chosen = None
        if session is not None:
            with self._lock:
                pinned = self._affinity.get(session)
            if pinned is not None:
                for r in candidates:
                    if r.name == pinned:
                        chosen = r
                        break
                if chosen is None:
                    # sticky replica gone/ejected: re-pin below
                    self._c_affinity.inc()
        if chosen is None:
            if self.policy == "round_robin":
                with self._lock:
                    self._rr += 1
                    chosen = candidates[self._rr % len(candidates)]
            else:
                chosen = min(candidates, key=lambda r: r.load_score())
        if chosen.breaker.state != "closed" \
                and not chosen.breaker.try_acquire_probe():
            # lost the probe race to a concurrent dispatcher: exactly
            # one request may be the half-open probe — sit this round
            # out (the caller's loop re-picks)
            return None
        if session is not None:
            with self._lock:
                self._affinity[session] = chosen.name
        return chosen

    # -- dispatch ------------------------------------------------------------
    def submit(self, feed: Dict[str, Any],
               session: Optional[str] = None,
               deadline_ms: Optional[float] = None) -> FleetFuture:
        if self._closed:
            raise EngineClosedError("router is closed")
        fut = FleetFuture()
        # one fleet-wide causal id per LOGICAL request, allocated here
        # (the pool worker's thread-locals don't inherit the caller's)
        # and propagated on every dispatch attempt
        fut.trace_id = trace.new_trace_id("req")
        feed = {k: np.asarray(v) for k, v in feed.items()}
        t0 = time.monotonic()
        try:
            self._pool.submit(self._run, fut, feed, session, deadline_ms,
                              t0)
        except RuntimeError as e:
            # raced close(): the pool refused the work — surface the
            # advertised error type, not the executor's RuntimeError
            raise EngineClosedError(f"router is closed: {e}") from e
        return fut

    def infer(self, feed, session=None, deadline_ms=None,
              timeout: Optional[float] = None):
        return self.submit(feed, session=session,
                           deadline_ms=deadline_ms).result(timeout)

    def _run(self, fut: FleetFuture, feed, session, deadline_ms,
             t0: float) -> None:
        exclude: set = set()
        last_exc: Optional[BaseException] = None
        info: Dict[str, Any] = {}
        rows = max((int(a.shape[0]) for a in feed.values()
                    if getattr(a, "ndim", 0) >= 1), default=1)
        t0_ns = trace.now() if trace.enabled() else None
        # the request's own deadline caps the retry budget: redispatching
        # expired work would burn replica batch slots on a result nobody
        # can use
        abs_dl = (t0 + deadline_ms / 1e3
                  if deadline_ms and deadline_ms > 0 else None)
        deadline = t0 + self.request_timeout_s
        if abs_dl is not None:
            deadline = min(deadline, abs_dl)
        while fut.attempts < self.max_attempts \
                and time.monotonic() < deadline:
            if self._closed:
                # a closing router must fail pending requests promptly,
                # not sleep out request_timeout_s inside pool.shutdown
                self._c_failures.inc()
                fut._reject(EngineClosedError(
                    "router closed while the request was pending"))
                return
            rem_ms = None
            att_timeout = self.attempt_timeout_s
            if abs_dl is not None:
                # decrement the budget per attempt: the replica's
                # admission queue sees only what remains
                rem_ms = (abs_dl - time.monotonic()) * 1e3
                if rem_ms <= 0:
                    break
                att_timeout = min(att_timeout, rem_ms / 1e3)
            r = self._pick(session, exclude)
            if r is None:
                if exclude:
                    # every admitted replica already failed this request
                    # — retry the full set (a readmission/replacement
                    # may have landed)
                    exclude = set()
                time.sleep(0.05)
                continue
            fut.attempts += 1
            self._c_dispatch.inc()
            if fut.attempts > 1:
                self._c_redispatch.inc()
            r._inc()
            info.clear()
            try:
                # the fleet id rides as ambient context: with tracing
                # on, ReplicaHandle.infer stamps it into the RPC header
                # so the replica's spans join under the router's id —
                # the SAME id on every redispatch attempt
                with trace.trace_context(fut.trace_id):
                    res = r.infer(feed, deadline_ms=rem_ms,
                                  timeout_s=att_timeout, info=info)
            except (ReplicaTransportError, TimeoutError) as e:
                # transport-class failure: trips the replica's breaker
                r.breaker.record_failure()
                last_exc = e
                exclude.add(r.name)
                # fast-failing transports (reset storms, corrupt-frame
                # windows) must not burn the whole attempt budget in
                # milliseconds — tiny growing backoff between attempts
                time.sleep(min(0.02 * fut.attempts, 0.2))
                continue
            except (QueueFullError, EngineClosedError) as e:
                # a healthy replica saying no — retryable elsewhere,
                # never a breaker signal
                last_exc = e
                exclude.add(r.name)
                time.sleep(min(0.02 * fut.attempts, 0.2))
                continue
            except BaseException as e:      # noqa: BLE001 — terminal
                self._c_failures.inc()
                fut._reject(e)
                return
            finally:
                r._dec()
            r.breaker.record_success()
            latency_s = time.monotonic() - t0
            self._h_latency.observe(latency_s)
            timing = {k: info[k] for k in ("queue_us", "device_us")
                      if info.get(k) is not None}
            fut.server_timing = timing or None
            if _flight.enabled():
                # parent-side wide event: fleet latency attributed to
                # the replica that served (plus its queue/device split
                # on traced requests) — what serve_bench's
                # slowest_requests joins on
                _flight.record_request(
                    fut.trace_id, rows, outcome="ok", replica=r.name,
                    queue_us=timing.get("queue_us"),
                    device_us=timing.get("device_us"),
                    latency_us=latency_s * 1e6)
            if t0_ns is not None and trace.enabled():
                trace.complete(
                    "fleet::request", t0_ns, cat="serving",
                    args={"trace_id": fut.trace_id, "replica": r.name,
                          "attempts": fut.attempts, "rows": rows})
            fut._resolve(res, r.name)
            return
        self._c_failures.inc()
        if abs_dl is not None and time.monotonic() >= abs_dl:
            fut._reject(DeadlineExceededError(
                f"deadline elapsed after {fut.attempts} attempts "
                f"(last: {last_exc})"))
            return
        fut._reject(NoReplicaError(
            f"no replica served the request after {fut.attempts} "
            f"attempts (last: {last_exc})"))

    # -- decode dispatch -----------------------------------------------------
    def submit_decode(self, prompt, max_new_tokens: int = 16,
                      eos_id: Optional[int] = None,
                      session: Optional[str] = None) -> FleetFuture:
        """Route one decode request.  ``session`` pins to the replica
        holding the session's warm KV pages (plain affinity); when the
        pinned replica is ejected mid-session the request redispatches
        and the NEW replica re-prefills the full prompt — prompt replay
        through the paged prefill is bit-interchangeable with decode, so
        the migrated stream stays token-identical (``decode.migrations``
        counts every forced re-pin).  The router owns the prompt until a
        replica answers: transport errors redispatch, and because the
        prompt is the session's complete history, a redispatched request
        regenerates the exact same greedy stream elsewhere."""
        if self._closed:
            raise EngineClosedError("router is closed")
        fut = FleetFuture()
        fut.trace_id = trace.new_trace_id("dec")
        prompt = np.asarray(prompt, dtype=np.int64).reshape(-1)
        t0 = time.monotonic()
        try:
            self._pool.submit(self._run_decode, fut, prompt,
                              int(max_new_tokens), eos_id, session, t0)
        except RuntimeError as e:
            raise EngineClosedError(f"router is closed: {e}") from e
        return fut

    def decode(self, prompt, max_new_tokens: int = 16,
               eos_id: Optional[int] = None,
               session: Optional[str] = None,
               timeout: Optional[float] = None) -> Dict[str, Any]:
        return self.submit_decode(prompt, max_new_tokens=max_new_tokens,
                                  eos_id=eos_id,
                                  session=session).result(timeout)

    def _run_decode(self, fut: FleetFuture, prompt, max_new, eos_id,
                    session, t0: float) -> None:
        exclude: set = set()
        last_exc: Optional[BaseException] = None
        deadline = t0 + self.request_timeout_s
        while fut.attempts < self.max_attempts \
                and time.monotonic() < deadline:
            if self._closed:
                self._c_failures.inc()
                fut._reject(EngineClosedError(
                    "router closed while the request was pending"))
                return
            r = self._pick(session, exclude)
            if r is None:
                if exclude:
                    exclude = set()
                time.sleep(0.05)
                continue
            fut.attempts += 1
            self._c_dispatch.inc()
            if fut.attempts > 1:
                self._c_redispatch.inc()
            r._inc()
            try:
                with trace.trace_context(fut.trace_id):
                    res = r.decode(prompt, max_new_tokens=max_new,
                                   eos_id=eos_id,
                                   timeout_s=self.attempt_timeout_s)
            except (ReplicaTransportError, TimeoutError) as e:
                r.breaker.record_failure()
                last_exc = e
                exclude.add(r.name)
                time.sleep(min(0.02 * fut.attempts, 0.2))
                continue
            except (QueueFullError, EngineClosedError) as e:
                last_exc = e
                exclude.add(r.name)
                time.sleep(min(0.02 * fut.attempts, 0.2))
                continue
            except BaseException as e:      # noqa: BLE001 — terminal
                self._c_failures.inc()
                fut._reject(e)
                return
            finally:
                r._dec()
            r.breaker.record_success()
            self._h_latency.observe(time.monotonic() - t0)
            if session is not None:
                self._note_decode_pin(session, r, prompt)
            fut._resolve(res, r.name)
            return
        self._c_failures.inc()
        fut._reject(NoReplicaError(
            f"no replica decoded the request after {fut.attempts} "
            f"attempts (last: {last_exc})"))

    def _note_decode_pin(self, session: str, r: ReplicaHandle,
                         prompt) -> None:
        """Record which replica now holds the session's KV pages; a
        changed pin is a MIGRATION — count it, notify the fleet, and
        tell the old replica (best-effort) to drop the session's warm
        pages so they are never leaked in its pool gauges."""
        with self._lock:
            prev = self._decode_pin.get(session)
            self._decode_pin[session] = r.name
        if prev is None or prev == r.name:
            return
        self._c_migrations.inc()
        cb = self.on_decode_migration
        if cb is not None:
            try:
                cb(session, prev, r.name)
            except Exception:           # noqa: BLE001 — observer only
                pass
        old = next((h for h in self.replicas if h.name == prev), None)
        if old is not None and old.alive():
            try:
                old.release_prefix(prompt)
            except Exception:           # noqa: BLE001 — the old replica
                pass                    # may be partitioned or dead

    def outstanding(self) -> int:
        return sum(r.outstanding for r in self.replicas)

    def close(self) -> None:
        self._closed = True
        self._pool.shutdown(wait=True)


class DecodeSession:
    """One multi-turn decode conversation routed through the fleet.

    The session object holds the AUTHORITATIVE token history (prompt +
    every generated token) parent-side, so the fleet can serve each turn
    anywhere: the pinned replica answers from its warm prefix pages,
    and a migrated turn re-prefills the identical history on the new
    replica — the emitted stream is bit-identical either way (the
    migration gate tests/test_fleet_topology.py enforces)."""

    _n = 0
    _n_lock = threading.Lock()

    def __init__(self, fleet, session: Optional[str] = None):
        self.router: Router = getattr(fleet, "router", fleet)
        if session is None:
            with DecodeSession._n_lock:
                DecodeSession._n += 1
                session = f"dsess-{DecodeSession._n}"
        self.session = session
        self.history: List[int] = []
        self.replica: Optional[str] = None

    def generate(self, tokens, max_new_tokens: int = 16,
                 eos_id: Optional[int] = None,
                 timeout: Optional[float] = None) -> Dict[str, Any]:
        """Append ``tokens`` to the history, decode ``max_new_tokens``
        through the router, fold the generated tokens back into the
        history.  Returns the replica reply plus ``replica``."""
        prompt = self.history + [int(t) for t in np.asarray(tokens,
                                                            dtype=np.int64)
                                 .reshape(-1)]
        fut = self.router.submit_decode(prompt,
                                        max_new_tokens=max_new_tokens,
                                        eos_id=eos_id,
                                        session=self.session)
        res = fut.result(timeout)
        self.history = prompt + [int(t) for t in res["tokens"]]
        self.replica = fut.replica
        return dict(res, replica=fut.replica, attempts=fut.attempts)


# ---------------------------------------------------------------------------
# fleet-wide metrics aggregation
# ---------------------------------------------------------------------------

class FleetMetricsAggregator:
    """Merges every replica's ``/stats`` + ``/metrics`` into one
    parent-side surface (docs/observability.md "Fleet observability").

    The fleet monitor feeds :meth:`record_scrape` on every health poll,
    building a bounded per-replica scrape history (also the incident
    bundle's router-side evidence window).  ``metrics_export`` serves
    the two views on the PARENT's endpoint once the fleet registers the
    aggregator as its fleet provider:

    * ``/fleet/stats`` — JSON: router stats + each replica's last
      compact payload + fleet rollups (summed counters, max p99);
    * ``/fleet/metrics`` — Prometheus text: every subprocess replica's
      samples re-labeled with ``replica="rN"`` plus ``fleet:``-prefixed
      rollups (counters summed, gauges as ``agg="min"``/``agg="max"``,
      summary quantiles as the max over replicas — a p99 upper bound —
      with ``_sum``/``_count`` summed exactly)."""

    def __init__(self, fleet: "ServingFleet", history: int = 240):
        self.fleet = fleet
        self._hist: Dict[str, deque] = {}
        self._hist_cap = int(history)
        self._lock = threading.Lock()

    # -- scrape history ------------------------------------------------------
    def record_scrape(self, name: str, stats: Dict[str, Any]) -> None:
        with self._lock:
            dq = self._hist.get(name)
            if dq is None:
                dq = self._hist[name] = deque(maxlen=self._hist_cap)
            dq.append({"ts": time.time(), "stats": stats})

    def scrape_history(self, name: Optional[str] = None,
                       since_ts: Optional[float] = None
                       ) -> Dict[str, List[Dict[str, Any]]]:
        with self._lock:
            if name is None:
                items = {n: list(dq) for n, dq in self._hist.items()}
            else:
                items = {name: list(self._hist.get(name, ()))}
        if since_ts is not None:
            items = {n: [s for s in v if s["ts"] >= since_ts]
                     for n, v in items.items()}
        return items

    # -- /fleet/stats --------------------------------------------------------
    def fleet_stats(self) -> Dict[str, Any]:
        replicas: Dict[str, Any] = {}
        rollup = {"requests": 0, "batches": 0, "rejected": 0,
                  "timeouts": 0}
        # decode-plane rollup over per-replica stats_payload "decode"
        # blocks: counters/gauges sum across the fleet, the acceptance
        # rate recomputes from the summed raw counters (a mean of
        # per-replica rates would weight an idle replica equally)
        decode_keys = ("requests", "tokens", "steps", "kv_pages_in_use",
                       "kv_page_pool_free", "prefix_hits",
                       "prefix_evictions", "spec_proposed",
                       "spec_accepted")
        decode = {k: 0 for k in decode_keys}
        decode_seen = False
        p99s: List[float] = []
        for r in list(self.fleet.router.replicas):
            st = dict(r.last_stats or {})
            st["state"] = r.state
            replicas[r.name] = st
            for k in rollup:
                try:
                    rollup[k] += int(st.get(k) or 0)
                except (TypeError, ValueError):
                    pass
            dec = st.get("decode")
            if isinstance(dec, dict):
                decode_seen = True
                for k in decode_keys:
                    try:
                        decode[k] += int(dec.get(k) or 0)
                    except (TypeError, ValueError):
                        pass
            if st.get("p99_ms") is not None:
                p99s.append(float(st["p99_ms"]))
            at = st.get("autotune")
            if isinstance(at, dict):
                # tuner-decision rollup: how many commits/reverts the
                # fleet's replicas made, without reaching into them
                ar = rollup.setdefault(
                    "autotune", {"accepts": 0, "rejects": 0,
                                 "reverts": 0})
                for k in ("accepts", "rejects", "reverts"):
                    try:
                        ar[k] += int(at.get(k) or 0)
                    except (TypeError, ValueError):
                        pass
                # per-topology attribution: decisions carry the
                # replica's mesh shape, so an 8-chip TP replica's
                # accepts roll up separately from a 1-chip one's
                for d in at.get("last_decisions") or []:
                    if not isinstance(d, dict):
                        continue
                    mesh = str(d.get("mesh") or "unsharded")
                    bym = ar.setdefault("by_mesh", {})
                    row = bym.setdefault(
                        mesh, {"accept": 0, "reject": 0, "revert": 0})
                    act = d.get("action")
                    if act in row:
                        row[act] += 1
        rollup["p99_ms_max"] = max(p99s) if p99s else None
        if decode_seen:
            decode["spec_accept_rate"] = (
                round(decode["spec_accepted"] / decode["spec_proposed"], 4)
                if decode["spec_proposed"] else None)
            rollup["decode"] = decode
        return {"fleet": self.fleet.stats(), "replicas": replicas,
                "rollup": rollup}

    # -- /fleet/metrics ------------------------------------------------------
    def fleet_metrics_text(self) -> str:
        from ..fluid import metrics_export as mx
        # family name -> {"type": str, "samples": [(sample_name,
        # labels, value, replica)]}
        fams: Dict[str, Dict[str, Any]] = {}
        notes: List[str] = []
        n_scraped = 0
        for r in list(self.fleet.router.replicas):
            if r.in_process or not r.metrics_port:
                # in-process replicas share the parent registry (the
                # plain /metrics endpoint already has them)
                notes.append(f"# replica {r.name}: in-process — see "
                             f"/metrics")
                continue
            try:
                text = urllib.request.urlopen(
                    f"http://{r.host}:{r.metrics_port}/metrics",
                    timeout=2.0).read().decode("utf-8", "replace")
            except Exception as e:  # noqa: BLE001 — a dead replica is a
                # fact to report, not a scrape failure
                notes.append(f"# replica {r.name}: scrape failed: "
                             f"{type(e).__name__}")
                continue
            n_scraped += 1
            for fam in mx.parse_prometheus_text(text):
                slot = fams.setdefault(
                    fam["name"], {"type": fam["type"], "samples": []})
                for sname, labels, value in fam["samples"]:
                    slot["samples"].append((sname, labels, value,
                                            r.name))
        out = [f"# fleet metrics: {n_scraped} replica(s) aggregated by "
               f"paddle_tpu ServingFleet"]
        out += notes
        for name in sorted(fams):
            fam = fams[name]
            ftype = fam["type"]
            out.append(f"# TYPE {name} {ftype}")
            for sname, labels, value, rep in fam["samples"]:
                lab = dict(labels)
                lab["replica"] = rep
                body = ",".join(f'{k}="{v}"' for k, v in lab.items())
                out.append(f"{sname}{{{body}}} {value:g}")
            out.extend(self._rollup_lines(name, ftype, fam["samples"]))
        return "\n".join(out) + "\n"

    @staticmethod
    def _rollup_lines(name: str, ftype: str, samples) -> List[str]:
        lines = [f"# TYPE fleet:{name} {ftype}"]
        if ftype == "counter":
            total = sum(v for sn, _l, v, _r in samples if sn == name)
            lines.append(f"fleet:{name} {total:g}")
        elif ftype == "gauge":
            vals = [v for sn, _l, v, _r in samples if sn == name]
            if vals:
                lines.append(f'fleet:{name}{{agg="min"}} {min(vals):g}')
                lines.append(f'fleet:{name}{{agg="max"}} {max(vals):g}')
        elif ftype == "summary":
            by_q: Dict[str, List[float]] = {}
            sums = {f"{name}_sum": 0.0, f"{name}_count": 0.0}
            for sname, labels, value, _r in samples:
                if sname in sums:
                    sums[sname] += value
                elif "quantile" in labels:
                    by_q.setdefault(labels["quantile"], []).append(value)
            for q in sorted(by_q):
                # max over replicas: a conservative fleet quantile
                # (exact merge needs the raw buckets)
                lines.append(f'fleet:{name}{{quantile="{q}"}} '
                             f'{max(by_q[q]):g}')
            for sname, v in sums.items():
                lines.append(f"fleet:{sname} {v:g}")
        return lines


# ---------------------------------------------------------------------------
# the fleet manager
# ---------------------------------------------------------------------------

class ServingFleet:
    """N replicas + router + health monitor + replacement.

    Subprocess fleet (CPU hosts; refused on a TPU host, where a chip
    belongs to one process and the parent already holds it)::

        fleet = ServingFleet(spec=demo_mlp_spec(), n_replicas=3,
                             persistent_cache_dir="/var/cache/paddle_tpu",
                             auto_replace=True)
        fut = fleet.submit({"x": rows})
        out = fut.result(timeout=5)
        fleet.close()

    In-process fleet (the only shape on a TPU host — one process drives
    every chip, each engine on its own device; also tests)::

        fleet = ServingFleet(replicas=[ReplicaHandle("r0", engine=e0),
                                       ReplicaHandle("r1", engine=e1)])

    The monitor thread polls each replica's ``/stats`` every
    ``scrape_interval_s``: a ``stalled``/``breached`` verdict (the PR-9
    watchdog served on /healthz — NOT a router-local timeout) or
    ``missed_scrape_limit`` consecutive missed scrapes ejects the
    replica; an ``ok`` verdict readmits it; a dead process is replaced
    when ``auto_replace`` (warm via the shared persistent cache).
    ``fleet.events`` records every transition with timestamps — the
    bench reads ejection latency and warm spin-up from it."""

    def __init__(self, spec: Optional[Dict[str, Any]] = None,
                 n_replicas: int = 2,
                 replicas: Optional[Sequence[ReplicaHandle]] = None,
                 policy: str = "least_queue",
                 scrape_interval_s: Optional[float] = None,
                 missed_scrape_limit: Optional[int] = None,
                 auto_replace: bool = False,
                 persistent_cache_dir: Optional[str] = None,
                 rpc_timeout_s: float = 15.0,
                 spawn_timeout_s: float = 180.0,
                 max_workers: int = 32,
                 max_attempts: int = 6,
                 request_timeout_s: float = 120.0,
                 env: Optional[Dict[str, str]] = None,
                 quiet_children: bool = False,
                 trace_dir: Optional[str] = None,
                 incident_bundles: Optional[bool] = None,
                 diagnostic_dir: Optional[str] = None,
                 hosts: Optional[Sequence[str]] = None):
        from ..fluid import core
        self.spec = spec
        # host-level placement: "host:port" endpoints of running host
        # agents (python -m paddle_tpu.distributed.launch --host-agent).
        # Replicas place round-robin across agents; the monitor
        # heartbeats each agent over the chaos-hardened framed RPC and a
        # partitioned host ejects EVERY replica it placed there
        # (fleet.hosts_up is the gauge, host_down/host_up the events).
        self.host_agents: List[Dict[str, Any]] = []
        if hosts:
            from ..distributed.launch import HostAgentClient
            for ep in hosts:
                h, p = str(ep).rsplit(":", 1)
                self.host_agents.append({
                    "endpoint": str(ep),
                    "client": HostAgentClient(h, int(p)),
                    "up": True, "missed": 0})
        # observability knobs: trace_dir turns tracing on in every
        # replica subprocess, one trace file per replica
        # (<trace_dir>/trace-<name>.json) for tools/timeline.py stitch;
        # incident_bundles (default FLAGS_fleet_incident_bundles=True)
        # freezes one fleet bundle per ejection into diagnostic_dir
        self.trace_dir = trace_dir
        self.incident_bundles = bool(
            core.get_flag("fleet_incident_bundles", True)
            if incident_bundles is None else incident_bundles)
        self.diagnostic_dir = diagnostic_dir
        self.bundles: List[str] = []
        self.aggregator = FleetMetricsAggregator(self)
        self.scrape_interval_s = float(
            scrape_interval_s if scrape_interval_s is not None
            else core.get_flag("fleet_scrape_interval_s", 1.0))
        self.missed_scrape_limit = int(
            missed_scrape_limit if missed_scrape_limit is not None
            else core.get_flag("fleet_missed_scrapes", 3))
        self.auto_replace = bool(auto_replace)
        self.persistent_cache_dir = persistent_cache_dir
        self.rpc_timeout_s = float(rpc_timeout_s)
        self.spawn_timeout_s = float(spawn_timeout_s)
        self.env = dict(env or {})
        self.quiet_children = bool(quiet_children)
        self.events: List[Dict[str, Any]] = []
        self._ev_lock = threading.Lock()
        self._n_spawned = 0
        self._replacing: set = set()
        m = trace.metrics()
        self._c_eject = m.counter("fleet.ejections")
        self._c_readmit = m.counter("fleet.readmissions")
        self._c_replace = m.counter("fleet.replacements")
        self._c_miss = m.counter("fleet.scrape_misses")
        self._g_up = m.gauge("fleet.replicas_up")
        self._g_hosts = m.gauge("fleet.hosts_up")
        if self.host_agents:
            self._g_hosts.set(len(self.host_agents))

        handles = list(replicas or [])
        if not handles:
            if spec is None:
                raise ValueError("ServingFleet needs a spec (subprocess "
                                 "fleet) or explicit replicas")
            try:
                for _ in range(int(n_replicas)):
                    handles.append(self.spawn_replica())
            except BaseException:
                # a failed spawn must not orphan the replicas that DID
                # come up (they would keep serving until the parent died)
                for h in handles:
                    try:
                        h.stop(timeout_s=5.0)
                    except Exception:       # noqa: BLE001 — teardown
                        if h.proc is not None:
                            h.proc.kill()
                raise
        self.router = Router(handles, policy=policy,
                             max_workers=max_workers,
                             max_attempts=max_attempts,
                             attempt_timeout_s=rpc_timeout_s,
                             request_timeout_s=request_timeout_s)
        self.router.on_decode_migration = \
            lambda sess, old, new: self._event(
                "decode_migrate", new, session=sess, source=old)
        for h in handles:
            self._wire_breaker(h)
        self._g_up.set(len(self.router.admitted()))
        self._stop = threading.Event()
        self._monitor_t = threading.Thread(target=self._monitor,
                                           name="fleet-monitor",
                                           daemon=True)
        self._monitor_t.start()
        # publish the fleet views on the parent's export endpoint
        # (/fleet/metrics + /fleet/stats); latest fleet wins if several
        # coexist in one process
        from ..fluid import metrics_export
        metrics_export.register_fleet_provider(self.aggregator)

    # -- events --------------------------------------------------------------
    def _event(self, kind: str, replica: str, **fields) -> None:
        ev = {"t_mono": time.monotonic(), "ts": time.time(),
              "kind": kind, "replica": replica, **fields}
        with self._ev_lock:
            self.events.append(ev)

    def events_of(self, kind: str) -> List[Dict[str, Any]]:
        with self._ev_lock:
            return [e for e in self.events if e["kind"] == kind]

    # -- spawn ---------------------------------------------------------------
    def spawn_replica(self, name: Optional[str] = None) -> ReplicaHandle:
        """Start one replica subprocess and wait for its ready line
        (engine built + warmed + export plane up).  With host agents
        configured the replica places round-robin across them (the
        agent forks and supervises the process); otherwise it is a
        direct child."""
        self._n_spawned += 1
        name = name or f"r{self._n_spawned - 1}"
        if self.host_agents:
            return self._spawn_on_agent(name)
        import jax
        if jax.default_backend() == "tpu":
            # a chip belongs to one process: this parent holds it (it has
            # just asked JAX for the backend), so a child that needs the
            # chip would fail or hang at start-up
            raise RuntimeError(
                "ServingFleet: refusing to start subprocess replicas on a "
                "TPU host — a chip belongs to one process at a time.  Use "
                "in-process replicas, each engine on its own device: "
                "ServingFleet(replicas=[ReplicaHandle(name, engine=e), "
                "...])")
        env = dict(os.environ)
        env.update(self.env)
        env.update(self._spec_env())
        if self.persistent_cache_dir:
            env["FLAGS_persistent_cache_dir"] = str(
                self.persistent_cache_dir)
        if self.trace_dir:
            os.makedirs(self.trace_dir, exist_ok=True)
            env["FLAGS_enable_trace"] = "1"
            env["FLAGS_trace_path"] = os.path.join(
                self.trace_dir, f"trace-{name}.json")
        elif "{replica}" in env.get("FLAGS_trace_path", ""):
            # caller-supplied template (env={"FLAGS_trace_path":
            # "/tmp/t-{replica}.json"}) — substitute the replica name
            env["FLAGS_trace_path"] = \
                env["FLAGS_trace_path"].format(replica=name)
        t_spawn = time.monotonic()
        proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.serving.fleet",
             "--serve-replica", "--spec", json.dumps(self.spec)],
            stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL if self.quiet_children else None,
            env=env, text=True)
        line_box: List[str] = []
        done = threading.Event()

        def read_ready():
            line_box.append(proc.stdout.readline())
            done.set()

        t = threading.Thread(target=read_ready, daemon=True)
        t.start()
        if not done.wait(self.spawn_timeout_s) or not line_box[0]:
            proc.kill()
            raise RuntimeError(
                f"replica {name} produced no ready line within "
                f"{self.spawn_timeout_s:.0f}s")
        info = json.loads(line_box[0])
        handle = ReplicaHandle(name, proc=proc,
                               rpc_port=info["rpc_port"],
                               metrics_port=info["metrics_port"],
                               rpc_timeout_s=self.rpc_timeout_s,
                               warmup_report=info.get("warmup"))
        handle.spawned_at = t_spawn
        handle.ready_at = time.monotonic()
        self._event("spawn", name,
                    spinup_s=round(handle.ready_at - t_spawn, 3),
                    warmup=info.get("warmup"), pid=info.get("pid"))
        return handle

    def _spec_env(self) -> Dict[str, str]:
        """Env the replica spec implies for its child process: the
        emulated multi-chip host (XLA must see the device count BEFORE
        jax initialises in the child — an env var, not a spec the child
        could apply too late) and, for sharded replicas, the
        device-truth capture that feeds the /stats hbm block."""
        env: Dict[str, str] = {}
        spec = self.spec or {}
        n_dev = int(spec.get("emulate_devices") or 0)
        if n_dev > 1:
            flag = f"--xla_force_host_platform_device_count={n_dev}"
            base = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in base:
                env["XLA_FLAGS"] = (base + " " + flag).strip()
        if spec.get("mesh"):
            env.setdefault("FLAGS_device_cost_analysis", "true")
        return env

    def _spawn_on_agent(self, name: str) -> ReplicaHandle:
        """Place one replica on the next up host agent (round-robin)."""
        live = [a for a in self.host_agents if a["up"]]
        if not live:
            raise RuntimeError("no host agent is up")
        agent = live[(self._n_spawned - 1) % len(live)]
        env = dict(self.env)
        env.update(self._spec_env())
        if self.persistent_cache_dir:
            env["FLAGS_persistent_cache_dir"] = str(
                self.persistent_cache_dir)
        t_spawn = time.monotonic()
        info = agent["client"].spawn(name, self.spec, env=env,
                                     timeout_s=self.spawn_timeout_s)
        handle = ReplicaHandle(name,
                               rpc_port=info["rpc_port"],
                               metrics_port=info["metrics_port"],
                               rpc_timeout_s=self.rpc_timeout_s,
                               warmup_report=info.get("warmup"),
                               host=agent["client"].host,
                               agent=agent["client"])
        handle.host_endpoint = agent["endpoint"]
        handle.spawned_at = t_spawn
        handle.ready_at = time.monotonic()
        self._event("spawn", name, host=agent["endpoint"],
                    spinup_s=round(handle.ready_at - t_spawn, 3),
                    warmup=info.get("warmup"), pid=info.get("pid"))
        return handle

    # -- breaker lifecycle ---------------------------------------------------
    def _wire_breaker(self, h: ReplicaHandle) -> None:
        """Breaker transitions feed the ejection/readmission lifecycle:
        open ejects (reason ``breaker_open``), a half-open probe that
        closes the breaker readmits."""
        h.breaker.on_open = lambda h=h: self._on_breaker_open(h)
        h.breaker.on_close = lambda h=h: self._on_breaker_close(h)

    def _on_breaker_open(self, r: ReplicaHandle) -> None:
        self._event("breaker_open", r.name,
                    failures=r.breaker.consecutive_failures)
        self.eject(r, "breaker_open")

    def _on_breaker_close(self, r: ReplicaHandle) -> None:
        self._event("breaker_close", r.name)
        if r.state == "ejected" and r.ejected_reason == "breaker_open":
            self.readmit(r)

    # -- monitor -------------------------------------------------------------
    def _monitor(self) -> None:
        while not self._stop.wait(self.scrape_interval_s):
            if self.host_agents:
                self._heartbeat_hosts()
            for r in list(self.router.replicas):
                if r.state in ("stopped", "draining", "dead"):
                    continue
                if not r.alive():
                    self._mark_dead(r, "died")
                    continue
                # breaker-ejected replicas get no traffic, so the
                # monitor drives the half-open probe: a transport
                # round-trip that closes the breaker readmits
                if r.state == "ejected" \
                        and r.ejected_reason == "breaker_open" \
                        and r.breaker.probe_ready():
                    r.breaker.begin_probe()
                    try:
                        ok = r.probe()
                    except Exception:   # noqa: BLE001 — a failed probe
                        ok = False      # reopens, never kills the loop
                    self._event("breaker_probe", r.name, ok=ok)
                    (r.breaker.record_success if ok
                     else r.breaker.record_failure)()
                try:
                    st = r.scrape(timeout_s=max(
                        1.0, self.scrape_interval_s * 2))
                except Exception:       # noqa: BLE001 — a missed scrape
                    r.missed_scrapes += 1
                    self._c_miss.inc()
                    if r.missed_scrapes >= self.missed_scrape_limit \
                            and r.state == "up":
                        self.eject(r, "unreachable")
                    continue
                r.missed_scrapes = 0
                r.last_stats = st
                self.aggregator.record_scrape(r.name, st)
                verdict = str(st.get("status", "ok"))
                if r.state == "up" and verdict in ("stalled", "breached"):
                    self.eject(r, verdict)
                elif r.state == "ejected" and verdict == "ok" \
                        and r.ejected_reason not in ("breaker_open",
                                                     "host_partition"):
                    # breaker ejections readmit through the probe path
                    # only — a healthy /healthz can't outrun an open
                    # breaker (the RPC plane may be partitioned while
                    # the HTTP plane still answers); host_partition
                    # ejections readmit only when the HOST's heartbeat
                    # recovers (the whole box is suspect, not one
                    # process)
                    self.readmit(r)
            self._g_up.set(len(self.router.admitted()))

    def _heartbeat_hosts(self) -> None:
        """One framed-RPC ping per agent per tick: ``missed_scrape_limit``
        consecutive misses flips the host down and ejects every replica
        it placed (reason ``host_partition``); a recovered ping flips it
        up and readmits exactly those."""
        for ag in self.host_agents:
            try:
                ag["client"].ping()
                ok = True
            except Exception:           # noqa: BLE001 — a missed
                ok = False              # heartbeat is the signal
            if ok:
                ag["missed"] = 0
                if not ag["up"]:
                    ag["up"] = True
                    self._event("host_up", ag["endpoint"])
                    for r in self._host_replicas(ag["endpoint"]):
                        if r.state == "ejected" \
                                and r.ejected_reason == "host_partition":
                            self.readmit(r)
            else:
                ag["missed"] += 1
                if ag["missed"] >= self.missed_scrape_limit and ag["up"]:
                    ag["up"] = False
                    self._event("host_down", ag["endpoint"],
                                missed=ag["missed"])
                    for r in self._host_replicas(ag["endpoint"]):
                        self.eject(r, "host_partition")
        self._g_hosts.set(sum(1 for a in self.host_agents if a["up"]))

    def _host_replicas(self, endpoint: str) -> List[ReplicaHandle]:
        return [r for r in list(self.router.replicas)
                if getattr(r, "host_endpoint", None) == endpoint]

    def _mark_dead(self, r: ReplicaHandle, reason: str) -> None:
        if r.state != "dead":
            if r.state == "up":
                self.eject(r, reason)
            r.state = "dead"
            self._event("dead", r.name, reason=reason)
            if self.auto_replace and r.name not in self._replacing:
                self._replacing.add(r.name)
                threading.Thread(target=self._replace, args=(r,),
                                 daemon=True).start()

    def _replace(self, dead: ReplicaHandle) -> None:
        try:
            handle = self.spawn_replica()
            self._wire_breaker(handle)
            self.router.add_replica(handle)
            self._c_replace.inc()
            self._event("replace", handle.name, replaced=dead.name,
                        warmup=handle.warmup_report)
        except Exception as e:          # noqa: BLE001 — monitor survives
            self._event("replace_failed", dead.name, error=str(e))
        finally:
            self._replacing.discard(dead.name)

    # -- ejection lifecycle --------------------------------------------------
    def eject(self, replica, reason: str) -> None:
        """Remove a replica from dispatch rotation.  Its outstanding
        requests redispatch on their next attempt; accepted work is
        never lost (the router owns the payloads)."""
        r = self._resolve(replica)
        if r.state != "up":
            return
        r.state = "ejected"
        r.ejected_reason = reason
        self._c_eject.inc()
        self._event("eject", r.name, reason=reason)
        self._g_up.set(len(self.router.admitted()))
        if self.incident_bundles:
            # ONE fleet bundle per incident, frozen off the hot path:
            # eject() is the single funnel every ejection cause
            # (verdict, breaker, death) passes through, and a replica
            # re-ejected later is a NEW incident.  The freeze thread
            # must not block the monitor/breaker callback — the
            # replica-side fetch rides an HTTP timeout.
            threading.Thread(target=self._freeze_fleet_bundle,
                             args=(r, reason), name="fleet-bundle",
                             daemon=True).start()

    def _freeze_fleet_bundle(self, r: ReplicaHandle, reason: str) -> None:
        """Coordinated incident bundle: the router-side view of the
        ejection window (routing decisions, breaker states, scrape
        history) plus the ejected replica's OWN watchdog bundle fetched
        before any teardown — one JSON document `diagnose.py --fleet`
        renders as the cross-process story."""
        from ..fluid import watchdog as wdog
        try:
            now = time.time()
            window_s = 120.0
            with self._ev_lock:
                events = [e for e in self.events
                          if now - e["ts"] <= window_s]
            router_view = {
                "stats": self.stats(),
                "events": events,
                "breakers": {h.name: h.breaker.describe()
                             for h in list(self.router.replicas)},
                "in_flight": self.router.outstanding(),
                # routing decisions: the parent-side flight records the
                # router writes per dispatched request (replica
                # attribution + queue/device split when traced)
                "requests": [rec for rec in
                             _flight.recorder().snapshot(last=500)
                             if rec.get("kind") == "request"],
                "scrape_history": self.aggregator.scrape_history(
                    since_ts=now - window_s),
                "window_s": window_s,
            }
            bundles: Dict[str, Any] = {}
            try:
                bundles[r.name] = r.fetch_bundle(
                    timeout_s=max(2.0, self.rpc_timeout_s / 3),
                    reason=f"fleet_{reason}")
            except Exception as e:      # noqa: BLE001 — a dead/
                # partitioned replica can't answer; the router-side
                # view still ships
                bundles[r.name] = {"error": f"{type(e).__name__}: {e}"}
            path = wdog.dump_fleet_bundle(
                reason, r.name, router_view, bundles,
                diagnostic_dir=self.diagnostic_dir)
            if path:
                self.bundles.append(path)
                self._event("fleet_bundle", r.name, reason=reason,
                            path=path)
        except Exception:               # noqa: BLE001 — diagnostics
            # must never take the control plane down with them
            trace.metrics().counter("fleet.bundle_errors").inc()

    def readmit(self, replica) -> None:
        r = self._resolve(replica)
        if r.state != "ejected":
            return
        r.state = "up"
        r.ejected_reason = None
        self._c_readmit.inc()
        self._event("readmit", r.name)
        self._g_up.set(len(self.router.admitted()))

    def _resolve(self, replica) -> ReplicaHandle:
        if isinstance(replica, ReplicaHandle):
            return replica
        for r in self.router.replicas:
            if r.name == replica:
                return r
        raise KeyError(f"no replica named {replica!r}")

    # -- planned shutdown ----------------------------------------------------
    def remove_replica(self, replica, timeout_s: float = 60.0) -> None:
        """Planned drain-without-loss: stop dispatching to the replica,
        wait for its in-flight requests to complete, drain its engine,
        stop it."""
        r = self._resolve(replica)
        r.state = "draining"
        self._event("drain", r.name)
        deadline = time.monotonic() + timeout_s
        while r.outstanding > 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        try:
            r.drain()
        except ServingError:
            pass
        r.stop()
        self.router.remove(r)
        self._event("removed", r.name)
        self._g_up.set(len(self.router.admitted()))

    def kill_replica(self, replica) -> ReplicaHandle:
        """SIGKILL a replica (chaos drill).  Returns the handle so the
        caller can correlate the kill with the later eject event."""
        r = self._resolve(replica)
        self._event("kill", r.name)
        r.kill()
        return r

    # -- dispatch ------------------------------------------------------------
    def submit(self, feed, session=None, deadline_ms=None) -> FleetFuture:
        return self.router.submit(feed, session=session,
                                  deadline_ms=deadline_ms)

    def infer(self, feed, session=None, deadline_ms=None, timeout=None):
        return self.router.infer(feed, session=session,
                                 deadline_ms=deadline_ms, timeout=timeout)

    def submit_decode(self, prompt, max_new_tokens=16, eos_id=None,
                      session=None) -> FleetFuture:
        return self.router.submit_decode(prompt,
                                         max_new_tokens=max_new_tokens,
                                         eos_id=eos_id, session=session)

    def decode(self, prompt, max_new_tokens=16, eos_id=None,
               session=None, timeout=None) -> Dict[str, Any]:
        return self.router.decode(prompt, max_new_tokens=max_new_tokens,
                                  eos_id=eos_id, session=session,
                                  timeout=timeout)

    def decode_session(self, session: Optional[str] = None
                       ) -> DecodeSession:
        return DecodeSession(self, session=session)

    # -- introspection -------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        m = trace.metrics()
        lat = m.histogram("fleet.latency_seconds").stats()
        out = {
            "replicas": [{
                "name": r.name, "state": r.state,
                "reason": r.ejected_reason,
                "host": getattr(r, "host_endpoint", None),
                "outstanding": r.outstanding,
                "queue_depth": r.last_stats.get("queue_depth"),
                "status": r.last_stats.get("status"),
                "breaker": r.breaker.describe(),
            } for r in self.router.replicas],
            "admitted": len(self.router.admitted()),
            "dispatches": m.counter("fleet.dispatches").value,
            "redispatches": m.counter("fleet.redispatches").value,
            "ejections": self._c_eject.value,
            "readmissions": self._c_readmit.value,
            "replacements": self._c_replace.value,
            "breaker_opens": m.counter("fleet.breaker_opens").value,
            "breaker_closes": m.counter("fleet.breaker_closes").value,
            "failures": m.counter("fleet.failures").value,
            "decode_migrations": m.counter("decode.migrations").value,
            "latency": {k: lat[k] for k in
                        ("count", "avg", "p50", "p95", "p99")},
            "events": len(self.events),
        }
        if self.host_agents:
            out["hosts"] = [{"endpoint": a["endpoint"], "up": a["up"],
                             "missed": a["missed"],
                             "replicas": [r.name for r in
                                          self._host_replicas(
                                              a["endpoint"])]}
                            for a in self.host_agents]
            out["hosts_up"] = sum(1 for a in self.host_agents if a["up"])
        return out

    def close(self, timeout_s: float = 30.0) -> None:
        from ..fluid import metrics_export
        metrics_export.unregister_fleet_provider(self.aggregator)
        self._stop.set()
        self._monitor_t.join(timeout=10)
        self.router.close()
        for r in list(self.router.replicas):
            try:
                r.stop(timeout_s=timeout_s)
            except Exception:           # noqa: BLE001 — teardown
                if r.proc is not None:
                    r.proc.kill()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False


# ---------------------------------------------------------------------------
# child entry point
# ---------------------------------------------------------------------------

def _main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="serving-fleet replica process")
    ap.add_argument("--serve-replica", action="store_true")
    ap.add_argument("--spec", default="{}")
    args = ap.parse_args(argv)
    if not args.serve_replica:
        ap.error("only --serve-replica mode is supported")
    from ..fluid import compile_cache
    compile_cache.enable_jax_cache()
    serve_replica(json.loads(args.spec))
    return 0


if __name__ == "__main__":
    sys.exit(_main())
