"""Serving fleet: router policies, health-based ejection/readmission,
drain-without-loss, per-engine instrument namespacing, the compact
/stats endpoint, and the RPC replica server.

Policy/lifecycle tests run on IN-PROCESS replica handles with injected
``infer_fn``/``health_fn`` (no subprocesses, no device work) — the
router/monitor logic is identical for both kinds.  One subprocess test
covers the real spawn/ready/stop path; the full kill-mid-burst drill
lives in tools/ci_smoke.py.
"""
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import paddle_tpu.fluid as fluid                          # noqa: E402
from paddle_tpu.fluid import trace                        # noqa: E402
from paddle_tpu.fluid.core import Scope, scope_guard      # noqa: E402
from paddle_tpu import serving                            # noqa: E402
from paddle_tpu.serving import fleet as F                 # noqa: E402


def make_stub(name, depth=0, status="ok", fail_times=0, delay=0.0,
              record=None):
    """An in-process replica handle around injected functions."""
    state = {"fails": fail_times, "depth": depth, "status": status}

    def infer(feed):
        if record is not None:
            record.append(name)
        if state["fails"] > 0:
            state["fails"] -= 1
            raise F.ReplicaTransportError(f"{name} transient")
        if delay:
            time.sleep(delay)
        return {"y": np.asarray(feed["x"]) * 2.0}

    def health():
        if state["status"] == "unreachable":
            raise OSError("scrape refused")
        return {"status": state["status"],
                "queue_depth": state["depth"]}

    h = F.ReplicaHandle(name, infer_fn=infer, health_fn=health)
    h._stub_state = state
    return h


def make_fleet(handles, **kw):
    kw.setdefault("scrape_interval_s", 0.03)
    kw.setdefault("missed_scrape_limit", 2)
    return F.ServingFleet(replicas=handles, **kw)


def wait_for(cond, timeout=10.0, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


class TestRouterPolicies:
    def test_least_queue_prefers_shallow(self):
        record = []
        a = make_stub("a", depth=0, record=record)
        b = make_stub("b", depth=7, record=record)
        fl = make_fleet([a, b])
        try:
            wait_for(lambda: a.last_stats and b.last_stats,
                     msg="first scrapes")
            for _ in range(8):
                fl.submit({"x": np.ones(2, "float32")}).result(5)
            assert record.count("a") > record.count("b")
            # flip the depths: the router follows the signal
            a._stub_state["depth"], b._stub_state["depth"] = 9, 0
            wait_for(lambda: b.last_stats.get("queue_depth") == 0,
                     msg="rescrape")
            record.clear()
            for _ in range(8):
                fl.submit({"x": np.ones(2, "float32")}).result(5)
            assert record.count("b") > record.count("a")
        finally:
            fl.close()

    def test_round_robin_rotates(self):
        record = []
        handles = [make_stub(n, record=record) for n in ("a", "b", "c")]
        fl = make_fleet(handles, policy="round_robin")
        try:
            for _ in range(9):
                fl.submit({"x": np.ones(1, "float32")}).result(5)
            counts = {n: record.count(n) for n in ("a", "b", "c")}
            assert counts == {"a": 3, "b": 3, "c": 3}, counts
        finally:
            fl.close()

    def test_bad_policy_rejected(self):
        with pytest.raises(ValueError):
            F.Router([], policy="random")

    def test_session_affinity_sticks_and_rebinds(self):
        record = []
        handles = [make_stub(n, record=record) for n in ("a", "b")]
        fl = make_fleet(handles, policy="round_robin")
        try:
            futs = [fl.submit({"x": np.ones(1, "float32")},
                              session="s1") for _ in range(6)]
            [f.result(5) for f in futs]
            served = {f.replica for f in futs}
            assert len(served) == 1, served     # sticky
            pinned = served.pop()
            rebind0 = trace.metrics().counter(
                "fleet.affinity_rebinds").value
            # eject the pinned replica: the session re-pins elsewhere
            fl.eject(pinned, "stalled")
            futs = [fl.submit({"x": np.ones(1, "float32")},
                              session="s1") for _ in range(4)]
            [f.result(5) for f in futs]
            served2 = {f.replica for f in futs}
            assert len(served2) == 1 and served2 != {pinned}
            assert trace.metrics().counter(
                "fleet.affinity_rebinds").value > rebind0
        finally:
            fl.close()


class TestEjectionLifecycle:
    def test_eject_on_stalled_verdict_and_readmit(self):
        a = make_stub("a")
        b = make_stub("b")
        fl = make_fleet([a, b])
        try:
            b._stub_state["status"] = "stalled"
            wait_for(lambda: b.state == "ejected", msg="verdict eject")
            assert b.ejected_reason == "stalled"
            # dispatch avoids the ejected replica entirely
            futs = [fl.submit({"x": np.ones(1, "float32")})
                    for _ in range(5)]
            assert {f.result(5) and f.replica for f in futs} == {"a"}
            # recovery: ok verdict readmits
            b._stub_state["status"] = "ok"
            wait_for(lambda: b.state == "up", msg="readmission")
            assert b.ejected_reason is None
        finally:
            fl.close()

    def test_eject_on_missed_scrapes(self):
        a = make_stub("a")
        b = make_stub("b")
        fl = make_fleet([a, b], missed_scrape_limit=3)
        try:
            b._stub_state["status"] = "unreachable"
            wait_for(lambda: b.state == "ejected", msg="unreachable eject")
            assert b.ejected_reason == "unreachable"
            assert b.missed_scrapes >= 3
            ev = fl.events_of("eject")
            assert any(e["replica"] == "b"
                       and e["reason"] == "unreachable" for e in ev)
        finally:
            fl.close()

    def test_redispatch_preserves_accepted_requests(self):
        # replica a fails its first two attempts at transport level:
        # the router owns the payload and redispatches — zero loss
        record = []
        a = make_stub("a", fail_times=2, record=record)
        b = make_stub("b", depth=9, record=record)   # worse score
        fl = make_fleet([a, b])
        try:
            wait_for(lambda: a.last_stats and b.last_stats, msg="scrape")
            redis0 = trace.metrics().counter("fleet.redispatches").value
            out = fl.submit({"x": np.ones(3, "float32")}).result(10)
            assert np.array_equal(out["y"], np.full(3, 2.0, "float32"))
            assert trace.metrics().counter(
                "fleet.redispatches").value > redis0
        finally:
            fl.close()

    def test_drain_without_loss_on_planned_shutdown(self):
        record = []
        a = make_stub("a", delay=0.15, record=record)
        b = make_stub("b", depth=9, record=record)
        fl = make_fleet([a, b])
        try:
            wait_for(lambda: a.last_stats and b.last_stats, msg="scrape")
            futs = [fl.submit({"x": np.ones(1, "float32")})
                    for _ in range(4)]
            time.sleep(0.05)       # in flight on a (the shallow one)
            fl.remove_replica("a")
            outs = [f.result(20) for f in futs]
            assert len(outs) == 4 and all(o is not None for o in outs)
            assert "a" not in [r.name for r in fl.router.replicas]
            kinds = [e["kind"] for e in fl.events]
            assert "drain" in kinds and "removed" in kinds
        finally:
            fl.close()

    def test_no_replica_error_after_attempts(self):
        a = make_stub("a", fail_times=99)
        fl = make_fleet([a], request_timeout_s=2.0)
        try:
            fut = fl.submit({"x": np.ones(1, "float32")})
            with pytest.raises(F.NoReplicaError):
                fut.result(15)
        finally:
            fl.close()


class TestEngineNamespacing:
    def _demo_engine(self, exe, name):
        main_p, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_p, startup):
            x = fluid.data(f"x_{name}", [-1, 4])
            logits = fluid.layers.fc(x, 3)
        exe.run(startup)
        frozen = serving.freeze_program(main_p, [f"x_{name}"], [logits])
        eng = serving.ServingEngine(frozen, executor=exe, max_batch=8,
                                    max_wait_us=500, name=name)
        return eng, f"x_{name}", logits.name

    def test_named_engines_attribute_separately(self):
        m = trace.metrics()
        exe = fluid.Executor()
        with scope_guard(Scope()):
            ea, feed_a, _ = self._demo_engine(exe, "ra")
            eb, feed_b, _ = self._demo_engine(exe, "rb")
            base_a = m.counter("serving.ra.requests").value
            base_b = m.counter("serving.rb.requests").value
            base_plain = m.counter("serving.requests").value
            with ea, eb:
                fa = [ea.submit({feed_a: np.ones((2, 4), "float32")})
                      for _ in range(3)]
                fb = [eb.submit({feed_b: np.ones((1, 4), "float32")})
                      for _ in range(5)]
                [f.result(30) for f in fa + fb]
            # per-engine families attribute exactly
            assert m.counter("serving.ra.requests").value - base_a == 3
            assert m.counter("serving.rb.requests").value - base_b == 5
            # the plain family aggregates BOTH (default-engine alias
            # stays a fleet-wide roll-up)
            assert m.counter("serving.requests").value - base_plain == 8
            # stats() reads the engine's own family
            assert ea.stats()["requests"] == \
                m.counter("serving.ra.requests").value
            assert ea.stats()["name"] == "ra"

    def test_unnamed_engine_keeps_plain_family(self):
        m = trace.metrics()
        exe = fluid.Executor()
        with scope_guard(Scope()):
            eng, feed_n, _ = self._demo_engine(exe, "plainx")
            # build an UNNAMED engine over the same frozen program
            eng2 = serving.ServingEngine(eng._backend.program,
                                         executor=exe, max_batch=8,
                                         max_wait_us=500)
            base = m.counter("serving.requests").value
            with eng2:
                f = eng2.submit({feed_n: np.ones((2, 4), "float32")})
                f.result(30)
            assert m.counter("serving.requests").value == base + 1
            assert eng2.stats()["name"] is None
            eng.close()


class TestStatsEndpoint:
    def test_stats_payload_and_endpoint(self):
        from paddle_tpu.fluid import metrics_export as mx
        m = trace.metrics()
        # seed a named family so the engines block renders
        m.gauge("serving.sx.queue_depth").set(3)
        m.counter("serving.sx.requests").inc(2)
        m.histogram("serving.sx.latency_seconds").observe(0.01)
        payload = mx.stats_payload()
        for key in ("status", "uptime_s", "queue_depth", "p99_ms",
                    "requests", "batches"):
            assert key in payload, payload
        assert payload["engines"]["sx"]["queue_depth"] == 3
        assert payload["engines"]["sx"]["requests"] == 2
        assert payload["engines"]["sx"]["p99_ms"] > 0
        srv = mx.start_http(port=0)
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/stats", timeout=10).read()
            doc = json.loads(body)
            assert doc["status"] in ("ok", "stalled", "breached")
            assert "engines" in doc
        finally:
            mx.stop_http()


class TestReplicaServer:
    def test_rpc_roundtrip_pause_stats_drain(self):
        exe = fluid.Executor()
        with scope_guard(Scope()):
            main_p, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main_p, startup):
                x = fluid.data("x", [-1, 4])
                logits = fluid.layers.fc(x, 3)
            exe.run(startup)
            frozen = serving.freeze_program(main_p, ["x"], [logits])
            eng = serving.ServingEngine(frozen, executor=exe,
                                        max_batch=8, max_wait_us=500)
            srv = F.ReplicaServer(eng, info={"warmup": None}).start()
            handle = F.ReplicaHandle("r", rpc_port=srv.port,
                                     rpc_timeout_s=10.0)
            try:
                # hello
                reply, _ = handle.call({"op": "hello"})
                assert reply["ok"] and reply["pid"] == os.getpid()
                # infer round-trips arrays through the real engine
                feed = np.arange(8, dtype="float32").reshape(2, 4)
                out = handle.infer({"x": feed})
                ref, = exe.run(frozen, feed={"x": feed},
                               fetch_list=[logits])
                assert np.array_equal(out[logits.name], np.asarray(ref))
                # stats carries the watchdog verdict word
                reply, _ = handle.call({"op": "stats"})
                assert reply["stats"]["status"] in ("ok", "stalled",
                                                    "breached")
                # pause blocks dispatch; resume releases it
                handle.pause()
                assert eng.paused()
                fut = eng.submit({"x": feed})
                time.sleep(0.1)
                assert not fut.done()
                handle.resume()
                fut.result(timeout=30)
                # unknown op reports, does not kill the connection
                reply, _ = handle.call({"op": "nope"})
                assert not reply["ok"]
                handle.drain()
            finally:
                srv.stop()

    def test_transport_error_is_retryable_shape(self):
        handle = F.ReplicaHandle("gone", rpc_port=1, rpc_timeout_s=0.2)
        with pytest.raises(F.ReplicaTransportError):
            handle.infer({"x": np.ones((1, 4), "float32")})


class TestCircuitBreaker:
    def test_open_halfopen_close_lifecycle(self):
        clock = [0.0]
        events = []
        b = F.CircuitBreaker(failures=3, cooldown_s=1.0,
                             now_fn=lambda: clock[0],
                             on_open=lambda: events.append("open"),
                             on_close=lambda: events.append("close"))
        assert b.available()
        b.record_failure()
        b.record_failure()
        assert b.state == "closed" and b.available()
        b.record_failure()                 # 3rd consecutive: open
        assert b.state == "open" and events == ["open"]
        assert not b.available()           # cooling down
        clock[0] = 1.5
        assert b.probe_ready() and b.available()
        b.begin_probe()
        assert b.state == "half_open"
        assert not b.available()           # one probe at a time
        b.record_failure()                 # probe failed: reopen
        assert b.state == "open" and not b.probe_ready()
        clock[0] = 3.0
        assert b.probe_ready()
        b.begin_probe()
        b.record_success()                 # probe ok: close
        assert b.state == "closed" and events == ["open", "close"]
        assert b.available()

    def test_success_resets_consecutive_count(self):
        b = F.CircuitBreaker(failures=3, cooldown_s=1.0)
        b.record_failure()
        b.record_failure()
        b.record_success()
        b.record_failure()
        b.record_failure()
        assert b.state == "closed"         # never 3 CONSECUTIVE

    def test_threshold_zero_disables(self):
        b = F.CircuitBreaker(failures=0, cooldown_s=0.1)
        for _ in range(50):
            b.record_failure()
        assert b.state == "closed"


class TestBreakerFleet:
    def _flaky(self, name, state):
        def infer(feed):
            if not state["healthy"]:
                raise F.ReplicaTransportError(f"{name} transport down")
            return {"y": np.asarray(feed["x"]) * 2.0}

        return F.ReplicaHandle(
            name, infer_fn=infer,
            health_fn=lambda: {"status": "ok", "queue_depth": 0},
            probe_fn=lambda: state["healthy"],
            breaker=F.CircuitBreaker(failures=2, cooldown_s=0.05,
                                     name=name))

    def test_breaker_opens_ejects_probes_readmits(self):
        state = {"healthy": False}
        bad = self._flaky("bad", state)
        good = make_stub("good", depth=5)
        fl = make_fleet([bad, good])
        try:
            # requests flow despite the dead-transport replica: the
            # router redispatches, the breaker opens after 2 consecutive
            # transport failures and EJECTS via the fleet lifecycle
            # (sequential submits so each pick sees settled load scores)
            outs = [fl.submit({"x": np.ones(1, "float32")}).result(15)
                    for _ in range(6)]
            assert len(outs) == 6          # zero lost
            wait_for(lambda: bad.state == "ejected"
                     and bad.ejected_reason == "breaker_open",
                     msg="breaker ejection")
            assert fl.events_of("breaker_open")
            # an ok VERDICT must not readmit a breaker-ejected replica
            # while its transport stays dead (probes keep failing)
            time.sleep(0.3)
            assert bad.state == "ejected"
            assert bad.breaker.state == "open"
            # heal the transport: the monitor's half-open probe closes
            # the breaker, which readmits
            state["healthy"] = True
            wait_for(lambda: bad.state == "up", msg="breaker readmission")
            assert bad.breaker.state == "closed"
            assert fl.events_of("breaker_close")
            assert fl.events_of("breaker_probe")
            # and it serves again
            record = []
            bad._infer_fn_orig = None
            futs = [fl.submit({"x": np.ones(1, "float32")})
                    for _ in range(8)]
            served = {f.result(10) and f.replica for f in futs}
            assert "bad" in served or "good" in served
            # breaker state is surfaced in fleet stats
            st = fl.stats()
            names = {r["name"]: r["breaker"]["state"]
                     for r in st["replicas"]}
            assert names["bad"] == "closed"
            assert st["breaker_opens"] >= 1
        finally:
            fl.close()

    def test_open_breaker_gates_dispatch_before_ejection(self):
        """Router-level: an open breaker excludes the replica from
        _pick even while still formally admitted."""
        state = {"healthy": False}
        bad = self._flaky("bad", state)
        good = make_stub("good", depth=5)
        router = F.Router([bad, good], max_attempts=8)
        try:
            for _ in range(4):
                router.submit({"x": np.ones(1, "float32")}).result(10)
            assert bad.breaker.state in ("open", "half_open")
            assert bad.state == "up"       # no fleet monitor: not ejected
            # while open (cooldown running), only good is pickable
            picked = router._pick(None, set())
            assert picked is None or picked.name == "good" \
                or bad.breaker.state == "half_open"
        finally:
            router.close()


class TestDeadlinePropagation:
    def _capture_handle(self, seen, delay=0.0):
        def infer(feed, deadline_ms=None):
            seen.append(deadline_ms)
            if delay:
                time.sleep(delay)
            return {"y": np.asarray(feed["x"])}

        return F.ReplicaHandle(
            "d", infer_fn=infer,
            health_fn=lambda: {"status": "ok", "queue_depth": 0})

    def test_deadline_decrements_through_router(self):
        seen = []
        fl = make_fleet([self._capture_handle(seen)])
        try:
            fl.submit({"x": np.ones(1, "float32")},
                      deadline_ms=5000).result(5)
            assert seen[-1] is not None and 0 < seen[-1] <= 5000
            fl.submit({"x": np.ones(1, "float32")}).result(5)
            assert seen[-1] is None        # no deadline -> none invented
        finally:
            fl.close()

    def test_expired_deadline_rejects_typed(self):
        def infer(feed, deadline_ms=None):
            time.sleep(0.08)
            raise F.ReplicaTransportError("flaky")

        h = F.ReplicaHandle(
            "d", infer_fn=infer,
            health_fn=lambda: {"status": "ok", "queue_depth": 0})
        fl = make_fleet([h])
        try:
            fut = fl.submit({"x": np.ones(1, "float32")}, deadline_ms=120)
            from paddle_tpu.serving.engine import DeadlineExceededError
            with pytest.raises(DeadlineExceededError):
                fut.result(15)
        finally:
            fl.close()

    def test_replica_server_sheds_expired_infer(self):
        """An already-expired request is shed at the replica's door —
        it never reaches the engine's admission queue."""
        from paddle_tpu.distributed.ps.rpc import recv_msg, send_msg
        import socket as sk
        srv = F.ReplicaServer(engine=None, info={})    # engine untouched
        srv.start()
        shed0 = trace.metrics().counter("rpc.deadline_shed").value
        s = sk.create_connection(("127.0.0.1", srv.port))
        try:
            send_msg(s, {"op": "infer", "feeds": ["x"],
                         "deadline_ts": time.time() - 1.0},
                     [np.ones((1, 2), "float32")])
            reply, _ = recv_msg(s)
        finally:
            s.close()
            srv.stop()
        assert reply["ok"] is False and reply.get("shed")
        assert reply["error"] == "DeadlineExceededError"
        assert trace.metrics().counter(
            "rpc.deadline_shed").value == shed0 + 1


class TestSubprocessReplica:
    def test_spawn_serve_remove(self, tmp_path):
        """The real child path: spawn one demo replica, serve over RPC,
        scrape /stats over HTTP, planned remove.  (The kill-mid-burst
        drill is the ci_smoke fleet gate.)"""
        fl = F.ServingFleet(
            spec=F.demo_mlp_spec(hidden=16, max_batch=8),
            n_replicas=1, scrape_interval_s=0.2,
            persistent_cache_dir=str(tmp_path / "cache"),
            rpc_timeout_s=10.0, quiet_children=True)
        try:
            r = fl.router.replicas[0]
            assert r.warmup_report and r.warmup_report["compiles"] >= 1
            rng = np.random.RandomState(0)
            futs = [fl.submit({"x": rng.randn(1 + i % 4, 16)
                               .astype("float32")}) for i in range(12)]
            outs = [f.result(30) for f in futs]
            assert len(outs) == 12
            st = r.scrape()
            assert st["status"] == "ok" and st["requests"] >= 12
            fl.remove_replica(r)
            assert r.state == "stopped"
            assert r.proc.poll() is not None
        finally:
            fl.close()

    def test_refused_on_a_tpu_host(self, monkeypatch):
        """A chip belongs to one process, and the parent that asks JAX
        for the platform holds it: subprocess replicas are refused there,
        with a message that names in-process replicas."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="in-process replicas"):
            F.ServingFleet(spec=F.demo_mlp_spec(hidden=16), n_replicas=1,
                           quiet_children=True)
