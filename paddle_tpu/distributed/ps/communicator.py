"""Trainer-side communicators: when grads travel to the PS.

Reference: paddle/fluid/operators/distributed/communicator.h —
AsyncCommunicator (:268, background send/recv threads draining per-var
queues), HalfAsyncCommunicator (:340, batched flush without global
ordering), SyncCommunicator (:383, barrier per step), GeoCommunicator
(:414, delta pushes every k local steps).  TPU-native: the trainer's
whole dense step is one XLA program, so the communicator only moves
host-side numpy grads; overlap comes from the send thread running while
the next device step computes.
"""
from __future__ import annotations

import queue
import threading
from typing import Dict, Optional, Tuple

import numpy as np

from .rpc import PsClient


class AsyncCommunicator:
    """Fire-and-forget push: grads enqueue, a background thread drains
    (communicator.h:268).  Pulls hit the server directly, with bounded
    staleness: at most ``FLAGS_ps_staleness`` pushes may be outstanding
    before a pull fences, so what a pull reads does not depend on how fast
    the drain thread ran (0, the default, = every push lands first; k > 0
    lets k pushes overlap the next pull, as the reference's unbounded
    queue does)."""

    def __init__(self, client: PsClient, queue_size=64):
        from ...fluid import core
        self.client = client
        self.staleness = int(core.get_flag("ps_staleness", 0))
        self._cv = threading.Condition()
        self._pushed = self._applied = 0
        self._q: "queue.Queue[Optional[Tuple]]" = queue.Queue(queue_size)
        self._thread = threading.Thread(target=self._drain, daemon=True)
        self._err: Optional[BaseException] = None
        self._running = True
        self._thread.start()

    def _drain(self):
        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            try:
                kind, name, a, b = item
                if kind == "sparse":
                    self.client.push_sparse(name, a, b)
                else:
                    self.client.push_dense(name, a)
            except BaseException as e:       # noqa: BLE001 — surfaced on next call
                self._err = e
            finally:
                with self._cv:
                    self._applied += 1
                    self._cv.notify_all()
                self._q.task_done()

    def _check(self):
        if self._err is not None:
            err, self._err = self._err, None
            raise RuntimeError(f"async communicator send failed: {err}")

    def _enqueue(self, item):
        with self._cv:
            self._pushed += 1
        self._q.put(item)

    def _fence(self):
        """Wait until at most ``staleness`` pushes are outstanding."""
        with self._cv:
            while (self._pushed - self._applied > self.staleness
                   and self._err is None and self._thread.is_alive()):
                self._cv.wait(0.1)
        self._check()

    def push_sparse(self, name, ids, grads):
        self._check()
        self._enqueue(("sparse", name, np.asarray(ids), np.asarray(grads)))

    def push_dense(self, name, grad):
        self._check()
        self._enqueue(("dense", name, np.asarray(grad), None))

    def pull_sparse(self, name, ids):
        self._fence()
        return self.client.pull_sparse(name, ids)

    def pull_dense(self, name):
        self._fence()
        return self.client.pull_dense(name)

    def flush(self):
        self._q.join()
        self._check()

    def stop(self):
        if self._running:
            self._running = False
            self._q.put(None)
            self._thread.join(timeout=10)


class HalfAsyncCommunicator(AsyncCommunicator):
    """Batched flush each step, no cross-trainer barrier
    (communicator.h:340): push_* enqueue, step() drains the queue."""

    def step(self):
        self.flush()


class SyncCommunicator(AsyncCommunicator):
    """Synchronous PS-SGD (communicator.h:383): every step flushes sends
    and joins the global barrier so all trainers advance together."""

    def push_sparse(self, name, ids, grads):
        self._check()
        self.client.push_sparse(name, ids, grads)   # inline, no queue

    def push_dense(self, name, grad):
        self._check()
        self.client.push_dense(name, grad)

    def step(self):
        self.client.barrier()


class GeoCommunicator:
    """GEO-SGD (communicator.h:414 + SparseGeoTable): trainers own a local
    copy, train on it, and every `push_nums` steps exchange DELTAS with the
    server — push (local - base), pull fresh global, rebase."""

    def __init__(self, client: PsClient, push_nums=100):
        self.client = client
        self.push_nums = push_nums
        self._step = 0
        # dense: name -> (local value ref getter/setter via dicts)
        self._dense_base: Dict[str, np.ndarray] = {}
        self._sparse_base: Dict[str, Dict[int, np.ndarray]] = {}
        self._touched: Dict[str, set] = {}

    # -- dense --------------------------------------------------------------
    def register_dense(self, name, value):
        """Start tracking a dense param; returns the initial global value."""
        server_val = self.client.pull_dense(name)
        self._dense_base[name] = server_val.copy()
        return server_val

    def sync_dense(self, name, local_value):
        """Push delta, pull fresh, rebase; returns the new local value."""
        delta = np.asarray(local_value, np.float32) - self._dense_base[name]
        self.client.push_dense(name, delta, delta=True)
        fresh = self.client.pull_dense(name)
        self._dense_base[name] = fresh.copy()
        return fresh

    # -- sparse -------------------------------------------------------------
    def pull_sparse(self, name, ids):
        vals = self.client.pull_sparse(name, ids)
        base = self._sparse_base.setdefault(name, {})
        touched = self._touched.setdefault(name, set())
        flat = np.asarray(ids, np.int64).reshape(-1)
        for k, i in enumerate(flat.tolist()):
            if i not in base:
                base[i] = vals[k].copy()
            touched.add(i)
        return vals

    def sync_sparse(self, name, local_rows: Dict[int, np.ndarray]):
        """Push per-id deltas for touched rows, pull fresh, rebase."""
        base = self._sparse_base.setdefault(name, {})
        touched = sorted(self._touched.get(name, ()))
        if not touched:
            return {}
        ids = np.array(touched, np.int64)
        deltas = np.stack([
            np.asarray(local_rows[i], np.float32) - base[i]
            for i in touched])
        self.client.push_sparse(name, ids, deltas, delta=True)
        fresh = self.client.pull_sparse(name, ids)
        out = {}
        for k, i in enumerate(touched):
            base[i] = fresh[k].copy()
            out[i] = fresh[k]
        self._touched[name] = set()
        return out

    def step(self) -> bool:
        """Returns True when this step is a sync point."""
        self._step += 1
        return self._step % self.push_nums == 0


class HeartBeater:
    """Background liveness pings to every server shard (the trainer half
    of heart_beat_monitor.cc).  Attached to a communicator by the PS
    runtime; failures are ignored — a dying server must not take the
    trainer down with it, the monitor's job is the reverse."""

    def __init__(self, client: PsClient, rank: int, interval: float = 2.0):
        self.client = client
        self.rank = int(rank)
        self.interval = interval
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._beat, daemon=True)
        self._thread.start()

    def _beat(self):
        while not self._stop.wait(self.interval):
            try:
                self.client.heartbeat(self.rank)
            except Exception:                # noqa: BLE001 — see class doc
                pass

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)


def make_communicator(mode: str, client: PsClient, **kw):
    mode = (mode or "async").lower()
    if mode in ("async", "a_sync"):
        return AsyncCommunicator(client, **kw)
    if mode in ("half_async", "halfasync"):
        return HalfAsyncCommunicator(client, **kw)
    if mode == "sync":
        return SyncCommunicator(client, **kw)
    if mode == "geo":
        return GeoCommunicator(client, **kw)
    raise ValueError(f"unknown communicator mode {mode}")
