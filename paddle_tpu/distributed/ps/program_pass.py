"""PS program pass — wire a user program's embeddings to the PS tier.

Reference: python/paddle/fluid/transpiler/distribute_transpiler.py:256
(`transpile` rewrites lookup_table ops into distributed lookups and splits
optimizer ops onto the pservers) executed per batch by
paddle/fluid/framework/downpour_worker.cc:739 (pull) /:183 (FillSparseValue)
/:765 (push).  TPU-native redesign: the device step stays ONE jitted XLA
program; the pass rewrites each sparse `lookup_table[_v2]` op into a
`ps_lookup_rows` op that consumes a per-batch host feed of pulled rows, and
training runs the host-side pull -> device step -> push loop around the
normal Executor.  Parameter updates happen in the server tables (table.py
accessors), so the trainer program carries backward ops but NO optimizer
ops — exactly the reference's trainer/pserver program split, with XLA
owning everything that runs on chip.

Choreography per batch (run_program_with_ps):
  sync   pull dense+rows -> barrier -> jitted fwd+bwd -> inline push
         -> barrier  (all trainers step together; SGD pushes commute, so
         the server trajectory equals a single process applying every
         trainer's grads — the oracle the tests check against)
  async  pull -> step -> enqueue pushes on the AsyncCommunicator; no
         barriers (hogwild over the table, reference communicator.h:268);
         a pull waits while more than FLAGS_ps_staleness of this
         trainer's pushes are unapplied (0 by default)

GEO mode keeps the explicit communicator API (its delta-exchange semantics
need trainer-local optimizer state, not a server push per batch).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

ROWS_SUFFIX = "@PSROWS"
GRAD_SUFFIX = "@GRAD"

_SPARSE_LOOKUP_TYPES = ("lookup_table", "lookup_table_v2")


class PsPlan:
    """Pure-data description of the PS rewiring (deepcopy-safe: it travels
    inside program._hints through Program.clone)."""

    def __init__(self, mode: str, optimizer: str, lr: float):
        self.mode = mode                    # "sync" | "async" | "geo"
        self.optimizer = optimizer          # table accessor kind
        self.lr = lr
        # {table, dim, ids, rows, grad, init_kind, init_scale, v1}
        self.sparse: List[Dict[str, Any]] = []
        # {param, grad, shape}
        self.dense: List[Dict[str, Any]] = []

    def __deepcopy__(self, memo):
        import copy
        p = PsPlan(self.mode, self.optimizer, self.lr)
        p.sparse = copy.deepcopy(self.sparse, memo)
        p.dense = copy.deepcopy(self.dense, memo)
        return p


def _accessor_kind(optimizer) -> str:
    name = type(optimizer).__name__.lower()
    for kind in ("adamw", "adam", "adagrad", "sgd"):
        if kind in name:
            return "adam" if kind == "adamw" else kind
    raise ValueError(
        f"PS tables support sgd/adagrad/adam accessors; got {name}. "
        f"(reference ps.proto accessor classes map the same three)")


def _constant_lr(optimizer) -> float:
    lr = optimizer._learning_rate
    if callable(lr) or not isinstance(lr, (int, float)):
        raise ValueError(
            "PS-served training needs a constant learning rate: the update "
            "runs in the server table, which holds one lr per table "
            "(ps.proto sparse_sgd_param.learning_rate)")
    return float(lr)


def _startup_init_kind(startup_program, w_name):
    """Infer the table initializer from the startup op that fills W, then
    REMOVE those ops — the trainer must not materialise a vocab-sized dense
    table (that is the point of the PS tier)."""
    kind, scale = "uniform", 0.07
    if startup_program is None:
        return kind, scale
    for b in startup_program.blocks:
        for op in b.ops:
            if w_name not in op.output_arg_names:
                continue
            if op.type == "fill_constant":
                kind, scale = "zeros", 0.0
            elif op.type in ("gaussian_random",
                             "truncated_gaussian_random"):
                kind, scale = "gaussian", float(op.attr("std", 1.0))
            elif op.type == "uniform_random":
                lo = float(op.attr("min", -0.07))
                hi = float(op.attr("max", 0.07))
                kind, scale = "uniform", max(abs(lo), abs(hi))
        b.ops = [op for op in b.ops if w_name not in op.output_arg_names]
        b.program._bump_version()
    return kind, scale


def apply_ps_pass(loss, startup_program, optimizer, strategy, role_maker,
                  parameter_list=None, no_grad_set=None):
    """Rewrite the program for PS-served training.  Returns
    (params_grads, plan).  Called from fleet.minimize in PS mode INSTEAD of
    optimizer.minimize: backward ops are appended, optimizer ops are not
    (the server table IS the optimizer — transpiler trainer-program split).
    """
    from ...fluid.framework import Parameter

    program = loss.block.program
    block = program.global_block()

    geo_k = int((getattr(strategy, "a_sync_configs", {}) or {}).get(
        "k_steps", -1) or -1)
    if getattr(strategy, "a_sync", False):
        mode = "geo" if geo_k > 0 else "async"
    else:
        mode = "sync"
    plan = PsPlan(mode, _accessor_kind(optimizer), _constant_lr(optimizer))

    # -- 1. rewrite sparse lookups into pulled-row consumers ----------------
    sparse_params = set()
    for i, op in enumerate(block.ops):
        if op.type not in _SPARSE_LOOKUP_TYPES:
            continue
        w_name = op.input("W")[0]
        w = block._find_var_recursive(w_name)
        if not isinstance(w, Parameter):
            continue
        if not (op.attr("is_sparse") or op.attr("is_distributed")
                or getattr(w, "is_distributed", False)):
            continue                      # dense embedding: dense table path
        ids_name = op.input("Ids")[0]
        dim = int(w.shape[-1])
        k = len(plan.sparse)
        rows_name = f"{w_name}{ROWS_SUFFIX}{k}"
        rows = block.create_var(name=rows_name, shape=(-1, dim),
                                dtype=w.dtype, is_data=True)
        rows.stop_gradient = False
        # in-place op swap: same output var, new inputs — downstream ops and
        # shape inference are untouched
        is_v1 = op.type == "lookup_table"
        pad = op.attr("padding_idx", -1)
        op.type = "ps_lookup_rows"
        op.inputs = {"Rows": [rows_name], "Ids": [ids_name]}
        op.attrs = {"padding_idx": pad, "v1": is_v1, "op_role": 0}
        init_kind, init_scale = _startup_init_kind(startup_program, w_name)
        plan.sparse.append({
            "table": w_name, "dim": dim, "ids": ids_name,
            "rows": rows_name, "grad": rows_name + GRAD_SUFFIX,
            "init_kind": init_kind, "init_scale": init_scale})
        sparse_params.add(w_name)

    # a PS-served W must have NO other consumers: the trainer never holds
    # the table, so a weight-tied read (e.g. embedding reused as the output
    # projection) would see an uninitialised variable
    for b in program.blocks:
        for op in b.ops:
            tied = sparse_params.intersection(op.input_arg_names)
            if tied:
                raise ValueError(
                    f"PS-served embedding {sorted(tied)} is also read by "
                    f"op '{op.type}' — weight tying cannot cross the PS "
                    f"boundary (the vocab-sized table never materialises "
                    f"on the trainer); keep that parameter dense "
                    f"(is_sparse=False)")

    # -- 2. backward only (no optimizer ops on the trainer) -----------------
    params_grads = optimizer.backward(loss, startup_program, parameter_list,
                                      no_grad_set)
    params_grads = [(p, g) for p, g in params_grads
                    if p.name not in sparse_params]
    for s in plan.sparse:
        if not block.has_var(s["grad"]):
            raise RuntimeError(
                f"PS pass: no gradient reached pulled rows '{s['rows']}' — "
                f"is the lookup output disconnected from the loss?")
    for p, g in params_grads:
        plan.dense.append({"param": p.name, "grad": g.name,
                           "shape": list(p.shape)})

    program._hints["ps_plan"] = plan
    return params_grads, plan


# ---------------------------------------------------------------------------
# runtime side: the per-batch pull/step/push loop
# ---------------------------------------------------------------------------
def _current_runtime():
    from ..fleet import _fleet_singleton
    rt = _fleet_singleton._runtime_handle
    if rt is None:
        raise RuntimeError(
            "PS-served program: call fleet.init_worker() (after fleet."
            "minimize) before executor.run — the runtime handle owns the "
            "table connections")
    return rt


def _ensure_tables(rt, plan: PsPlan, scope):
    """Idempotent table creation + dense init (worker 0 seeds server values
    from its startup-initialised scope, every worker then pulls — the
    transpiler's startup-program split, init flowing trainer0 -> servers)."""
    ready = rt._ps_tables_ready          # per-name: multiple PS programs
    todo_sparse = [s for s in plan.sparse if s["table"] not in ready]
    todo_dense = [d for d in plan.dense if d["param"] not in ready]
    if not todo_sparse and not todo_dense:
        return
    client = rt.client
    for s in todo_sparse:
        rt.create_sparse_table(s["table"], s["dim"], plan.optimizer, plan.lr,
                               init_kind=s["init_kind"],
                               init_scale=s["init_scale"])
        ready.add(s["table"])
    worker0 = rt._role_maker._worker_index() == 0
    for d in todo_dense:
        init = scope.find_var(d["param"])
        if init is None:
            raise RuntimeError(
                f"PS init: dense param '{d['param']}' missing from scope — "
                f"run the startup program before the first training step")
        rt.create_dense_table(d["param"], d["shape"], plan.optimizer,
                              plan.lr)
        if worker0:
            rt.ps_set_dense(d["param"], np.asarray(init, np.float32))
        ready.add(d["param"])
    if client is not None:
        client.barrier()            # inits visible before anyone pulls


def _ps_setup(program, scope):
    """Shared preamble: resolve plan/runtime, validate mode, ensure
    tables.  Returns (plan, rt, comm, scope, train, multiproc)."""
    from ...fluid.core import global_scope

    plan: PsPlan = program._hints["ps_plan"]
    if plan.mode == "geo":
        raise NotImplementedError(
            "GEO mode trains on trainer-local state; use the communicator "
            "API (distributed/ps/communicator.py GeoCommunicator) — the "
            "program path serves sync/async")
    rt = _current_runtime()
    comm = rt.communicator
    from ..ps.communicator import GeoCommunicator
    if isinstance(comm, GeoCommunicator):
        raise NotImplementedError("program path does not drive a "
                                  "GeoCommunicator (see plan.mode note)")
    scope = scope or global_scope()
    _ensure_tables(rt, plan, scope)
    train = not bool(program._hints.get("is_test"))
    return plan, rt, comm, scope, train, rt.client is not None


def _ps_pull_phase(rt, plan, program, feed, scope):
    """Host sparse/dense pull for ONE batch (downpour PULL_SPARSE stage).
    Mutates `feed` in place (rows vars + wide-id remaps) and returns the
    original full-width flat ids for the push phase."""
    # capture EVERY slot's original ids first: slots may share one ids var,
    # and the device remap below must never leak into another slot's pull
    # or into the push phase (full-width ids only)
    flat_ids = {}                   # slot rows-name -> ORIGINAL flat ids
    for s in plan.sparse:
        if s["ids"] not in feed:
            raise KeyError(f"PS run: feed missing ids var '{s['ids']}'")
        flat_ids[s["rows"]] = np.asarray(feed[s["ids"]]).reshape(-1).copy()
    remaps = {}
    for s in plan.sparse:
        flat = flat_ids[s["rows"]]
        ids = flat.reshape(np.shape(feed[s["ids"]]))
        rows = rt.ps_pull_sparse(s["table"], flat)   # full-width host pull
        feed[s["rows"]] = np.asarray(rows, np.float32).reshape(
            len(flat), s["dim"])
        if ids.dtype in (np.int64, np.uint64) and ids.size \
                and ids.max(initial=0) > 2 ** 31 - 1:
            # the DEVICE only reads ids for shape + padding positions (the
            # rows feed is positional); wide feasigns must not truncate on
            # staging, so remap to a safe int32 pattern preserving ==pad
            pads = {int(op.attr("padding_idx", -1))
                    for op in program.global_block().ops
                    if op.type == "ps_lookup_rows"
                    and op.input("Ids") == [s["ids"]]}
            pads.discard(-1)        # -1 = no padding: insensitive to remap
            if len(pads) > 1:
                # one int32 remap pattern serves every lookup reading this
                # ids var; conflicting pads would zero the wrong rows
                raise ValueError(
                    f"PS run: ids var '{s['ids']}' is read by "
                    f"ps_lookup_rows ops with conflicting padding_idx "
                    f"values {sorted(pads)}; feed each lookup a separate "
                    f"ids var or align their padding_idx")
            pad = pads.pop() if pads else -1
            safe_val = 0 if pad == 1 else 1     # never collide with pad
            safe = (np.where(ids == pad, pad, safe_val).astype(np.int64)
                    if pad >= 0
                    else np.full_like(ids, safe_val, dtype=np.int64))
            remaps[s["ids"]] = safe
    feed.update(remaps)             # after ALL pulls read the originals
    for d in plan.dense:
        val = rt.ps_pull_dense(d["param"])
        scope.set_var(d["param"],
                      np.asarray(val, np.float32).reshape(d["shape"]))
    return flat_ids


def _ps_push_phase(rt, plan, comm, grads, flat_ids, sync_multiproc):
    """Host sparse/dense grad push for ONE batch (PUSH_GRAD stage)."""
    k = 0
    for s in plan.sparse:
        flat = flat_ids[s["rows"]]
        rt.ps_push_sparse(s["table"], flat,
                          np.asarray(grads[k]).reshape(len(flat),
                                                       s["dim"]))
        k += 1
    for d in plan.dense:
        rt.ps_push_dense(d["param"], np.asarray(grads[k]))
        k += 1
    if sync_multiproc:
        rt.ps_step()                # pushes land before the next pull
    elif comm is not None and hasattr(comm, "step"):
        comm.step()                 # half-async per-step flush


def _ps_device_step(exe, program, feed, user_fetch, plan, train, scope,
                    return_numpy, use_program_cache):
    extra = ([s["grad"] for s in plan.sparse]
             + [d["grad"] for d in plan.dense]) if train else []
    exe._in_ps_run = True
    try:
        return exe.run(program, feed=feed, fetch_list=user_fetch + extra,
                       scope=scope, return_numpy=return_numpy,
                       use_program_cache=use_program_cache)
    finally:
        exe._in_ps_run = False


def run_program_with_ps(exe, program, feed, fetch_list, scope, return_numpy,
                        use_program_cache):
    """Executor.run delegate when program._hints['ps_plan'] is set: the
    downpour_worker.cc:739/765 loop around one XLA device step."""
    plan, rt, comm, scope, train, multiproc = _ps_setup(program, scope)
    feed = dict(feed or {})

    flat_ids = _ps_pull_phase(rt, plan, program, feed, scope)
    if train and plan.mode == "sync" and multiproc:
        rt.ps_barrier()             # everyone pulled before anyone pushes

    user_fetch = list(fetch_list or [])
    outs = _ps_device_step(exe, program, feed, user_fetch, plan, train,
                           scope, return_numpy, use_program_cache)

    if train:
        _ps_push_phase(rt, plan, comm, outs[len(user_fetch):], flat_ids,
                       sync_multiproc=(plan.mode == "sync" and multiproc))
    return outs[:len(user_fetch)]


def train_ps_pipelined(exe, program, feeds, fetch_list=None, scope=None,
                       depth=2, return_numpy=True):
    """Heter-worker-style overlap for ASYNC PS programs
    (heter_service.h:73 task pipeline PULL_SPARSE -> OP_RUN -> PUSH_GRAD;
    trainer.h:163 HeterXpuTrainer overlaps the host sparse plane with
    device compute): batch t+1's host pulls run on a prefetch thread and
    batch t's grad pushes drain on a dedicated push thread while the
    device computes batch t.  Requires mode='async' — async SGD already
    tolerates the one-batch staleness this pipeline introduces; sync mode
    has a barrier between pull and push, so overlap would change its
    semantics and is refused.

    `feeds` is an iterable of feed dicts; returns the per-batch fetch
    values (push of the final batch is joined before returning)."""
    import queue as _q
    import threading

    plan, rt, comm, scope, train, multiproc = _ps_setup(program, scope)
    if plan.mode != "async":
        raise ValueError(
            "train_ps_pipelined requires an async-mode plan; sync mode "
            "barriers between pull and push (use Executor.run per batch)")
    user_fetch = list(fetch_list or [])

    from ...utils.prefetch import Prefetcher

    def pulled():
        for f in feeds:
            f = dict(f)
            flat_ids = _ps_pull_phase(rt, plan, program, f, scope)
            yield f, flat_ids

    push_q: "_q.Queue" = _q.Queue(maxsize=max(1, depth))
    push_err = []

    def pusher():
        while True:
            item = push_q.get()
            if item is None:
                return
            grads, flat_ids = item
            try:
                _ps_push_phase(rt, plan, comm, grads, flat_ids,
                               sync_multiproc=False)
            except BaseException as e:          # noqa: BLE001 — forwarded
                push_err.append(e)
                return

    push_thread = threading.Thread(target=pusher, daemon=True)
    push_thread.start()
    results = []
    pf = Prefetcher(pulled(), capacity=max(1, depth))
    try:
        for f, flat_ids in pf:
            if push_err:
                raise push_err[0]
            outs = _ps_device_step(exe, program, f, user_fetch, plan,
                                   train, scope, return_numpy, True)
            if train:
                push_q.put((outs[len(user_fetch):], flat_ids))
            results.append(outs[:len(user_fetch)])
    finally:
        pf.close()
        try:
            push_q.put_nowait(None)
        except _q.Full:
            # pusher died with the queue full: drain so the sentinel fits
            # (a blocking put here would hang forever with no consumer)
            try:
                while True:
                    push_q.get_nowait()
            except _q.Empty:
                pass
            push_q.put_nowait(None)
        push_thread.join(timeout=30)
    if push_err:
        raise push_err[0]
    return results
