"""Unified SPMD sharding plane: one mesh + one rule engine for every plane.

Reference: the reference system distributes by *dispatching ops* — Fleet's
meta-optimizers append per-gradient ``c_allreduce_sum`` ops bound to NCCL
ring ids (meta_optimizers/common.py, collective_helper.h), one collective
launch per tensor per step, invisible to the compiler.  TPU-native the
whole decision collapses into data: every param, gradient, and optimizer
accumulator gets a ``PartitionSpec`` from a **regex rule set** (the
``match_partition_rules`` idiom, SNIPPETS.md [2]), the executor jits the
WHOLE step with those shardings and buffer donation, and XLA's sharding
propagation materialises the communication the rules imply — the
``c_allreduce`` that used to be a dispatched op becomes a sharding
constraint the compiler can fuse, overlap, and schedule.

One plan object serves every customer:

* the executor's sharded-compile path (``wrap_with_plan``) — whole-step
  pjit, ``in_shardings`` from the rules, replicated-constraint rewrites of
  Fleet collectives (``fluid/passes`` ``shard_collectives``), donation for
  the state-aliasing arguments;
* the checkpoint plane — ``make_shard_and_gather_fns``-style addressable-
  shard IO (``fluid/checkpoint.py`` saves each shard's local data, never
  gathering a sharded param to host);
* the serving plane — ``freeze_program(..., mesh=)`` /
  ``ServingEngine(..., mesh=)`` run a TP-sharded frozen program;
* observability — per-device HBM (``fluid/device_stats.py``) and the
  implied-vs-dispatched collective split
  (``sharding.collectives_implied`` / ``sharding.collectives_dispatched``).

Rule syntax and the ``BuildStrategy.sharding`` knob table live in
docs/sharding.md.
"""
from __future__ import annotations

import re
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import mesh as mesh_registry
from ..fluid import trace

__all__ = [
    "ShardingPlan", "build_plan", "match_partition_rules",
    "make_shard_and_gather_fns", "rules_for", "tp_rules_for_program",
    "wrap_with_plan", "HYBRID_RULES", "FSDP",
]

# sentinel spec: shard the first divisible dim over the plan's data axis
# (the ZeRO-3 / FSDP placement — resolved per shape, since a regex cannot
# see shapes)
FSDP = "fsdp"

# ops whose persistable second operand is a weight the TP rules classify
_MATMUL_OPS = ("mul", "matmul", "matmul_v2")
_EMBEDDING_OPS = ("lookup_table", "lookup_table_v2", "c_embedding")

# hybrid.py's transformer schema, re-expressed as rules so the per-module
# table and the generic engine are the same mechanism (the names are the
# schema's, the axes the (dp, pp, tp, sp) mesh of parallel/hybrid.py)
HYBRID_RULES: List[Tuple[str, Any]] = [
    (r"^embed$", P("tp", None)),
    (r"^pos$", P("sp", None)),
    (r"^w[qkv]$", P("pp", None, "tp", None)),
    (r"^wo$", P("pp", "tp", None, None)),
    (r"^w1$", P("pp", None, "tp")),
    (r"^b1$", P("pp", "tp")),
    (r"^w2$", P("pp", "tp", None)),
    (r"^(b2|ln1_[gb]|ln2_[gb])$", P("pp", None)),
    (r"^lnf_[gb]$", P(None)),
    (r"^head$", P(None, "tp")),
]


def _as_spec(spec) -> Any:
    """Normalise a rule's right-hand side: PartitionSpec passes through,
    tuples/lists become one, the FSDP sentinel survives for shape-time
    resolution."""
    if spec == FSDP or isinstance(spec, P):
        return spec
    if spec is None:
        return P()
    if isinstance(spec, (tuple, list)):
        return P(*spec)
    raise TypeError(f"partition rule spec must be a PartitionSpec, tuple, "
                    f"None, or 'fsdp' — got {spec!r}")


def _resolve_fsdp(shape, axis: str, size: int) -> P:
    """FSDP placement for one shape: the first dim divisible by the axis
    size is sharded, everything else replicated; undividable shapes stay
    replicated (correct, just not memory-saving)."""
    shape = tuple(int(d) for d in shape)
    for i, d in enumerate(shape):
        if d >= size and d % size == 0:
            return P(*([None] * i + [axis]))
    return P()


def match_partition_rules(rules: Sequence[Tuple[str, Any]],
                          params: Dict[str, Any],
                          mesh: Optional[Mesh] = None,
                          on_unmatched: str = "replicate"
                          ) -> Dict[str, P]:
    """Assign a PartitionSpec to every entry of ``params`` (name ->
    shape/array) by first-matching regex (``re.search``, SNIPPETS.md [2]
    semantics).  Scalars and single-element arrays never partition.

    ``on_unmatched``: ``"replicate"`` (default) falls back to ``P()`` with
    a one-shot warning + the ``sharding.unmatched_params`` counter;
    ``"raise"`` keeps the strict fmengine behavior.
    """
    data_axis = _data_axis_of(mesh) if mesh is not None else "dp"
    size = (mesh.shape[data_axis]
            if mesh is not None and data_axis in mesh.axis_names else 1)
    out: Dict[str, P] = {}
    unmatched: List[str] = []
    for name, leaf in params.items():
        shape = tuple(np.shape(leaf)) if not _is_shape(leaf) \
            else tuple(int(d) for d in leaf)
        if len(shape) == 0 or int(np.prod(shape) or 1) == 1:
            out[name] = P()         # never partition scalar values
            continue
        for rule, spec in rules:
            if re.search(rule, name) is not None:
                spec = _as_spec(spec)
                out[name] = (_resolve_fsdp(shape, data_axis, size)
                             if spec == FSDP else spec)
                break
        else:
            if on_unmatched == "raise":
                raise ValueError(
                    f"Partition rule not found for param: {name}")
            unmatched.append(name)
            out[name] = P()
    if unmatched:
        _note_unmatched(unmatched)
    return out


def _is_shape(leaf) -> bool:
    return (isinstance(leaf, (tuple, list))
            and all(isinstance(d, (int, np.integer)) for d in leaf))


_warned_unmatched = [False]


def _note_unmatched(names: List[str]) -> None:
    trace.metrics().counter("sharding.unmatched_params").inc(len(names))
    if not _warned_unmatched[0]:
        _warned_unmatched[0] = True
        print(f"paddle_tpu: WARNING: {len(names)} param(s) matched no "
              f"partition rule and fall back to replicated "
              f"(e.g. {sorted(names)[:3]}); add a rule or accept the "
              f"replica (docs/sharding.md).  Further misses are counted "
              f"in sharding.unmatched_params only.", file=sys.stderr)


def _data_axis_of(mesh: Optional[Mesh]) -> Optional[str]:
    if mesh is None:
        return None
    for ax in ("dp", "fsdp", "data"):
        if ax in mesh.axis_names:
            return ax
    return None


def batch_shard_axis(mesh: Optional[Mesh]) -> Optional[str]:
    """The axis of a mesh that shards activations on the batch and on
    nothing else: its data axis, where every other axis has size 1 (the
    ``"dp"`` and ``"fsdp"`` modes' default mesh).  There each chip's shard
    of an activation is a whole one-chip tensor, which is what lets a
    lowering run its Pallas kernel once per shard
    (``LoweringContext.kernel_site``); None for every other mesh."""
    axis = _data_axis_of(mesh)
    if axis is None or mesh.shape[axis] != mesh.size:
        return None
    return axis


# ---------------------------------------------------------------------------
# rule sets per BuildStrategy.sharding mode
# ---------------------------------------------------------------------------

def rules_for(mode: str, program=None, mesh: Optional[Mesh] = None
              ) -> List[Tuple[str, Any]]:
    """The rule set a ``BuildStrategy.sharding`` mode lowers to:

    * ``"dp"``   — every param replicated; feeds batch-shard over ``dp``
      (XLA inserts the gradient reduce the replicated-update constraint
      implies — the AllReduceOpHandle, fused and compiler-scheduled).
    * ``"fsdp"`` — every param/accumulator shards its first divisible dim
      over the data axis (ZeRO-3 placement); feeds batch-shard too.
    * ``"tp"``   — Megatron column/row placement derived from the
      program's matmul chain + vocab-sharded embeddings
      (:func:`tp_rules_for_program`); feeds replicate.
    """
    mode = (mode or "").lower()
    if mode == "dp":
        return [(r".*", P())]
    if mode == "fsdp":
        return [(r".*", FSDP)]
    if mode == "tp":
        if program is None:
            raise ValueError("sharding='tp' derives column/row rules from "
                             "the program — pass one")
        return tp_rules_for_program(program)
    raise ValueError(f"unknown sharding mode {mode!r}: use 'dp', 'tp', "
                     f"'fsdp', or a custom [(regex, spec), ...] list")


def tp_rules_for_program(program, axis: str = "tp"
                         ) -> List[Tuple[str, Any]]:
    """Walk the program's op stream and emit exact-name rules: matmul
    weights alternate column-parallel ``P(None, tp)`` / row-parallel
    ``P(tp, None)`` along the chain (Megatron MLP placement — the
    row-parallel reduce is the ``c_allreduce_sum`` TP used to dispatch),
    a column-parallel matmul's bias shards with its output features, and
    embedding tables shard their vocab rows (the ``c_embedding``
    pattern).  Any valid assignment is *correct* under GSPMD; this one
    keeps the activation collectives where Megatron puts them."""
    block = program.global_block()
    persist = {n: v for n, v in block.vars.items() if v.persistable}
    rules: List[Tuple[str, Any]] = []
    assigned: Dict[str, P] = {}

    def add(name: str, spec: P):
        if name not in assigned:
            assigned[name] = spec
            rules.append((f"^{re.escape(name)}$", spec))

    # map matmul output -> column/row so the consuming bias can follow
    col_out: Dict[str, bool] = {}
    column = True
    for op in block.ops:
        if op.type in _MATMUL_OPS:
            y = (op.inputs.get("Y") or [None])[0]
            if y in persist:
                if y not in assigned:
                    add(y, P(None, axis) if column else P(axis, None))
                    for o in op.output_arg_names:
                        col_out[o] = column
                    column = not column
                else:
                    for o in op.output_arg_names:
                        col_out[o] = assigned[y] == P(None, axis)
        elif op.type in _EMBEDDING_OPS:
            w = (op.inputs.get("W") or [None])[0]
            if w in persist:
                add(w, P(axis, None))
        elif op.type in ("elementwise_add", "fused_elemwise_activation"):
            # bias of a column-parallel projection lives on the sharded
            # feature dim; row-parallel biases replicate (post-reduce).
            # The fused add+act form (inference preset / fusion passes)
            # keeps the same X=proj, Y=bias slots.
            x = (op.inputs.get("X") or [None])[0]
            y = (op.inputs.get("Y") or [None])[0]
            if y in persist and col_out.get(x) and \
                    len(persist[y].shape or ()) == 1:
                add(y, P(axis))
    # every remaining PARAMETER replicates by an explicit rule: the TP
    # set is total over params by construction, so replicated row biases
    # and LN scales never fire the unmatched fallback/counter.  Only
    # params — optimizer accumulators must keep deriving their spec from
    # their base param, which an exact-name rule here would short-circuit.
    from ..fluid.framework import Parameter
    for name, v in persist.items():
        if name not in assigned and isinstance(v, Parameter):
            add(name, P())
    return rules


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------

class ShardingPlan:
    """Mesh + resolved specs for one program: the single sharding
    abstraction the executor, checkpoint, serving, and observability
    planes consume.  Grad (``@GRAD``) and optimizer-accumulator names
    (``AdamOptimizer_moment1_<param>``, ``..._master_weight_<param>``)
    inherit their base param's spec by suffix derivation, so a rule set
    written against param names covers the whole optimizer state."""

    def __init__(self, mesh: Mesh, rules: Sequence[Tuple[str, Any]],
                 mode: str = "custom", param_names: Sequence[str] = ()):
        self.mesh = mesh
        self.rules = [(r, _as_spec(s)) for r, s in rules]
        self.mode = mode
        self.data_axis = _data_axis_of(mesh)
        # param names known at build time, longest first: the accumulator
        # suffix derivation must prefer "fc.w_0" over "w_0"
        self._param_names = sorted({str(n) for n in param_names},
                                   key=len, reverse=True)
        self._specs: Dict[str, P] = {}
        self._repl = NamedSharding(mesh, P())

    # -- spec resolution ----------------------------------------------------
    def base_param_of(self, name: str) -> Optional[str]:
        """The param an optimizer-state var belongs to, by the repo's
        naming convention (``<Opt>_<slot>_<param>`` suffix, ``@GRAD``)."""
        if name.endswith("@GRAD"):
            return name[:-len("@GRAD")]
        for p in self._param_names:
            if name != p and (name.endswith("_" + p)
                              or name.endswith("." + p)):
                return p
        return None

    def spec_for(self, name: str, shape) -> P:
        key = (name, tuple(int(d) for d in shape))
        hit = self._specs.get(key)
        if hit is not None:
            return hit
        shape = key[1]
        if len(shape) == 0 or int(np.prod(shape) or 1) == 1:
            spec = P()
        else:
            spec = None
            for rule, rspec in self.rules:
                if re.search(rule, name) is not None:
                    spec = rspec
                    break
            if spec is None:
                # optimizer state inherits its param's placement (same
                # shape only: beta_pow scalars etc. replicate above)
                base = self.base_param_of(name)
                if base is not None:
                    spec = self._base_spec(base, shape)
            if spec is None:
                _note_unmatched([name])
                spec = P()
            if spec == FSDP:
                size = (self.mesh.shape[self.data_axis]
                        if self.data_axis else 1)
                spec = _resolve_fsdp(shape, self.data_axis or "dp", size)
        # specs naming axes the mesh lacks degrade to replicated on the
        # missing axis (a tp rule set on a dp-only mesh stays runnable)
        spec = self._clip_to_mesh(spec, shape)
        self._specs[key] = spec
        return spec

    def _base_spec(self, base: str, shape) -> Optional[P]:
        for rule, rspec in self.rules:
            if re.search(rule, base) is not None:
                return rspec
        return None

    def _clip_to_mesh(self, spec: P, shape) -> P:
        names = set(self.mesh.axis_names)
        parts = []
        for i, ax in enumerate(tuple(spec)):
            keep = ax
            if ax is not None:
                axes = ax if isinstance(ax, (tuple, list)) else (ax,)
                axes = tuple(a for a in axes if a in names)
                # a dim must stay divisible by the product of its axes
                n = int(np.prod([self.mesh.shape[a] for a in axes]) or 1)
                if not axes or i >= len(shape) or shape[i] % n != 0:
                    keep = None
                else:
                    keep = axes if len(axes) > 1 else axes[0]
            parts.append(keep)
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)

    def sharding_for(self, name: str, shape) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(name, shape))

    def data_sharding(self, shape) -> NamedSharding:
        """Batch-axis sharding for a feed of ``shape`` — replicated when
        the plan has no data axis or the leading dim does not divide."""
        shape = tuple(int(d) for d in shape)
        if (self.data_axis is None or not shape
                or shape[0] % self.mesh.shape[self.data_axis] != 0):
            return self._repl
        return NamedSharding(self.mesh, P(self.data_axis))

    @property
    def replicated(self) -> NamedSharding:
        return self._repl

    # -- introspection ------------------------------------------------------
    @property
    def n_devices(self) -> int:
        return int(self.mesh.size)

    def mesh_shape(self) -> Dict[str, int]:
        return {str(a): int(self.mesh.shape[a])
                for a in self.mesh.axis_names}

    def describe(self) -> Dict[str, Any]:
        """JSON-able summary (program hints, bench rows, manifests)."""
        return {"mode": self.mode, "mesh_shape": self.mesh_shape(),
                "data_axis": self.data_axis,
                "n_rules": len(self.rules)}

    def __repr__(self):
        return (f"ShardingPlan(mode={self.mode!r}, "
                f"mesh={self.mesh_shape()}, rules={len(self.rules)})")


def build_plan(program=None, mode: str = "dp",
               mesh: Optional[Mesh] = None,
               rules: Optional[Sequence[Tuple[str, Any]]] = None,
               devices=None) -> ShardingPlan:
    """Lower a ``BuildStrategy.sharding`` knob value into a plan.

    ``mode`` is ``"dp"`` | ``"tp"`` | ``"fsdp"``; passing ``rules``
    overrides the mode's rule set (custom-rules knob).  ``mesh`` defaults
    to the process mesh both planes share (``parallel.api.resolved_mesh``)
    or, absent one, a fresh 1-axis mesh over all local devices named for
    the mode's primary axis — installed as the current mesh so the
    explicit-collective plane resolves the SAME object."""
    from .api import resolved_mesh
    mode_name = mode if isinstance(mode, str) else "custom"
    if not isinstance(mode, str):
        rules = rules or mode
    mesh = resolved_mesh(mesh)
    if mesh is None:
        axis = "tp" if mode_name == "tp" else "dp"
        mesh = mesh_registry.build_mesh(
            {axis: len(devices or jax.devices())}, devices=devices)
    if rules is None:
        rules = rules_for(mode_name, program=program, mesh=mesh)
    param_names: List[str] = []
    if program is not None:
        from ..fluid.framework import Parameter
        prog = getattr(program, "_program", program)
        blk = prog.global_block()
        param_names = [n for n, v in blk.vars.items()
                       if isinstance(v, Parameter)]
        if not param_names:   # programs built without Parameter marking
            param_names = [n for n, v in blk.vars.items() if v.persistable]
    return ShardingPlan(mesh, rules, mode=mode_name,
                        param_names=param_names)


# ---------------------------------------------------------------------------
# shard / gather functions (SNIPPETS.md [2] make_shard_and_gather_fns)
# ---------------------------------------------------------------------------

def make_shard_and_gather_fns(plan: ShardingPlan,
                              names_shapes: Dict[str, Any]):
    """Per-name ``(shard_fns, gather_fns)``: ``shard_fns[n](arr)`` places
    a host/global array onto the plan's sharding for ``n`` (device_put —
    each device receives only its slice); ``gather_fns[n](arr)`` returns
    the fully-replicated global value.  The checkpoint plane prefers raw
    ``addressable_shards`` IO over gather_fns (no host gather); these are
    the generic API for everything else."""
    shard_fns, gather_fns = {}, {}
    for n, leaf in names_shapes.items():
        shape = tuple(leaf) if _is_shape(leaf) else tuple(np.shape(leaf))
        sh = plan.sharding_for(n, shape)

        def _shard(arr, _sh=sh):
            return jax.device_put(arr, _sh)

        def _gather(arr, _repl=plan.replicated):
            return np.asarray(jax.device_put(arr, _repl))

        shard_fns[n] = _shard
        gather_fns[n] = _gather
    return shard_fns, gather_fns


# ---------------------------------------------------------------------------
# the executor's sharded-compile path
# ---------------------------------------------------------------------------

def wrap_with_plan(fn, plan: ShardingPlan, shapes: Dict[str, Any],
                   mut_names: Sequence[str], ro_names: Sequence[str],
                   feed: Dict[str, Any], block=None,
                   donate: bool = False):
    """Whole-step pjit: jit ``fn(mut, ro, feeds, key)`` with
    ``in_shardings`` resolved from the plan's rules, donation of the
    mutable-state argument (the optimizer update aliases its buffers
    in-place, the enable_inplace analog), and replicated PRNG key.  The
    written-back state is pinned to the same shardings inside the step
    (``with_sharding_constraint``), so donated inputs alias their outputs
    and the rules — not per-op collectives — imply every reduce.

    Returns ``(wrapped, jitted)``: ``wrapped`` device_puts each argument
    onto its sharding first (``wrapped.place``: a no-op on the device once
    state has settled onto the plan, though still one call per array on
    the host; necessary on step one, when the startup program left
    single-device arrays), ``jitted`` is the lowerable jit wrapper
    device_stats AOT-analyses."""
    mesh = plan.mesh

    def _state_sh(n):
        return plan.sharding_for(n, np.shape(shapes[n]))

    mut_sh = {n: _state_sh(n) for n in mut_names}
    ro_sh = {n: _state_sh(n) for n in ro_names}

    def _feed_sh(name, v):
        shape = tuple(np.shape(v))
        if block is not None:
            var = block._find_var_recursive(name)
            if var is not None and var.shape is not None \
                    and len(var.shape) >= 1 and var.shape[0] != -1:
                return plan.replicated     # static leading dim: not batch
        return plan.data_sharding(shape)

    feed_sh = {k: _feed_sh(k, v) for k, v in feed.items()}
    key_sh = plan.replicated

    def constrained(mut_params, ro_params, feeds, step_key):
        fetches, new_vals = fn(mut_params, ro_params, feeds, step_key)
        # out-side pin: written state keeps the in-side placement, so
        # donation aliases and the implied collectives land HERE
        new_vals = {
            n: jax.lax.with_sharding_constraint(
                v, plan.sharding_for(n, np.shape(v)))
            for n, v in new_vals.items()}
        return fetches, new_vals

    jitted = jax.jit(
        constrained,
        in_shardings=(mut_sh, ro_sh, feed_sh, key_sh),
        donate_argnums=(0,) if donate else ())

    def place(mut_params, ro_params, feeds, step_key):
        mut = {n: jax.device_put(v, mut_sh[n])
               for n, v in mut_params.items()}
        ro = {n: jax.device_put(v, ro_sh[n])
              for n, v in ro_params.items()}
        fd = {k: jax.device_put(v, feed_sh.get(k, key_sh))
              for k, v in feeds.items()}
        key = jax.device_put(step_key, key_sh)
        return mut, ro, fd, key

    def wrapped(mut_params, ro_params, feeds, step_key):
        return jitted(*place(mut_params, ro_params, feeds, step_key))

    # where the jitted step's arguments really are: what an AOT lowering of
    # the very program that ran describes (device_stats.sds_tree)
    wrapped.in_shardings = (mut_sh, ro_sh, feed_sh, key_sh)
    # the seam for a caller that times the two halves apart (the Executor's
    # step clock): ``jitted(*wrapped.place(...))`` is ``wrapped(...)``
    wrapped.place = place
    return wrapped, jitted
