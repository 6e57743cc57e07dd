"""Operator registry: the TPU-native analog of fluid's op/kernel registry.

Reference design: paddle/fluid/framework/op_registry.h:256-304 registers an
OperatorBase subclass plus per-device kernels per op type, and a GradOpDescMaker
(grad_op_desc_maker.h) that emits grad OpDescs.  Here an op is a *pure JAX
lowering rule* `fn(inputs, attrs, ctx) -> outputs`; the whole block is compiled
by XLA (executor.py), so there is no per-device kernel dispatch — XLA is the
kernel library.  Gradients come from one generic `jax.vjp`-based grad lowering
(see backward.py), replacing 676 hand-written GradOpMakers; ops may still
register a custom grad when vjp semantics are wrong (e.g. straight-through).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence

# inputs/outputs are Dict[slot_name, List[jax.Array]] mirroring OpDesc's named
# variadic slots (framework.proto:74 `OpDesc.Var { parameter, arguments }`).
LoweringFn = Callable[..., Dict[str, Any]]


def wide_int():
    """The dtype for index/length/id outputs the reference declares int64.

    An EXPLICIT choice, not a silent truncation: int64 when jax x64 mode is
    on (FLAGS_enable_x64), else int32 — requesting jnp.int64 with x64 off
    would produce int32 anyway, plus a per-call TracerWarning.  True 64-bit
    id paths (feasigns) are guarded separately: the executor refuses
    silently-truncating int64 feeds (executor.py check_feed_width), the
    assign_value lowering rejects over-range int64 constants, and the PS
    tier keeps ids host-side in real int64.  Single source of truth for the
    64->32 policy is framework.device_dtype.
    """
    import jax.numpy as jnp
    from ..fluid.framework import device_dtype
    return jnp.int64 if device_dtype("int64") == "int64" else jnp.int32


@dataclasses.dataclass
class OpDef:
    type: str
    fn: LoweringFn                       # fn(ins, attrs, ctx) -> outs
    # slots that are never differentiated (int indices, seeds, masks...)
    nondiff_inputs: Sequence[str] = ()
    # outputs that carry no cotangent (int outputs, saved state)
    nondiff_outputs: Sequence[str] = ()
    differentiable: bool = True          # False: treated as leaf (optimizer ops)
    # why a differentiable=False op is excluded from the grad sweep
    # (populated from ops/nondiff_reasons.py; test_op_grads_auto enforces
    # that every non-differentiable op carries one)
    nondiff_reason: Optional[str] = None
    stateful_rng: bool = False           # needs a PRNG key (dropout, *_random)
    custom_grad: Optional[Callable] = None  # (ins, outs, out_grads, attrs, ctx) -> in_grads
    # optional shape/dtype inference for IR bookkeeping (advisory; XLA retraces)
    infer: Optional[Callable] = None
    # True for user plugin ops (load_op_library) — outside the framework's
    # catalog/grad-audit contract
    custom: bool = False


_OP_REGISTRY: Dict[str, OpDef] = {}


def register_op(type: str, fn: LoweringFn = None, **kwargs):
    """Register a lowering rule. Usable as decorator or direct call."""
    def deco(f):
        if type in _OP_REGISTRY:
            raise ValueError(f"op '{type}' already registered")
        _OP_REGISTRY[type] = OpDef(type=type, fn=f, **kwargs)
        return f
    if fn is not None:
        return deco(fn)
    return deco


def get_op(type: str) -> OpDef:
    if type not in _OP_REGISTRY:
        raise NotImplementedError(
            f"op '{type}' has no TPU lowering rule registered "
            f"({len(_OP_REGISTRY)} ops available)")
    return _OP_REGISTRY[type]


def has_op(type: str) -> bool:
    return type in _OP_REGISTRY


def all_ops() -> List[str]:
    return sorted(_OP_REGISTRY)


class KernelSite:
    """How a lowering calls its Pallas kernel (``LoweringContext.
    kernel_site``): directly (``shards`` 1), or once per shard of the batch
    under ``shard_map`` over the plan's mesh, each chip's call seeing the
    rows it holds."""

    def __init__(self, mesh=None, axis=None):
        self.mesh, self.axis = mesh, axis
        self.shards = 1 if mesh is None else int(mesh.shape[axis])

    @classmethod
    def on(cls, mesh, x) -> Optional["KernelSite"]:
        """The site, on a chip, of a kernel over the batch-major ``x`` in a
        program compiled over ``mesh`` (a sharding plan's; None or one
        device: not partitioned).  Direct where nothing is partitioned;
        once per shard where the mesh shards activations on the batch alone
        (``sharding.batch_shard_axis``: the ``dp`` and ``fsdp`` plans) and
        the axis divides ``x``'s leading dim (a declared -1 is whatever
        the feed brings); None, no kernel, on every other mesh.  The one
        place that answers this: the lowerings ask it through
        ``LoweringContext.kernel_site``, the ``fuse_attention`` pass with
        the plan it is handed."""
        if mesh is None or mesh.devices.size <= 1:
            return cls()
        from ..parallel.sharding import batch_shard_axis
        axis = batch_shard_axis(mesh)
        if axis is None or not x.shape \
                or (x.shape[0] > 0 and x.shape[0] % mesh.shape[axis]):
            return None
        return cls(mesh, axis)

    def local(self, x):
        """What one call sees of the batch-major ``x``: its shape for the
        kernel's ``*_supported`` check."""
        if self.shards == 1 or x.shape[0] < 0:
            return x
        import jax
        return jax.ShapeDtypeStruct(
            (x.shape[0] // self.shards,) + tuple(x.shape[1:]), x.dtype)

    def call(self, kernel, operands, batch_major=None, key=None, static=()):
        """``kernel(*operands, key, *static)``: ``kernel`` a module-level
        function, ``static`` hashable, ``key`` the op's PRNG key or None.
        Per shard, the operands marked ``batch_major`` (default: all)
        arrive split on dim 0 and the rest whole, every output is
        batch-major, and the chip's index along the axis is folded into
        ``key``: the on-core PRNG streams are seeded from (seed, grid
        position) and every shard's grid starts at 0, so one key would
        draw one mask on every chip.  The fold happens once, outside the
        kernel's custom_vjp, so the seed it saves regenerates the same
        mask in backward."""
        if self.shards == 1:
            return kernel(*operands, key, *static)
        from ..fluid import trace
        trace.metrics().counter("kernel.shard_map_calls").inc()
        return _per_shard(
            kernel, static, self.mesh, self.axis,
            tuple(batch_major or (True,) * len(operands)),
            key is not None)(key, *operands)


def chip_site() -> Optional[KernelSite]:
    """A direct site on the tpu backend, None (XLA) off it: for code that
    already runs per chip (a ``shard_map`` body in parallel/) and has no
    ``LoweringContext`` to ask."""
    import jax
    return KernelSite() if jax.default_backend() == "tpu" else None


@functools.lru_cache(maxsize=64)
def _per_shard(kernel, static, mesh, axis, batch_major, keyed):
    """The jitted ``shard_map`` of one kernel call.  One object per
    (kernel, statics, mesh): the layers of a model trace, differentiate and
    lower it once (37 call sites in a BERT-base step: 12 attention and 25
    dropout ops, each lowered once, under ``jax.vjp``)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from ..parallel.api import compat_shard_map

    def body(key, *local):
        if keyed:
            key = jax.random.fold_in(key, jax.lax.axis_index(axis))
        return kernel(*local, key, *static)

    return jax.jit(compat_shard_map(
        body, mesh=mesh,
        in_specs=(P(),) + tuple(P(axis) if b else P() for b in batch_major),
        out_specs=P(axis)))


class LoweringContext:
    """Per-compilation context handed to lowering rules.

    Carries the PRNG base key (random ops fold in their static `op_seed` attr,
    so a forward traced again by its grad op draws the forward's randomness),
    the vjp a forward op kept for its grad op (``kept_vjp``), the mesh axis
    registry for collective ops (parallel/mesh.py), and mode flags.
    """

    def __init__(self, base_key=None, mesh_axes=None, is_test=False):
        self.base_key = base_key
        self.mesh_axes = mesh_axes or {}   # ring_id -> mesh axis name(s)
        self.is_test = is_test
        self.p2p = {}                      # ring_id -> in-flight send_v2 value
        # shape bucketing (fluid/compile_cache.py): when the executor pads
        # feeds up to a bucket edge, batch_padded is the static padded
        # leading dim and batch_valid the traced true batch size; batch
        # reductions consult batch_mask() to stay padding-invariant
        self.batch_valid = None
        self.batch_padded = None
        # per-op IR hint set by run_block_ops: False when the op's primary
        # input is a persistable var (parameter/state — its rows are never
        # the batch, even if dim 0 aliases the bucket size), True when the
        # IR marks it batch-major (-1 leading dim), None when unknown
        self.cur_op_batch_major = None
        # the Program op being lowered (run_block_ops sets it; None under
        # shape inference, whose stand-in batch is no run's): for a lowering
        # that publishes a trace-time reading under its own variables' names
        self.cur_op = None
        # set by the executor when the whole block compiles as ONE
        # GSPMD-partitioned program over a multi-device mesh
        # (parallel/sharding.py wrap_with_plan, parallel/api.py
        # wrap_with_mesh); ``mesh`` is the sharding plan's, None without
        # a plan
        self.partitioned = False
        self.mesh = None
        # set by run_block_ops just before it lowers a generic_grad whose
        # forward partner it lowered under jax.vjp (fluid/backward.py
        # lower_under_vjp): that call's (primal_outs, vjp_fn).  The grad
        # lowering takes it and resets it; None means trace the forward
        # again
        self.kept_vjp = None

    def pallas_ok(self) -> bool:
        """May a lowering call its Pallas TPU kernel directly?  On the tpu
        backend, and not inside a GSPMD-partitioned program: Mosaic calls
        cannot be partitioned automatically (jax refuses at lowering).
        There a kernel runs only per shard, under ``shard_map``, which is
        ``kernel_site``'s answer; a lowering that asks only this keeps
        its XLA spelling in every partitioned program."""
        import jax
        return jax.default_backend() == "tpu" and not self.partitioned

    def kernel_site(self, x) -> Optional["KernelSite"]:
        """Where the Pallas kernel of an op over the batch-major ``x`` runs,
        read from what the context holds: called directly where
        ``pallas_ok``; on the tpu backend in a program partitioned over a
        sharding plan's mesh, ``KernelSite.on(mesh, x)``'s answer; None,
        no kernel here, off the chip, under ``wrap_with_mesh`` (no plan,
        no mesh on the context) and for an op whose primary input is a
        parameter (its rows are not the batch)."""
        if self.pallas_ok():
            return KernelSite()
        import jax
        if jax.default_backend() != "tpu" or self.mesh is None \
                or self.cur_op_batch_major is False:
            return None
        return KernelSite.on(self.mesh, x)

    def batch_mask(self, dim0):
        """Row-validity mask (bool[dim0]) when ``dim0`` is the bucketed
        batch axis under shape bucketing, else None.  The IR hint
        (cur_op_batch_major) vetoes masking for persistable inputs; for
        unknown provenance the dim0-equality heuristic applies — pick
        bucket edges disjoint from model dims if that ever aliases
        (docs/performance.md)."""
        if self.batch_valid is None or self.batch_padded != dim0 \
                or self.cur_op_batch_major is False:
            return None
        import jax.numpy as jnp
        return jnp.arange(int(dim0)) < self.batch_valid

    def key_for(self, op_seed: int):
        import jax
        if self.base_key is None:
            import jax.random as jr
            return jr.PRNGKey(int(op_seed))
        return jax.random.fold_in(self.base_key, int(op_seed))

    def axis_for_ring(self, ring_id: int):
        return self.mesh_axes.get(int(ring_id), None)
