"""Collective communication ops — ICI/XLA collectives replace NCCL rings.

Reference: paddle/fluid/operators/collective/ (SURVEY §2.5): c_allreduce_{sum,
max,min,prod}, c_allgather, c_reducescatter, c_broadcast, c_reduce_*,
send_v2/recv_v2, barrier, plus bootstrap ops c_gen_nccl_id/c_comm_init.  The
reference pattern `ring_id -> NCCLCommContext::Instance().Get(rid)` becomes
`ring_id -> mesh axis name` via LoweringContext.mesh_axes (registered by
parallel/mesh.py).  Under shard_map over a jax.sharding.Mesh these lower to
lax.psum/all_gather/ppermute on ICI; outside any mesh they are identity
(single-replica), mirroring how a 1-GPU NCCL ring degenerates.

Bootstrap ops (c_gen_nccl_id, c_comm_init*, c_sync_*_stream) are no-ops: XLA
programs are globally scheduled and jax.distributed.initialize is the
gen_nccl_id analog (SURVEY §5 comm-backend note).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..fluid import trace
from .registry import register_op


def _axis(ctx, attrs):
    return ctx.axis_for_ring(attrs.get("ring_id", 0))


def _annotate(op_type, fn):
    """Observability-plane comm annotation: spans (cat="comm") carry the
    ring -> mesh-axis resolution so a timeline shows WHICH collective on
    WHICH axis, nested inside the generic per-op dispatch span.  At
    trace/lowering time only (XLA owns the device schedule); one boolean
    when the plane is off."""
    def lower(ins, attrs, ctx):
        if not trace.enabled():
            return fn(ins, attrs, ctx)
        t0 = trace.now()
        out = fn(ins, attrs, ctx)
        trace.complete(op_type, t0, cat="comm",
                       args={"ring_id": int(attrs.get("ring_id", 0)),
                             "axis": _axis(ctx, attrs)})
        return out
    lower.__name__ = f"comm_{op_type}"
    return lower


def register_comm_op(type, fn=None, **kwargs):
    """register_op for data-moving collectives: same contract, comm-span
    annotated (bootstrap no-ops stay unannotated)."""
    if fn is not None:
        return register_op(type, _annotate(type, fn), **kwargs)

    def deco(f):
        register_op(type, _annotate(type, f), **kwargs)
        return f
    return deco


def _note_dispatched(n: int = 1):
    """The other half of the implied-vs-dispatched split
    (parallel/sharding.py): a collective that lowers to a REAL psum/
    pmean launch counts here, once per compile (trace time).  The
    sharding plane's ``shard_collectives`` rewrite counts into
    ``sharding.collectives_implied`` instead — a sharded executable
    gates on this counter staying at zero."""
    trace.metrics().counter("sharding.collectives_dispatched").inc(n)


def _allreduce(reducer):
    def lower(ins, attrs, ctx):
        x = ins["X"][0]
        axis = _axis(ctx, attrs)
        if axis is None:
            return {"Out": [x]}
        _note_dispatched()
        return {"Out": [reducer(x, axis_name=axis)]}
    return lower


@register_comm_op("c_allreduce_coalesced", differentiable=False)
def _c_allreduce_coalesced(ins, attrs, ctx):
    """Bucketed gradient all-reduce (fuse_all_reduce_op_pass +
    coalesce_tensor analog), emitted by the coalesce_allreduce graph pass:
    N small per-grad launches become ONE flattened psum/pmean over the
    concatenated bucket, then the slices go back to their own shapes and
    dtypes.  Mixed dtypes ride in the promoted dtype and are cast back —
    same-or-better precision than per-tensor reduction."""
    xs = list(ins["X"])
    axis = _axis(ctx, attrs)
    if axis is None:
        return {"Out": xs}
    _note_dispatched(len(xs))
    reducer = lax.pmean if attrs.get("reduce", "sum") == "avg" else lax.psum
    flat = jnp.concatenate([x.reshape(-1) for x in xs])
    red = reducer(flat, axis_name=axis)
    outs, off = [], 0
    for x in xs:
        n = int(x.size)
        outs.append(red[off:off + n].reshape(x.shape).astype(x.dtype))
        off += n
    return {"Out": outs}


register_comm_op("c_allreduce_sum", _allreduce(lax.psum))
register_comm_op("c_allreduce_max", _allreduce(lax.pmax))
register_comm_op("c_allreduce_min", _allreduce(lax.pmin))
register_comm_op("c_allreduce_prod", _allreduce(
    lambda x, axis_name: jnp.exp(lax.psum(jnp.log(x), axis_name=axis_name))))
register_comm_op("allreduce", _allreduce(lax.psum))  # legacy operators/nccl era
register_comm_op("c_allreduce_avg", _allreduce(lax.pmean))


@register_comm_op("c_allgather")
def _c_allgather(ins, attrs, ctx):
    x = ins["X"][0]
    axis = _axis(ctx, attrs)
    if axis is None:
        return {"Out": [x]}
    g = lax.all_gather(x, axis_name=axis)           # (n, ...) leading axis
    return {"Out": [g.reshape((-1,) + x.shape[1:])]}


@register_comm_op("c_reducescatter")
def _c_reducescatter(ins, attrs, ctx):
    x = ins["X"][0]
    axis = _axis(ctx, attrs)
    if axis is None:
        return {"Out": [x]}
    return {"Out": [lax.psum_scatter(x, axis_name=axis, tiled=True)]}


@register_comm_op("c_broadcast")
def _c_broadcast(ins, attrs, ctx):
    x = ins["X"][0]
    axis = _axis(ctx, attrs)
    if axis is None:
        return {"Out": [x]}
    root = attrs.get("root", 0)
    idx = lax.axis_index(axis)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return {"Out": [lax.psum(masked, axis_name=axis)]}


def _c_reduce(reducer):
    # result only meaningful on root; we produce it everywhere (SPMD)
    def lower(ins, attrs, ctx):
        x = ins["X"][0]
        axis = _axis(ctx, attrs)
        if axis is None:
            return {"Out": [x]}
        return {"Out": [reducer(x, axis_name=axis)]}
    return lower


register_comm_op("c_reduce_sum", _c_reduce(lax.psum))
register_comm_op("c_reduce_max", _c_reduce(lax.pmax))
register_comm_op("c_reduce_min", _c_reduce(lax.pmin))
register_comm_op("c_reduce_prod", _c_reduce(
    lambda x, axis_name: jnp.exp(lax.psum(jnp.log(x), axis_name=axis_name))))


@register_comm_op("c_scatter")
def _c_scatter(ins, attrs, ctx):
    x = ins["X"][0]
    axis = _axis(ctx, attrs)
    if axis is None:
        return {"Out": [x]}
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    chunks = x.reshape((n, -1) + x.shape[1:])
    return {"Out": [lax.dynamic_index_in_dim(chunks, idx, keepdims=False)]}


@register_comm_op("c_concat")
def _c_concat(ins, attrs, ctx):
    # tensor-parallel all-gather along last dim (model-parallel fc output)
    x = ins["X"][0]
    axis = _axis(ctx, attrs)
    if axis is None:
        return {"Out": [x]}
    return {"Out": [lax.all_gather(x, axis_name=axis, axis=x.ndim - 1,
                                   tiled=True)]}


@register_comm_op("c_split")
def _c_split(ins, attrs, ctx):
    x = ins["X"][0]
    axis = _axis(ctx, attrs)
    if axis is None:
        return {"Out": [x]}
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    step = x.shape[-1] // n
    return {"Out": [lax.dynamic_slice_in_dim(x, idx * step, step, x.ndim - 1)]}


@register_op("c_identity")
def _c_identity(ins, attrs, ctx):
    # TP forward-identity/backward-allreduce boundary op
    return {"Out": [ins["X"][0]]}


@register_op("shard_constraint", differentiable=False)
def _shard_constraint(ins, attrs, ctx):
    """PartitionSpec-implied communication (parallel/sharding.py): the
    ``shard_collectives`` pass rewrites ring-id allreduce ops into this
    marker.  Under a sharded compile (``ctx.mesh`` set by the executor's
    plan path) each value is pinned to the attr's spec — replicated ``[]``
    for a rewritten gradient allreduce — and GSPMD inserts the reduce the
    constraint implies; with no live mesh it is identity, so the
    rewritten program still runs unsharded (the per-op fallback)."""
    xs = list(ins["X"])
    mesh = getattr(ctx, "mesh", None)
    if mesh is None:
        return {"Out": xs}
    from jax.sharding import NamedSharding, PartitionSpec
    spec = PartitionSpec(*(attrs.get("spec") or ()))
    sh = NamedSharding(mesh, spec)
    return {"Out": [lax.with_sharding_constraint(x, sh) for x in xs]}


@register_comm_op("send_v2", differentiable=False)
def _send_v2(ins, attrs, ctx):
    """p2p pipeline send (reference: operators/collective/send_v2_op.cc).

    SPMD model: every rank executes both sides of the pair, so send stores
    its value in the compilation-scoped mailbox and the matching recv_v2
    applies the ring ppermute — together they are exactly the NCCL
    ncclSend/ncclRecv pair, but scheduled by XLA.  The pipeline composite
    path (parallel/pipeline.py) threads boundaries natively and doesn't
    need these ops."""
    ctx.p2p[int(attrs.get("ring_id", 0))] = ins["X"][0]
    return {}


@register_comm_op("recv_v2", differentiable=False)
def _recv_v2(ins, attrs, ctx):
    ring = int(attrs.get("ring_id", 0))
    if ring not in ctx.p2p:
        raise ValueError(
            f"recv_v2(ring_id={ring}) has no matching send_v2 earlier in "
            f"the block — p2p ops must be paired (send stores, recv shifts)")
    x = ctx.p2p.pop(ring)   # consume: a second recv needs its own send
    axis = _axis(ctx, attrs)
    if axis is None:
        return {"Out": [x]}
    n = lax.axis_size(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    return {"Out": [lax.ppermute(x, axis, perm)]}


@register_comm_op("partial_send", differentiable=False)
def _partial_send(ins, attrs, ctx):
    return {}


@register_comm_op("c_ppermute")
def _c_ppermute(ins, attrs, ctx):
    """Native ring shift (no reference analog — exposed for ring attention
    and pipeline p2p).  attrs: shift (+1 = to next rank)."""
    x = ins["X"][0]
    axis = _axis(ctx, attrs)
    if axis is None:
        return {"Out": [x]}
    n = lax.axis_size(axis)
    shift = attrs.get("shift", 1)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return {"Out": [lax.ppermute(x, axis, perm)]}


@register_comm_op("barrier", differentiable=False)
def _barrier(ins, attrs, ctx):
    x = ins["X"][0] if ins.get("X") else jnp.zeros((1,), jnp.float32)
    axis = _axis(ctx, attrs)
    if axis is None:
        return {"Out": [x]}
    # a psum over a zero token is a full synchronisation point
    return {"Out": [x + lax.psum(jnp.zeros_like(x), axis_name=axis) * 0]}


@register_op("c_sync_calc_stream", differentiable=False)
def _sync_calc(ins, attrs, ctx):
    return {"Out": [ins["X"][0]]}


@register_op("c_sync_comm_stream", differentiable=False)
def _sync_comm(ins, attrs, ctx):
    return {"Out": list(ins["X"])}


for _t in ("c_gen_nccl_id", "c_comm_init", "c_comm_init_all",
           "c_comm_init_multitrainer", "gen_nccl_id"):
    register_op(_t, lambda ins, attrs, ctx: {}, differentiable=False)


@register_op("c_embedding", nondiff_inputs=("Ids",))
def _c_embedding(ins, attrs, ctx):
    """Vocab-sharded (tensor-parallel) embedding: each rank owns rows
    [start_index, start_index + local_vocab); out-of-range ids contribute
    zeros which the following c_allreduce_sum fills in."""
    w, ids = ins["W"][0], ins["Ids"][0].astype(jnp.int32)
    start = attrs.get("start_index", 0)
    local = ids - start
    valid = (local >= 0) & (local < w.shape[0])
    out = jnp.take(w, jnp.clip(local, 0, w.shape[0] - 1), axis=0)
    return {"Out": [jnp.where(valid[..., None], out, 0.0)]}
