"""Tensor manipulation + creation lowering rules.

Reference: paddle/fluid/operators/{reshape_op,transpose_op,concat_op,split_op,
slice_op,gather_op,scatter_op,stack_op,expand_op,...}.cc (SURVEY A.1
"Tensor manipulation" group).  Gather/scatter over int indices keep indices in
the nondiff slot so the generic vjp grad never differentiates them.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from .registry import register_op, wide_int


def _x(ins, slot="X", i=0):
    return ins[slot][i]


@register_op("reshape2", nondiff_inputs=("Shape", "ShapeTensor"))
def _reshape2(ins, attrs, ctx):
    x = _x(ins)
    if ins.get("Shape"):
        shape = [int(s) for s in np.asarray(ins["Shape"][0])]
    else:
        shape = list(attrs["shape"])
    # fluid semantics: 0 means copy input dim at that position
    shape = [x.shape[i] if d == 0 else d for i, d in enumerate(shape)]
    return {"Out": [x.reshape(shape)], "XShape": [jnp.zeros((0,), x.dtype)]}


register_op("reshape", lambda ins, a, c:
            {"Out": [_x(ins).reshape([_x(ins).shape[i] if d == 0 else d
                                      for i, d in enumerate(a["shape"])])]})


@register_op("transpose2")
def _transpose2(ins, attrs, ctx):
    x = _x(ins)
    return {"Out": [jnp.transpose(x, attrs["axis"])],
            "XShape": [jnp.zeros((0,), x.dtype)]}


register_op("transpose", lambda ins, a, c:
            {"Out": [jnp.transpose(_x(ins), a["axis"])]})


@register_op("flatten2")
def _flatten2(ins, attrs, ctx):
    x = _x(ins)
    ax = attrs.get("axis", 1)
    lead = int(np.prod(x.shape[:ax])) if ax > 0 else 1
    return {"Out": [x.reshape((lead, -1))], "XShape": [jnp.zeros((0,), x.dtype)]}


register_op("flatten", lambda ins, a, c: {"Out": [
    _x(ins).reshape((int(np.prod(_x(ins).shape[:a.get("axis", 1)])) or 1, -1))]})


@register_op("flatten_contiguous_range")
def _flatten_range(ins, attrs, ctx):
    x = _x(ins)
    start, stop = attrs.get("start_axis", 1), attrs.get("stop_axis", -1)
    nd = x.ndim
    start, stop = start % nd, stop % nd
    shape = x.shape[:start] + (-1,) + x.shape[stop + 1:]
    return {"Out": [x.reshape(shape)], "XShape": [jnp.zeros((0,), x.dtype)]}


@register_op("squeeze2")
def _squeeze2(ins, attrs, ctx):
    x = _x(ins)
    axes = attrs.get("axes", [])
    axes = [a % x.ndim for a in axes] or [i for i, d in enumerate(x.shape) if d == 1]
    out = x.reshape([d for i, d in enumerate(x.shape)
                     if not (i in axes and d == 1)])
    return {"Out": [out], "XShape": [jnp.zeros((0,), x.dtype)]}


register_op("squeeze", lambda ins, a, c: {"Out": [jnp.squeeze(
    _x(ins), tuple(a.get("axes")) if a.get("axes") else None)]})


@register_op("unsqueeze2")
def _unsqueeze2(ins, attrs, ctx):
    x = _x(ins)
    out = x
    for ax in sorted(attrs["axes"]):
        out = jnp.expand_dims(out, ax if ax >= 0 else ax + out.ndim + 1)
    return {"Out": [out], "XShape": [jnp.zeros((0,), x.dtype)]}


register_op("unsqueeze", lambda ins, a, c: {"Out": [
    jnp.expand_dims(_x(ins), tuple(a["axes"]))]})


@register_op("concat")
def _concat(ins, attrs, ctx):
    axis = ins["AxisTensor"][0] if ins.get("AxisTensor") else attrs.get("axis", 0)
    return {"Out": [jnp.concatenate(ins["X"], axis=int(axis))]}


@register_op("split")
def _split(ins, attrs, ctx):
    x = _x(ins)
    axis = attrs.get("axis", 0)
    num = attrs.get("num", 0)
    sections = attrs.get("sections", [])
    if sections:
        total, neg = 0, -1
        sections = list(sections)
        for i, s in enumerate(sections):
            if s < 0:
                neg = i
            else:
                total += s
        if neg >= 0:
            sections[neg] = x.shape[axis] - total
        idx = np.cumsum(sections[:-1])
        outs = jnp.split(x, idx, axis=axis)
    else:
        outs = jnp.split(x, num, axis=axis)
    return {"Out": outs}


@register_op("stack")
def _stack(ins, attrs, ctx):
    return {"Y": [jnp.stack(ins["X"], axis=attrs.get("axis", 0))]}


@register_op("unstack")
def _unstack(ins, attrs, ctx):
    x = _x(ins)
    axis = attrs.get("axis", 0)
    n = attrs.get("num", x.shape[axis])
    return {"Y": [jnp.squeeze(s, axis) for s in jnp.split(x, n, axis=axis)]}


@register_op("unbind")
def _unbind(ins, attrs, ctx):
    x = _x(ins)
    axis = attrs.get("axis", 0)
    return {"Out": [jnp.squeeze(s, axis)
                    for s in jnp.split(x, x.shape[axis], axis=axis)]}


@register_op("slice", nondiff_inputs=("StartsTensor", "EndsTensor"))
def _slice(ins, attrs, ctx):
    x = _x(ins, "Input")
    axes = attrs["axes"]
    starts = list(attrs.get("starts", []))
    ends = list(attrs.get("ends", []))
    slices = [slice(None)] * x.ndim
    for ax, s, e in zip(axes, starts, ends):
        dim = x.shape[ax]
        s = max(s + dim, 0) if s < 0 else min(s, dim)
        e = max(e + dim, 0) if e < 0 else min(e, dim)
        slices[ax] = slice(s, e)
    out = x[tuple(slices)]
    for ax in sorted(attrs.get("decrease_axis", []) or [], reverse=True):
        out = jnp.squeeze(out, ax)
    return {"Out": [out]}


@register_op("strided_slice")
def _strided_slice(ins, attrs, ctx):
    x = _x(ins, "Input")
    slices = [slice(None)] * x.ndim
    for ax, s, e, st in zip(attrs["axes"], attrs["starts"], attrs["ends"],
                            attrs["strides"]):
        slices[ax] = slice(s, e, st)
    return {"Out": [x[tuple(slices)]]}


@register_op("gather", nondiff_inputs=("Index",))
def _gather(ins, attrs, ctx):
    x, idx = _x(ins), _x(ins, "Index")
    axis = int(attrs.get("axis", 0))
    return {"Out": [jnp.take(x, idx.astype(jnp.int32), axis=axis)]}


@register_op("gather_nd", nondiff_inputs=("Index",))
def _gather_nd(ins, attrs, ctx):
    x, idx = _x(ins), _x(ins, "Index")
    k = idx.shape[-1]
    out = x[tuple(jnp.moveaxis(idx, -1, 0).astype(jnp.int32))]
    return {"Out": [out]}


@register_op("scatter", nondiff_inputs=("Ids",))
def _scatter(ins, attrs, ctx):
    x, ids, upd = _x(ins), _x(ins, "Ids"), _x(ins, "Updates")
    ids = ids.astype(jnp.int32).reshape(-1)
    if attrs.get("overwrite", True):
        return {"Out": [x.at[ids].set(upd)]}
    return {"Out": [x.at[ids].set(0.).at[ids].add(upd)]}


@register_op("scatter_nd_add", nondiff_inputs=("Index",))
def _scatter_nd_add(ins, attrs, ctx):
    x, idx, upd = _x(ins), _x(ins, "Index"), _x(ins, "Updates")
    return {"Out": [x.at[tuple(jnp.moveaxis(idx, -1, 0).astype(jnp.int32))]
                    .add(upd)]}


@register_op("index_select", nondiff_inputs=("Index",))
def _index_select(ins, attrs, ctx):
    return {"Out": [jnp.take(_x(ins), _x(ins, "Index").astype(jnp.int32),
                             axis=attrs.get("dim", 0))]}


@register_op("index_sample", nondiff_inputs=("Index",))
def _index_sample(ins, attrs, ctx):
    x, idx = _x(ins), _x(ins, "Index").astype(jnp.int32)
    return {"Out": [jnp.take_along_axis(x, idx, axis=1)]}


@register_op("masked_select", differentiable=False)
def _masked_select(ins, attrs, ctx):
    # dynamic output shape — only usable outside jit (dygraph eager path)
    return {"Y": [_x(ins)[_x(ins, "Mask").astype(bool)]]}


@register_op("where", nondiff_inputs=("Condition",))
def _where(ins, attrs, ctx):
    return {"Out": [jnp.where(_x(ins, "Condition").astype(bool),
                              _x(ins), _x(ins, "Y"))]}


register_op("where_index", lambda ins, a, c:
            {"Out": [jnp.argwhere(_x(ins, "Condition"))]},
            differentiable=False)


@register_op("expand")
def _expand(ins, attrs, ctx):
    x = _x(ins)
    times = attrs["expand_times"]
    return {"Out": [jnp.tile(x, times)]}


@register_op("expand_v2")
def _expand_v2(ins, attrs, ctx):
    x = _x(ins)
    shape = list(attrs["shape"])
    # -1 keeps input dim; leading new dims broadcast
    nd = len(shape)
    xs = (1,) * (nd - x.ndim) + x.shape
    shape = [xs[i] if d == -1 else d for i, d in enumerate(shape)]
    return {"Out": [jnp.broadcast_to(x.reshape(xs), shape)]}


@register_op("expand_as_v2")
def _expand_as(ins, attrs, ctx):
    x = _x(ins)
    shape = attrs.get("target_shape") or ins["Y"][0].shape
    xs = (1,) * (len(shape) - x.ndim) + x.shape
    return {"Out": [jnp.broadcast_to(x.reshape(xs), shape)]}


@register_op("tile")
def _tile(ins, attrs, ctx):
    return {"Out": [jnp.tile(_x(ins), attrs["repeat_times"])]}


@register_op("flip")
def _flip(ins, attrs, ctx):
    return {"Out": [jnp.flip(_x(ins), tuple(attrs["axis"]))]}


@register_op("roll")
def _roll(ins, attrs, ctx):
    axis = attrs.get("axis", None)
    return {"Out": [jnp.roll(_x(ins), attrs["shifts"],
                             tuple(axis) if axis else None)]}


@register_op("reverse")
def _reverse(ins, attrs, ctx):
    return {"Out": [jnp.flip(_x(ins), tuple(attrs["axis"]))]}


@register_op("pad")
def _pad(ins, attrs, ctx):
    x = _x(ins)
    p = attrs["paddings"]
    pads = [(p[2 * i], p[2 * i + 1]) for i in range(x.ndim)]
    return {"Out": [jnp.pad(x, pads, constant_values=attrs.get("pad_value", 0.0))]}


@register_op("pad2d")
def _pad2d(ins, attrs, ctx):
    x = _x(ins)
    p = attrs["paddings"]  # [top, bottom, left, right]
    mode = attrs.get("mode", "constant")
    fmt = attrs.get("data_format", "NCHW")
    if fmt == "NCHW":
        pads = [(0, 0), (0, 0), (p[0], p[1]), (p[2], p[3])]
    else:
        pads = [(0, 0), (p[0], p[1]), (p[2], p[3]), (0, 0)]
    if mode == "constant":
        return {"Out": [jnp.pad(x, pads, constant_values=attrs.get("pad_value", 0.0))]}
    jmode = {"reflect": "reflect", "edge": "edge"}[mode]
    return {"Out": [jnp.pad(x, pads, mode=jmode)]}


@register_op("pad3d")
def _pad3d(ins, attrs, ctx):
    x = _x(ins)
    p = attrs["paddings"]  # [left, right, top, bottom, front, back]
    fmt = attrs.get("data_format", "NCDHW")
    if fmt == "NCDHW":
        pads = [(0, 0), (0, 0), (p[4], p[5]), (p[2], p[3]), (p[0], p[1])]
    else:
        pads = [(0, 0), (p[4], p[5]), (p[2], p[3]), (p[0], p[1]), (0, 0)]
    mode = attrs.get("mode", "constant")
    if mode == "constant":
        return {"Out": [jnp.pad(x, pads, constant_values=attrs.get("value", 0.0))]}
    return {"Out": [jnp.pad(x, pads, mode={"reflect": "reflect",
                                           "replicate": "edge",
                                           "circular": "wrap"}[mode])]}


@register_op("cast")
def _cast(ins, attrs, ctx):
    from ..fluid.framework import device_dtype
    return {"Out": [_x(ins).astype(device_dtype(attrs["out_dtype"]))]}


@register_op("fill_constant", differentiable=False)
def _fill_constant(ins, attrs, ctx):
    from ..fluid.framework import device_dtype
    shape = attrs.get("shape", [])
    if ins.get("ShapeTensor"):
        shape = [int(d) for d in np.asarray(ins["ShapeTensor"][0])]
    dtype = device_dtype(attrs.get("dtype", "float32"))
    return {"Out": [jnp.full(shape, attrs.get("value", 0.0), dtype=dtype)]}


@register_op("fill_any_like")
def _fill_any_like(ins, attrs, ctx):
    from ..fluid.framework import device_dtype
    dt = attrs.get("dtype", None)
    x = _x(ins)
    dtype = device_dtype(dt) if dt not in (None, -1) else x.dtype
    return {"Out": [jnp.full_like(x, attrs.get("value", 0.0), dtype=dtype)]}


register_op("fill_zeros_like", lambda ins, a, c:
            {"Out": [jnp.zeros_like(_x(ins))]})


@register_op("assign")
def _assign(ins, attrs, ctx):
    return {"Out": [_x(ins)]}


@register_op("assign_value", differentiable=False)
def _assign_value(ins, attrs, ctx):
    from ..fluid.framework import device_dtype
    dtype = device_dtype(attrs.get("dtype", "float32"))
    for key in ("fp32_values", "int32_values", "int64_values", "bool_values"):
        if attrs.get(key):
            vals = attrs[key]
            break
    else:
        vals = []
    arr = np.array(vals).reshape(attrs["shape"])
    if dtype == "int32" and arr.dtype == np.int64 and arr.size \
            and (arr.max() > np.iinfo(np.int32).max
                 or arr.min() < np.iinfo(np.int32).min):
        # same contract as the executor's feed guard: 64-bit ids must not
        # wrap silently when x64 is off
        raise ValueError(
            "assign_value carries int64 constants exceeding int32 range "
            "and x64 is off; enable FLAGS_enable_x64 to keep them exact")
    return {"Out": [jnp.asarray(arr, dtype=dtype)]}


register_op("shape", lambda ins, a, c:
            {"Out": [jnp.asarray(ins["Input"][0].shape, jnp.int32)]},
            differentiable=False)
register_op("size", lambda ins, a, c:
            {"Out": [jnp.asarray(ins["Input"][0].size, wide_int())]},
            differentiable=False)
register_op("rank", lambda ins, a, c:
            {"Out": [jnp.asarray(ins["Input"][0].ndim, jnp.int32)]},
            differentiable=False)


@register_op("eye", differentiable=False)
def _eye(ins, attrs, ctx):
    from ..fluid.framework import device_dtype
    n = attrs["num_rows"]
    m = attrs.get("num_columns", n)
    return {"Out": [jnp.eye(n, m if m > 0 else n,
                            dtype=device_dtype(attrs.get("dtype", "float32")))]}


@register_op("linspace", differentiable=False)
def _linspace(ins, attrs, ctx):
    start, stop, num = ins["Start"][0], ins["Stop"][0], ins["Num"][0]
    from ..fluid.framework import device_dtype
    dtype = device_dtype(attrs.get("dtype", "float32"))
    return {"Out": [jnp.linspace(start.reshape(()), stop.reshape(()),
                                 int(num), dtype=dtype)]}


@register_op("range", differentiable=False)
def _range(ins, attrs, ctx):
    s, e, st = ins["Start"][0], ins["End"][0], ins["Step"][0]
    return {"Out": [jnp.arange(s.reshape(()), e.reshape(()), st.reshape(()))]}


@register_op("increment")
def _increment(ins, attrs, ctx):
    return {"Out": [_x(ins) + attrs.get("step", 1.0)]}


@register_op("one_hot", nondiff_inputs=("X",), differentiable=False)
def _one_hot(ins, attrs, ctx):
    x = _x(ins).astype(jnp.int32)
    depth = attrs["depth"]
    out = jax.nn.one_hot(x.reshape(x.shape[:-1]) if x.shape[-1] == 1 else x,
                         depth, dtype=jnp.float32)
    return {"Out": [out]}


register_op("one_hot_v2", lambda ins, a, c: {"Out": [
    jax.nn.one_hot(_x(ins).astype(jnp.int32), a["depth"], dtype=jnp.float32)]},
    differentiable=False)


@register_op("diag_v2", differentiable=False)
def _diag_v2(ins, attrs, ctx):
    x = _x(ins)
    k = attrs.get("offset", 0)
    out = jnp.diag(x, k=k)
    pad = attrs.get("padding_value", 0)
    if x.ndim == 1 and pad:
        # off-diagonal fill (tensor/creation.py diag padding_value)
        mask = jnp.diag(jnp.ones_like(x, dtype=bool), k=k)
        out = jnp.where(mask, out, jnp.asarray(pad, out.dtype))
    return {"Out": [out]}


@register_op("diag_embed")
def _diag_embed(ins, attrs, ctx):
    x = _x(ins, "Input")
    return {"Out": [jnp.apply_along_axis(jnp.diag, -1, x)] if x.ndim > 1
            else [jnp.diag(x, k=attrs.get("offset", 0))]}


@register_op("meshgrid")
def _meshgrid(ins, attrs, ctx):
    return {"Out": list(jnp.meshgrid(*ins["X"], indexing="ij"))}


@register_op("tril_triu")
def _tril_triu(ins, attrs, ctx):
    x = _x(ins)
    k = attrs.get("diagonal", 0)
    f = jnp.tril if attrs.get("lower", True) else jnp.triu
    return {"Out": [f(x, k)]}


@register_op("unique_with_counts", differentiable=False)
def _unique_with_counts(ins, attrs, ctx):
    x = _x(ins)
    u, idx, counts = np.unique(np.asarray(x), return_inverse=True,
                               return_counts=True)
    return {"Out": [jnp.asarray(u)], "Index": [jnp.asarray(idx)],
            "Count": [jnp.asarray(counts)]}


@register_op("shard_index", differentiable=False)
def _shard_index(ins, attrs, ctx):
    x = _x(ins)
    index_num, nshards = attrs["index_num"], attrs["nshards"]
    shard_id = attrs["shard_id"]
    ignore = attrs.get("ignore_value", -1)
    size = (index_num + nshards - 1) // nshards
    mask = (x // size) == shard_id
    return {"Out": [jnp.where(mask, x % size, ignore)]}


def embedding_grad_lowering(ctx, w) -> str:
    """Where the gradient of a lookup into the dense table ``w`` is added up:
    ``kernel`` (``pallas_kernels.embedding_grad_tpu``: each block of table
    rows written once, the tokens' rows added on the matmul unit) where a
    kernel runs directly (``ctx.pallas_ok``: a one-chip program on the TPU)
    and the kernel takes ``w``; ``xla`` (``jax.vjp`` of ``jnp.take``: XLA
    sorts the ids and scatter-adds a row at a time) elsewhere: off the chip
    and in a partitioned program.  Alone, the kernel was the faster at every
    table measured, two rows to 30522 (``docs/performance.md`` "The
    embedding's gradient")."""
    from . import pallas_kernels as pk
    return ("kernel" if ctx.pallas_ok() and pk.embedding_grad_supported(w)
            else "xla")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _take(w, ids, vocab, lowering):
    return jnp.take(w, ids, axis=0)


def _take_fwd(w, ids, vocab, lowering):
    return jnp.take(w, ids, axis=0), ids


def _take_bwd(vocab, lowering, ids, g):
    from ..fluid import trace
    from . import pallas_kernels as pk
    trace.metrics().counter(f"embedding.grad_lowering.{lowering}").inc()
    if lowering == "kernel":
        return pk.embedding_grad_tpu(ids, g.reshape(-1, g.shape[-1]),
                                     vocab), None
    w = jnp.zeros((vocab,) + g.shape[ids.ndim:], g.dtype)
    return jax.vjp(lambda w: jnp.take(w, ids, axis=0), w)[1](g)[0], None


_take.defvjp(_take_fwd, _take_bwd)


def _embedding(w, ids, attrs, ctx):
    """``ids``' rows of ``w``, zero where the id is ``padding_idx``; the
    gradient is added where ``embedding_grad_lowering`` says, counted under
    ``embedding.grad_lowering.<kernel|xla>`` each time one is formed."""
    out = _take(w, ids, w.shape[0], embedding_grad_lowering(ctx, w))
    padding_idx = attrs.get("padding_idx", -1)
    if padding_idx is not None and padding_idx >= 0:
        out = jnp.where((ids == padding_idx)[..., None], 0.0, out)
    return out


@register_op("lookup_table_v2", nondiff_inputs=("Ids",))
def _lookup_table_v2(ins, attrs, ctx):
    """Embedding (operators/lookup_table_v2_op).  SelectedRows sparse grad
    becomes a dense gradient (``_embedding``)."""
    w, ids = _x(ins, "W"), _x(ins, "Ids").astype(jnp.int32)
    return {"Out": [_embedding(w, ids, attrs, ctx)]}


@register_op("lookup_table", nondiff_inputs=("Ids",))
def _lookup_table(ins, attrs, ctx):
    w, ids = _x(ins, "W"), _x(ins, "Ids").astype(jnp.int32)
    ids = ids.reshape(ids.shape[:-1]) if ids.shape[-1] == 1 else ids
    return {"Out": [_embedding(w, ids, attrs, ctx)]}


@register_op("space_to_depth")
def _space_to_depth(ins, attrs, ctx):
    x = _x(ins)
    b = attrs["blocksize"]
    n, c, h, w = x.shape
    x = x.reshape(n, c, h // b, b, w // b, b)
    x = jnp.transpose(x, (0, 3, 5, 1, 2, 4))
    return {"Out": [x.reshape(n, c * b * b, h // b, w // b)]}


@register_op("pixel_shuffle")
def _pixel_shuffle(ins, attrs, ctx):
    x = _x(ins)
    r = attrs["upscale_factor"]
    n, c, h, w = x.shape
    x = x.reshape(n, c // (r * r), r, r, h, w)
    x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
    return {"Out": [x.reshape(n, c // (r * r), h * r, w * r)]}


@register_op("unfold")
def _unfold(ins, attrs, ctx):
    x = _x(ins)
    ks = attrs["kernel_sizes"]
    st = attrs.get("strides", [1, 1])
    pd = attrs.get("paddings", [0, 0, 0, 0])
    dl = attrs.get("dilations", [1, 1])
    n, c, h, w = x.shape
    x = jnp.pad(x, [(0, 0), (0, 0), (pd[0], pd[2] if len(pd) > 2 else pd[0]),
                    (pd[1], pd[3] if len(pd) > 3 else pd[1])])
    patches = jax.lax.conv_general_dilated_patches(
        x, ks, st, "VALID", rhs_dilation=dl,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    n2, ckk, oh, ow = patches.shape
    return {"Y": [patches.reshape(n2, ckk, oh * ow)]}


@register_op("fill_constant_batch_size_like", differentiable=False)
def _fill_constant_bsl(ins, attrs, ctx):
    from ..fluid.framework import device_dtype
    ref = ins["Input"][0]
    shape = list(attrs["shape"])
    shape[attrs.get("output_dim_idx", 0)] = ref.shape[attrs.get("input_dim_idx", 0)]
    return {"Out": [jnp.full(shape, attrs.get("value", 0.0),
                             dtype=device_dtype(attrs.get("dtype", "float32")))]}
