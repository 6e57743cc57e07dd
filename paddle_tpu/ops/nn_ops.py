"""NN ops: conv/pool/norm/softmax/dropout/interpolate lowering rules.

Reference: paddle/fluid/operators/{conv_op,conv_cudnn_op,pool_op,batch_norm_op,
layer_norm_op,group_norm_op,instance_norm_op,softmax_op,dropout_op,
interpolate_op,...}.cc|cu (SURVEY §2.5).  Convs lower to
lax.conv_general_dilated which XLA tiles onto the MXU; there is no cuDNN-style
algo search — the compiler picks the schedule.  batch_norm keeps fluid's
running-stat update semantics by emitting the updated moving stats as extra
outputs that the executor writes back to the scope (the analog of fluid's
in-place MeanOut/VarianceOut aliasing).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op


def _x(ins, slot="X", i=0):
    return ins[slot][i]


def _conv_pad(padding, algorithm, ndim_sp):
    if algorithm == "SAME":
        return "SAME"
    if algorithm == "VALID":
        return "VALID"
    p = list(padding)
    if len(p) == ndim_sp:
        return [(pi, pi) for pi in p]
    if len(p) == 2 * ndim_sp:
        return [(p[2 * i], p[2 * i + 1]) for i in range(ndim_sp)]
    return [(p[0], p[0])] * ndim_sp


@register_op("conv2d")
def _conv2d(ins, attrs, ctx):
    x, w = _x(ins, "Input"), _x(ins, "Filter")
    fmt = attrs.get("data_format", "NCHW")
    if fmt in ("NCHW", "AnyLayout"):
        dn = ("NCHW", "OIHW", "NCHW")
    else:
        dn = ("NHWC", "OIHW", "NHWC")
    groups = attrs.get("groups", 1)
    out = lax.conv_general_dilated(
        x, w,
        window_strides=attrs.get("strides", [1, 1]),
        padding=_conv_pad(attrs.get("paddings", [0, 0]),
                          attrs.get("padding_algorithm", "EXPLICIT"), 2),
        rhs_dilation=attrs.get("dilations", [1, 1]),
        dimension_numbers=dn,
        feature_group_count=groups)
    # no preferred_element_type: XLA already accumulates bf16 convs in f32
    # on the MXU, and conv_general_dilated's transpose rule rejects mixed
    # operand dtypes when the cotangent arrives in the accumulation type
    return {"Output": [out.astype(x.dtype)]}


@register_op("depthwise_conv2d")
def _depthwise_conv2d(ins, attrs, ctx):
    x, w = _x(ins, "Input"), _x(ins, "Filter")
    fmt = attrs.get("data_format", "NCHW")
    nhwc = fmt == "NHWC"
    groups = attrs.get("groups", x.shape[-1] if nhwc else x.shape[1])
    dn = ("NHWC", "OIHW", "NHWC") if nhwc else ("NCHW", "OIHW", "NCHW")
    out = lax.conv_general_dilated(
        x, w,
        window_strides=attrs.get("strides", [1, 1]),
        padding=_conv_pad(attrs.get("paddings", [0, 0]),
                          attrs.get("padding_algorithm", "EXPLICIT"), 2),
        rhs_dilation=attrs.get("dilations", [1, 1]),
        dimension_numbers=dn,
        feature_group_count=groups)
    return {"Output": [out.astype(x.dtype)]}


@register_op("conv2d_transpose")
def _conv2d_transpose(ins, attrs, ctx):
    """Transposed conv (conv2d_transpose_op.cc) as a dilated conv: fluid
    filter layout (C_in, C_out/groups, kh, kw) maps directly onto IOHW with
    the kernel spatially flipped, lhs_dilation = strides, and padding
    (k_eff - 1 - p) — the exact adjoint of the conv2d lowering (verified by
    <conv(x,w), y> == <x, convT(y,w)> in test_op_grads_auto)."""
    x, w = _x(ins, "Input"), _x(ins, "Filter")
    s = list(attrs.get("strides", [1, 1]))
    d = list(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    kh = (w.shape[2] - 1) * d[0] + 1
    kw = (w.shape[3] - 1) * d[1] + 1
    p = list(attrs.get("paddings", [0, 0]))
    algo = attrs.get("padding_algorithm", "EXPLICIT")
    if algo == "VALID":
        p = [0, 0, 0, 0]
    elif algo == "SAME":
        # out == in * stride exactly: total crop per dim = k_eff - s,
        # remainder on the high side (may be negative when k < s)
        p = [(kh - s[0]) // 2, (kh - s[0]) - (kh - s[0]) // 2,
             (kw - s[1]) // 2, (kw - s[1]) - (kw - s[1]) // 2]
    if len(p) == 2:                    # symmetric [ph, pw]
        p = [p[0], p[0], p[1], p[1]]
    # default out = (in-1)*s - (p_lo+p_hi) + k_eff; output_size (absolute)
    # or output_padding (extra) add rows on the high edge for stride > 1
    extra = [0, 0]
    osize = attrs.get("output_size")
    opad = attrs.get("output_padding")
    if osize:
        dh = (x.shape[2] - 1) * s[0] - p[0] - p[1] + kh
        dw = (x.shape[3] - 1) * s[1] - p[2] - p[3] + kw
        extra = [int(osize[0]) - dh, int(osize[1]) - dw]
    elif opad:
        extra = [int(opad[0]), int(opad[1])]
    pad = [(kh - 1 - p[0], kh - 1 - p[1] + extra[0]),
           (kw - 1 - p[2], kw - 1 - p[3] + extra[1])]
    if groups > 1:
        # (Cin, Cout/g, kh, kw) -> grouped IOHW expects I = Cin/g per group
        # with O totalling Cout: split, run per group, concat (XLA fuses)
        xs = jnp.split(x, groups, axis=1)
        ws = jnp.split(w, groups, axis=0)
        outs = [lax.conv_general_dilated(
            xi, jnp.flip(wi, (2, 3)), window_strides=(1, 1), padding=pad,
            lhs_dilation=s, rhs_dilation=d,
            dimension_numbers=("NCHW", "IOHW", "NCHW"))
            for xi, wi in zip(xs, ws)]
        out = jnp.concatenate(outs, axis=1)
    else:
        out = lax.conv_general_dilated(
            x, jnp.flip(w, (2, 3)), window_strides=(1, 1), padding=pad,
            lhs_dilation=s, rhs_dilation=d,
            dimension_numbers=("NCHW", "IOHW", "NCHW"))
    return {"Output": [out.astype(x.dtype)]}


@register_op("conv3d")
def _conv3d(ins, attrs, ctx):
    x, w = _x(ins, "Input"), _x(ins, "Filter")
    out = lax.conv_general_dilated(
        x, w, attrs.get("strides", [1, 1, 1]),
        _conv_pad(attrs.get("paddings", [0, 0, 0]),
                  attrs.get("padding_algorithm", "EXPLICIT"), 3),
        rhs_dilation=attrs.get("dilations", [1, 1, 1]),
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=attrs.get("groups", 1))
    return {"Output": [out.astype(x.dtype)]}


@register_op("pool2d")
def _pool2d(ins, attrs, ctx):
    x = _x(ins)
    ptype = attrs.get("pooling_type", "max")
    nhwc = attrs.get("data_format", "NCHW") == "NHWC"
    if attrs.get("global_pooling", False):
        axis = (1, 2) if nhwc else (2, 3)
        out = (jnp.max(x, axis, keepdims=True) if ptype == "max"
               else jnp.mean(x, axis, keepdims=True))
        return {"Out": [out]}
    ks = attrs["ksize"]
    st = attrs.get("strides", ks)
    pd = attrs.get("paddings", [0, 0])
    algo = attrs.get("padding_algorithm", "EXPLICIT")
    sp_pads = ([(pd[0], pd[1]), (pd[2], pd[3])] if len(pd) == 4
               else [(pd[0], pd[0]), (pd[1], pd[1])])
    if algo == "SAME":
        pads = "SAME"
    elif nhwc:
        pads = [(0, 0)] + sp_pads + [(0, 0)]
    else:
        pads = [(0, 0), (0, 0)] + sp_pads
    if nhwc:
        dims, strides = (1, ks[0], ks[1], 1), (1, st[0], st[1], 1)
    else:
        dims, strides = (1, 1, ks[0], ks[1]), (1, 1, st[0], st[1])
    if ptype == "max":
        out = lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, pads)
    else:
        summed = lax.reduce_window(x, 0.0, lax.add, dims, strides, pads)
        if attrs.get("exclusive", True) and pads != "SAME" and any(
                p != (0, 0) for p in (pads if isinstance(pads, list) else [])):
            ones = jnp.ones_like(x)
            counts = lax.reduce_window(ones, 0.0, lax.add, dims, strides, pads)
            out = summed / counts
        else:
            out = summed / (ks[0] * ks[1])
    return {"Out": [out]}


@register_op("adaptive_pool2d")
def _adaptive_pool2d(ins, attrs, ctx):
    x = _x(ins)
    oh, ow = attrs["ksize"] if "ksize" in attrs else attrs["output_size"]
    nhwc = attrs.get("data_format", "NCHW") == "NHWC"
    if nhwc:
        n, h, w, c = x.shape
    else:
        n, c, h, w = x.shape
    # adaptive pooling with uniform bins (exact when divisible; fluid common case)
    assert h % oh == 0 and w % ow == 0, "adaptive pool needs divisible dims"
    if nhwc:
        x = x.reshape(n, oh, h // oh, ow, w // ow, c)
        red = (2, 4)
    else:
        x = x.reshape(n, c, oh, h // oh, ow, w // ow)
        red = (3, 5)
    if attrs.get("pooling_type", "avg") == "avg":
        return {"Out": [x.mean(axis=red)]}
    return {"Out": [x.max(axis=red)]}


@register_op("softmax")
def _softmax(ins, attrs, ctx):
    return {"Out": [jax.nn.softmax(_x(ins), axis=attrs.get("axis", -1))]}


@register_op("log_softmax")
def _log_softmax(ins, attrs, ctx):
    return {"Out": [jax.nn.log_softmax(_x(ins), axis=attrs.get("axis", -1))]}


def _dropout_and_mask(x, key, rate, upscale):
    from .pallas_kernels import fused_dropout_tpu
    out, mask_fn = fused_dropout_tpu(x, key, rate, upscale_in_train=upscale)
    # mask comes from a second kernel re-running the same PRNG stream;
    # under jit XLA DCEs it unless Mask is actually fetched
    return out, mask_fn()


@register_op("dropout", stateful_rng=True, nondiff_outputs=("Mask",))
def _dropout(ins, attrs, ctx):
    x = _x(ins)
    p = attrs.get("dropout_prob", 0.5)
    is_test = attrs.get("is_test", False) or ctx.is_test
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if is_test:
        out = x if impl == "upscale_in_train" else x * (1.0 - p)
        return {"Out": [out], "Mask": [jnp.ones_like(x, dtype=jnp.uint8)]}
    key = ctx.key_for(attrs.get("op_seed", attrs.get("seed", 0) or 0))
    if p <= 0.0:
        return {"Out": [x], "Mask": [jnp.ones_like(x, dtype=jnp.uint8)]}
    if p >= 1.0:        # everything dropped; also guards 1/(1-p) below
        return {"Out": [jnp.zeros_like(x)],
                "Mask": [jnp.zeros_like(x, dtype=jnp.uint8)]}
    # TPU: pallas fused kernel — on-core PRNG mask, regenerated (not saved)
    # in backward, so mask bytes and uniforms stop round-tripping HBM; once
    # per chip in a data-parallel program (ctx.kernel_site).  v5e, BERT-base
    # at 128 x 128 tokens a chip over four chips: 1.5 ms a step against 21.3
    # for the lowering below (my chip run, PR 27; ledger, PR 26).
    site = ctx.kernel_site(x)
    if site is not None:
        from .pallas_kernels import fused_dropout_supported
        if fused_dropout_supported(site.local(x)):
            out, mask = site.call(_dropout_and_mask, [x], key=key,
                                  static=(p, impl == "upscale_in_train"))
            return {"Out": [out], "Mask": [mask]}
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    if impl == "upscale_in_train":
        out = jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
    else:
        out = jnp.where(keep, x, 0.0).astype(x.dtype)
    return {"Out": [out], "Mask": [keep.astype(jnp.uint8)]}


def _dropout_common(attrs, ctx):
    p = attrs.get("dropout_prob", 0.5)
    is_test = attrs.get("is_test", False) or ctx.is_test
    upscale = (attrs.get("dropout_implementation", "upscale_in_train")
               == "upscale_in_train")
    return p, is_test, upscale


@register_op("fused_dropout_add", stateful_rng=True)
def _fused_dropout_add_op(ins, attrs, ctx):
    """out = dropout(X) + Residual, one fused kernel on TPU (the residual
    add no longer costs an HBM pass at the pallas boundary); backward
    regenerates the mask.  No reference op of this exact shape — it exists
    because pallas calls are opaque to XLA fusion; the reference's
    analogous fusion tier is operators/fused/fused_dropout_helper.h."""
    x, r = _x(ins), _x(ins, "Residual")
    p, is_test, upscale = _dropout_common(attrs, ctx)
    if p <= 0.0:
        return {"Out": [x + r]}
    if is_test:
        return {"Out": [(x if upscale else x * (1.0 - p)) + r]}
    if p >= 1.0:
        return {"Out": [r]}
    key = ctx.key_for(attrs.get("op_seed", attrs.get("seed", 0) or 0))
    site = ctx.kernel_site(x)
    if site is not None:
        from .pallas_kernels import (fused_dropout_add_tpu,
                                     fused_dropout_supported)
        if fused_dropout_supported(site.local(x)) and x.shape == r.shape:
            return {"Out": [site.call(fused_dropout_add_tpu, [x, r],
                                      key=key, static=(p, upscale))]}
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    scale = 1.0 / (1.0 - p) if upscale else 1.0
    return {"Out": [(jnp.where(keep, x * scale, 0.0).astype(x.dtype)
                     + r)]}


@register_op("fused_act_dropout", stateful_rng=True)
def _fused_act_dropout_op(ins, attrs, ctx):
    """out = dropout(act(X)) — the MLP mid-epilogue — fused so the
    activation does not cost its own HBM pass next to the pallas dropout;
    backward fuses act'(x) with the regenerated mask."""
    x = _x(ins)
    act = attrs.get("act", "gelu")
    p, is_test, upscale = _dropout_common(attrs, ctx)
    act_jnp = {"gelu": lambda v: jax.nn.gelu(v, approximate=False),
               "relu": jax.nn.relu}[act]
    if is_test or p <= 0.0:
        a = act_jnp(x)
        return {"Out": [a if upscale or p <= 0.0 else a * (1.0 - p)]}
    if p >= 1.0:
        return {"Out": [jnp.zeros_like(x)]}
    key = ctx.key_for(attrs.get("op_seed", attrs.get("seed", 0) or 0))
    site = ctx.kernel_site(x)
    if site is not None:
        from .pallas_kernels import (fused_act_dropout_tpu,
                                     fused_dropout_supported)
        if fused_dropout_supported(site.local(x)):
            return {"Out": [site.call(fused_act_dropout_tpu, [x], key=key,
                                      static=(p, upscale, act))]}
    a = act_jnp(x)
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    scale = 1.0 / (1.0 - p) if upscale else 1.0
    return {"Out": [jnp.where(keep, a * scale, 0.0).astype(x.dtype)]}


def _masked_batch_stats(xf, ctx, red_axes):
    """Batch-norm mean/variance over the VALID rows only (shape bucketing:
    executor pads the leading batch dim — zero-padded rows must not drag
    the statistics, or padded-step training diverges from the unpadded
    run).  Returns (mean, var) or None when masking does not apply."""
    from .reduction import masked_batch_reduce
    m = masked_batch_reduce(xf, ctx, red_axes, mean=True)
    if m is None:
        return None
    msq = masked_batch_reduce(jnp.square(xf), ctx, red_axes, mean=True)
    return m, msq - jnp.square(m)


@register_op("batch_norm",
             nondiff_inputs=("Mean", "Variance"),
             nondiff_outputs=("MeanOut", "VarianceOut", "SavedMean",
                              "SavedVariance"))
def _batch_norm(ins, attrs, ctx):
    x = _x(ins)
    scale, bias = _x(ins, "Scale"), _x(ins, "Bias")
    mean, var = _x(ins, "Mean"), _x(ins, "Variance")
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    fmt = attrs.get("data_layout", "NCHW")
    is_test = (attrs.get("is_test", False) or ctx.is_test
               or attrs.get("use_global_stats", False))
    ch_axis = 1 if fmt == "NCHW" else x.ndim - 1
    red_axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    shape = [1] * x.ndim
    shape[ch_axis] = x.shape[ch_axis]

    if is_test:
        m, v = mean, var
        mean_out, var_out = mean, var
    else:
        xf = x.astype(jnp.float32)
        stats = _masked_batch_stats(xf, ctx, red_axes)
        if stats is not None:
            m, v = stats
        else:
            m = jnp.mean(xf, axis=red_axes)
            v = jnp.var(xf, axis=red_axes)
        mean_out = momentum * mean + (1 - momentum) * m
        var_out = momentum * var + (1 - momentum) * v
    inv = lax.rsqrt(v.astype(jnp.float32) + eps)
    out = ((x.astype(jnp.float32) - m.reshape(shape)) * inv.reshape(shape)
           * scale.reshape(shape) + bias.reshape(shape)).astype(x.dtype)
    return {"Y": [out], "MeanOut": [mean_out], "VarianceOut": [var_out],
            "SavedMean": [m], "SavedVariance": [inv]}


@register_op("sync_batch_norm",
             nondiff_inputs=("Mean", "Variance"),
             nondiff_outputs=("MeanOut", "VarianceOut", "SavedMean",
                              "SavedVariance"))
def _sync_batch_norm(ins, attrs, ctx):
    """Cross-replica batch norm (operators/sync_batch_norm_op.cu).  Stats are
    psum-reduced over the data-parallel mesh axis when running under
    shard_map; falls back to local stats otherwise."""
    x = _x(ins)
    scale, bias = _x(ins, "Scale"), _x(ins, "Bias")
    mean, var = _x(ins, "Mean"), _x(ins, "Variance")
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False) or ctx.is_test
    ch_axis = 1
    red_axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    shape = [1] * x.ndim
    shape[ch_axis] = x.shape[ch_axis]
    axis_name = ctx.axis_for_ring(attrs.get("ring_id", 0)) or ctx.mesh_axes.get("dp")
    if is_test:
        m, v = mean, var
        mean_out, var_out = mean, var
    else:
        xf = x.astype(jnp.float32)
        stats = None if axis_name is not None else \
            _masked_batch_stats(xf, ctx, red_axes)
        if stats is not None:
            m, v = stats
        else:
            m = jnp.mean(xf, axis=red_axes)
            msq = jnp.mean(jnp.square(xf), axis=red_axes)
            if axis_name is not None:
                m = lax.pmean(m, axis_name)
                msq = lax.pmean(msq, axis_name)
            v = msq - jnp.square(m)
        mean_out = momentum * mean + (1 - momentum) * m
        var_out = momentum * var + (1 - momentum) * v
    inv = lax.rsqrt(v + eps)
    out = ((x.astype(jnp.float32) - m.reshape(shape)) * inv.reshape(shape)
           * scale.reshape(shape) + bias.reshape(shape)).astype(x.dtype)
    return {"Y": [out], "MeanOut": [mean_out], "VarianceOut": [var_out],
            "SavedMean": [m], "SavedVariance": [inv]}


@register_op("layer_norm", nondiff_outputs=("Mean", "Variance"))
def _layer_norm(ins, attrs, ctx):
    x = _x(ins)
    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=axes, keepdims=True)
    v = jnp.var(xf, axis=axes, keepdims=True)
    out = (xf - m) * lax.rsqrt(v + eps)
    norm_shape = x.shape[begin:]
    if ins.get("Scale"):
        out = out * ins["Scale"][0].reshape(norm_shape)
    if ins.get("Bias"):
        out = out + ins["Bias"][0].reshape(norm_shape)
    return {"Y": [out.astype(x.dtype)],
            "Mean": [m.reshape(x.shape[:begin])],
            "Variance": [v.reshape(x.shape[:begin])]}


@register_op("instance_norm", nondiff_outputs=("SavedMean", "SavedVariance"))
def _instance_norm(ins, attrs, ctx):
    x = _x(ins)
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    xf = x.astype(jnp.float32)     # f32 stats with bf16 I/O (AMP-gray norm)
    m = jnp.mean(xf, axis=axes, keepdims=True)
    v = jnp.var(xf, axis=axes, keepdims=True)
    out = (xf - m) * lax.rsqrt(v + eps)
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    if ins.get("Scale"):
        out = out * ins["Scale"][0].reshape(shape)
    if ins.get("Bias"):
        out = out + ins["Bias"][0].reshape(shape)
    return {"Y": [out.astype(x.dtype)], "SavedMean": [jnp.squeeze(m)],
            "SavedVariance": [jnp.squeeze(lax.rsqrt(v + eps))]}


@register_op("group_norm", nondiff_outputs=("Mean", "Variance"))
def _group_norm(ins, attrs, ctx):
    x = _x(ins)
    g = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[:2]
    xg = x.astype(jnp.float32).reshape((n, g, c // g) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))     # f32 stats with bf16 I/O (AMP-gray)
    m = jnp.mean(xg, axis=axes, keepdims=True)
    v = jnp.var(xg, axis=axes, keepdims=True)
    out = ((xg - m) * lax.rsqrt(v + eps)).reshape(x.shape)
    shape = (1, c) + (1,) * (x.ndim - 2)
    if ins.get("Scale"):
        out = out * ins["Scale"][0].reshape(shape)
    if ins.get("Bias"):
        out = out + ins["Bias"][0].reshape(shape)
    return {"Y": [out.astype(x.dtype)], "Mean": [m.reshape(n, g)],
            "Variance": [v.reshape(n, g)]}


# data_norm (CTR summary-stat normalization) lives in ctr_ops.py: the full
# semantics — persistable stat accumulation, slot show-gating, decay — are
# CTR machinery, not a norm-family variant.


@register_op("l2_normalize")
def _l2_normalize(ins, attrs, ctx):
    x = _x(ins)
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": [x / norm], "Norm": [norm]}


register_op("norm", lambda ins, a, c: _l2_normalize(ins, a, c))


@register_op("lrn")
def _lrn(ins, attrs, ctx):
    x = _x(ins)
    n = attrs.get("n", 5)
    k, alpha, beta = attrs.get("k", 2.0), attrs.get("alpha", 1e-4), attrs.get("beta", 0.75)
    sq = jnp.square(x)
    pad = n // 2
    sq_p = jnp.pad(sq, [(0, 0), (pad, pad), (0, 0), (0, 0)])
    acc = sum(sq_p[:, i:i + x.shape[1]] for i in range(n))
    mid = k + alpha * acc
    return {"Out": [x / jnp.power(mid, beta)], "MidOut": [mid]}


@register_op("maxout")
def _maxout(ins, attrs, ctx):
    x = _x(ins)
    g = attrs["groups"]
    n, c, h, w = x.shape
    return {"Out": [x.reshape(n, c // g, g, h, w).max(axis=2)]}


def _interp_ratio(i, o, align_corners):
    # interpolate_op.h:895-904
    if o <= 1:
        return 0.0
    return (i - 1) / (o - 1) if align_corners else i / o


def _interp_axis_idx(r, o, i, align_flag):
    """Per-axis (lo, hi, frac) source indices for linear interpolation —
    the BilinearInterpolation/TrilinearInterpolation index math."""
    k = jnp.arange(o, dtype=jnp.float32)
    src = r * (k + 0.5) - 0.5 if align_flag else r * k
    lo = jnp.maximum(jnp.floor(src).astype(jnp.int32), 0)
    hi = jnp.minimum(lo + 1, i - 1)
    frac = (jnp.maximum(src, 0.0) - lo) if align_flag else r * k - lo
    return lo, hi, frac


def _interp(ins, attrs, ctx, method):
    x = _x(ins)
    nhwc = attrs.get("data_layout", "NCHW") == "NHWC"
    if nhwc:
        n, h, w, c = x.shape
    else:
        n, c, h, w = x.shape
    oh = attrs.get("out_h", -1)
    ow = attrs.get("out_w", -1)
    if ins.get("OutSize"):
        sz = np.asarray(ins["OutSize"][0])
        oh, ow = int(sz[0]), int(sz[1])
    elif oh <= 0:
        scale = attrs.get("scale", 1.0)
        sh, sw = ((scale[0], scale[1])
                  if isinstance(scale, (list, tuple)) else (scale, scale))
        oh, ow = int(h * sh), int(w * sw)
    xt = x if nhwc else jnp.transpose(x, (0, 2, 3, 1))
    align_corners = attrs.get("align_corners", False)
    align_mode = attrs.get("align_mode", 1)

    def ratio(i, o):
        return _interp_ratio(i, o, align_corners)

    rh, rw = ratio(h, oh), ratio(w, ow)
    if method == "nearest":
        # interpolate_op.h:96-101: trunc(ratio*k + 0.5) with corners,
        # trunc(ratio*k) origin-aligned otherwise — NOT half-pixel
        off = 0.5 if align_corners else 0.0
        iy = jnp.clip((rh * jnp.arange(oh) + off).astype(jnp.int32),
                      0, h - 1)
        ix = jnp.clip((rw * jnp.arange(ow) + off).astype(jnp.int32),
                      0, w - 1)
        out = xt[:, iy][:, :, ix]
    elif method == "bilinear":
        # interpolate_op.h BilinearInterpolation: three alignment modes
        align_flag = (align_mode == 0 and not align_corners)
        y0, y1, fy = _interp_axis_idx(rh, oh, h, align_flag)
        x0, x1, fx = _interp_axis_idx(rw, ow, w, align_flag)
        fy = fy[None, :, None, None]
        fx = fx[None, None, :, None]
        g = lambda yy, xx: xt[:, yy][:, :, xx]
        out = ((1 - fy) * (1 - fx) * g(y0, x0)
               + (1 - fy) * fx * g(y0, x1)
               + fy * (1 - fx) * g(y1, x0)
               + fy * fx * g(y1, x1))
    elif method == "bicubic":
        # interpolate_op.h BicubicInterpolation: Keys kernel A=-0.75,
        # src = ratio*k (corners) or ratio*(k+0.5)-0.5; 4 taps per axis
        # clamped into range
        def cubic_weights(r, o):
            k = jnp.arange(o, dtype=jnp.float32)
            src = r * k if align_corners else r * (k + 0.5) - 0.5
            base = jnp.floor(src).astype(jnp.int32)
            t = src - base
            A = -0.75

            def cc1(v):
                return ((A + 2) * v - (A + 3)) * v * v + 1

            def cc2(v):
                return ((A * v - 5 * A) * v + 8 * A) * v - 4 * A
            w4 = jnp.stack([cc2(t + 1.0), cc1(t), cc1(1.0 - t),
                            cc2(2.0 - t)])            # [4, o]
            return base, w4

        by, wy = cubic_weights(rh, oh)
        bx, wx = cubic_weights(rw, ow)
        out = 0.0
        for i in range(4):
            yy = jnp.clip(by + (i - 1), 0, h - 1)
            row = 0.0
            for j in range(4):
                xx = jnp.clip(bx + (j - 1), 0, w - 1)
                row = row + wx[j][None, None, :, None] \
                    * xt[:, yy][:, :, xx]
            out = out + wy[i][None, :, None, None] * row
    else:
        # every registered 2D method has a reference-exact branch above;
        # a half-pixel jax.image fallback here would silently diverge
        raise ValueError(f"unsupported interpolation method {method!r}")
    out = out.astype(x.dtype)
    return {"Out": [out if nhwc else jnp.transpose(out, (0, 3, 1, 2))]}


def _trilinear_interp(ins, attrs, ctx):
    """interpolate_op.h TrilinearInterpolation: 5D NCDHW/NDHWC with the
    same three alignment modes as bilinear, over d/h/w."""
    x = _x(ins)
    ndhwc = attrs.get("data_layout", "NCDHW") == "NDHWC"
    if ndhwc:
        n, d, h, w, c = x.shape
    else:
        n, c, d, h, w = x.shape
    od = attrs.get("out_d", -1)
    oh = attrs.get("out_h", -1)
    ow = attrs.get("out_w", -1)
    if ins.get("OutSize"):
        sz = np.asarray(ins["OutSize"][0])
        od, oh, ow = int(sz[0]), int(sz[1]), int(sz[2])
    elif od <= 0:
        scale = attrs.get("scale", 1.0)
        sd, sh, sw = (tuple(scale[:3]) if isinstance(scale, (list, tuple))
                      else (scale, scale, scale))
        od, oh, ow = int(d * sd), int(h * sh), int(w * sw)
    align_corners = attrs.get("align_corners", False)
    align_mode = attrs.get("align_mode", 1)
    align_flag = (align_mode == 0 and not align_corners)

    xt = x if ndhwc else jnp.transpose(x, (0, 2, 3, 4, 1))  # N D H W C
    d0, d1, fd = _interp_axis_idx(_interp_ratio(d, od, align_corners),
                                  od, d, align_flag)
    y0, y1, fy = _interp_axis_idx(_interp_ratio(h, oh, align_corners),
                                  oh, h, align_flag)
    x0, x1, fx = _interp_axis_idx(_interp_ratio(w, ow, align_corners),
                                  ow, w, align_flag)
    fd = fd[None, :, None, None, None]
    fy = fy[None, None, :, None, None]
    fx = fx[None, None, None, :, None]
    g = lambda dd, yy, xx: xt[:, dd][:, :, yy][:, :, :, xx]
    out = 0.0
    for wd, dd in ((1 - fd, d0), (fd, d1)):
        for wh, yy in ((1 - fy, y0), (fy, y1)):
            for ww, xx in ((1 - fx, x0), (fx, x1)):
                out = out + wd * wh * ww * g(dd, yy, xx)
    out = out.astype(x.dtype)
    return {"Out": [out if ndhwc else jnp.transpose(out,
                                                    (0, 4, 1, 2, 3))]}


register_op("nearest_interp", lambda ins, a, c: _interp(ins, a, c, "nearest"),
            nondiff_inputs=("OutSize",))
register_op("bilinear_interp", lambda ins, a, c: _interp(ins, a, c, "bilinear"),
            nondiff_inputs=("OutSize",))
register_op("bicubic_interp", lambda ins, a, c: _interp(ins, a, c, "bicubic"),
            nondiff_inputs=("OutSize",))
register_op("trilinear_interp", _trilinear_interp,
            nondiff_inputs=("OutSize",))


@register_op("grid_sampler")
def _grid_sampler(ins, attrs, ctx):
    x, grid = _x(ins), _x(ins, "Grid")
    n, c, h, w = x.shape
    gx = (grid[..., 0] + 1) * (w - 1) / 2
    gy = (grid[..., 1] + 1) * (h - 1) / 2
    x0 = jnp.floor(gx).astype(jnp.int32)
    y0 = jnp.floor(gy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1
    wx, wy = gx - x0, gy - y0
    def sample(yy, xx):
        yy = jnp.clip(yy, 0, h - 1)
        xx = jnp.clip(xx, 0, w - 1)
        return jax.vmap(lambda img, Y, X: img[:, Y, X])(x, yy, xx)
    v00, v01 = sample(y0, x0), sample(y0, x1)
    v10, v11 = sample(y1, x0), sample(y1, x1)
    wx = wx[:, None]
    wy = wy[:, None]
    out = (v00 * (1 - wx) * (1 - wy) + v01 * wx * (1 - wy)
           + v10 * (1 - wx) * wy + v11 * wx * wy)
    return {"Output": [out]}


@register_op("affine_channel")
def _affine_channel(ins, attrs, ctx):
    x, s, b = _x(ins), _x(ins, "Scale"), _x(ins, "Bias")
    shape = [1, x.shape[1]] + [1] * (x.ndim - 2)
    return {"Out": [x * s.reshape(shape) + b.reshape(shape)]}


@register_op("temporal_shift")
def _temporal_shift(ins, attrs, ctx):
    x = _x(ins)
    t = attrs["seg_num"]
    ratio = attrs.get("shift_ratio", 0.25)
    nt, c, h, w = x.shape
    n = nt // t
    x = x.reshape(n, t, c, h, w)
    c1 = int(c * ratio)
    fwd = jnp.pad(x[:, 1:, :c1], [(0, 0), (0, 1), (0, 0), (0, 0), (0, 0)])
    bwd = jnp.pad(x[:, :-1, c1:2 * c1], [(0, 0), (1, 0), (0, 0), (0, 0), (0, 0)])
    out = jnp.concatenate([fwd, bwd, x[:, :, 2 * c1:]], axis=2)
    return {"Out": [out.reshape(nt, c, h, w)]}
