"""fluid.layers op-builder API (reference: python/paddle/fluid/layers/nn.py,
216 public defs).  Layer functions append ops to the default main program (or
execute eagerly under a dygraph tracer) — same call surface, zero CUDA.
Auto-generated wrappers cover the unary/elementwise/reduce families the way
the reference's layer_function_generator.py builds them from OpProtos.
"""
from __future__ import annotations

import sys

import numpy as np

from ..framework import Variable, in_dygraph_mode, unique_name
from ..layer_helper import LayerHelper
from ..initializer import ConstantInitializer, XavierInitializer
from .tensor import _to_variable

_this = sys.modules[__name__]


def _single_out(op_type, x, attrs=None, dtype=None, out_slot="Out",
                in_slot="X", stop_gradient=False, name=None):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(
        dtype=dtype or getattr(x, "dtype", "float32"),
        stop_gradient=stop_gradient)
    op = helper.append_op(op_type, inputs={in_slot: [x]},
                          outputs={out_slot: [out]}, attrs=attrs or {})
    return op[out_slot][0] if in_dygraph_mode() else out


# ---- generated unary layers ------------------------------------------------
_UNARY = [
    "relu", "relu6", "sigmoid", "logsigmoid", "tanh", "tanh_shrink", "gelu",
    "erf", "exp", "log", "log2", "log10", "log1p", "sqrt", "rsqrt", "square",
    "abs", "ceil", "floor", "round", "reciprocal", "sign", "sin", "cos",
    "tan", "asin", "acos", "atan", "sinh", "cosh", "softplus", "softsign",
    "softshrink", "hard_shrink", "hard_sigmoid", "hard_swish", "swish",
    "mish", "selu", "elu", "leaky_relu", "brelu", "thresholded_relu",
    "stanh", "silu", "logsumexp",
]
for _name in _UNARY:
    def _mk(op_type):
        def f(x, name=None, **attrs):
            attrs.pop("inplace", None)
            return _single_out(op_type, x, attrs, name=name)
        f.__name__ = op_type
        return f
    setattr(_this, _name, _mk(_name))


def elementwise_op(op_type, x, y, axis=-1, act=None, name=None):
    helper = LayerHelper(op_type, name=name)
    y = _to_variable(None, y, getattr(x, "dtype", None)) \
        if not isinstance(y, Variable) and not in_dygraph_mode() else y
    out = helper.create_variable_for_type_inference(
        dtype=getattr(x, "dtype", "float32"))
    op = helper.append_op(op_type, inputs={"X": [x], "Y": [y]},
                          outputs={"Out": [out]}, attrs={"axis": axis})
    out = op["Out"][0] if in_dygraph_mode() else out
    return helper.append_activation(out, act)


for _name in ["elementwise_add", "elementwise_sub", "elementwise_mul",
              "elementwise_div", "elementwise_min", "elementwise_max",
              "elementwise_pow", "elementwise_mod", "elementwise_floordiv"]:
    def _mk2(op_type):
        def f(x, y, axis=-1, act=None, name=None):
            return elementwise_op(op_type, x, y, axis, act, name)
        f.__name__ = op_type
        return f
    setattr(_this, _name, _mk2(_name))


def _reduce_layer(op_type, x, dim=None, keep_dim=False, name=None):
    helper = LayerHelper(op_type, name=name)
    if dim is None:
        dim, reduce_all = [0], True
    else:
        dim = [dim] if isinstance(dim, int) else list(dim)
        reduce_all = False
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    op = helper.append_op(op_type, inputs={"X": [x]}, outputs={"Out": [out]},
                          attrs={"dim": dim, "keep_dim": keep_dim,
                                 "reduce_all": reduce_all})
    return op["Out"][0] if in_dygraph_mode() else out


for _name in ["reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
              "reduce_prod", "reduce_all", "reduce_any"]:
    def _mkr(op_type):
        def f(x, dim=None, keep_dim=False, name=None):
            return _reduce_layer(op_type, x, dim, keep_dim, name)
        f.__name__ = op_type
        return f
    setattr(_this, _name, _mkr(_name))


def mean(x, name=None):
    return _single_out("mean", x)


# ---- dense layers ----------------------------------------------------------
def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, name=None):
    """Fully-connected (layers/nn.py fc).  input [d0..dk, in] -> [d0..dk, size]
    via mul op (reference mul_op.cc flatten semantics)."""
    helper = LayerHelper("fc", name=name)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    outs = []
    for inp in inputs:
        in_dim = int(np.prod(inp.shape[num_flatten_dims:])) \
            if not in_dygraph_mode() else int(np.prod(
                inp.shape[num_flatten_dims:]))
        w = helper.create_parameter(param_attr, [in_dim, size], inp.dtype)
        tmp = helper.create_variable_for_type_inference(dtype=inp.dtype)
        op = helper.append_op("mul", inputs={"X": [inp], "Y": [w]},
                              outputs={"Out": [tmp]},
                              attrs={"x_num_col_dims": num_flatten_dims,
                                     "y_num_col_dims": 1})
        outs.append(op["Out"][0] if in_dygraph_mode() else tmp)
    if len(outs) > 1:
        from .tensor import sums
        pre_bias = sums(outs)
    else:
        pre_bias = outs[0]
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [size], pre_bias.dtype,
                                    is_bias=True)
        pre_act = helper.append_bias_op(pre_bias, b, axis=num_flatten_dims)
    else:
        pre_act = pre_bias
    return helper.append_activation(pre_act, act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32"):
    """layers/nn.py embedding -> lookup_table_v2.  is_sparse maps to the
    dense vjp-scatter grad (SelectedRows has no XLA analog, SURVEY §7 #3)."""
    helper = LayerHelper("embedding")
    w = helper.create_parameter(param_attr, list(size), dtype)
    out = helper.create_variable_for_type_inference(dtype=dtype)
    padding_idx = -1 if padding_idx is None else (
        padding_idx if padding_idx >= 0 else size[0] + padding_idx)
    op = helper.append_op("lookup_table_v2",
                          inputs={"W": [w], "Ids": [input]},
                          outputs={"Out": [out]},
                          attrs={"padding_idx": padding_idx,
                                 "is_sparse": is_sparse})
    return op["Out"][0] if in_dygraph_mode() else out


def data_norm(input, act=None, epsilon=1e-4, param_attr=None,
              data_layout="NCHW", in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=True, slot_dim=-1,
              sync_stats=False, summary_decay_rate=0.9999999,
              enable_scale_and_shift=False):
    """CTR feature normalization with persistable summary statistics
    (layers/nn.py:3281 data_norm -> operators/data_norm_op.cc).  The three
    summary params (batch_size init 1e4, batch_sum 0, batch_square_sum
    1e4) are training state, not weights: the op itself emits their
    decayed running update (see ops/ctr_ops.py data_norm).  The stats fed
    to the op go through an `assign` snapshot so the backward reads
    forward-time values even though the update writes the real vars."""
    from ..initializer import ConstantInitializer
    from ..param_attr import ParamAttr

    helper = LayerHelper("data_norm", name=name)
    c = int(input.shape[-1])
    cfg = param_attr if isinstance(param_attr, dict) else {}
    base = name or unique_name("data_norm")
    stats = {}
    for suffix, default in (("batch_size", cfg.get("batch_size", 1e4)),
                            ("batch_sum", cfg.get("batch_sum", 0.0)),
                            ("batch_square_sum",
                             cfg.get("batch_square", 1e4))):
        p = helper.create_parameter(
            ParamAttr(name=f"{base}.{suffix}",
                      initializer=ConstantInitializer(float(default))),
            [c], input.dtype)
        p.stop_gradient = True
        p.trainable = False
        snap = helper.create_variable_for_type_inference(dtype=input.dtype)
        helper.append_op("assign", inputs={"X": [p]},
                         outputs={"Out": [snap]})
        stats[suffix] = (p, snap)
    inputs = {"X": [input], "BatchSize": [stats["batch_size"][1]],
              "BatchSum": [stats["batch_sum"][1]],
              "BatchSquareSum": [stats["batch_square_sum"][1]]}
    if enable_scale_and_shift:
        sw = helper.create_parameter(
            ParamAttr(name=f"{base}.scale_w",
                      initializer=ConstantInitializer(
                          float(cfg.get("scale_w", 1.0)))), [c], input.dtype)
        b = helper.create_parameter(
            ParamAttr(name=f"{base}.bias",
                      initializer=ConstantInitializer(
                          float(cfg.get("bias", 0.0)))), [c], input.dtype)
        inputs["ScaleW"], inputs["Bias"] = [sw], [b]
    y = helper.create_variable_for_type_inference(dtype=input.dtype)
    means = helper.create_variable_for_type_inference(dtype=input.dtype)
    scales = helper.create_variable_for_type_inference(dtype=input.dtype)
    op = helper.append_op(
        "data_norm", inputs=inputs,
        outputs={"Y": [y], "Means": [means], "Scales": [scales],
                 "BatchSizeOut": [stats["batch_size"][0]],
                 "BatchSumOut": [stats["batch_sum"][0]],
                 "BatchSquareSumOut": [stats["batch_square_sum"][0]]},
        attrs={"epsilon": epsilon, "slot_dim": slot_dim,
               "summary_decay_rate": summary_decay_rate,
               "enable_scale_and_shift": enable_scale_and_shift})
    out = op["Y"][0] if in_dygraph_mode() else y
    return helper.append_activation(out, act)


def pull_box_sparse(input, size, table_name="default_box", dtype="float32"):
    """layers.pull_box_sparse (pull_box_sparse_op.cc) — embedding lookups
    served by the BoxPS tier (distributed/ps/box.py): the host table can
    exceed HBM; the op gathers from the per-pass HBM cache PARAMETER, whose
    rows begin_pass stages and end_pass writes back.  The ids the program
    sees are cache slots — the trainer's box plan translates raw feasign
    ids per batch (BoxWrapper::PullSparse:141 analog, with the GPU replica
    cache redesigned as a normal donated XLA buffer trained by the regular
    optimizer ops)."""
    from ..framework import default_main_program

    helper = LayerHelper("pull_box_sparse")
    inputs = list(input) if isinstance(input, (list, tuple)) else [input]
    main = default_main_program()
    gb = main.global_block()
    cache_name = f"{table_name}@HBMCACHE"
    # validate BEFORE mutating the program: a half-appended op on error
    # would leave the graph corrupted
    prior = main._hints.get("box_plan")
    if prior is not None:
        if prior["table"] != table_name:
            raise ValueError(
                "one box table per program (reference BoxWrapper is a "
                f"singleton); got a second table '{table_name}' vs "
                f"'{prior['table']}'")
        if prior["dim"] != int(size):
            raise ValueError(
                f"box table '{table_name}' used with size {size} but was "
                f"first declared with size {prior['dim']}")
    if gb.has_var(cache_name):
        w = gb.var(cache_name)
    else:
        # no startup init op on purpose: begin_pass seeds the scope value
        w = gb.create_parameter(name=cache_name, shape=(-1, int(size)),
                                dtype=dtype)
    outs = [helper.create_variable_for_type_inference(dtype=dtype)
            for _ in inputs]
    helper.append_op("pull_box_sparse",
                     inputs={"W": [w], "Ids": inputs},
                     outputs={"Out": outs},
                     attrs={"size": int(size)})
    plan = main._hints.setdefault(
        "box_plan", {"table": table_name, "cache": cache_name,
                     "dim": int(size), "ids": []})
    for v in inputs:
        n = v.name if hasattr(v, "name") else str(v)
        if n not in plan["ids"]:
            plan["ids"].append(n)
    if isinstance(input, (list, tuple)):
        return outs
    return outs[0]


def cos_sim(X, Y, name=None):
    """Cosine similarity along the last axis (cos_sim_op.cc)."""
    helper = LayerHelper("cos_sim", name=name)
    out = helper.create_variable_for_type_inference(dtype=X.dtype)
    xn = helper.create_variable_for_type_inference(dtype=X.dtype)
    yn = helper.create_variable_for_type_inference(dtype=X.dtype)
    op = helper.append_op("cos_sim", inputs={"X": [X], "Y": [Y]},
                          outputs={"Out": [out], "XNorm": [xn],
                                   "YNorm": [yn]})
    return op["Out"][0] if in_dygraph_mode() else out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    op = helper.append_op("matmul", inputs={"X": [x], "Y": [y]},
                          outputs={"Out": [out]},
                          attrs={"transpose_X": transpose_x,
                                 "transpose_Y": transpose_y,
                                 "alpha": float(alpha)})
    return op["Out"][0] if in_dygraph_mode() else out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1):
    helper = LayerHelper("mul")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    op = helper.append_op("mul", inputs={"X": [x], "Y": [y]},
                          outputs={"Out": [out]},
                          attrs={"x_num_col_dims": x_num_col_dims,
                                 "y_num_col_dims": y_num_col_dims})
    return op["Out"][0] if in_dygraph_mode() else out


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None, data_format="NCHW"):
    helper = LayerHelper("conv2d", name=name)
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    stride = [stride, stride] if isinstance(stride, int) else list(stride)
    dilation = [dilation, dilation] if isinstance(dilation, int) else list(dilation)
    padding_algorithm = "EXPLICIT"
    if isinstance(padding, str):
        padding_algorithm = padding.upper()
        padding = [0, 0]
    elif isinstance(padding, int):
        padding = [padding, padding]
    num_channels = input.shape[1 if data_format == "NCHW" else -1]
    w_shape = [num_filters, num_channels // groups] + filter_size
    import math
    fan_in = (num_channels // groups) * filter_size[0] * filter_size[1]
    std = math.sqrt(2.0 / fan_in)
    from ..initializer import NormalInitializer
    w = helper.create_parameter(param_attr, w_shape, input.dtype,
                                default_initializer=NormalInitializer(0., std))
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    op = helper.append_op(
        "conv2d", inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": stride, "paddings": list(padding),
               "dilations": dilation, "groups": groups,
               "padding_algorithm": padding_algorithm,
               "data_format": data_format})
    out = op["Output"][0] if in_dygraph_mode() else out
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [num_filters], input.dtype,
                                    is_bias=True)
        out = helper.append_bias_op(out, b, axis=1)
    return helper.append_activation(out, act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     stride=1, padding=0, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    helper = LayerHelper("conv2d_transpose", name=name)
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    stride = [stride, stride] if isinstance(stride, int) else list(stride)
    padding = [padding, padding] if isinstance(padding, int) else list(padding)
    dilation = [dilation, dilation] if isinstance(dilation, int) else list(dilation)
    num_channels = input.shape[1]
    w = helper.create_parameter(
        param_attr, [num_channels, num_filters // groups] + filter_size,
        input.dtype)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    op = helper.append_op(
        "conv2d_transpose", inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [out]},
        attrs={"strides": stride, "paddings": padding, "dilations": dilation,
               "groups": groups})
    out = op["Output"][0] if in_dygraph_mode() else out
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [num_filters], input.dtype,
                                    is_bias=True)
        out = helper.append_bias_op(out, b, axis=1)
    return helper.append_activation(out, act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, exclusive=True, name=None, data_format="NCHW"):
    helper = LayerHelper("pool2d", name=name)
    pool_size = [pool_size, pool_size] if isinstance(pool_size, int) else list(pool_size)
    pool_stride = [pool_stride, pool_stride] if isinstance(pool_stride, int) else list(pool_stride)
    pool_padding = [pool_padding, pool_padding] if isinstance(pool_padding, int) else list(pool_padding)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    op = helper.append_op(
        "pool2d", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"ksize": pool_size, "pooling_type": pool_type,
               "strides": pool_stride, "paddings": pool_padding,
               "global_pooling": global_pooling, "exclusive": exclusive,
               "ceil_mode": ceil_mode, "data_format": data_format})
    return op["Out"][0] if in_dygraph_mode() else out


def adaptive_pool2d(input, pool_size, pool_type="max", name=None,
                    data_format="NCHW"):
    helper = LayerHelper("adaptive_pool2d", name=name)
    pool_size = [pool_size, pool_size] if isinstance(pool_size, int) else list(pool_size)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    op = helper.append_op("adaptive_pool2d", inputs={"X": [input]},
                          outputs={"Out": [out]},
                          attrs={"ksize": pool_size,
                                 "pooling_type": pool_type,
                                 "data_format": data_format})
    return op["Out"][0] if in_dygraph_mode() else out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=True,
               use_global_stats=False):
    helper = LayerHelper("batch_norm", name=name)
    c = input.shape[1 if data_layout == "NCHW" else -1]
    scale = helper.create_parameter(param_attr, [c], input.dtype,
                                    default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(bias_attr, [c], input.dtype, is_bias=True)
    # moving stats: persistable, non-trainable; updated in place by the op
    from ..param_attr import ParamAttr
    mean = helper.create_parameter(
        ParamAttr(name=moving_mean_name, trainable=False,
                  initializer=ConstantInitializer(0.0)), [c], input.dtype)
    var = helper.create_parameter(
        ParamAttr(name=moving_variance_name, trainable=False,
                  initializer=ConstantInitializer(1.0)), [c], input.dtype)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    saved_m = helper.create_variable_for_type_inference(dtype="float32",
                                                        stop_gradient=True)
    saved_v = helper.create_variable_for_type_inference(dtype="float32",
                                                        stop_gradient=True)
    op = helper.append_op(
        "batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [var]},
        outputs={"Y": [out], "MeanOut": [mean], "VarianceOut": [var],
                 "SavedMean": [saved_m], "SavedVariance": [saved_v]},
        attrs={"momentum": momentum, "epsilon": epsilon,
               "is_test": is_test, "data_layout": data_layout,
               "use_global_stats": use_global_stats})
    out = op["Y"][0] if in_dygraph_mode() else out
    return helper.append_activation(out, act)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    helper = LayerHelper("layer_norm", name=name)
    norm_shape = [int(np.prod(input.shape[begin_norm_axis:]))]
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(param_attr, norm_shape, input.dtype,
                                    default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(bias_attr, norm_shape, input.dtype,
                                    is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    m = helper.create_variable_for_type_inference(dtype="float32",
                                                  stop_gradient=True)
    v = helper.create_variable_for_type_inference(dtype="float32",
                                                  stop_gradient=True)
    op = helper.append_op("layer_norm", inputs=inputs,
                          outputs={"Y": [out], "Mean": [m], "Variance": [v]},
                          attrs={"epsilon": epsilon,
                                 "begin_norm_axis": begin_norm_axis})
    out = op["Y"][0] if in_dygraph_mode() else out
    return helper.append_activation(out, act)


def rms_norm(input, epsilon=1e-6, param_attr=None, name=None):
    """``input / sqrt(mean(input^2, last axis) + epsilon) * scale``."""
    helper = LayerHelper("rms_norm", name=name)
    scale = helper.create_parameter(
        param_attr, [int(input.shape[-1])], "float32",
        default_initializer=ConstantInitializer(1.0))
    return _append_single(helper, "rms_norm",
                          {"X": [input], "Scale": [scale]}, input.dtype,
                          {"epsilon": epsilon}, out_slot="Y")


def rotary_embedding(input, inv_freq, scale=1.0, name=None, positions=None,
                     sections=None):
    """Rotary position embedding of ``input`` [..., S, D] (rotate-half
    convention) under the D/2 frequencies ``inv_freq``, cos and sin times
    ``scale``.  ``positions`` [R, S] replaces 0..S-1: frequency ``i`` turns
    by the row that ``sections`` (how many consecutive frequencies each row
    takes; they add up to D/2) gives it."""
    helper = LayerHelper("rotary_embedding", name=name)
    inputs = {"X": [input]}
    attrs = {"inv_freq": [float(f) for f in inv_freq], "scale": float(scale)}
    if positions is not None:
        inputs["Positions"] = [positions]
        attrs["sections"] = [int(n) for n in sections or [len(inv_freq)]]
    return _append_single(helper, "rotary_embedding", inputs, input.dtype,
                          attrs)


def linear_cross_entropy(input, label, size, param_attr=None, name=None,
                         tied_to=None):
    """A decoder's head and its loss as one op: ``-log softmax(input W)
    [label]`` of every token, ``input`` [..., H], ``label`` [..., 1] ->
    [..., 1] float32, with ``W`` [H, ``size``] this layer's parameter.
    What ``fc`` + ``softmax_with_cross_entropy`` compute, in blocks of
    tokens: the [tokens, size] logits, their softmax and their gradient never
    exist whole (at 16384 tokens and 18992 classes they are 2.3 GiB of the
    step's fullest moment), at the price of the head's matmul once more in
    backward.

    ``tied_to`` (an embedding's [``size``, H] parameter; no ``param_attr``
    then): the head has no matrix of its own and reads the embedding's rows,
    ``logits = input E^T``; ``append_backward`` adds the head's gradient to
    the lookup's."""
    helper = LayerHelper("linear_cross_entropy", name=name)
    if tied_to is not None:
        if param_attr is not None or list(tied_to.shape) != [
                size, int(input.shape[-1])]:
            raise ValueError(
                f"linear_cross_entropy: tied_to is the embedding's [size, H] "
                f"parameter and takes param_attr's place; got "
                f"{list(tied_to.shape)} for size {size}, H "
                f"{int(input.shape[-1])}")
    w = tied_to if tied_to is not None else helper.create_parameter(
        param_attr, [int(input.shape[-1]), size], "float32")
    return _append_single(helper, "linear_cross_entropy",
                          {"X": [input], "W": [w], "Label": [label]},
                          "float32",
                          {"transpose_w": True} if tied_to is not None
                          else None, out_slot="Loss")


def selective_scan(x, dt, A, B, C, D, gauges=None, name=None):
    """The selective scan of a state-space layer (ops/selective_scan.py,
    docs/state_space.md): ``x``, ``dt`` [B, S, Di] (the convolved input and
    the step size after its softplus), ``A`` [Di, N] (negative), ``B``,
    ``C`` [B, S, N], ``D`` [Di] -> ``y`` [B, S, Di] with ``s_t = exp(dt_t
    (x) A) * s_{t-1} + (dt_t * x_t) (x) B_t`` from no state and ``y_t = s_t
    C_t + D * x_t``; the state [Di, N] is float32 and exists a chunk at a
    time.  With ``gauges`` (a name such as ``layer_0``) the largest |state|
    at a chunk's end and the mean step size stay on the device in
    ``<gauges>.state_abs_max`` and ``.dt_mean`` and are published as
    ``ssm.<gauges>.…`` when an ``AsyncStepRunner`` drains."""
    helper = LayerHelper("selective_scan", name=name)
    y = helper.create_variable_for_type_inference(dtype=x.dtype)
    outputs = {"Y": [y]}
    if gauges:
        outputs.update(_device_gauges(
            helper, gauges, {"StateAbsMax": "state_abs_max",
                             "DtMean": "dt_mean"}, family="ssm"))
    helper.append_op("selective_scan",
                     inputs={"X": [x], "Dt": [dt], "A": [A], "B": [B],
                             "C": [C], "D": [D]}, outputs=outputs)
    return y


def causal_conv1d(input, kernel_size, param_attr=None, bias_attr=None,
                  name=None):
    """Depthwise causal convolution over time of ``input`` [B, S, C]: token
    t sees itself and the ``kernel_size`` - 1 tokens before it (zeros before
    the first), each channel under its own ``kernel_size`` weights (the
    parameter is [``kernel_size``, C], the tap on the current token last)
    and bias."""
    helper = LayerHelper("causal_conv1d", name=name)
    channels = int(input.shape[-1])
    inputs = {"X": [input],
              "W": [helper.create_parameter(
                  param_attr, [int(kernel_size), channels], "float32")]}
    if bias_attr is not False:
        inputs["Bias"] = [helper.create_parameter(
            bias_attr, [channels], "float32", is_bias=True)]
    return _append_single(helper, "causal_conv1d", inputs, input.dtype)


def sparse_attention_index(qi, ki, w, topk, gauges=None, name=None):
    """The indexer of attention over a learned key set (ops/
    sparse_attention.py): index queries ``qi`` [B, HI, S, DI], one index key
    a token ``ki`` [B, S, DI], per-token head weights ``w`` [B, S, HI] ->
    the selection [B, S, S / 8] uint8 (a bit a pair) of the ``min(t + 1,
    topk)`` best keys ``s <= t`` of every query by ``sum_j w[t, j] relu(qi[t, j] . ki[s])``,
    exact, ties to the smaller ``s``; no gradient passes.  With ``gauges``
    (a name such as ``layer_3``) the mean number of selected keys and the
    share of causal 512 x 512 tiles that hold a selected pair stay on the
    device in ``<gauges>.selected_keys_mean`` and ``.tile_occupancy`` and
    are published as ``dsa.<gauges>.…`` when an ``AsyncStepRunner``
    drains."""
    helper = LayerHelper("sparse_attention_index", name=name)
    sel = helper.create_variable_for_type_inference(dtype="uint8",
                                                    stop_gradient=True)
    outputs = {"Selection": [sel]}
    if gauges:
        outputs.update(_device_gauges(helper, gauges, {
            "SelectedKeysMean": "selected_keys_mean",
            "TileOccupancy": "tile_occupancy"}))
    helper.append_op("sparse_attention_index",
                     inputs={"QI": [qi], "KI": [ki], "W": [w]},
                     outputs=outputs, attrs={"topk": int(topk)})
    return sel


def sparse_attention_index_loss(qi, ki, w, q, k, lse, selection, scale,
                                weight=1.0, gauges=None, name=None):
    """What trains the indexer: ``weight`` x the mean over queries of ``KL(p
    || softmax over the selected keys of the index scores)``, ``p`` the
    selected attention's probabilities (of ``q`` [B, Hq, S, D] and ``k``
    [B, Hkv, S, D] under ``scale``, as the attention op sees them, and its
    log-sum-exp ``lse``: ``fused_multihead_attention(return_lse=True)``)
    averaged over the heads and held constant: [1] float32, whose gradient
    reaches ``qi``, ``ki`` and ``w`` and nothing else.  With ``gauges`` the
    unweighted loss is published as ``dsa.<gauges>.index_kl``."""
    helper = LayerHelper("sparse_attention_index_loss", name=name)
    loss = helper.create_variable_for_type_inference(dtype="float32")
    outputs = {"Loss": [loss]}
    if gauges:
        outputs.update(_device_gauges(helper, gauges,
                                      {"IndexKL": "index_kl"}))
    helper.append_op(
        "sparse_attention_index_loss",
        inputs={"QI": [qi], "KI": [ki], "W": [w], "Q": [q], "K": [k],
                "LSE": [lse], "Selection": [selection]},
        outputs=outputs, attrs={"scale": float(scale),
                                "weight": float(weight)})
    return loss


def _device_gauges(helper, prefix, slots, family="dsa"):
    """{slot: [a persistable float32 [1] variable ``<prefix>.<suffix>``]},
    each entered among the program's device counters as ``<family>.
    <prefix>.<suffix>`` (``AsyncStepRunner`` publishes them when it
    drains)."""
    from ..framework import default_main_program
    counters = default_main_program()._hints.setdefault("device_counters",
                                                        {})
    out = {}
    for slot, suffix in slots.items():
        var = helper.block().create_var(
            name=f"{prefix}.{suffix}", shape=[1], dtype="float32",
            persistable=True, stop_gradient=True)
        counters[var.name] = f"{family}.{var.name}"
        out[slot] = [var]
    return out


def fused_multihead_attention(q, k, v, scale=None, causal=False, window=0,
                              name=None, selection=None, return_lse=False):
    """softmax(q k^T * scale) v over [B, heads, S, D] operands; ``scale``
    defaults to D ** -0.5.  ``v`` may be [B, heads, S, Dv] with another
    width than the scores' D (the output then has Dv).
    ``k`` and ``v`` may have fewer heads than ``q`` (each shared by a group
    of query heads); ``causal`` attends j <= i, ``window`` > 0 only
    0 <= i - j < window; ``selection`` [B, S, S / 8] uint8
    (``sparse_attention_index``'s; with ``causal``) only the keys it
    marks.  ``return_lse`` (with a ``selection``): also the log-sum-exp of
    every query's scores over its keys, [B, heads, S] float32, which no
    gradient passes through."""
    helper = LayerHelper("fused_multihead_attention", name=name)
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if selection is not None:
        inputs["Selection"] = [selection]
    attrs = {"causal": bool(causal), "window": int(window),
             "num_kv_heads": int(k.shape[1])}
    if scale is not None:
        attrs["scale"] = float(scale)
    if return_lse:
        if selection is None:
            raise ValueError("return_lse: only a call with a selection "
                             "hands its log-sum-exp over")
        out = helper.create_variable_for_type_inference(dtype=q.dtype)
        lse = helper.create_variable_for_type_inference(
            dtype="float32", stop_gradient=True)
        helper.append_op("fused_multihead_attention", inputs=inputs,
                         outputs={"Out": [out], "LSE": [lse]}, attrs=attrs)
        return out, lse
    return _append_single(helper, "fused_multihead_attention", inputs,
                          q.dtype, attrs)


def expert_layer(input, num_experts, top_k, expert_size, first_expert=0,
                 num_held=None, router_attr=None, gate_attr=None,
                 up_attr=None, down_attr=None, name=None, scoring="softmax",
                 routed_scaling_factor=1.0, correction_bias_attr=None,
                 shared_size=0, shared_gate_attr=None, shared_up_attr=None,
                 shared_down_attr=None, max_held_rows=None):
    """Sparse experts over tokens ``input`` [T, D] without dropping: the
    router scores all ``num_experts``, each token goes to its ``top_k`` best,
    and this program holds the ``num_held`` experts from ``first_expert`` on
    (default: all) and adds their weighted gated FFNs (width ``expert_size``)
    of the tokens routed to them (parallel/moe.py, docs/moe.md).  Four kinds
    of op and the ``swiglu`` gate between the grouped matmuls.  The routing's counts accumulate
    on the device in ``<name>.tokens_per_expert`` [num_held] and
    ``<name>.steps`` [1], published as gauges of ``trace.metrics()`` under
    ``moe.<name>.…`` when an ``AsyncStepRunner`` drains, and with them
    ``moe.<name>.rows_visited_share``: the share of the buffers' rows that
    held an assignment, which is all the permutation's gathers visit.

    ``scoring`` ``"softmax"``: top-k of the logits, softmax over the chosen.
    ``"sigmoid"``: top-k of ``sigmoid(logits) + correction bias`` (a
    parameter [num_experts] that no gradient reaches, zero unless
    ``correction_bias_attr`` says otherwise), weights the chosen sigmoids
    over their sum.  Both times ``routed_scaling_factor``.  ``shared_size``
    > 0 adds a shared expert of that width, a gated FFN every token takes
    at weight 1.  ``max_held_rows`` sizes the buffers of held assignments
    (default: the worst case, T * ``top_k``); a step that routes more to
    the held experts fails (NaN) and drops nothing."""
    from .tensor import create_global_var
    from ..framework import default_main_program
    name = name or unique_name("expert_layer")
    helper = LayerHelper("expert_layer", name=name)
    num_held = num_experts if num_held is None else num_held
    d = int(input.shape[-1])
    router = helper.create_parameter(router_attr, [d, num_experts], "float32")
    w_gate = helper.create_parameter(gate_attr, [num_held, d, expert_size],
                                     "float32")
    w_up = helper.create_parameter(up_attr, [num_held, d, expert_size],
                                   "float32")
    w_down = helper.create_parameter(down_attr, [num_held, expert_size, d],
                                     "float32")
    counts = create_global_var([num_held], 0, "int32", persistable=True,
                               name=name + ".tokens_per_expert")
    steps = create_global_var([1], 0, "int32", persistable=True,
                              name=name + ".steps")
    counters = default_main_program()._hints.setdefault("device_counters",
                                                        {})
    counters.update({counts.name: "moe." + counts.name,
                     steps.name: "moe." + steps.name})
    # how far the permutation engages: of the rows its buffers hold, the
    # share the gathers visit, sum(tokens_per_expert) / (steps x rows); the
    # rows are the trace's (``moe_route`` sets the gauge as it is lowered)
    counters[name + ".rows_visited_share"] = (
        "moe." + name + ".rows_visited_share", counts.name, steps.name,
        "moe." + steps.name + ".buffer_rows")

    def var(dtype, stop_gradient=False):
        return helper.create_variable_for_type_inference(
            dtype=dtype, stop_gradient=stop_gradient)

    weight = var("float32")
    plan = {"Order": [var("int32", True)], "Pos": [var("int32", True)],
            "GroupSizes": [var("int32", True)]}
    route_inputs = {"X": [input], "RouterWeight": [router],
                    "Counts": [counts], "Steps": [steps]}
    if scoring == "sigmoid":
        from ..param_attr import ParamAttr
        given = ParamAttr._to_attr(correction_bias_attr)
        route_inputs["CorrectionBias"] = [helper.create_parameter(
            ParamAttr(name=given.name, initializer=given.initializer,
                      trainable=False),
            [num_experts], "float32",
            default_initializer=ConstantInitializer(0.0))]
    helper.append_op(
        "moe_route", inputs=route_inputs,
        outputs={"TopKWeight": [weight], "CountsOut": [counts],
                 "StepsOut": [steps], **plan},
        attrs={"top_k": int(top_k), "first_expert": int(first_expert),
               "num_held": int(num_held), "scoring": str(scoring),
               "routed_scaling_factor": float(routed_scaling_factor),
               **({"max_rows": int(max_held_rows)} if max_held_rows
                  else {})})
    rows = _append_single(helper, "moe_dispatch", {"X": [input], **plan},
                          input.dtype)

    def grouped(x, w):
        return _append_single(
            helper, "moe_grouped_matmul",
            {"X": [x], "W": [w], "GroupSizes": plan["GroupSizes"]}, x.dtype)

    hidden = _append_single(
        helper, "swiglu",
        {"X": [grouped(rows, w_gate)], "Y": [grouped(rows, w_up)]},
        input.dtype)
    out = _append_single(
        helper, "moe_combine",
        {"X": [grouped(hidden, w_down)], "TopKWeight": [weight], **plan},
        input.dtype)
    if shared_size:
        out = out + gated_ffn(input, shared_size, shared_gate_attr,
                              shared_up_attr, shared_down_attr,
                              num_flatten_dims=1, name=name + ".shared")
    return out


def swiglu(x, y, name=None):
    """``silu(x) * y`` as one op: the gate of a gated FFN, of a state-space
    mixer (``y * silu(z)``) and of a gated memory unit; in ``x``'s dtype."""
    return _append_single(LayerHelper("swiglu", name=name), "swiglu",
                          {"X": [x], "Y": [y]}, x.dtype)


def gated_ffn(input, size, gate_attr=None, up_attr=None, down_attr=None,
              num_flatten_dims=2, name=None):
    """``W_down(silu(W_gate x) * W_up x)`` of width ``size``, no biases: the
    dense FFN of a decoder layer, and an expert every token shares.  Under a
    ``name`` every output is ``<name>.…`` (the three matmuls' ``<name>.gate``,
    ``.up``, ``.down``), which is how a trace tells the block's rows."""
    helper = LayerHelper("gated_ffn", name=name)

    def dense(x, width, attr, part):
        return fc(x, width, num_flatten_dims=num_flatten_dims,
                  param_attr=attr, bias_attr=False,
                  name=name and f"{name}.{part}")
    hidden = _append_single(
        helper, "swiglu", {"X": [dense(input, size, gate_attr, "gate")],
                           "Y": [dense(input, size, up_attr, "up")]},
        input.dtype)
    return dense(hidden, int(input.shape[-1]), down_attr, "down")


def hyper_connection_mix(stream, n, epsilon=1e-6, sinkhorn_iters=20,
                         hc_eps=1e-6, clamp=(-30.0, 30.0), alpha_init=0.01,
                         res_init_diagonal=8.0, phi_attr=None,
                         alpha_attr=None, b_attr=None, name=None):
    """Before a branch of a residual path of ``n`` streams (``stream`` [...,
    n * d] float32, stream i in columns i * d .. (i + 1) * d): returns
    ``(y, post, c)``, the branch's input ``y`` [..., d] = sum_i pre_i
    stream[i] and the token's coefficients for ``hyper_connection_merge``.
    Parameters (named by their attrs): phi [n * d, n * n + 2 n], alpha [3]
    (``alpha_init`` unless its attr brings an initializer) and b [n * n +
    2 n] (likewise: pre = 1 / n, post = 1 and ``res_init_diagonal`` on the
    diagonal of the mixing logits, which is the plain residual ``stream[i]
    + z`` while the streams are equal).  The largest ``|row sum of C - 1|``
    of a step stays on the device in ``<name>.res_row_sum_error`` [1] and is
    published as the gauge ``hc.<name>.res_row_sum_error`` when an
    ``AsyncStepRunner`` drains."""
    import math
    from ..framework import default_main_program
    from ..initializer import NumpyArrayInitializer
    name = name or unique_name("hyper_connection")
    helper = LayerHelper("hyper_connection_mix", name=name)
    width = int(stream.shape[-1])
    k = n * n + 2 * n
    b0 = np.concatenate([np.full(n, -math.log(n - 1.0)), np.zeros(n),
                         res_init_diagonal * np.eye(n).ravel()]
                        ).astype("float32")
    phi = helper.create_parameter(phi_attr, [width, k], "float32")
    alpha = helper.create_parameter(
        alpha_attr, [3], "float32",
        default_initializer=ConstantInitializer(alpha_init))
    b = helper.create_parameter(b_attr, [k], "float32",
                                default_initializer=NumpyArrayInitializer(b0))
    error = helper.block().create_var(
        name=name + ".res_row_sum_error", shape=[1], dtype="float32",
        persistable=True, stop_gradient=True)
    default_main_program()._hints.setdefault("device_counters", {})[
        error.name] = "hc." + error.name
    y, post, c = (helper.create_variable_for_type_inference(dtype="float32")
                  for _ in range(3))
    helper.append_op(
        "hyper_connection_mix",
        inputs={"X": [stream], "Phi": [phi], "Alpha": [alpha], "B": [b]},
        outputs={"Y": [y], "Post": [post], "C": [c],
                 "RowSumError": [error]},
        attrs={"n": int(n), "epsilon": float(epsilon),
               "sinkhorn_iters": int(sinkhorn_iters),
               "hc_eps": float(hc_eps), "clamp_min": float(clamp[0]),
               "clamp_max": float(clamp[1])})
    return y, post, c


def hyper_connection_merge(stream, z, post, c, name=None):
    """After the branch: the new streams ``out[i] = post_i z + sum_j c[i, j]
    stream[j]``, [..., n * d] float32."""
    helper = LayerHelper("hyper_connection_merge", name=name)
    return _append_single(
        helper, "hyper_connection_merge",
        {"X": [stream], "Z": [z], "Post": [post], "C": [c]}, "float32")


def _append_single(helper, op_type, inputs, dtype, attrs=None,
                   out_slot="Out"):
    out = helper.create_variable_for_type_inference(dtype=dtype)
    op = helper.append_op(op_type, inputs=inputs, outputs={out_slot: [out]},
                          attrs=attrs or {})
    return op[out_slot][0] if in_dygraph_mode() else out


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, data_layout="NCHW", name=None):
    helper = LayerHelper("group_norm", name=name)
    c = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        inputs["Scale"] = [helper.create_parameter(
            param_attr, [c], input.dtype,
            default_initializer=ConstantInitializer(1.0))]
    if bias_attr is not False:
        inputs["Bias"] = [helper.create_parameter(bias_attr, [c], input.dtype,
                                                  is_bias=True)]
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    m = helper.create_variable_for_type_inference(dtype="float32", stop_gradient=True)
    v = helper.create_variable_for_type_inference(dtype="float32", stop_gradient=True)
    op = helper.append_op("group_norm", inputs=inputs,
                          outputs={"Y": [out], "Mean": [m], "Variance": [v]},
                          attrs={"groups": groups, "epsilon": epsilon})
    out = op["Y"][0] if in_dygraph_mode() else out
    return helper.append_activation(out, act)


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None,
                  name=None):
    helper = LayerHelper("instance_norm", name=name)
    c = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        inputs["Scale"] = [helper.create_parameter(
            param_attr, [c], input.dtype,
            default_initializer=ConstantInitializer(1.0))]
    if bias_attr is not False:
        inputs["Bias"] = [helper.create_parameter(bias_attr, [c], input.dtype,
                                                  is_bias=True)]
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    sm = helper.create_variable_for_type_inference(dtype="float32", stop_gradient=True)
    sv = helper.create_variable_for_type_inference(dtype="float32", stop_gradient=True)
    op = helper.append_op("instance_norm", inputs=inputs,
                          outputs={"Y": [out], "SavedMean": [sm],
                                   "SavedVariance": [sv]},
                          attrs={"epsilon": epsilon})
    return op["Y"][0] if in_dygraph_mode() else out


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    mask = helper.create_variable_for_type_inference(dtype="uint8",
                                                     stop_gradient=True)
    attrs = {"dropout_prob": dropout_prob, "is_test": is_test,
             "dropout_implementation": dropout_implementation}
    if not in_dygraph_mode():
        attrs["op_seed"] = seed or helper.main_program.next_op_seed()
    else:
        attrs["op_seed"] = seed or 0
    op = helper.append_op("dropout", inputs={"X": [x]},
                          outputs={"Out": [out], "Mask": [mask]}, attrs=attrs)
    return op["Out"][0] if in_dygraph_mode() else out


def _fused_dropout_attrs(helper, dropout_prob, is_test, seed,
                         dropout_implementation):
    attrs = {"dropout_prob": dropout_prob, "is_test": is_test,
             "dropout_implementation": dropout_implementation}
    if not in_dygraph_mode():
        attrs["op_seed"] = seed or helper.main_program.next_op_seed()
    else:
        attrs["op_seed"] = seed or 0
    return attrs


def fused_dropout_add(x, residual, dropout_prob, is_test=False, seed=None,
                      name=None, dropout_implementation="upscale_in_train"):
    """dropout(x) + residual as ONE op: on TPU a single pallas kernel
    (no HBM pass for the add at the kernel boundary), mask regenerated in
    backward.  The transformer residual epilogue
    (fused_dropout_helper.h analog, TPU-first shape)."""
    helper = LayerHelper("fused_dropout_add", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    attrs = _fused_dropout_attrs(helper, dropout_prob, is_test, seed,
                                 dropout_implementation)
    op = helper.append_op("fused_dropout_add",
                          inputs={"X": [x], "Residual": [residual]},
                          outputs={"Out": [out]}, attrs=attrs)
    return op["Out"][0] if in_dygraph_mode() else out


def fused_act_dropout(x, act="gelu", dropout_prob=0.0, is_test=False,
                      seed=None, name=None,
                      dropout_implementation="upscale_in_train"):
    """dropout(act(x)) as ONE op (MLP mid-epilogue); backward fuses
    act'(x) with the regenerated mask."""
    helper = LayerHelper("fused_act_dropout", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    attrs = _fused_dropout_attrs(helper, dropout_prob, is_test, seed,
                                 dropout_implementation)
    attrs["act"] = act
    op = helper.append_op("fused_act_dropout", inputs={"X": [x]},
                          outputs={"Out": [out]}, attrs=attrs)
    return op["Out"][0] if in_dygraph_mode() else out


def softmax(input, axis=-1, use_cudnn=False, name=None):
    return _single_out("softmax", input, {"axis": axis})


def log_softmax(input, axis=-1):
    return _single_out("log_softmax", input, {"axis": axis})


def one_hot(input, depth, allow_out_of_range=False):
    return _single_out("one_hot", input, {"depth": depth}, dtype="float32",
                       stop_gradient=True)


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    ids = helper.create_variable_for_type_inference(dtype="int64",
                                                    stop_gradient=True)
    op = helper.append_op("top_k", inputs={"X": [input]},
                          outputs={"Out": [out], "Indices": [ids]},
                          attrs={"k": k})
    if in_dygraph_mode():
        return op["Out"][0], op["Indices"][0]
    return out, ids


def cast(x, dtype):
    from .tensor import cast as _cast
    return _cast(x, dtype)


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape2", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    xshape = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                       stop_gradient=True)
    op = helper.append_op("reshape2", inputs={"X": [x]},
                          outputs={"Out": [out], "XShape": [xshape]},
                          attrs={"shape": list(shape)})
    out = op["Out"][0] if in_dygraph_mode() else out
    return helper.append_activation(out, act)


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze2", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    xshape = helper.create_variable_for_type_inference(dtype=input.dtype,
                                                       stop_gradient=True)
    op = helper.append_op("squeeze2", inputs={"X": [input]},
                          outputs={"Out": [out], "XShape": [xshape]},
                          attrs={"axes": list(axes)})
    return op["Out"][0] if in_dygraph_mode() else out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze2", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    xshape = helper.create_variable_for_type_inference(dtype=input.dtype,
                                                       stop_gradient=True)
    op = helper.append_op("unsqueeze2", inputs={"X": [input]},
                          outputs={"Out": [out], "XShape": [xshape]},
                          attrs={"axes": list(axes)})
    return op["Out"][0] if in_dygraph_mode() else out


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose2", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    xshape = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                       stop_gradient=True)
    op = helper.append_op("transpose2", inputs={"X": [x]},
                          outputs={"Out": [out], "XShape": [xshape]},
                          attrs={"axis": list(perm)})
    return op["Out"][0] if in_dygraph_mode() else out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten2", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    xshape = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                       stop_gradient=True)
    op = helper.append_op("flatten2", inputs={"X": [x]},
                          outputs={"Out": [out], "XShape": [xshape]},
                          attrs={"axis": axis})
    return op["Out"][0] if in_dygraph_mode() else out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    if isinstance(num_or_sections, int):
        n, sections = num_or_sections, []
    else:
        n, sections = len(num_or_sections), list(num_or_sections)
    outs = [helper.create_variable_for_type_inference(dtype=input.dtype)
            for _ in range(n)]
    op = helper.append_op("split", inputs={"X": [input]},
                          outputs={"Out": outs},
                          attrs={"axis": dim, "num": n, "sections": sections})
    return list(op["Out"]) if in_dygraph_mode() else outs


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    op = helper.append_op("slice", inputs={"Input": [input]},
                          outputs={"Out": [out]},
                          attrs={"axes": list(axes), "starts": list(starts),
                                 "ends": list(ends)})
    return op["Out"][0] if in_dygraph_mode() else out


def gather(input, index, overwrite=True):
    helper = LayerHelper("gather")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    op = helper.append_op("gather", inputs={"X": [input], "Index": [index]},
                          outputs={"Out": [out]})
    return op["Out"][0] if in_dygraph_mode() else out


def gather_nd(input, index, name=None):
    helper = LayerHelper("gather_nd", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    op = helper.append_op("gather_nd", inputs={"X": [input], "Index": [index]},
                          outputs={"Out": [out]})
    return op["Out"][0] if in_dygraph_mode() else out


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper("scatter", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    op = helper.append_op("scatter",
                          inputs={"X": [input], "Ids": [index],
                                  "Updates": [updates]},
                          outputs={"Out": [out]},
                          attrs={"overwrite": overwrite})
    return op["Out"][0] if in_dygraph_mode() else out


def stack(x, axis=0):
    helper = LayerHelper("stack")
    x = x if isinstance(x, (list, tuple)) else [x]
    out = helper.create_variable_for_type_inference(dtype=x[0].dtype)
    op = helper.append_op("stack", inputs={"X": x}, outputs={"Y": [out]},
                          attrs={"axis": axis})
    return op["Y"][0] if in_dygraph_mode() else out


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack")
    num = num or x.shape[axis]
    outs = [helper.create_variable_for_type_inference(dtype=x.dtype)
            for _ in range(num)]
    op = helper.append_op("unstack", inputs={"X": [x]}, outputs={"Y": outs},
                          attrs={"axis": axis, "num": num})
    return list(op["Y"]) if in_dygraph_mode() else outs


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    op = helper.append_op("expand", inputs={"X": [x]}, outputs={"Out": [out]},
                          attrs={"expand_times": list(expand_times)})
    return op["Out"][0] if in_dygraph_mode() else out


def expand_as(x, target_tensor, name=None):
    helper = LayerHelper("expand_as_v2", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    op = helper.append_op("expand_as_v2",
                          inputs={"X": [x], "Y": [target_tensor]},
                          outputs={"Out": [out]})
    return op["Out"][0] if in_dygraph_mode() else out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    op = helper.append_op("pad", inputs={"X": [x]}, outputs={"Out": [out]},
                          attrs={"paddings": list(paddings),
                                 "pad_value": float(pad_value)})
    return op["Out"][0] if in_dygraph_mode() else out


def pad2d(input, paddings=[0, 0, 0, 0], mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    helper = LayerHelper("pad2d", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    op = helper.append_op("pad2d", inputs={"X": [input]},
                          outputs={"Out": [out]},
                          attrs={"paddings": list(paddings), "mode": mode,
                                 "pad_value": float(pad_value),
                                 "data_format": data_format})
    return op["Out"][0] if in_dygraph_mode() else out


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    op = helper.append_op("scale", inputs={"X": [x]}, outputs={"Out": [out]},
                          attrs={"scale": float(scale), "bias": float(bias),
                                 "bias_after_scale": bias_after_scale})
    out = op["Out"][0] if in_dygraph_mode() else out
    return helper.append_activation(out, act)


def clip(x, min, max, name=None):
    return _single_out("clip", x, {"min": float(min), "max": float(max)})


def clip_by_norm(x, max_norm, name=None):
    return _single_out("clip_by_norm", x, {"max_norm": float(max_norm)})


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    norm = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                     stop_gradient=True)
    op = helper.append_op("l2_normalize", inputs={"X": [x]},
                          outputs={"Out": [out], "Norm": [norm]},
                          attrs={"axis": axis, "epsilon": epsilon})
    return op["Out"][0] if in_dygraph_mode() else out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_variable_for_type_inference(dtype=dtype)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    op = helper.append_op("label_smooth", inputs=inputs,
                          outputs={"Out": [out]},
                          attrs={"epsilon": float(epsilon)})
    return op["Out"][0] if in_dygraph_mode() else out


def where(condition, x, y):
    helper = LayerHelper("where")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    op = helper.append_op("where", inputs={"Condition": [condition],
                                           "X": [x], "Y": [y]},
                          outputs={"Out": [out]})
    return op["Out"][0] if in_dygraph_mode() else out


def cond_value(cond, tv, fv):  # helper used by higher layers
    return where(cond, tv, fv)


def uniform_random(shape, dtype="float32", min=-1.0, max=1.0, seed=0):
    helper = LayerHelper("uniform_random")
    out = helper.create_variable_for_type_inference(dtype=dtype,
                                                    stop_gradient=True)
    attrs = {"shape": list(shape), "dtype": dtype, "min": min, "max": max}
    if not in_dygraph_mode():
        attrs["op_seed"] = seed or helper.main_program.next_op_seed()
    op = helper.append_op("uniform_random", outputs={"Out": [out]}, attrs=attrs)
    return op["Out"][0] if in_dygraph_mode() else out


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("gaussian_random")
    out = helper.create_variable_for_type_inference(dtype=dtype,
                                                    stop_gradient=True)
    attrs = {"shape": list(shape), "dtype": dtype, "mean": mean, "std": std}
    if not in_dygraph_mode():
        attrs["op_seed"] = seed or helper.main_program.next_op_seed()
    op = helper.append_op("gaussian_random", outputs={"Out": [out]}, attrs=attrs)
    return op["Out"][0] if in_dygraph_mode() else out


def relu_(x):  # inplace alias
    return getattr(_this, "relu")(x)


def matmul_v2(x, y, trans_x=False, trans_y=False):
    helper = LayerHelper("matmul_v2")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    op = helper.append_op("matmul_v2", inputs={"X": [x], "Y": [y]},
                          outputs={"Out": [out]},
                          attrs={"trans_x": trans_x, "trans_y": trans_y})
    return op["Out"][0] if in_dygraph_mode() else out


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    helper = LayerHelper("unfold", name=name)
    k = [kernel_sizes] * 2 if isinstance(kernel_sizes, int) else list(kernel_sizes)
    s = [strides] * 2 if isinstance(strides, int) else list(strides)
    p = [paddings] * 4 if isinstance(paddings, int) else list(paddings)
    d = [dilations] * 2 if isinstance(dilations, int) else list(dilations)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    op = helper.append_op("unfold", inputs={"X": [x]}, outputs={"Y": [out]},
                          attrs={"kernel_sizes": k, "strides": s,
                                 "paddings": p, "dilations": d})
    return op["Y"][0] if in_dygraph_mode() else out
