"""ResNet-50 training as a fluid Program: torchvision's ``resnet50`` (He et
al. 2015, Table 1, 50-layer, with the stride of a down-sampling block on its
3x3 convolution: "v1.5"), spelled from ``fluid.layers`` in NHWC.

Stem (7x7/2 convolution, BatchNorm, ReLU, 3x3/2 max-pool), four stages of
bottleneck blocks (1x1 -> 3x3 -> 1x1 with a BatchNorm after each, identity or
projection shortcut, ReLU after the sum), global average pool, 1000-way
classifier; softmax cross-entropy; Momentum with L2 decay.  Every parameter
has a fixed name, so ``reference.py`` reads the same weights from the scope.

Also here, because they belong to this configuration: the operations and
bytes one training step requires, computed from its shapes.
"""
from __future__ import annotations

import math

def conv_shapes(cfg):
    """Every convolution of the network in order, as ``(name, kernel, c_in,
    c_out, stride, h_out)`` (square images and kernels), and the classifier's
    input width.  The one description of the architecture: the Program, the
    reference and the FLOPs are all built from it."""
    size = cfg["image_size"]
    convs = []
    h = (size + 2 * 3 - 7) // 2 + 1
    convs.append(("conv1", 7, cfg["image_channels"], cfg["stem_width"], 2, h))
    h = (h + 2 * 1 - 3) // 2 + 1                        # the max-pool
    c_in = cfg["stem_width"]
    for s, (blocks, width) in enumerate(zip(cfg["stage_blocks"],
                                            cfg["stage_widths"])):
        c_out = width * cfg["bottleneck_expansion"]
        for b in range(blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            pre = f"stage_{s}.block_{b}."
            convs.append((pre + "conv1", 1, c_in, width, 1, h))
            h_out = h // stride
            convs.append((pre + "conv2", 3, width, width, stride, h_out))
            convs.append((pre + "conv3", 1, width, c_out, 1, h_out))
            if b == 0:
                convs.append((pre + "shortcut", 1, c_in, c_out, stride,
                              h_out))
            c_in, h = c_out, h_out
    return convs, c_in


def build(cfg, mix, train=True):
    """The Program for ``cfg`` under ``mix``.  ``train=True``: forward,
    backward and Momentum.  ``train=False``: forward and backward only, for
    the comparison with the reference (BatchNorm uses batch statistics in
    both); ``grads`` then maps parameter name -> gradient variable name."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers as L
    from paddle_tpu.fluid.initializer import (ConstantInitializer,
                                              NormalInitializer)
    from paddle_tpu.fluid.param_attr import ParamAttr

    fmt = cfg["data_format"]
    if fmt != "NHWC":
        raise ValueError(f"data_format {fmt!r}: only NHWC is spelled here")
    size = mix.get("image_size", cfg["image_size"])
    if size != cfg["image_size"]:
        raise ValueError("the mix's image_size differs from the config's")
    shapes = {name: rest for name, *rest in conv_shapes(cfg)[0]}

    def conv_bn(x, name, act=None):
        k, _c_in, c_out, stride, _h = shapes[name]
        x = L.conv2d(
            x, c_out, k, stride=stride, padding=(k - 1) // 2, bias_attr=False,
            data_format=fmt, param_attr=ParamAttr(
                name=name + ".w", initializer=NormalInitializer(
                    0.0, math.sqrt(2.0 / (k * k * c_out)))))
        return L.batch_norm(
            x, act=act, momentum=cfg["batch_norm_momentum"],
            epsilon=cfg["batch_norm_eps"], data_layout=fmt,
            param_attr=ParamAttr(name=name + ".bn.scale",
                                 initializer=ConstantInitializer(1.0)),
            bias_attr=ParamAttr(name=name + ".bn.bias",
                                initializer=ConstantInitializer(0.0)),
            moving_mean_name=name + ".bn.mean",
            moving_variance_name=name + ".bn.variance")

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        image = fluid.data("image", [-1, size, size, cfg["image_channels"]],
                           dtype="float32")
        label = fluid.data("label", [-1, 1], dtype="int64")
        x = conv_bn(image, "conv1", act="relu")
        x = L.pool2d(x, 3, "max", pool_stride=2, pool_padding=1,
                     data_format=fmt)
        for s, blocks in enumerate(cfg["stage_blocks"]):
            for b in range(blocks):
                pre = f"stage_{s}.block_{b}."
                short = conv_bn(x, pre + "shortcut") if b == 0 else x
                y = conv_bn(x, pre + "conv1", act="relu")
                y = conv_bn(y, pre + "conv2", act="relu")
                y = conv_bn(y, pre + "conv3")
                x = L.relu(y + short)
        x = L.pool2d(x, pool_type="avg", global_pooling=True, data_format=fmt)
        logits = L.fc(
            L.reshape(x, [0, -1]), cfg["num_classes"],
            param_attr=ParamAttr(name="fc.w",
                                 initializer=NormalInitializer(0.0, 0.01)),
            bias_attr=ParamAttr(name="fc.b",
                                initializer=ConstantInitializer(0.0)))
        loss = L.mean(L.softmax_with_cross_entropy(logits, label))

        grads = {}
        if train:
            o = cfg["optimizer"]
            if o["type"] != "momentum":
                raise ValueError(f"optimizer {o['type']!r}: only momentum")
            fluid.optimizer.MomentumOptimizer(
                learning_rate=o["learning_rate"], momentum=o["momentum"],
                regularization=fluid.regularizer.L2Decay(o["l2_decay"])
            ).minimize(loss)
        else:
            from paddle_tpu.fluid.backward import append_backward
            grads = {p.name: g.name for p, g in append_backward(loss)}
    return {"main": main, "startup": startup, "loss": loss, "grads": grads}


def param_count(cfg):
    """Trainable parameters (BatchNorm's moving statistics are state)."""
    convs, c_last = conv_shapes(cfg)
    return sum(k * k * c_in * c_out + 2 * c_out
               for _, k, c_in, c_out, _, _ in convs) \
        + c_last * cfg["num_classes"] + cfg["num_classes"]


def flops_per_sample(cfg, mix):
    """Forward + backward FLOPs one image requires (2 per multiply-add,
    backward = 2 x forward): the convolutions and the classifier.  BatchNorm,
    ReLU, pooling and the optimizer are not counted."""
    convs, c_last = conv_shapes(cfg)
    fwd = sum(2 * k * k * c_in * c_out * h * h
              for _, k, c_in, c_out, _, h in convs) \
        + 2 * c_last * cfg["num_classes"]
    return 3.0 * fwd


def bytes_per_step(cfg, mix, batch):
    """HBM bytes one training step of ``batch`` images on one chip cannot
    avoid.  Parameters are read in forward and in backward as stored
    (float32), gradients written once and read once, Momentum reads and
    writes parameter and velocity (32 B per parameter in all).  Activations:
    what backward needs without recomputing — each convolution's input and
    its output (BatchNorm's input) — written once and read once in
    bfloat16."""
    convs, _ = conv_shapes(cfg)
    acts = sum(c_in * (h * stride) ** 2 + c_out * h * h
               for _, _, c_in, c_out, stride, h in convs)
    return 32.0 * param_count(cfg) + 2 * 2 * batch * acts
