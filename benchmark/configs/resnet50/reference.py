"""Plain float32 ``jax.numpy`` reference of the ResNet-50 training loss.

Forward as published (He et al. 2015), BatchNorm on the batch's own
statistics; no kernels, no mixed precision.  The harness differentiates it
(``compare.reference_loss_and_grads``, convolutions and matmuls at
``highest`` precision).  It shares with the code
under test the parameter names and ``model.conv_shapes`` (the table of the
architecture), nothing that computes.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from benchmark.harness.registry import load_module

_model = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "model.py"))


def loss(params, batch, cfg):
    """Mean softmax cross-entropy of one batch (``image`` NHWC float32,
    ``label`` [B,1]) under ``params`` (name -> float32 array; convolution
    filters are [out, in, kh, kw])."""
    p = params
    shapes = {name: rest for name, *rest in _model.conv_shapes(cfg)[0]}
    eps = cfg["batch_norm_eps"]

    def conv_bn(x, name, relu=False):
        k, _, _, stride, _ = shapes[name]
        pad = (k - 1) // 2
        x = jax.lax.conv_general_dilated(
            x, p[name + ".w"], (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "OIHW", "NHWC"))
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
        x = (x - mean) / jnp.sqrt(var + eps) * p[name + ".bn.scale"] \
            + p[name + ".bn.bias"]
        return jnp.maximum(x, 0.0) if relu else x

    x = conv_bn(batch["image"].astype(jnp.float32), "conv1", relu=True)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])
    for s, blocks in enumerate(cfg["stage_blocks"]):
        for b in range(blocks):
            pre = f"stage_{s}.block_{b}."
            short = conv_bn(x, pre + "shortcut") if b == 0 else x
            y = conv_bn(x, pre + "conv1", relu=True)
            y = conv_bn(y, pre + "conv2", relu=True)
            y = conv_bn(y, pre + "conv3")
            x = jnp.maximum(y + short, 0.0)
    logits = jnp.mean(x, axis=(1, 2)) @ p["fc.w"] + p["fc.b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    labels = batch["label"].astype(jnp.int32)
    return -jnp.mean(jnp.take_along_axis(logp, labels, axis=-1))

