"""paddle.vision.ops namespace (reference vision/ops.py): detection op
builders re-exported from the fluid layer tier + the DeformConv2D class,
plus the TPU-native fixed-k batched_nms used inside jit (the dynamic-
shape multiclass_nms replacement)."""
from __future__ import annotations

import numpy as np

from ..fluid import layers as _L
from ..fluid.layers.detection import yolo_box
from ..fluid.layers import deformable_conv as deform_conv2d
from ..dygraph.layers import Layer
from ..fluid.layer_helper import LayerHelper

__all__ = ["yolo_loss", "yolo_box", "deform_conv2d", "DeformConv2D",
           "batched_nms"]


def yolo_loss(x, gt_box, gt_label, anchors, anchor_mask, class_num,
              ignore_thresh, downsample_ratio, gt_score=None,
              use_label_smooth=True, name=None, scale_x_y=1.0):
    return _L.yolov3_loss(x, gt_box, gt_label, anchors, anchor_mask,
                          class_num, ignore_thresh, downsample_ratio,
                          gt_score=gt_score,
                          use_label_smooth=use_label_smooth, name=name)


class DeformConv2D(Layer):
    """2.0 class over the deformable-conv lowering (vision/ops.py)."""

    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, dilation=1, deformable_groups=1, groups=1,
                 weight_attr=None, bias_attr=None):
        super().__init__()
        ks = [kernel_size] * 2 if isinstance(kernel_size, int) \
            else list(kernel_size)
        helper = LayerHelper("deform_conv2d")
        self.weight = helper.create_parameter(
            weight_attr, [out_channels, in_channels // groups] + ks,
            "float32")
        self.bias = helper.create_parameter(
            bias_attr, [out_channels], "float32", is_bias=True) \
            if bias_attr is not False else None
        self._cfg = dict(stride=stride, padding=padding, dilation=dilation,
                         deformable_groups=deformable_groups, groups=groups,
                         kernel=ks, out_channels=out_channels)

    def forward(self, x, offset, mask=None):
        from ..fluid.layer_helper import emit_op
        c = self._cfg
        modulated = mask is not None
        ins = {"Input": [x], "Offset": [offset], "Filter": [self.weight]}
        if modulated:
            ins["Mask"] = [mask]
        st = [c["stride"]] * 2 if isinstance(c["stride"], int) \
            else list(c["stride"])
        pd = [c["padding"]] * 2 if isinstance(c["padding"], int) \
            else list(c["padding"])
        dl = [c["dilation"]] * 2 if isinstance(c["dilation"], int) \
            else list(c["dilation"])
        out = emit_op(
            "deform_conv2d",
            "deformable_conv" if modulated else "deformable_conv_v1",
            ins, ("Output",),
            {"strides": st, "paddings": pd, "dilations": dl,
             "groups": c["groups"],
             "deformable_groups": c["deformable_groups"],
             "im2col_step": 1})["Output"][0]
        if self.bias is not None:
            out = _L.elementwise_add(out, self.bias, axis=1)
        return out


def batched_nms(boxes, scores, iou_threshold=0.5, top_k=100,
                max_outputs=None):
    """Fixed-k NMS usable under jit (static shapes): returns the top_k
    surviving box indices padded with -1 — the TPU-native answer to the
    dynamic-shape multiclass_nms family.

    ``max_outputs`` is the pre-round-4 keyword for ``top_k``, kept as an
    alias; the old (boxes, scores, mask) tuple return became the single
    -1-padded index array."""
    if max_outputs is not None:
        top_k = max_outputs
    import jax.numpy as jnp

    boxes = getattr(boxes, "_value", boxes)
    scores = getattr(scores, "_value", scores)
    boxes = jnp.asarray(boxes)
    scores = jnp.asarray(scores)
    n = boxes.shape[0]
    if n == 0:                      # no detections: all-pad, contract kept
        return jnp.full((top_k,), -1, jnp.int32)
    order = jnp.argsort(-scores)
    boxes_s = boxes[order]

    x1, y1, x2, y2 = (boxes_s[:, 0], boxes_s[:, 1], boxes_s[:, 2],
                      boxes_s[:, 3])
    area = jnp.maximum(x2 - x1, 0) * jnp.maximum(y2 - y1, 0)
    xx1 = jnp.maximum(x1[:, None], x1[None, :])
    yy1 = jnp.maximum(y1[:, None], y1[None, :])
    xx2 = jnp.minimum(x2[:, None], x2[None, :])
    yy2 = jnp.minimum(y2[:, None], y2[None, :])
    inter = jnp.maximum(xx2 - xx1, 0) * jnp.maximum(yy2 - yy1, 0)
    iou = inter / jnp.maximum(area[:, None] + area[None, :] - inter, 1e-9)

    tri = jnp.tril(jnp.ones((n, n), bool), k=-1)     # earlier (higher) boxes
    keep = jnp.ones((n,), bool)
    # iterative suppression as a fori-style scan over rows
    def body(i, keep):
        suppressed = jnp.any(tri[i] & keep & (iou[i] > iou_threshold))
        return keep.at[i].set(~suppressed & keep[i])
    import jax
    keep = jax.lax.fori_loop(0, n, body, keep)
    kept_sorted = jnp.where(keep, jnp.arange(n), n)
    # fixed-k contract: ALWAYS top_k entries, -1 padded (pad before the
    # slice so n < top_k keeps the promised output shape)
    padded = jnp.concatenate(
        [jnp.sort(kept_sorted),
         jnp.full((max(top_k - n, 0),), n, kept_sorted.dtype)])[:top_k]
    out = jnp.where(padded < n, order[jnp.minimum(padded, n - 1)], -1)
    return out
