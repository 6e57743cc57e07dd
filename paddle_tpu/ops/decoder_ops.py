"""Ops of a causal decoder block with sparse experts: ``rms_norm``,
``rotary_embedding``, ``swiglu`` and the expert layer as four ops (``moe_route``,
``moe_dispatch``, ``moe_grouped_matmul``, ``moe_combine``), so that the
device time of the grouped matmuls can be told from the routing and the
permutation around them.  The attention of such a block is the
``fused_multihead_attention`` op with ``causal``, ``window`` and fewer
key/value heads than query heads (ops/attention.py).  The arithmetic of the
expert layer lives in ``parallel/moe.py``; gradients come from
``generic_grad`` over these lowerings.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..parallel import moe
from .registry import register_op


@register_op("rms_norm")
def _rms_norm(ins, attrs, ctx):
    """Y = X / sqrt(mean(X^2, last axis) + epsilon) * Scale.  Statistics in
    float32; the result in X's dtype."""
    x = ins["X"][0]
    xf = x.astype(jnp.float32)
    inv = jnp.reciprocal(jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1,
                                            keepdims=True)
                                   + float(attrs.get("epsilon", 1e-6))))
    y = xf * inv * ins["Scale"][0].astype(jnp.float32)
    return {"Y": [y.astype(x.dtype)]}


@register_op("rotary_embedding")
def _rotary_embedding(ins, attrs, ctx):
    """Rotate the halves of X [..., S, D] by position: with ``inv_freq`` the
    D/2 frequencies (an attribute: a layer's frequencies are data, not code)
    and ``scale`` the factor on cos and sin (YaRN's attention factor),
    ``Out = X * cos + rotate_half(X) * sin``, cos and sin over
    ``position * concat(inv_freq, inv_freq)``, positions 0..S-1.  Angles,
    cos and sin in float32; the result in X's dtype."""
    x = ins["X"][0]
    s, d = x.shape[-2], x.shape[-1]
    inv_freq = jnp.asarray(attrs["inv_freq"], jnp.float32)
    if inv_freq.shape != (d // 2,):
        raise ValueError(f"rotary_embedding: {inv_freq.shape[0]} frequencies "
                         f"for a head of {d}")
    pos = jnp.arange(s, dtype=jnp.float32)
    angle = pos[:, None] * jnp.concatenate([inv_freq, inv_freq])[None, :]
    scale = float(attrs.get("scale", 1.0))
    cos, sin = jnp.cos(angle) * scale, jnp.sin(angle) * scale
    xf = x.astype(jnp.float32)
    rotated = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], axis=-1)
    return {"Out": [(xf * cos + rotated * sin).astype(x.dtype)]}


@register_op("swiglu")
def _swiglu(ins, attrs, ctx):
    """Out = silu(X) * Y, the gate of a gated FFN, as one op: backward
    needs X and Y and no third array between them."""
    x, y = ins["X"][0], ins["Y"][0]
    xf = x.astype(jnp.float32)
    return {"Out": [(xf * jax.nn.sigmoid(xf)
                     * y.astype(jnp.float32)).astype(x.dtype)]}


def _plan(ins):
    return moe.Plan(ins["Order"][0], ins["Pos"][0], ins["GroupSizes"][0])


_PLAN_SLOTS = ("Order", "Pos", "GroupSizes")


@register_op("moe_route", nondiff_inputs=("Counts", "Steps"),
             nondiff_outputs=("Order", "Pos", "GroupSizes", "CountsOut",
                              "StepsOut"))
def _moe_route(ins, attrs, ctx):
    """Router and plan.  X [T, D], RouterWeight [D, E] -> TopKWeight
    [T, top_k] float32 and the plan of the held experts ``[first_expert,
    first_expert + num_held)``: Order [T * top_k], Pos [T, top_k], GroupSizes
    [num_held].  Counts [num_held] and Steps [1] (int32, persistable) are the
    device's own counters: tokens per held expert and calls, added to here
    and read by the host when a runner drains."""
    weights, experts = moe.route(ins["X"][0], ins["RouterWeight"][0],
                                 int(attrs["top_k"]))
    plan = moe.dispatch_plan(experts, int(attrs.get("first_expert", 0)),
                             int(attrs["num_held"]))
    out = {"TopKWeight": [weights], "Order": [plan.order],
           "Pos": [plan.pos], "GroupSizes": [plan.group_sizes]}
    if ins.get("Counts"):
        out["CountsOut"] = [ins["Counts"][0] + plan.group_sizes]
        out["StepsOut"] = [ins["Steps"][0] + 1]
    return out


@register_op("moe_dispatch", nondiff_inputs=_PLAN_SLOTS)
def _moe_dispatch(ins, attrs, ctx):
    """X [T, D] -> Out [T * top_k, D]: the tokens in the plan's order."""
    return {"Out": [moe.dispatch(ins["X"][0], _plan(ins))]}


@register_op("moe_grouped_matmul", nondiff_inputs=("GroupSizes",))
def _moe_grouped_matmul(ins, attrs, ctx):
    """X [R, K], W [num_held, K, N] -> Out [R, N]: each held expert's matrix
    over its own group of rows, float32 accumulation, zero past the last
    group."""
    return {"Out": [moe.grouped_matmul(ins["X"][0], ins["W"][0],
                                       ins["GroupSizes"][0],
                                       use_kernel=ctx.pallas_ok())]}


@register_op("moe_combine", nondiff_inputs=_PLAN_SLOTS)
def _moe_combine(ins, attrs, ctx):
    """X [T * top_k, D] (the held experts' outputs, in the plan's order),
    TopKWeight [T, top_k] -> Out [T, D]: each token's weighted sum over its
    held assignments."""
    return {"Out": [moe.combine(ins["X"][0], ins["TopKWeight"][0],
                                _plan(ins))]}
