"""Ops of a causal decoder block with sparse experts: ``rms_norm``,
``rotary_embedding``, ``swiglu`` and the expert layer as four ops (``moe_route``,
``moe_dispatch``, ``moe_grouped_matmul``, ``moe_combine``), so that the
device time of the grouped matmuls can be told from the routing and the
permutation around them.  The attention of such a block is the
``fused_multihead_attention`` op with ``causal``, ``window`` and fewer
key/value heads than query heads (ops/attention.py).  The arithmetic of the
expert layer lives in ``parallel/moe.py``; gradients come from
``generic_grad`` over these lowerings.

A residual path of several streams (manifold-constrained hyper-connections,
arXiv 2512.24880 over 2409.19606) is two ops around each branch:
``hyper_connection_mix`` reads the streams and gives the branch its input
and the token's mixing coefficients, ``hyper_connection_merge`` writes the
streams the branch's output and the mixed old streams.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

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


@register_op("rotary_embedding", nondiff_inputs=("Positions",))
def _rotary_embedding(ins, attrs, ctx):
    """Rotate the halves of X [..., S, D] by position: with ``inv_freq`` the
    D/2 frequencies (an attribute: a layer's frequencies are data, not code)
    and ``scale`` the factor on cos and sin (YaRN's attention factor),
    ``Out = X * cos + rotate_half(X) * sin``, cos and sin over
    ``position * concat(inv_freq, inv_freq)``.  Positions are 0..S-1, or
    the optional input Positions [R, S]: frequency ``i`` then turns by row
    ``r(i)`` of it, ``sections`` giving how many consecutive frequencies
    each row takes (multimodal rotary: temporal, height, width; one row
    needs no ``sections``).  Angles, cos and sin in float32; the result in
    X's dtype."""
    x = ins["X"][0]
    s, d = x.shape[-2], x.shape[-1]
    inv_freq = jnp.asarray(attrs["inv_freq"], jnp.float32)
    if inv_freq.shape != (d // 2,):
        raise ValueError(f"rotary_embedding: {inv_freq.shape[0]} frequencies "
                         f"for a head of {d}")
    if ins.get("Positions"):
        pos = ins["Positions"][0].astype(jnp.float32)
        sections = [int(n) for n in attrs.get("sections") or [d // 2]]
        if pos.shape != (len(sections), s) or sum(sections) != d // 2:
            raise ValueError(f"rotary_embedding: positions {pos.shape} and "
                             f"sections {sections} for [{s}, {d}]")
        row = np.repeat(np.arange(len(sections)), sections)
        half = pos[row].T * inv_freq[None, :]
    else:
        half = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([half, half], axis=-1)
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


# tokens of one block of ``linear_cross_entropy``: [2048, V] float32 logits
# are 148 MiB at 18992 classes, where the whole [16384, V] array and the
# softmax beside it are 2.3 GiB at the step's fullest moment
_HEAD_ROWS = 2048


def _head_blocks(x, labels):
    t = x.shape[0]
    rows = min(_HEAD_ROWS, t)
    while t % rows:
        rows -= 1
    return x.reshape(t // rows, rows, -1), labels.reshape(t // rows, rows)


@jax.custom_vjp
def linear_cross_entropy(x, w, labels):
    """``-log softmax(x w)[label]`` of every token, x [T, H], w [H, V],
    labels [T] int -> [T] float32, in blocks of tokens: the logits of a
    block exist while it is worked on, forward and backward (which computes
    them again: one more pass of the matmul), and only the tokens'
    log-sum-exp is kept."""
    return _linear_cross_entropy_fwd(x, w, labels)[0]


def _block_logits(xb, w):
    return jnp.dot(xb, w.astype(xb.dtype), preferred_element_type=jnp.float32)


def _linear_cross_entropy_fwd(x, w, labels):
    def block(_, args):
        xb, lb = args
        logits = _block_logits(xb, w)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return None, (lse - picked, lse)
    _, (loss, lse) = jax.lax.scan(block, None, _head_blocks(x, labels))
    return loss.reshape(-1), (x, w, labels, lse)


def _linear_cross_entropy_bwd(res, g):
    x, w, labels, lse = res

    def block(dw, args):
        xb, lb, lse_b, gb = args
        p = jnp.exp(_block_logits(xb, w) - lse_b[:, None])
        hit = lb[:, None] == jnp.arange(p.shape[-1], dtype=lb.dtype)[None, :]
        d = ((p - hit) * gb[:, None]).astype(xb.dtype)
        dw = dw + jnp.dot(xb.T, d, preferred_element_type=jnp.float32)
        return dw, jnp.dot(d, w.astype(xb.dtype).T,
                           preferred_element_type=jnp.float32)
    xs, ls = _head_blocks(x, labels)
    dw, dx = jax.lax.scan(
        block, jnp.zeros(w.shape, jnp.float32),
        (xs, ls, lse, g.astype(jnp.float32).reshape(lse.shape)))
    return dx.reshape(x.shape).astype(x.dtype), dw.astype(w.dtype), None


linear_cross_entropy.defvjp(_linear_cross_entropy_fwd,
                            _linear_cross_entropy_bwd)


@register_op("linear_cross_entropy", nondiff_inputs=("Label",))
def _linear_cross_entropy(ins, attrs, ctx):
    """X [..., H], W [H, V], Label [..., 1] (or [...]) -> Loss [..., 1]
    float32: a decoder's head and its loss as one op, so that the [tokens,
    V] logits, their softmax and their gradient never exist whole
    (``linear_cross_entropy``).  ``transpose_w``: W is an embedding's [V, H]
    rows (a tied head), read as they lie."""
    x, w = ins["X"][0], ins["W"][0]
    if attrs.get("transpose_w", False):
        w = w.T
    labels = ins["Label"][0].reshape(x.shape[:-1]).astype(jnp.int32)
    loss = linear_cross_entropy(x.reshape(-1, x.shape[-1]), w,
                                labels.reshape(-1))
    return {"Loss": [loss.reshape(x.shape[:-1] + (1,))]}


def _plan(ins):
    return moe.Plan(ins["Order"][0], ins["Pos"][0], ins["GroupSizes"][0])


_PLAN_SLOTS = ("Order", "Pos", "GroupSizes")


@register_op("moe_route",
             nondiff_inputs=("Counts", "Steps", "CorrectionBias"),
             nondiff_outputs=("Order", "Pos", "GroupSizes", "CountsOut",
                              "StepsOut"))
def _moe_route(ins, attrs, ctx):
    """Router and plan.  X [T, D], RouterWeight [D, E] -> TopKWeight
    [T, top_k] float32 and the plan of the held experts ``[first_expert,
    first_expert + num_held)``: Order [T * top_k], Pos [T, top_k], GroupSizes
    [num_held].  ``scoring`` (``softmax``, the default, or ``sigmoid``),
    ``routed_scaling_factor`` and the optional CorrectionBias [E] are
    ``parallel.moe.route``'s.  Counts [num_held] and Steps [1] (int32,
    persistable) are the device's own counters: tokens per held expert and
    calls, added to here and read by the host when a runner drains.
    ``max_rows`` > 0 bounds the buffer of held assignments (Order is
    [max_rows], not [T * top_k]); a step that routes more than that to the
    held experts gets NaN weights, so it fails and drops nothing in
    silence."""
    bias = ins["CorrectionBias"][0] if ins.get("CorrectionBias") else None
    weights, experts = moe.route(
        ins["X"][0], ins["RouterWeight"][0], int(attrs["top_k"]),
        attrs.get("scoring", "softmax"), bias,
        float(attrs.get("routed_scaling_factor", 1.0)))
    max_rows = int(attrs.get("max_rows", 0) or 0)
    plan = moe.dispatch_plan(experts, int(attrs.get("first_expert", 0)),
                             int(attrs["num_held"]), max_rows or None)
    if max_rows:
        weights = jnp.where(jnp.sum(plan.group_sizes) > max_rows, jnp.nan,
                            weights)
    out = {"TopKWeight": [weights], "Order": [plan.order],
           "Pos": [plan.pos], "GroupSizes": [plan.group_sizes]}
    if ins.get("Counts"):
        out["CountsOut"] = [ins["Counts"][0] + plan.group_sizes]
        out["StepsOut"] = [ins["Steps"][0] + 1]
        if ctx.cur_op is not None and ctx.cur_op.type == "moe_route":
            # (not a grad op tracing this forward again: its slots differ)
            # the rows of the buffers this trace sized, beside the counts:
            # their quotient is the share the permutation visits
            from ..fluid import trace
            trace.metrics().gauge(
                f"moe.{ctx.cur_op.input('Steps')[0]}.buffer_rows").set(
                    float(plan.order.shape[0]))
    return out


@register_op("moe_dispatch", nondiff_inputs=_PLAN_SLOTS)
def _moe_dispatch(ins, attrs, ctx):
    """X [T, D] -> Out [T * top_k, D]: the tokens in the plan's order."""
    return {"Out": [moe.dispatch(ins["X"][0], _plan(ins),
                                 ctx.pallas_ok())]}


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
                                _plan(ins), ctx.pallas_ok())]}


# ---------------------------------------------------------------------------
# hyper-connections: a residual path of ``n`` streams.  The streams of a
# token lie side by side in one row, X [..., n * d] (stream i is columns
# i * d .. (i + 1) * d): every slice is whole lane groups and no array has a
# short second-minor axis that the chip's tiling would pad.
# ---------------------------------------------------------------------------

def _streams(x, n):
    d = x.shape[-1] // n
    return [x[..., i * d:(i + 1) * d] for i in range(n)]


def hyper_connection_coefficients_t(mt, alpha, bt, n, iters, hc_eps, clamp):
    """The mixers' small arithmetic with the tokens on the last axis, as
    XLA runs it and as the Pallas kernel runs it on a tile.  ``mt`` [n * n +
    2 n, T] the tokens' projections, ``alpha`` three scalars (an array or an
    SMEM ref), ``bt`` [n * n + 2 n, 1] -> ``pre`` [n, T] = sigmoid(alpha_0
    m[:n] + b[:n]), ``post`` [n, T] = 2 sigmoid(alpha_1 m[n:2n] + b[n:2n]),
    ``c`` [n * n, T] row-major = Sinkhorn-Knopp of ``exp(clip(alpha_2 m[2n:]
    + b[2n:]))``: ``iters`` times rows over (their sum + ``hc_eps``), then
    columns likewise; and ``|row sum of c - 1|`` [n, T]."""
    pre = jax.nn.sigmoid(alpha[0] * mt[:n] + bt[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * mt[n:2 * n] + bt[n:2 * n])
    c = jnp.exp(jnp.clip(alpha[2] * mt[2 * n:] + bt[2 * n:], *clamp))
    c = c.reshape(n, n, -1)                                  # [row, col, T]
    for _ in range(iters):
        c = c / (jnp.sum(c, axis=1, keepdims=True) + hc_eps)
        c = c / (jnp.sum(c, axis=0, keepdims=True) + hc_eps)
    row_error = jnp.abs(jnp.sum(jax.lax.stop_gradient(c), axis=1) - 1.0)
    return pre, post, c.reshape(n * n, -1), row_error


def hyper_connection_coefficients(m, alpha, b, n, iters, hc_eps, clamp):
    """(pre [T, n], post [T, n], C [T, n * n] row-major, the largest |row
    sum of C - 1|) of the tokens' projections ``m`` [T, n * n + 2 n]:
    ``hyper_connection_coefficients_t`` with the tokens moved to the last
    axis for the small arithmetic."""
    pre, post, c, row_error = hyper_connection_coefficients_t(
        m.T, alpha, b[:, None], n, iters, hc_eps, clamp)
    return pre.T, post.T, c.T, jnp.max(row_error)


def _mixer_kernels(ctx, x, n):
    """``pallas_kernels`` where the mixers' kernels run: on the chip, outside
    a partitioned program, over streams they cover; else None, the ``jnp``
    spelling.  Counted once per lowering, ``hyper_connection.lowering.
    <pallas|xla>`` in ``trace.metrics()``."""
    from ..fluid import trace
    from . import pallas_kernels as pk
    use = ctx.pallas_ok() and pk.hyper_connection_supported(x, n)
    trace.metrics().counter(
        f"hyper_connection.lowering.{'pallas' if use else 'xla'}").inc()
    return pk if use else None


@register_op("hyper_connection_mix", nondiff_outputs=("RowSumError",))
def _hyper_connection_mix(ins, attrs, ctx):
    """X [..., n * d] float32 streams, Phi [n * d, n * n + 2 n], Alpha [3],
    B [n * n + 2 n] -> Y [..., d] = sum_i pre_i X[i], the branch's input;
    Post [..., n]; C [..., n * n].  ``m = (x Phi) rsqrt(mean(x^2) +
    epsilon)`` over the token's whole row, the matmul at the highest
    precision (its 24 outputs decide how every stream moves).  RowSumError
    [1] is the largest ``|row sum of C - 1|`` over the call's tokens, kept
    on the device and read by the host when a runner drains."""
    x = ins["X"][0]
    n = int(attrs["n"])
    lead = x.shape[:-1]
    phi, alpha, b = ins["Phi"][0], ins["Alpha"][0], ins["B"][0]
    epsilon = float(attrs.get("epsilon", 1e-6))
    iters, hc_eps = int(attrs["sinkhorn_iters"]), float(attrs["hc_eps"])
    clamp = (float(attrs["clamp_min"]), float(attrs["clamp_max"]))
    pk = _mixer_kernels(ctx, x, n)
    if pk:
        y, post, c, row_error = pk.hyper_connection_mix_tpu(
            x.reshape(-1, x.shape[-1]), phi, alpha, b, n, epsilon, iters,
            hc_eps, clamp)
    else:
        rows = x.astype(jnp.float32).reshape(-1, x.shape[-1])
        inv = jax.lax.rsqrt(jnp.mean(jnp.square(rows), axis=-1,
                                     keepdims=True) + epsilon)
        m = jnp.dot(rows, phi.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST) * inv
        # the 20 iterations are recomputed in backward: 16 numbers a token
        pre, post, c, row_error = jax.checkpoint(
            hyper_connection_coefficients, static_argnums=(3, 4, 5, 6))(
            m, alpha.astype(jnp.float32), b.astype(jnp.float32), n, iters,
            hc_eps, clamp)
        y = sum(pre[:, i:i + 1] * xi
                for i, xi in enumerate(_streams(rows, n)))
    return {"Y": [y.reshape(lead + (y.shape[-1],))],
            "Post": [post.reshape(lead + (n,))],
            "C": [c.reshape(lead + (n * n,))],
            "RowSumError": [row_error.reshape(1)]}


@register_op("hyper_connection_merge")
def _hyper_connection_merge(ins, attrs, ctx):
    """X [..., n * d], Z [..., d] (the branch's output), Post [..., n], C
    [..., n * n] -> Out [..., n * d]: ``Out[i] = post_i Z + sum_j C[i, j]
    X[j]``, float32."""
    x, z, post, c = (ins[slot][0] for slot in ("X", "Z", "Post", "C"))
    n = post.shape[-1]
    pk = _mixer_kernels(ctx, x, n)
    if pk:
        out = pk.hyper_connection_merge_tpu(
            x.reshape(-1, x.shape[-1]), z.reshape(-1, z.shape[-1]),
            post.reshape(-1, n), c.reshape(-1, n * n))
        return {"Out": [out.reshape(x.shape)]}
    z, post, c = (a.astype(jnp.float32) for a in (z, post, c))
    xs = _streams(x.astype(jnp.float32), n)
    out = [post[..., i:i + 1] * z
           + sum(c[..., i * n + j:i * n + j + 1] * xs[j] for j in range(n))
           for i in range(n)]
    return {"Out": [jnp.concatenate(out, axis=-1)]}
