"""Sparse experts: top-k routing without token dropping, for a chip that
holds a stated range of the experts.

The contract (docs/moe.md).  A layer has ``E`` experts and every token goes
to its ``top_k`` best by the router's float32 logits, weighted by the softmax
over those ``top_k`` logits.  A chip *holds* the experts ``[first_expert,
first_expert + E_held)``: it routes over all ``E``, and computes, for every
assignment whose expert it holds, that expert's gated FFN of the token, times
the assignment's weight, summed into the token's row.  What the experts it
does not hold would add is not its to compute: on one chip alone the result
is that partial sum (the shares of the chips that together hold all ``E`` add
up to the whole layer); with the ``ep`` mesh axis bound, two all-to-alls carry
the tokens to the chips that hold their experts and the results back, and the
result is the whole layer for the chip's own tokens.

No capacity, no dropping: the assignments are sorted by expert into one
``[T * top_k, D]`` buffer (its worst case: every assignment held), the held
experts multiply their own contiguous groups of rows (``grouped_matmul``:
the megablox kernel on a TPU, ``lax.ragged_dot`` elsewhere; both skip the
rows past the last group), and rows past the last group are zero.  Every
shape is static; only ``group_sizes`` carries the data-dependent counts.

The pieces are separate functions because they are separate Program ops
(``ops/decoder_ops.py``: ``moe_route``, ``moe_dispatch``, ``moe_grouped_matmul``,
``moe_combine``), so a profile tells the matmuls from the permutation around
them.  ``expert_layer`` is the same pieces in one call.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

# rows of one grid step of the megablox kernels, and the widest contraction
# or column tile: a step then holds operands and a float32 accumulator of a
# few MiB of the core's 16 MiB of scoped VMEM, and its matmul (512 x 768 x
# 896) outweighs the ~0.35 us a grid step costs.  At the kernel's default of
# 128 x 128 x 128 the grouped matmuls ran at a tenth of this (docs/moe.md)
_GMM_ROWS = 512
_GMM_MAX_TILE = 768
_LANES = 128


def _tile(width: int) -> int:
    """The widest multiple of 128 that divides ``width``, up to one lane
    group over ``_GMM_MAX_TILE``: 2304 -> 768; 896 = 7 x 128, whose only
    other aligned divisor is 128, is taken whole."""
    best = _LANES
    for t in range(_LANES, min(width, _GMM_MAX_TILE + _LANES) + 1, _LANES):
        if width % t == 0:
            best = t
    return best


class Plan(NamedTuple):
    """Where each of the ``R = T * top_k`` assignments sits in the buffer
    sorted by held expert.  ``order[r]`` is the flat assignment (``t * top_k
    + k``) in row ``r``; ``pos[t, k]`` is the row of assignment ``(t, k)``;
    rows ``>= sum(group_sizes)`` hold the assignments of experts not held."""
    order: jax.Array          # [R] int32
    pos: jax.Array            # [T, top_k] int32
    group_sizes: jax.Array    # [E_held] int32


def route(x, router_w, top_k: int, scoring: str = "softmax", bias=None,
          scale: float = 1.0):
    """(weights [T, top_k] float32, experts [T, top_k] int32) of tokens ``x``
    [T, D] under router ``router_w`` [D, E].  Logits in float32 at the
    highest matmul precision whatever ``x`` is: a bf16 logit moves the
    last-chosen expert.  Two scorings, one function:

    ``softmax``: the ``top_k`` largest logits, weighted by the softmax over
    those (= softmax over all, top-k, renormalised).
    ``sigmoid`` (DeepSeek-V3's ``noaux_tc``): scores ``s = sigmoid(logits)``;
    the ``top_k`` largest of ``s + bias`` (``bias`` [E], the correction that
    balances load without a loss term; it chooses and does not weigh); the
    weights are the chosen ``s`` over their sum.

    Both times ``scale`` (the routed scaling factor)."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if scoring == "softmax":
        top, experts = lax.top_k(logits, top_k)
        weights = jax.nn.softmax(top, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        biased = scores if bias is None else scores + bias.astype(jnp.float32)
        _, experts = lax.top_k(biased, top_k)
        chosen = jnp.take_along_axis(scores, experts, axis=-1)
        weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    else:
        raise ValueError(f"route: scoring {scoring!r} is not 'softmax' or "
                         f"'sigmoid'")
    if scale != 1.0:
        weights = weights * scale
    return weights, experts.astype(jnp.int32)


def dispatch_plan(experts, first_expert: int, num_held: int,
                  max_rows: Optional[int] = None) -> Plan:
    """Sort the assignments ``experts`` [T, top_k] by held expert (stable, so
    a group keeps token order); those of experts outside ``[first_expert,
    first_expert + num_held)`` go last.  ``max_rows`` keeps only that many
    rows of the buffer (a chip that holds an eighth of the experts fills an
    eighth of the worst case): the held assignments must fit, which is the
    caller's to check against ``group_sizes`` (``moe_route`` fails the step
    where they do not)."""
    local = experts - first_expert
    key = jnp.where((local >= 0) & (local < num_held), local,
                    num_held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    pos = jnp.argsort(order).astype(jnp.int32).reshape(experts.shape)
    group_sizes = jnp.sum(key[:, None] == jnp.arange(num_held)[None, :],
                          axis=0, dtype=jnp.int32)
    if max_rows is not None:
        order = order[:max_rows]
    return Plan(order, pos, group_sizes)


def _rows_valid(plan: Plan):
    """[R, 1]: is row ``r`` inside a held expert's group."""
    return (jnp.arange(plan.order.shape[0]) < jnp.sum(plan.group_sizes))[:, None]


def _held(plan: Plan):
    """[T, top_k]: does assignment ``(t, k)`` go to a held expert."""
    return plan.pos < jnp.sum(plan.group_sizes)


@jax.custom_vjp
def dispatch(x, plan: Plan):
    """Rows of ``x`` [T, D] in the plan's order: [R, D], zero past the last
    group (the assignments of experts not held)."""
    top_k = plan.pos.shape[1]
    return jnp.where(_rows_valid(plan),
                     jnp.take(x, plan.order // top_k, axis=0), 0)


def _dispatch_fwd(x, plan):
    return dispatch(x, plan), plan


def _dispatch_bwd(plan, g):
    # the transpose of a gather is a scatter-add; the plan knows the rows of
    # each token, so it is a gather and a sum over top_k instead
    rows = jnp.take(g, plan.pos, axis=0, mode="clip")          # [T, K, D]
    gx = jnp.sum(jnp.where(_held(plan)[..., None], rows, 0)
                 .astype(jnp.float32), axis=1)
    return gx.astype(g.dtype), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(y, weights, plan: Plan):
    """out[t] = sum over the held assignments (t, k) of weights[t, k] *
    y[pos[t, k]]: [T, D] in ``y``'s dtype, accumulated in float32."""
    rows = jnp.take(y, plan.pos, axis=0, mode="clip")          # [T, K, D]
    out = jnp.sum(jnp.where(_held(plan)[..., None], rows.astype(jnp.float32)
                            * weights[..., None].astype(jnp.float32), 0),
                  axis=1)
    return out.astype(y.dtype)


def _combine_fwd(y, weights, plan):
    return combine(y, weights, plan), (y, weights, plan)


def _combine_bwd(res, g):
    y, weights, plan = res
    top_k = plan.pos.shape[1]
    rows = jnp.take(y, plan.pos, axis=0, mode="clip")          # [T, K, D]
    gw = jnp.sum(rows.astype(jnp.float32)
                 * g[:, None, :].astype(jnp.float32), axis=-1)
    gw = jnp.where(_held(plan), gw, 0).astype(weights.dtype)
    w_row = jnp.take(weights.reshape(-1), plan.order)          # [R]
    g_row = jnp.take(g, plan.order // top_k, axis=0)           # [R, D]
    gy = jnp.where(_rows_valid(plan), g_row.astype(jnp.float32)
                   * w_row[:, None].astype(jnp.float32), 0)
    return gy.astype(y.dtype), gw, None


combine.defvjp(_combine_fwd, _combine_bwd)


def gmm_lowering(use_kernel: bool, x, w) -> str:
    """``megablox`` or ``ragged_dot``: the kernel wants the TPU backend, rows
    in whole tiles and lane-aligned widths."""
    if use_kernel and x.shape[0] % _GMM_ROWS == 0 \
            and w.shape[1] % _LANES == 0 and w.shape[2] % _LANES == 0:
        return "megablox"
    return "ragged_dot"


def grouped_matmul(x, w, group_sizes, use_kernel: bool = False):
    """``x`` [R, K] times ``w[e]`` [K, N] for the rows of group ``e``
    (``group_sizes[e]`` rows each, in order): [R, N] in ``x``'s dtype,
    accumulated in float32.  Rows past the last group are zero, in the result
    and in the gradient with respect to ``x``."""
    lowering = gmm_lowering(use_kernel, x, w)
    from ..fluid import trace
    trace.metrics().counter(f"moe.gmm_lowering.{lowering}").inc()
    w = w.astype(x.dtype)
    if lowering == "ragged_dot":
        return lax.ragged_dot(x, w, group_sizes,
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)
    # the kernel never visits the tiles past the last group: what it leaves
    # there (and in their gradient) is whatever the buffer held
    valid = (jnp.arange(x.shape[0]) < jnp.sum(group_sizes))[:, None]
    return jnp.where(valid, _megablox(jnp.where(valid, x, 0), w,
                                      group_sizes), 0)


def _megablox_kernels():
    # the package re-exports its differentiable ``gmm`` under the module's
    # name: the kernels themselves (``gmm``, ``tgmm``) are in the module
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


@jax.custom_vjp
def _megablox(x, w, group_sizes):
    """jax's megablox grouped matmul with a tile plan per call: its own
    ``ops.gmm`` hands one tiling to the forward and both backward kernels,
    and 2304 and 896 share no tile wider than 128."""
    backend = _megablox_kernels()
    return backend.gmm(x, w, group_sizes, preferred_element_type=x.dtype,
                       tiling=(_GMM_ROWS, _tile(w.shape[1]),
                               _tile(w.shape[2])))


def _megablox_fwd(x, w, group_sizes):
    return _megablox(x, w, group_sizes), (x, w, group_sizes)


def _megablox_bwd(res, g):
    backend = _megablox_kernels()
    x, w, group_sizes = res
    k, n = w.shape[1], w.shape[2]
    gx = backend.gmm(g, w, group_sizes, preferred_element_type=x.dtype,
                     tiling=(_GMM_ROWS, _tile(n), _tile(k)),
                     transpose_rhs=True)
    gw = backend.tgmm(x.swapaxes(0, 1), g, group_sizes,
                      preferred_element_type=w.dtype,
                      tiling=(_GMM_ROWS, _tile(k), _tile(n)),
                      num_actual_groups=w.shape[0])
    return gx, gw, None


_megablox.defvjp(_megablox_fwd, _megablox_bwd)


def held_ffn(xs, group_sizes, w_gate, w_up, w_down, use_kernel=False):
    """The held experts' gated FFNs over their groups of rows:
    ``W_down(silu(W_gate x) * W_up x)``."""
    gm = functools.partial(grouped_matmul, group_sizes=group_sizes,
                           use_kernel=use_kernel)
    return gm(jax.nn.silu(gm(xs, w_gate)) * gm(xs, w_up), w_down)


def expert_layer(x, router_w, w_gate, w_up, w_down, *, top_k: int,
                 first_expert: int = 0, axis_name: Optional[str] = None,
                 use_kernel: bool = False, scoring: str = "softmax",
                 bias=None, scale: float = 1.0):
    """The layer over tokens ``x`` [T, D]: router ``router_w`` [D, E], held
    experts ``w_gate``/``w_up`` [E_held, D, F] and ``w_down`` [E_held, F, D].

    ``axis_name=None``: this chip's share, the held experts' part of every
    token's result (the whole layer when it holds all ``E``).  With the
    ``ep`` axis bound (inside ``shard_map``; ``first_expert`` is then the
    axis index times ``E_held``, taken from the axis), ``x`` is this chip's
    tokens and the result is the whole layer for them: tokens travel to the
    chips that hold their experts and back.

    ``scoring``, ``bias``, ``scale``: ``route``'s.  A shared expert is no
    part of this function: every chip holds it whole beside the routed ones
    (``fluid.layers.expert_layer(shared_size=)``)."""
    weights, experts = route(x, router_w, top_k, scoring, bias, scale)
    num_held = w_gate.shape[0]
    if axis_name is None:
        plan = dispatch_plan(experts, first_expert, num_held)
        ys = held_ffn(dispatch(x, plan), plan.group_sizes, w_gate, w_up,
                      w_down, use_kernel)
        return combine(ys, weights, plan)

    n = lax.axis_size(axis_name)
    t, d = x.shape
    # by destination chip: one [cap, D] slab each, cap the worst case (every
    # assignment of every token on one chip), so nothing is ever dropped
    cap = t * min(top_k, num_held)
    by_chip = dispatch_plan(experts // num_held, 0, n)
    start = jnp.cumsum(by_chip.group_sizes) - by_chip.group_sizes
    slot = jnp.arange(cap)[None, :]
    filled = slot < by_chip.group_sizes[:, None]               # [n, cap]
    row = jnp.where(filled, start[:, None] + slot, 0)
    sent = jnp.where(filled[..., None],
                     jnp.take(dispatch(x, by_chip), row, axis=0), 0)
    sent_expert = jnp.where(
        filled, jnp.take(experts.reshape(-1), by_chip.order)[row] % num_held,
        num_held)
    got = lax.all_to_all(sent, axis_name, 0, 0, tiled=True)
    got_expert = lax.all_to_all(sent_expert, axis_name, 0, 0, tiled=True)
    # each received row is one assignment to a held expert (or an empty slot)
    here = dispatch_plan(got_expert.reshape(-1, 1), 0, num_held)
    ys = held_ffn(dispatch(got.reshape(-1, d), here), here.group_sizes,
                  w_gate, w_up, w_down, use_kernel)
    back = combine(ys, jnp.ones((n * cap, 1), jnp.float32), here)
    back = lax.all_to_all(back.reshape(n, cap, d), axis_name, 0, 0,
                          tiled=True)
    # slab (chip, slot) is row start[chip] + slot of the by-chip order
    chip = experts // num_held
    at = chip * cap + (by_chip.pos - start[chip])
    rows = jnp.take(back.reshape(n * cap, d), at, axis=0)      # [T, K, D]
    return jnp.sum(rows.astype(jnp.float32) * weights[..., None],
                   axis=1).astype(x.dtype)


def moe_partition_rules(axis: str = "ep"):
    """Placement through the shared rule engine (parallel/sharding.py): the
    router replicates (every chip routes over all experts), the experts'
    weights shard their expert dimension over ``axis``."""
    from jax.sharding import PartitionSpec as P
    return [
        (r"(^|[_/.])router(\.w)?$", P()),
        (r"(^|[_/.])experts\.(gate|up|down)$", P(axis, None, None)),
    ]
