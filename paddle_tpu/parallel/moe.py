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
rows past the last group), and rows past the last group are zero, which
since PR 35 also means never read: what reads the buffer (``combine``, its
gradient, ``dispatch``'s gradient) visits its first ``sum(group_sizes)``
rows in blocks of 512 under a trip count read from the plan, so a chip that
holds a quarter of the experts moves a quarter of the buffer, and no
operator gathers a row per assignment (``[T, top_k, D]``).  Every shape is
static; only ``group_sizes`` carries the data-dependent counts.

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


def _num_held(plan: Plan):
    """``n``: the rows of the buffer that hold an assignment of a held
    expert, ``sum(group_sizes)``."""
    return jnp.sum(plan.group_sizes)


def _zero_from(n, rows, start=0):
    """``rows`` (the buffer's rows from ``start`` on) with those from row
    ``n`` of the buffer on set to zero."""
    keep = start + jnp.arange(rows.shape[0]) < n
    return jnp.where(keep.reshape((-1,) + (1,) * (rows.ndim - 1)), rows, 0)


def _visit_held_blocks(n, like, block_fn):
    """Buffers shaped ``like`` (``[R, ...]`` each) whose rows ``[0, n)`` are
    ``block_fn``'s and whose other rows are zero, visiting the held rows
    alone: ``block_fn(start, size)`` gives every buffer's rows ``[start,
    start + size)``; blocks of ``_GMM_ROWS`` rows, ``ceil(n / _GMM_ROWS)``
    trips.  The shapes are static, the trip count is the data's (one
    ``while``, the buffers updated in place); the rows past the last visited
    block cost the one fill."""
    rows = like[0].shape[0]
    size = min(_GMM_ROWS, rows)

    def first(i):
        # a last block that would overhang starts early and rewrites rows
        # an earlier trip wrote, with the same values
        return jnp.clip(i * size, 0, rows - size)

    def body(i, bufs):
        return tuple(
            lax.dynamic_update_slice_in_dim(buf, block.astype(buf.dtype),
                                            first(i), 0)
            for buf, block in zip(bufs, block_fn(first(i), size)))
    trips = (n + size - 1) // size
    bufs = lax.fori_loop(0, trips, body,
                         tuple(jnp.zeros(b.shape, b.dtype) for b in like))
    # the last visited block alone can hold rows from n on: one more pass
    # over it, not a select in every trip
    last = first(trips - 1)
    return tuple(
        lax.dynamic_update_slice_in_dim(buf, _zero_from(
            n, lax.dynamic_slice_in_dim(buf, last, size, 0), last), last, 0)
        for buf in bufs)


# tokens of one tile of the segment sum: the held rows of a tile's tokens
# are gathered side by side, and one grid step of the kernel adds a block of
# them into the tile's [128, D] result
_TOKEN_TILE = 128


def permutation_lowering(use_kernel: bool, rows, num_tokens: int) -> str:
    """``pallas`` or ``xla``, for the sum of each token's held rows: the
    kernel wants the TPU backend and the shapes ``held_rows_sum_supported``
    states."""
    from ..ops import pallas_kernels
    if use_kernel and pallas_kernels.held_rows_sum_supported(
            rows, num_tokens, _TOKEN_TILE):
        return "pallas"
    return "xla"


def _by_token_tile(plan: Plan, token_tile: int):
    """The held rows regrouped by tile of ``token_tile`` tokens, from the
    plan alone and without a sort: inside a held expert's group the rows are
    in assignment order (the plan's sort is stable), so the rows of (tile,
    expert) are one run of the buffer, and a tile's rows are its experts'
    runs one after the other.  A run keeps its order, so a row moves by its
    run's ``shift``, and everything is counts of ``[tiles, held experts]``
    and their running sums.  Returns ``sizes`` [tiles] (held rows a tile),
    ``at`` [T, top_k] (where assignment ``(t, k)``'s row goes, -1: not held)
    and ``index_block`` (the buffer rows that come to lie at ``[start, start
    + size)``)."""
    num_tokens, top_k = plan.pos.shape
    num_held = plan.group_sizes.shape[0]
    tiles = num_tokens // token_tile
    ends = jnp.cumsum(plan.group_sizes)
    starts = ends - plan.group_sizes
    hot = ((plan.pos[..., None] >= starts) & (plan.pos[..., None] < ends)
           ).reshape(tiles, token_tile * top_k, num_held)
    runs = jnp.sum(hot, axis=1, dtype=jnp.int32)            # [tiles, held]
    flat = runs.reshape(-1)
    run_to = (jnp.cumsum(flat) - flat).reshape(tiles, num_held)
    shift = starts[None, :] + jnp.cumsum(runs, axis=0) - runs - run_to
    at = plan.pos - jnp.sum(jnp.where(hot, shift[:, None, :], 0),
                            axis=-1).reshape(num_tokens, top_k)
    at = jnp.where(plan.pos < ends[-1], at, -1)
    # a position's run: its tile (the last that begins at or before it; an
    # empty one begins where the next does), then the last of the tile's runs
    # that begins at or before it; the tile's row of the two tables comes
    # through a one-hot matmul, exact on integers at the highest precision
    steps = jnp.concatenate([shift[:, :1], jnp.diff(shift, axis=1)], axis=1)
    table = jnp.concatenate([run_to, steps], axis=1).astype(jnp.float32)

    def index_block(start, size):
        to = start + jnp.arange(size, dtype=jnp.int32)
        tile = jnp.sum(to[:, None] >= run_to[None, :, 0], axis=1) - 1
        mine = jnp.dot(jax.nn.one_hot(tile, tiles, dtype=jnp.float32), table,
                       precision=lax.Precision.HIGHEST).astype(jnp.int32)
        return to + jnp.sum(jnp.where(to[:, None] >= mine[:, :num_held],
                                      mine[:, num_held:], 0), axis=1)
    return jnp.sum(runs, axis=1), at, index_block


def _sum_by_token(rows, weights, plan: Plan, use_kernel: bool):
    """out[t] = sum over the held assignments (t, k) of weights[t, k] *
    rows[pos[t, k]] ([T, D] in ``rows``'s dtype, summed in float32;
    ``weights`` None: 1): the transpose of the dispatch gather, without a
    row per assignment.  The held rows are regrouped by tile of tokens, then
    each token's are added: on a TPU by ``held_rows_sum_tpu``, which reads a
    tile's rows once and lets the matmul unit add them (float32 products
    and sums; a float32 weight enters as three bfloat16 terms whose sum it
    is exactly), elsewhere one ``k`` at a time.

    The regrouping gather reads ``rows`` from HBM, about 37 ns a row in one
    piece and 45 to 50 in blocks: up to two thirds of the buffer held, the
    held rows alone are visited (``_visit_held_blocks``: the fill and ``n``
    rows); a fuller buffer is permuted whole, in one piece (its rows from
    ``n`` on then hold the last held row, which nothing reads: the kernel
    picks a row only through ``at``)."""
    lowering = permutation_lowering(use_kernel, rows, plan.pos.shape[0])
    from ..fluid import trace
    trace.metrics().counter(f"moe.permutation_lowering.{lowering}").inc()
    return _sum_by_token_jit(rows, weights, plan, lowering)


@functools.partial(jax.jit, static_argnums=(3,))
def _sum_by_token_jit(rows, weights, plan: Plan, lowering: str):
    """One trace per shapes, shared by a program's expert layers."""
    num_tokens, top_k = plan.pos.shape
    total, n = rows.shape[0], _num_held(plan)
    tile = _TOKEN_TILE if num_tokens % _TOKEN_TILE == 0 else num_tokens
    sizes, at, index_block = _by_token_tile(plan, tile)

    def gather(start, size):
        # a position from n on reads the last held row: what lies past it
        # in ``rows`` need not be finite
        return (jnp.take(rows, jnp.minimum(index_block(start, size), n - 1),
                         axis=0, mode="clip"),)
    by_tile, = lax.cond(3 * n > 2 * total, lambda: gather(0, total),
                        lambda: _visit_held_blocks(n, (rows,), gather))
    if lowering == "pallas":
        from ..ops import pallas_kernels
        return pallas_kernels.held_rows_sum_tpu(by_tile, at, weights, sizes,
                                                tile)
    out = jnp.zeros((num_tokens, rows.shape[1]), jnp.float32)
    for k in range(top_k):
        term = jnp.take(by_tile, at[:, k], axis=0,
                        mode="clip").astype(jnp.float32)
        if weights is not None:
            term = term * weights[:, k, None].astype(jnp.float32)
        out = out + jnp.where(at[:, k, None] >= 0, term, 0)
    return out.astype(rows.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def dispatch(x, plan: Plan, use_kernel: bool = False):
    """Rows of ``x`` [T, D] in the plan's order: [R, D], zero past the last
    group (the assignments of experts not held).  One gather from ``x`` with
    a zero row appended, which the rows past the last group read: no select
    pass over the buffer follows it (that pass was half of the 1.4 ms this
    took at [65536, 2304]; ``x`` is small enough to wait on the core, so the
    gather runs at the speed the buffer is written, whatever the share
    held).  ``use_kernel`` is the gradient's."""
    top_k = plan.pos.shape[1]
    at = jnp.where(jnp.arange(plan.order.shape[0]) < _num_held(plan),
                   plan.order // top_k, x.shape[0])
    return jnp.take(jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:],
                                                  x.dtype)]),
                    at, axis=0, mode="clip")


def _dispatch_fwd(x, plan, use_kernel):
    return dispatch(x, plan, use_kernel), plan


def _dispatch_bwd(use_kernel, plan, g):
    # the transpose of a gather is a scatter-add; the plan knows the rows of
    # each token, so it is a regrouping gather and a sum of each token's
    # rows instead
    return _sum_by_token(g, None, plan, use_kernel), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def combine(y, weights, plan: Plan, use_kernel: bool = False):
    """out[t] = sum over the held assignments (t, k) of weights[t, k] *
    y[pos[t, k]]: [T, D] in ``y``'s dtype, accumulated in float32."""
    return _sum_by_token(y, weights, plan, use_kernel)


def _combine_fwd(y, weights, plan, use_kernel):
    return combine(y, weights, plan, use_kernel), (y, weights, plan)


def _combine_bwd(use_kernel, res, g):
    return _combine_bwd_jit(*res, g) + (None,)


@jax.jit
def _combine_bwd_jit(y, weights, plan: Plan, g):
    """One trace per shapes, shared by a program's expert layers."""
    top_k, n = plan.pos.shape[1], _num_held(plan)
    flat_w = weights.reshape(-1).astype(jnp.float32)

    def block(start, size):
        # row r's token gradient, once: times the row's weight it is y's
        # gradient, against y's row it is the weight's
        at = lax.dynamic_slice_in_dim(plan.order, start, size)
        g_row = jnp.take(g, at // top_k, axis=0,
                         mode="clip").astype(jnp.float32)
        y_row = lax.dynamic_slice_in_dim(y, start, size, 0)
        return (g_row * jnp.take(flat_w, at, mode="clip")[:, None],
                jnp.sum(y_row.astype(jnp.float32) * g_row, axis=-1))
    like = (y, jax.ShapeDtypeStruct(plan.order.shape, jnp.float32))

    # as in ``_sum_by_token``: the held rows alone up to two thirds of the
    # buffer, a fuller one whole
    gy, dot = lax.cond(
        3 * n > 2 * y.shape[0],
        lambda: tuple(_zero_from(n, b).astype(buf.dtype) for b, buf in zip(
            block(0, y.shape[0]), like)),
        lambda: _visit_held_blocks(n, like, block))
    gw = jnp.where(plan.pos < n, jnp.take(dot, plan.pos, mode="clip"), 0)
    return gy, gw.astype(weights.dtype)


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
    return _megablox(x, w, group_sizes)


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
    and 2304 and 896 share no tile wider than 128.  The kernels never visit
    the tiles past the last group, so what they leave there is whatever the
    buffer held: zeroed here, in the result and in the gradient of ``x``.
    Their operands need no such pass: a row that is not its tile's group's
    is masked where it is loaded (``tgmm``) or where its result is stored
    (``gmm``), so what ``x`` or a cotangent holds past the last group is
    never used."""
    backend = _megablox_kernels()
    return _zero_from(jnp.sum(group_sizes), backend.gmm(
        x, w, group_sizes, preferred_element_type=x.dtype,
        tiling=(_GMM_ROWS, _tile(w.shape[1]), _tile(w.shape[2]))))


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
    return _zero_from(jnp.sum(group_sizes), gx), gw, None


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
        ys = held_ffn(dispatch(x, plan, use_kernel), plan.group_sizes,
                      w_gate, w_up, w_down, use_kernel)
        return combine(ys, weights, plan, use_kernel)

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
                     jnp.take(dispatch(x, by_chip, use_kernel), row, axis=0),
                     0)
    sent_expert = jnp.where(
        filled, jnp.take(experts.reshape(-1), by_chip.order)[row] % num_held,
        num_held)
    got = lax.all_to_all(sent, axis_name, 0, 0, tiled=True)
    got_expert = lax.all_to_all(sent_expert, axis_name, 0, 0, tiled=True)
    # each received row is one assignment to a held expert (or an empty slot)
    here = dispatch_plan(got_expert.reshape(-1, 1), 0, num_held)
    ys = held_ffn(dispatch(got.reshape(-1, d), here, use_kernel),
                  here.group_sizes, w_gate, w_up, w_down, use_kernel)
    back = combine(ys, jnp.ones((n * cap, 1), jnp.float32), here, use_kernel)
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
