"""Pallas TPU kernels for the ops where XLA fusion leaves work on the table.

* attention — XLA writes the [B, H, T, T] scores, probabilities and dropout
  mask to HBM and reads them back, forward and backward.  Two kernels, picked
  by ``ops.attention.attention_path``: the fused attention kernel for the
  lengths BERT runs (T <= 512: whole score rows on the core, in-kernel
  dropout on the probabilities, the padding bias as its [B, 1, 1, T] row),
  and jax's production flash kernel, which streams K/V blocks through VMEM,
  for long dropout-free sequences (SURVEY §7 step 3: "Pallas kernels only
  where XLA fusion falls short, e.g. fused attention").
* fused dropout — the jax.random path writes per-element uniforms and a
  bool mask residual to HBM.  Here the mask is derived from the on-core
  hardware PRNG (pltpu.prng_random_bits) and the backward pass RE-SEEDS the
  same PRNG to regenerate it — zero mask bytes written, zero residuals
  saved.  What this buys per step is not measured on this code.
* hyper-connection mixers — a token's n float32 residual streams lie in one
  row [n * d], and XLA reads that row once per use (the projection, the mean
  square, the branch input, each again in backward: 6.5 / 11.3 / 2.9 / 6.3
  passes over the streams for mix, its gradient, merge, its gradient).  Five
  kernels hold a tile of whole rows in VMEM and do all an op needs of it in
  one visit (1.25 / 3.5 / 2.25 / 3.5 passes); the Sinkhorn iterations run on
  the core forward and stay in XLA, on [24, T] arrays, backward.
* attention over a key set the device chose a moment ago — a flash kernel
  whose mask is data: one [S, S] int8 selection, each tile of it read once
  for all the query heads of a key/value group; and the loss that trains
  the chooser, whose [heads, rows, keys] index scores and their gradient
  XLA wrote to HBM a 128-query block at a time: two passes that keep a
  tile's scores on the core.
* the expert layer's segment sum — each token's weighted sum of its held
  experts' rows, which XLA gathered as [T, top_k, D], a row at a time,
  padding included: the rows regrouped by tile of tokens are read once and
  added on the matmul unit (parallel/moe.py, docs/moe.md).
* paged decode attention, fused embedding gather+pool, bucketed optimizer
  updates — see each section.

The callers (ops/attention.py, ops/nn_ops.py, ops/ctr_ops.py,
ops/optimizer_ops.py, ops/decoder_ops.py, parallel/moe.py) take these on the ``tpu`` backend
only; every kernel here is compiled by Mosaic and checked against its XLA
reference by ``chip_smoke.py``'s kernel roll-call.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.flash_attention import (
    flash_attention as _jax_flash_attention)

from .selective_scan import CHUNK as _SSM_CHUNK

__all__ = ["flash_attention_tpu", "fused_attention_tpu", "fused_dropout_tpu",
           "fused_dropout_add_tpu", "fused_act_dropout_tpu",
           "fused_embedding_pool_tpu", "embedding_pool_grad_tpu",
           "paged_flash_attention_tpu", "hyper_connection_mix_tpu",
           "hyper_connection_merge_tpu", "selected_attention_tpu",
           "selected_probability_mean_tpu", "index_kl_tpu",
           "held_rows_sum_tpu", "selective_scan_tpu"]

# A pallas_call double-buffers every block it pipelines, and v5e's scoped
# VMEM default is 16 MiB: one block of every operand together stays under
# half of this budget.
_PIPELINE_VMEM_BYTES = 8 << 20
# Sublane tile: 8 rows of f32, 16 of bf16, 32 of the uint8 dropout mask.
# One alignment for all, so a forward, its backward and the mask kernel
# always cut an [m, n] operand into the same blocks.
_ROW_ALIGN = 32


def _block_rows(m: int, row_bytes: int) -> int:
    """Rows per block for a kernel over [m, n] operands whose one row,
    summed over every pipelined operand, is ``row_bytes``.  The whole array
    when it fits (a full dim needs no alignment), else a multiple of
    ``_ROW_ALIGN``; the grid is ``pl.cdiv(m, rows)`` and Pallas masks the
    ragged last block."""
    rows = _PIPELINE_VMEM_BYTES // (2 * row_bytes)
    if rows >= m:
        return m
    return max(_ROW_ALIGN, rows // _ROW_ALIGN * _ROW_ALIGN)


# ---------------------------------------------------------------------------
# flash attention: thin wrapper over jax's production pallas kernel
# ---------------------------------------------------------------------------

def flash_attention_tpu(q, k, v, scale=None, causal=False, ab=None):
    """q/k/v: [B, H, T, D]; ``ab`` an optional additive bias already
    broadcast to [B, H, Tq, Tk] (the kernel's attention-bias argument —
    how a BERT padding mask rides the Pallas path)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _jax_flash_attention(q, k, v, ab=ab, causal=causal,
                                sm_scale=float(scale))


# ---------------------------------------------------------------------------
# causal and sliding-window attention at long sequences: jax's splash
# kernel, which visits only the blocks inside the mask's band (forward, dQ
# and dK/dV), streams K/V through VMEM and shares each key/value head among
# its group of query heads.  Scores, probabilities and the mask never exist
# at [S, S]: the mask is a host-side description of which blocks are full,
# partial or empty.
# ---------------------------------------------------------------------------

# rows and keys of one grid step: at 128 (the kernel's default) the step
# overhead dominates; 512 x 512 float32 scores are 1 MiB of VMEM
_SPLASH_BLOCK = 512


def splash_attention_supported(q, k, v, mask) -> bool:
    """Does the splash kernel cover attention over these operands: no
    additive mask, query heads a multiple of the key/value heads, whole
    blocks, and a head the lanes take.  A head has two widths, the score
    width (the last axis of ``q`` and ``k``, contracted over) and the value
    width (the last axis of ``v`` and of the output); they may differ
    (latent attention scores over 192 = 128 + 64 rotary numbers and carries
    values of 128).  The value width is whole 128-lane groups, because the
    output and its accumulator are tiled by it; the score width is whole
    half groups of 64, because it is only ever contracted over (Mosaic
    compiles 64 and 192 as they stand, no operand is padded with zeros by
    the caller: differential attention scores over 64 and carries the pair
    of 64-wide values side by side, 128).  For one width this is the old
    rule, a multiple of 128."""
    seq = q.shape[2]
    score, value = q.shape[3], v.shape[3]
    return (mask is None and q.ndim == 4 and k.shape[:3] == v.shape[:3]
            and k.shape[2] == seq and k.shape[3] == score
            and value % _LANES == 0 and score % (_LANES // 2) == 0
            and score > 0 and q.shape[1] % k.shape[1] == 0
            and seq % min(_SPLASH_BLOCK, seq) == 0 and seq % _LANES == 0)


@functools.lru_cache(maxsize=16)
def _splash_kernel(seq, q_heads, window):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    mask = sm.LocalMask((seq, seq), (window - 1, 0), 0) if window \
        else sm.CausalMask((seq, seq))
    b = min(_SPLASH_BLOCK, seq)
    blocks = sk.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b, block_q_dkv=b,
        block_kv_dkv=b, block_kv_dkv_compute=b, block_q_dq=b, block_kv_dq=b)
    # the masks are numpy descriptions: build them outside any trace
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha(sm.MultiHeadMask([mask] * q_heads),
                                  block_sizes=blocks, head_shards=1,
                                  q_seq_shards=1)


def splash_attention_tpu(q, k, v, scale=None, window=0):
    """Causal attention, q [B, Hq, S, D], k [B, Hkv, S, D], v [B, Hkv, S,
    Dv] with Hq a multiple of Hkv (``splash_attention_supported`` has the
    rule for D and Dv); ``window`` > 0 keeps 0 <= i - j < window."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    kernel = _splash_kernel(q.shape[2], q.shape[1], int(window))
    # the kernel applies no scale of its own
    return jax.vmap(kernel)((q * scale).astype(q.dtype), k, v)


# ---------------------------------------------------------------------------
# attention over a key set chosen per query on the device (ops/
# sparse_attention.py).  The selection is data, shared by all the heads: a
# bit a pair as it is kept, a byte a pair ([S, S] int8, unpacked by one XLA
# pass before each of forward and backward) as the kernels read it.  A grid
# step holds one 512 x 512 tile of it beside one key/value head's block and the query blocks of ALL the
# heads of that group (8 for 32 : 4), so the tile and the keys are read
# once for 8 heads; blocks above the diagonal hold no causal pair and are
# neither fetched nor computed.  Every tile at or under the diagonal is
# computed whole and masked: with seeded random weights nearly every such
# tile holds a selected pair (``dsa.layer_<i>.tile_occupancy``), so skipping
# empty tiles would find nothing to skip.  (jax's splash kernel under a
# dynamic mask was tried first: it wants the mask tiled per head as int32,
# 1 GiB a kernel call at 16384 tokens, read again by every head:
# docs/sparse_attention.md has the numbers.)  Forward keeps the log-sum-exp;
# backward is two kernels, dq over the key blocks of a query block and
# dk/dv over the query blocks of a key block, summed over the group's heads
# on the core.
# ---------------------------------------------------------------------------

_SEL_BLOCK = 512
_SEL_MASKED = -0.7 * float(np.finfo(np.float32).max)
_NT = (((1,), (1,)), ((), ()))          # a @ b.T


def selected_attention_supported(q, k, v, sel) -> bool:
    """Does the selected-attention kernel cover these operands: grouped or
    equal heads of whole 128-lane groups, one width for scores and values,
    whole 512 x 512 tiles, one packed [S, S / 8] selection a sequence."""
    if q.ndim != 4 or k.ndim != 4 or k.shape != v.shape:
        return False
    b, hq, seq, d = q.shape
    return (k.shape[0] == b and k.shape[2] == seq and k.shape[3] == d
            and hq % k.shape[1] == 0 and d % _LANES == 0
            and seq % _SEL_BLOCK == 0
            and tuple(sel.shape) == (b, seq, seq // 8))


def _sel_params(name):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name=name)


def _sel_bytes(sel):
    """The packed selection [S, S / 8] as the kernels read it: [S, S] int8."""
    from .sparse_attention import unpack_selection
    return unpack_selection(sel).astype(jnp.int8)


def _sel_keep(mask_ref):
    return mask_ref[...].astype(jnp.int32) != 0


def _sel_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                    acc_ref, m_ref, l_ref, *, group):
    i, j = pl.program_id(1), pl.program_id(2)
    bk = k_ref.shape[0]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _SEL_MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j <= i)
    def _():
        keep = _sel_keep(mask_ref)
        k, v = k_ref[...], v_ref[...]
        for g in range(group):
            s = jax.lax.dot_general(q_ref[g], k, _NT,
                                    preferred_element_type=jnp.float32)
            s = jnp.where(keep, s, _SEL_MASKED)
            m_prev, l_prev = m_ref[g], l_ref[g]
            m_next = jnp.maximum(m_prev, s.max(axis=-1)[:, None])
            p = jnp.exp(s - jnp.tile(m_next, (1, bk // _LANES)))
            alpha = jnp.exp(m_prev - m_next)
            l_ref[g] = alpha * l_prev + jax.lax.broadcast_in_dim(
                p.sum(axis=-1), l_prev.shape, (0,))
            m_ref[g] = m_next
            acc_ref[g] = acc_ref[g] * jnp.tile(
                alpha, (1, acc_ref.shape[-1] // _LANES)) + jnp.dot(
                    p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        for g in range(group):
            l = l_ref[g]
            o_ref[g] = (acc_ref[g] * jnp.tile(
                1.0 / l, (1, acc_ref.shape[-1] // _LANES))
            ).astype(o_ref.dtype)
            lse_ref[g] = jnp.log(l) + m_ref[g]


def _sel_dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, di_ref,
                   dq_ref, acc_ref, *, group):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j <= i)
    def _():
        keep = _sel_keep(mask_ref)
        k, v = k_ref[...], v_ref[...]
        for g in range(group):
            s = jax.lax.dot_general(q_ref[g], k, _NT,
                                    preferred_element_type=jnp.float32)
            p = jnp.exp(jnp.where(keep, s, _SEL_MASKED)
                        - jnp.expand_dims(lse_ref[g], -1))
            dp = jax.lax.dot_general(do_ref[g], v, _NT,
                                     preferred_element_type=jnp.float32)
            ds = (dp - jnp.expand_dims(di_ref[g], -1)) * p
            acc_ref[g] += jnp.dot(ds.astype(k.dtype), k,
                                  preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _sel_dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, di_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, group):
    j, i = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(i >= j)
    def _():
        # scores with the keys on the rows: the tile is turned once, for all
        # the heads, and the queries' log-sum-exp is a row
        keep = mask_ref[...].astype(jnp.float32).T != 0.0
        k, v = k_ref[...], v_ref[...]
        for g in range(group):
            q, do = q_ref[g], do_ref[g]
            s = jax.lax.dot_general(k, q, _NT,
                                    preferred_element_type=jnp.float32)
            p = jnp.exp(jnp.where(keep, s, _SEL_MASKED) - lse_ref[g:g + 1, :])
            dv_acc[...] += jnp.dot(p.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(v, do, _NT,
                                     preferred_element_type=jnp.float32)
            ds = (dp - di_ref[g:g + 1, :]) * p
            dk_acc[...] += jnp.dot(ds.astype(q.dtype), q,
                                   preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _sel_specs(group, d, rows_then_cols):
    """Block specs of one grid step: ``rows_then_cols`` says whether the
    grid is (head, query block, key block) or (head, key block, query
    block).  The block of the inner axis is clamped to the diagonal, so the
    steps that compute nothing fetch nothing new."""
    b = _SEL_BLOCK
    if rows_then_cols:
        def at(h, i, j):
            return h, i, jnp.minimum(i, j)
    else:
        def at(h, j, i):
            return h, jnp.maximum(i, j), j
    heads = pl.BlockSpec((None, group, b, d),
                         lambda *g: (at(*g)[0], 0, at(*g)[1], 0))
    keys = pl.BlockSpec((None, b, d), lambda *g: (at(*g)[0], at(*g)[2], 0))
    mask = pl.BlockSpec((b, b), lambda *g: at(*g)[1:])
    rows = pl.BlockSpec((None, group, b), lambda *g: (at(*g)[0], 0,
                                                      at(*g)[1]))
    return heads, keys, mask, rows


@jax.custom_vjp
def _selected_attention_one(q, k, v, sel):
    """(out, the queries' log-sum-exp [Hkv, G, S]); no gradient passes
    through the log-sum-exp."""
    return _selected_attention_fwd(q, k, v, sel)[0]


def _selected_attention_fwd(q, k, v, sel):
    """q [Hkv, G, S, D] (already scaled), k, v [Hkv, S, D], sel [S, S / 8]
    uint8 (a bit a pair) -> out [Hkv, G, S, D]."""
    hkv, group, seq, d = q.shape
    pairs = _sel_bytes(sel)
    n = seq // _SEL_BLOCK
    heads, keys, mask, _ = _sel_specs(group, d, True)
    out, lse = pl.pallas_call(
        functools.partial(_sel_fwd_kernel, group=group),
        grid=(hkv, n, n),
        in_specs=[heads, keys, keys, mask],
        out_specs=[heads, pl.BlockSpec(
            (None, group, _SEL_BLOCK, _LANES), lambda h, i, j: (h, 0, i, 0))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((hkv, group, seq, _LANES),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((group, _SEL_BLOCK, d), jnp.float32),
                        pltpu.VMEM((group, _SEL_BLOCK, _LANES), jnp.float32),
                        pltpu.VMEM((group, _SEL_BLOCK, _LANES), jnp.float32)],
        **_sel_params("selected_attention_fwd"))(q, k, v, pairs)
    lse = lse[..., 0]
    return (out, lse), (q, k, v, sel, out, lse)


def _selected_attention_bwd(res, cotangents):
    q, k, v, sel, out, lse = res
    do = cotangents[0]
    hkv, group, seq, d = q.shape
    n = seq // _SEL_BLOCK
    # unpacked again, and only once the output's gradient is there: without
    # the barrier XLA merges this with the forward's unpacking and keeps the
    # byte-a-pair array (256 MiB a layer at 16384 tokens) across the step
    sel, do = jax.lax.optimization_barrier((sel, do))
    pairs = _sel_bytes(sel)
    di = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    do = do.astype(q.dtype)
    heads, keys, mask, rows = _sel_specs(group, d, True)
    dq = pl.pallas_call(
        functools.partial(_sel_dq_kernel, group=group),
        grid=(hkv, n, n),
        in_specs=[heads, keys, keys, mask, heads, rows, rows],
        out_specs=heads,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((group, _SEL_BLOCK, d), jnp.float32)],
        **_sel_params("selected_attention_dq"))(q, k, v, pairs, do, lse, di)
    heads, keys, mask, rows = _sel_specs(group, d, False)
    dk, dv = pl.pallas_call(
        functools.partial(_sel_dkv_kernel, group=group),
        grid=(hkv, n, n),
        in_specs=[heads, keys, keys, mask, heads, rows, rows],
        out_specs=[keys, keys],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((_SEL_BLOCK, d), jnp.float32),
                        pltpu.VMEM((_SEL_BLOCK, d), jnp.float32)],
        **_sel_params("selected_attention_dkv"))(q, k, v, pairs, do, lse, di)
    return dq, dk, dv, None


_selected_attention_one.defvjp(_selected_attention_fwd,
                               _selected_attention_bwd)


def selected_attention_tpu(q, k, v, sel, scale=None):
    """Softmax attention over each query's selected keys: q [B, Hq, S, D],
    k, v [B, Hkv, S, D], sel [B, S, S / 8] uint8 (``sparse_attention.
    pack_selection``: bit set, query t attends key s <= t; every query
    attends at least one key) -> (out [B, Hq, S, D], the log-sum-exp of
    every query's scaled scores over its keys [B, Hq, S] float32)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, hq, seq, d = q.shape
    hkv = k.shape[1]
    qs = (q * scale).astype(q.dtype).reshape(b, hkv, hq // hkv, seq, d)
    # one sequence after the other, traced once whatever the batch
    out, lse = jax.lax.map(lambda one: _selected_attention_one(*one),
                           (qs, k, v, sel))
    # no gradient passes through the log-sum-exp (and a cotangent for it
    # would not get through the ``lax.map`` above: jax hands the kernel's vjp
    # the stacked zeros)
    return (out.reshape(b, hq, seq, d),
            jax.lax.stop_gradient(lse.reshape(b, hq, seq)))


def _sel_pbar_kernel(q_ref, k_ref, lse_ref, mask_ref, o_ref, *, first_block,
                     scale):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j <= first_block + i)
    def _():
        keep = _sel_keep(mask_ref)
        hkv, group = q_ref.shape[0], q_ref.shape[1]
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for h in range(hkv):
            k = k_ref[h]
            for g in range(group):
                s = jax.lax.dot_general(
                    q_ref[h, g], k, _NT,
                    preferred_element_type=jnp.float32) * scale
                acc += jnp.exp(jnp.where(keep, s, _SEL_MASKED)
                               - jnp.expand_dims(lse_ref[h, g], -1))
        o_ref[...] = acc * (1.0 / (hkv * group))

    @pl.when(j > first_block + i)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def selected_probability_mean_tpu(qg, k, lse, sel, scale, r0, rows, extent):
    """The heads' mean attention probability of the queries ``r0 .. r0 +
    rows`` for the keys ``0 .. extent``: [rows, extent] float32, ``exp(q . k
    * scale - lse)`` where the pair is selected and 0 elsewhere, averaged
    over all the heads on the core (``sparse_attention.probability_mean`` is
    the ``jnp`` spelling).  ``qg`` [Hkv, G, S, D], ``k`` [Hkv, S, D],
    ``lse`` [Hkv, G, S] float32, ``sel`` [S, S / 8] uint8; ``r0``, ``rows``
    and ``extent`` static, whole 512-blocks.  A grid step holds one 512 x
    512 tile of the selection, the query blocks of all the heads and one
    key block of every key/value head."""
    hkv, group, seq, d = qg.shape
    b = _SEL_BLOCK
    first = r0 // b
    pairs = _sel_bytes(sel[r0:r0 + rows])                   # [rows, S] int8

    def col(i, j):
        return jnp.minimum(j, first + i)
    return pl.pallas_call(
        functools.partial(_sel_pbar_kernel, first_block=first,
                          scale=float(scale)),
        grid=(rows // b, extent // b),
        in_specs=[
            pl.BlockSpec((hkv, group, b, d),
                         lambda i, j: (0, 0, first + i, 0)),
            pl.BlockSpec((hkv, b, d), lambda i, j: (0, col(i, j), 0)),
            pl.BlockSpec((hkv, group, b), lambda i, j: (0, 0, first + i)),
            pl.BlockSpec((b, b), lambda i, j: (i, col(i, j)))],
        out_specs=pl.BlockSpec((b, b), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, extent), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name="selected_probability_mean")(qg, k, lse, pairs)


# ---------------------------------------------------------------------------
# the indexer's loss (ops/sparse_attention.py, ``index_kl_loss``): the KL
# from the heads' mean attention probabilities ``p`` to the softmax over the
# set of the index scores ``I[t, s] = sum_h W[t, h] relu(QI[h, t] . KI[s])``,
# and its gradients for QI, KI and W.  XLA wrote every [heads, rows, keys]
# float32 intermediate of the scores and of their vjp to HBM (128 MiB each a
# 128-query block at 16384 keys).  Here a grid step holds one tile of ``p``
# and of the selection and forms the heads' scores of that tile on the core,
# one head after the other: nothing with a head axis and a key axis leaves
# VMEM.  Two passes over the causal tiles of a super block of queries:
#
# 1. ``_idx_stats_kernel``: I, an online max / sum over the key tiles for
#    ``lse_I[t]``, and two running sums that give the loss without a second
#    pass: KL[t] = sum p (log p - I) + lse_I[t] sum p over the held pairs.
# 2. ``_idx_grad_kernel``: I again (the heads' pre-activations z_h kept in
#    VMEM), dI = keep (exp(I - lse_I) sum p - p), and with u_h = dI (z_h > 0),
#    the head's weight left out: G[h] = sum over the key tiles of KI^T u_h
#    gives both dQI[h] = W[:, h] G[h] and dW[:, h] = QI[h] . G[h] (relu(z_h) =
#    (z_h > 0) QI[h] . KI): two [DI, rows] products a row block instead of
#    three more passes over every tile of every head; the weight reaches dKI
#    through its other operand, dKI += u_h (W[:, h] QI[h]), into the whole
#    [extent, DI] float32 gradient, which stays resident for the call.
#
# Layout: a tile is worked on with the KEYS on the rows and the queries on
# the lanes (``p`` and the selection tile are turned once a grid step, as the
# selected attention's dk/dv kernel turns its tile).  Every per-query
# quantity (a head's weights, lse_I, sum p, the KL, dW) is then a row over
# the lanes, and all three matmuls of a head are plain or transposed-right
# ones: z_h^T = KI QI[h]^T, G[h]^T += KI^T u_h, dKI += u_h (W QI[h])^T.  The
# gradient pass takes QI and hands dQI over as [HI, DI, S], the queries on
# the lanes: that is how XLA itself lays out a [.., S, 64] array, while a
# row-major [S, 64] bfloat16 array lies in 128 lanes, and what the kernels
# ask decides QI's layout in the whole step and with it its gradient's (64
# MiB a layer from the forward to the backward pass, or 32).  The first pass
# takes QI row-major as the Program has it (its matmul is a transposed-right
# one either way).  What the step reserves at its fullest moment, the start
# of backward, by what the two passes are given (AOT for v5e at the cell's
# shape, PR 33; the parent 9.563 GB): both row-major 9.737 (the gradients
# padded), both turned 9.930 (XLA's rematerialisation then keeps one more
# 512 MiB gather of the expert layers: it stops wherever it is under its
# limit), this mix 9.654.
#
# Precision as the ``jnp`` spelling: QI, KI in the caller's dtype as matmul
# operands with float32 accumulation; everything between in float32; u_h and
# W[:, h] QI[h] rounded to the operand dtype only as operands of the gradient
# matmuls.
# ---------------------------------------------------------------------------

# query rows (lanes) of one grid step: the gradient pass keeps 16 heads'
# [512, rows] float32 pre-activations (8 MiB at 256) beside the resident
# dKI.  The loops over the heads are unrolled whole: rolled (``fori_loop``)
# the scheduler cannot put a head's vector work under the next head's matmul
# and the two passes take 26.5 ms a layer at 16384 tokens, in groups of 2 /
# 4 / 8 heads 20.4 / 17.6 / 16.2, unrolled 14.0 (my chip runs, PR 33, on the
# first version, queries on the rows; 11.8 as they stand)
_IDX_STAT_ROWS = 512
_IDX_GRAD_ROWS = 256


def index_loss_supported(qi, ki, w, q, k, sel, super_rows) -> bool:
    """Do the indexer-loss kernels cover these operands: what the selected
    attention covers of ``q``, ``k`` and ``sel``, super blocks of whole 512
    x 512 tiles, and index heads ``qi`` [B, HI, S, DI], ``ki`` [B, S, DI]
    of one dtype with DI whole half lane groups (64: what the chip has run)
    and ``w`` [B, S, HI] with HI whole sublane groups."""
    if not selected_attention_supported(q, k, k, sel) or qi.ndim != 4:
        return False
    b, hi, seq, di = qi.shape
    return (super_rows % _SEL_BLOCK == 0 and qi.dtype == ki.dtype
            and (b, seq) == (q.shape[0], q.shape[2])
            and ki.shape == (b, seq, di) and w.shape == (b, seq, hi)
            and di % (_LANES // 2) == 0 and hi % 8 == 0)


def _idx_last_block(i, rows, first_block):
    """The key tile that holds the diagonal of row block ``i``."""
    return first_block + (i * rows) // _SEL_BLOCK


def _idx_tile(p_ref, mask_ref):
    """(p, keep) of the grid step with the keys on the rows."""
    return p_ref[...].T, mask_ref[...].astype(jnp.float32).T != 0.0


def _idx_scores(z_of, hi, wt_ref, z_ref=None):
    """The tile's index scores [keys, rows] float32, a head after the
    other, ``z_of(h)`` head ``h``'s pre-activations, which go to
    ``z_ref[h]`` where one is given."""
    acc = None
    for h in range(hi):
        z = z_of(h)
        if z_ref is not None:
            z_ref[h] = z
        term = jnp.maximum(z, 0.0) * wt_ref[h:h + 1, :]
        acc = term if acc is None else acc + term
    return acc


def _idx_stats_kernel(qi_ref, ki_ref, wt_ref, p_ref, mask_ref, kl_ref,
                      lse_ref, sump_ref, m_ref, l_ref, d_ref, c_ref, *,
                      first_block):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _SEL_MASKED)
        for ref in (l_ref, d_ref, c_ref):
            ref[...] = jnp.zeros_like(ref)

    @pl.when(j <= _idx_last_block(i, p_ref.shape[0], first_block))
    def _():
        p, keep = _idx_tile(p_ref, mask_ref)
        ki = ki_ref[...]
        s = _idx_scores(lambda h: jax.lax.dot_general(
            ki, qi_ref[h], _NT, preferred_element_type=jnp.float32),
            qi_ref.shape[0], wt_ref)
        masked = jnp.where(keep, s, _SEL_MASKED)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, masked.max(axis=0, keepdims=True))
        e = jnp.where(keep, jnp.exp(masked - m_next), 0.0)
        l_ref[...] = jnp.exp(m_prev - m_next) * l_ref[...] \
            + e.sum(axis=0, keepdims=True)
        m_ref[...] = m_next
        held = keep & (p > 0.0)
        d_ref[...] += jnp.where(
            held, p * (jnp.log(jnp.where(held, p, 1.0)) - s),
            0.0).sum(axis=0, keepdims=True)
        c_ref[...] += jnp.where(held, p, 0.0).sum(axis=0, keepdims=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        lse = jnp.log(l_ref[...]) + m_ref[...]
        lse_ref[...] = lse
        sump_ref[...] = c_ref[...]
        kl_ref[...] = d_ref[...] + lse * c_ref[...]


def _idx_grad_kernel(qit_ref, ki_ref, wt_ref, p_ref, mask_ref, lse_ref,
                     sump_ref, kit_ref, dwt_ref, dqit_ref, dki_ref, z_ref,
                     qitw_ref, g_acc, *, first_block):
    i, j = pl.program_id(0), pl.program_id(1)
    hi = qit_ref.shape[0]
    bk, di = ki_ref.shape

    @pl.when((i == 0) & (j == 0))
    def _():
        dki_ref[...] = jnp.zeros_like(dki_ref)

    @pl.when(j == 0)
    def _():
        for h in range(hi):
            qitw_ref[h] = (qit_ref[h].astype(jnp.float32)
                           * wt_ref[h:h + 1, :]).astype(qitw_ref.dtype)
        g_acc[...] = jnp.zeros_like(g_acc)

    @pl.when(j <= _idx_last_block(i, p_ref.shape[0], first_block))
    def _():
        p, keep = _idx_tile(p_ref, mask_ref)
        ki, kit = ki_ref[...], kit_ref[...]
        s = _idx_scores(lambda h: jnp.dot(
            ki, qit_ref[h], preferred_element_type=jnp.float32),
            hi, wt_ref, z_ref)
        # d KL / d I = softmax_set(I) sum(p) - p over the set
        d_i = jnp.where(keep, jnp.exp(jnp.where(keep, s, _SEL_MASKED)
                                      - lse_ref[...]) * sump_ref[...] - p,
                        0.0)
        dki = jnp.zeros((bk, di), jnp.float32)
        for h in range(hi):
            u = jnp.where(z_ref[h] > 0.0, d_i, 0.0).astype(ki.dtype)
            g_acc[h] += jnp.dot(kit, u, preferred_element_type=jnp.float32)
            dki += jax.lax.dot_general(u, qitw_ref[h], _NT,
                                       preferred_element_type=jnp.float32)
        dki_ref[pl.ds(pl.multiple_of(j * bk, bk), bk), :] += dki

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        for h in range(hi):
            g = g_acc[h]                                    # [DI, rows]
            dqit_ref[h] = (g * wt_ref[h:h + 1, :]).astype(dqit_ref.dtype)
            dwt_ref[h:h + 1, :] = (qit_ref[h].astype(jnp.float32)
                                   * g).sum(axis=0, keepdims=True)


def _idx_specs(hi, di, rows, r0):
    """Block specs of one grid step (row block i, key tile j) of the super
    block that starts at query ``r0``, by operand: ``qi`` [HI, S, DI] or
    turned ``qit`` [HI, DI, S], ``ki`` [S, DI] or turned ``kit``, ``wt``
    [HI, S], a ``tile`` of p [sup, extent] and of the selection's bytes
    [sup, S], a per-query ``row`` [1, sup], and the gradients' ``dwt`` [HI,
    sup] and ``dqit`` [HI, DI, sup].  The key tile is clamped to the
    diagonal's, so the steps that compute nothing fetch nothing new."""
    b, at = _SEL_BLOCK, r0 // rows

    def col(i, j):
        return jnp.minimum(j, _idx_last_block(i, rows, r0 // b))
    return {
        "qi": pl.BlockSpec((hi, rows, di), lambda i, j: (0, at + i, 0)),
        "qit": pl.BlockSpec((hi, di, rows), lambda i, j: (0, 0, at + i)),
        "ki": pl.BlockSpec((b, di), lambda i, j: (col(i, j), 0)),
        "kit": pl.BlockSpec((di, b), lambda i, j: (0, col(i, j))),
        "wt": pl.BlockSpec((hi, rows), lambda i, j: (0, at + i)),
        "tile": pl.BlockSpec((rows, b), lambda i, j: (i, col(i, j))),
        "row": pl.BlockSpec((1, rows), lambda i, j: (0, i)),
        "dwt": pl.BlockSpec((hi, rows), lambda i, j: (0, i)),
        "dqit": pl.BlockSpec((hi, di, rows), lambda i, j: (0, 0, i))}


def _idx_call(kernel, name, grid, first_block, in_specs, operands, out_specs,
              out_shape, scratch):
    """One pass over the causal tiles of a super block: tiles above the
    diagonal are neither fetched nor computed."""
    return pl.pallas_call(
        functools.partial(kernel, first_block=first_block),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name=name)(*operands)


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _index_kl_jit(qi, ki, w, qg, k, lse, sel, scale, super_rows, with_grads):
    """One trace per (shapes, statics), shared by a program's layers."""
    hi, seq, di = qi.shape
    f32, b = jnp.float32, _SEL_BLOCK
    qit, kit, wt = jnp.swapaxes(qi, 1, 2), ki.T, w.astype(f32).T
    kl, dqit, dwt = [], [], []
    dki = jnp.zeros(ki.shape, f32)
    for r0 in range(0, seq, super_rows):
        extent = r0 + super_rows
        p = selected_probability_mean_tpu(qg, k, lse, sel, scale, r0,
                                          super_rows, extent)
        pairs = _sel_bytes(sel[r0:r0 + super_rows])
        rows = _IDX_STAT_ROWS
        at = _idx_specs(hi, di, rows, r0)
        kl_row, lse_i, sum_p = _idx_call(
            _idx_stats_kernel, "index_kl_stats",
            (super_rows // rows, extent // b), r0 // b,
            [at["qi"], at["ki"], at["wt"], at["tile"], at["tile"]],
            (qi, ki, wt, p, pairs), [at["row"]] * 3,
            [jax.ShapeDtypeStruct((1, super_rows), f32)] * 3,
            [pltpu.VMEM((1, rows), f32)] * 4)
        kl.append(jnp.sum(kl_row))
        if not with_grads:
            continue
        rows = _IDX_GRAD_ROWS
        at = _idx_specs(hi, di, rows, r0)
        dwt_rows, dqit_rows, dki_rows = _idx_call(
            _idx_grad_kernel, "index_kl_grad",
            (super_rows // rows, extent // b), r0 // b,
            [at["qit"], at["ki"], at["wt"], at["tile"], at["tile"],
             at["row"], at["row"], at["kit"]],
            (qit, ki, wt, p, pairs, lse_i, sum_p, kit),
            [at["dwt"], at["dqit"],
             pl.BlockSpec((extent, di), lambda i, j: (0, 0))],
            [jax.ShapeDtypeStruct((hi, super_rows), f32),
             jax.ShapeDtypeStruct((hi, di, super_rows), qi.dtype),
             jax.ShapeDtypeStruct((extent, di), f32)],
            [pltpu.VMEM((hi, b, rows), f32),
             pltpu.VMEM((hi, di, rows), qi.dtype),
             pltpu.VMEM((hi, di, rows), f32)])
        dwt.append(dwt_rows)
        dqit.append(dqit_rows)
        dki = dki.at[:extent].add(dki_rows)
    if not with_grads:
        return sum(kl), None
    return sum(kl), (jnp.swapaxes(jnp.concatenate(dqit, axis=2), 1, 2),
                     dki.astype(ki.dtype), jnp.concatenate(dwt, axis=1).T)


def index_kl_tpu(qi, ki, w, qg, k, lse, sel, scale, super_rows, with_grads):
    """One sequence's indexer loss on the core: (sum over queries of KL(p ||
    softmax over the set of I), and with ``with_grads`` its gradients for
    ``qi`` [HI, S, DI], ``ki`` [S, DI] and ``w`` [S, HI]); ``qg`` [Hkv, G, S,
    D], ``k`` [Hkv, S, D], ``lse`` [Hkv, G, S] and ``sel`` [S, S / 8] as
    ``selected_probability_mean_tpu`` takes them, which forms ``p`` a super
    block of ``super_rows`` queries at a time (``sparse_attention.
    _index_kl_one`` is the ``jnp`` spelling)."""
    return _index_kl_jit(qi, ki, w, qg, k, lse, sel, float(scale),
                         int(super_rows), bool(with_grads))


# ---------------------------------------------------------------------------
# fused attention for the lengths BERT runs (S <= 512): whole score rows on
# the core.
#
# One head's [S, S] float32 scores are at most 1 MiB, so nothing is streamed
# and no online softmax is needed: a grid step holds K and V of its heads
# whole and, per head, does scores -> softmax -> keep-mask -> PV in one
# pass.  The backward recomputes the scores, regenerates the mask and
# produces dQ, dK and dV in the same grid step (7 matmul units against
# flash's 9).  HBM sees Q, K, V, the [B, 1, 1, S] bias row, the output and
# the [B, H, S] log-sum-exp; scores, probabilities and mask never leave
# VMEM.  The keep-mask of a head is a function of (op seed, batch, head,
# [Sq, Sk]): forward, backward and the mask-export kernel draw the same bits.
#
# Layout.  The kernels read and write [B, S, H*D], the layout the Q/K/V
# projections produce and the output projection consumes, not [B, H, S, D]:
# the wrapper undoes the caller's head transpose and XLA cancels the pair,
# so no transposed copy of Q, K, V, O or their gradients is ever written
# (with [B, H, S, D] kernels those copies were 13 of 111 ms a step at
# seq 512 and cost seq 128 more memory than the kernel saved, and a layer's
# forward + backward took 2.0 ms against 1.3 here; my chip runs, PR 25).
# A head is then D of the 128 lanes of a lane group, and 128 // D heads
# share a group.  Nothing is sliced below a vreg: a head's scores are
# (q2 * m) @ k2.T over the whole group with the other heads' lanes of q2
# zeroed by m (the same multiply that applies the softmax scale), P @ v2 and
# the gradient matmuls produce the whole group's width, and a lane select
# keeps each head's own columns.  The MXU is 128 deep and wide: a 128-lane
# operand costs the passes a 64-lane one does.
#
# Precision, as AMP runs the unfused chain: the matmul operands in the
# caller's dtype (bf16 under AMP) with float32 accumulation; scores, max,
# sum and exp in float32; probabilities rounded to the operand dtype only as
# the PV / dV / dS matmul operand.
# ---------------------------------------------------------------------------

# Longest sequence whose [S, S] float32 score tile (1 MiB) and its few
# same-size temporaries are held whole; beyond it callers stream K/V through
# the flash kernel or take XLA.
_ATTN_MAX_SEQ = 512
# Query rows (heads x S) one grid step works through, so that S = 128 is
# not one head per ~0.35 us grid step: all 12 heads there, 6 at S = 512
# (v5e, forward + backward of a BERT-base layer: [128, 12, 128, 64] 0.854 ms
# with 6 heads a step, 0.759 with 12; [32, 12, 512, 64] 1.335 / 1.323 /
# 1.278 ms with 2 / 4 / 6; my chip runs, PR 25).
_ATTN_ROWS_PER_STEP = 4096
# The tile temporaries plus the double-buffered head blocks pass v5e's 16 MiB
# scoped default at S = 512; the core has 128 MiB.
_ATTN_VMEM_LIMIT_BYTES = 64 << 20
_LANES = 128

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _heads_per_group(d):
    """Heads that share one 128-lane group."""
    return max(1, _LANES // d)


def fused_attention_supported(q, k, v, bias=None) -> bool:
    """Shapes the fused attention kernel covers: [B, H, S, D] operands of
    one float dtype, lane-aligned lengths up to ``_ATTN_MAX_SEQ``, a head
    width that tiles the 128 lanes (32, 64, 128) with whole lane groups of
    heads, and an additive bias that is one row per batch entry,
    [B or 1, 1, 1, Sk] (a bias that varies over heads or query rows would
    have to sit in HBM at the scores' size, which is the traffic this kernel
    removes)."""
    if q.ndim != 4 or k.shape != v.shape or q.shape[:2] != k.shape[:2] \
            or q.shape[3] != k.shape[3]:
        return False
    if not (q.dtype == k.dtype == v.dtype) \
            or q.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if sk % 128 or sk > _ATTN_MAX_SEQ or sq % 128 or sq > _ATTN_MAX_SEQ \
            or d not in (32, 64, 128) or h % _heads_per_group(d):
        return False
    return bias is None or (bias.ndim == 4 and bias.shape[0] in (1, b)
                            and bias.shape[1:] == (1, 1, sk))


def _heads_per_step(h, sq, d):
    """Whole lane groups, about ``_ATTN_ROWS_PER_STEP`` query rows."""
    g = _heads_per_group(d)
    groups = h // g
    n = max(1, min(groups, _ATTN_ROWS_PER_STEP // (sq * g)))
    while groups % n:
        n -= 1
    return n * g


def _head_bits(seed_ref, head, shape):
    """One head's random bits: the on-core PRNG seeded from the op seed and
    the head's index over (batch, head); Mosaic takes two seed words.  (The
    CPU interpreter's PRNG is a stub; the tests put a counter-based
    generator here.)"""
    pltpu.prng_seed(seed_ref[0], head)
    return pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)


def _first_head(hb):
    """Index over (batch, head) of the grid step's first head, for a grid
    of (batch, blocks of ``hb`` heads)."""
    return (pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)) * hb


def _col_to_row(col):
    """[n, 1] -> [1, n] float32 through an aligned 2-d transpose: the
    statistics leave the core lane-dense ([B, H, 1, S]; a trailing dim of 1
    would be padded to 128 lanes in HBM)."""
    n = col.shape[0]
    return jnp.transpose(jnp.broadcast_to(col, (n, 128)))[:1, :]


def _row_to_col(row):
    """[1, n] -> [n, 1]."""
    n = row.shape[1]
    return jnp.transpose(jnp.broadcast_to(row, (128, n)))[:, :1]


def _lane_group_plan(d, scale, dtype):
    """How one lane group's heads are told apart: per head a bool [1, w]
    lane mask (None for a group of one head) and the [1, w] factor that
    zeroes the other heads' lanes of q; and the factor left for the scores.
    A power-of-two scale (D = 64: 1/8) rides in q's factor: exact in any
    binary float, and a pass over [S, w] instead of [S, S]."""
    g = _heads_per_group(d)
    in_q = math.frexp(scale)[0] == 0.5
    q_scale, s_scale = (scale, 1.0) if in_q else (1.0, scale)
    if g == 1:
        factor = None if q_scale == 1.0 else jnp.full((1, d), q_scale, dtype)
        return [(None, factor)], s_scale
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, g * d), 1)
    masks = [lane // d == x for x in range(g)]
    return [(m, jnp.where(m, q_scale, 0.0).astype(dtype))
            for m in masks], s_scale


def _own_lanes(plan, parts):
    """Each head's own columns of its [rows, w] float32 result."""
    out = parts[0]
    for (mask, _), part in zip(plan[1:], parts[1:]):
        out = jnp.where(mask, part, out)
    return out


def _attn_scores(q2, factor, k2, bias, s_scale):
    """One head's q @ k.T * scale + bias in float32, from its lane group."""
    if factor is not None:
        q2 = q2 * factor
    s = jax.lax.dot_general(q2, k2, _NT, preferred_element_type=jnp.float32)
    if s_scale != 1.0:
        s = s * s_scale
    return s if bias is None else s + bias


def _for_lane_groups(d, width, group):
    """Run ``group(columns, first head of the group within the step)`` for
    the grid step's lane groups, as straight-line code: heads are
    independent, and the scheduler fills one head's matmul latency with
    another's vector work (v5e, [128, 12, 128, 64] forward + backward of
    this kernel's [B, H, S, D] predecessor: 2.33 ms as a ``fori_loop`` over
    heads, 1.60 unrolled; at S = 512, 2.09 and 2.01; my chip runs, PR 25)."""
    g = _heads_per_group(d)
    w = g * d
    for i in range(width // w):
        group(slice(i * w, (i + 1) * w), i * g)


def _attn_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, *rest, d, scale,
                     threshold, out_scale, has_bias):
    b_ref = rest[0] if has_bias else None
    o_ref, lse_ref = rest[-2:]
    bias = b_ref[...] if has_bias else None
    plan, s_scale = _lane_group_plan(d, scale, q_ref.dtype)
    first = _first_head(lse_ref.shape[0])

    def group(cols, head0):
        q2, k2, v2 = q_ref[:, cols], k_ref[:, cols], v_ref[:, cols]
        outs = []
        for x, (_, factor) in enumerate(plan):
            s = _attn_scores(q2, factor, k2, bias, s_scale)
            m = jnp.max(s, axis=1, keepdims=True)
            e = jnp.exp(s - m)
            # the normaliser is the sum of the UNdropped exponentials
            l = jnp.sum(e, axis=1, keepdims=True)
            if threshold:
                keep = _head_bits(seed_ref, first + head0 + x, e.shape) \
                    >= jnp.uint32(threshold)
                e = jnp.where(keep, e, 0.0)
            o = jnp.dot(e.astype(v2.dtype), v2,
                        preferred_element_type=jnp.float32)
            outs.append(o * (out_scale / l))
            lse_ref[head0 + x] = _col_to_row(m + jnp.log(l))
        o_ref[:, cols] = _own_lanes(plan, outs).astype(o_ref.dtype)

    _for_lane_groups(d, q_ref.shape[1], group)


def _attn_bwd_kernel(seed_ref, q_ref, k_ref, v_ref, *rest, d, scale,
                     threshold, out_scale, has_bias):
    b_ref = rest[0] if has_bias else None
    o_ref, lse_ref, do_ref, dq_ref, dk_ref, dv_ref = rest[-6:]
    bias = b_ref[...] if has_bias else None
    f32 = jnp.float32
    plan, s_scale = _lane_group_plan(d, scale, q_ref.dtype)
    first = _first_head(lse_ref.shape[0])

    def group(cols, head0):
        q2, k2, v2, do2 = (q_ref[:, cols], k_ref[:, cols], v_ref[:, cols],
                           do_ref[:, cols])
        do_o = do2.astype(f32) * o_ref[:, cols].astype(f32)
        dqs, dks, dvs = [], [], []
        for x, (mask, factor) in enumerate(plan):
            p = jnp.exp(_attn_scores(q2, factor, k2, bias, s_scale)
                        - _row_to_col(lse_ref[head0 + x]))
            # out = out_scale * (keep . p) @ v, so with pk = keep . p and
            # dpk = keep . (do @ v.T):  ds = out_scale * p . (dpk - delta'),
            # delta' = rowsum(do . out) / out_scale; out_scale multiplies
            # the [S, w] results, never an [S, S] tile
            dox = do2 if mask is None else do2 * mask.astype(do2.dtype)
            dp = jax.lax.dot_general(dox, v2, _NT, preferred_element_type=f32)
            pk = p
            if threshold:
                keep = _head_bits(seed_ref, first + head0 + x, p.shape) \
                    >= jnp.uint32(threshold)
                pk = jnp.where(keep, p, 0.0)
                dp = jnp.where(keep, dp, 0.0)
            dvs.append(jax.lax.dot_general(pk.astype(do2.dtype), do2, _TN,
                                           preferred_element_type=f32))
            own = do_o if mask is None else jnp.where(mask, do_o, 0.0)
            delta = jnp.sum(own, axis=1, keepdims=True) * (1.0 / out_scale)
            ds = (p * (dp - delta)).astype(q2.dtype)
            dqs.append(jnp.dot(ds, k2, preferred_element_type=f32))
            dks.append(jax.lax.dot_general(ds, q2, _TN,
                                           preferred_element_type=f32))
        dq_ref[:, cols] = (_own_lanes(plan, dqs)
                           * (scale * out_scale)).astype(dq_ref.dtype)
        dk_ref[:, cols] = (_own_lanes(plan, dks)
                           * (scale * out_scale)).astype(dk_ref.dtype)
        dv_ref[:, cols] = (_own_lanes(plan, dvs)
                           * out_scale).astype(dv_ref.dtype)

    _for_lane_groups(d, q_ref.shape[1], group)


def _attn_mask_kernel(seed_ref, o_ref, *, threshold):
    first = _first_head(o_ref.shape[0])
    for h in range(o_ref.shape[0]):
        keep = _head_bits(seed_ref, first + h, o_ref.shape[1:]) \
            >= jnp.uint32(threshold)
        o_ref[h] = keep.astype(jnp.uint8)


_ATTN_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"),
    vmem_limit_bytes=_ATTN_VMEM_LIMIT_BYTES)


def _attn_call(kernel, operands, bias, heads, statics, more_specs,
               out_specs, out_shape):
    """One grid step per (batch entry, block of heads) over [B, S, H*D]
    arrays.  ``operands``: seed, q, k, v, then more; the bias row goes in
    after v.  ``more_specs`` and ``out_specs`` name their blocks ``q``,
    ``k`` or ``lse``."""
    bsz, sq, width = operands[1].shape
    sk = operands[2].shape[1]
    d = width // heads
    hb = _heads_per_step(heads, sq, d)
    specs = {
        "q": pl.BlockSpec((None, sq, hb * d), lambda b, j: (b, 0, j)),
        "k": pl.BlockSpec((None, sk, hb * d), lambda b, j: (b, 0, j)),
        "lse": pl.BlockSpec((None, hb, 1, sq), lambda b, j: (b, j, 0, 0)),
    }
    operands = list(operands)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), specs["q"],
                specs["k"], specs["k"]]
    if bias is not None:
        per_batch = bias.shape[0] != 1
        operands.insert(4, bias.reshape(bias.shape[0], 1, sk)
                        .astype(jnp.float32))
        in_specs.append(pl.BlockSpec(
            (None, 1, sk), lambda b, j: (b if per_batch else 0, 0, 0)))
    scale, rate, out_scale = statics
    return pl.pallas_call(
        functools.partial(kernel, d=d, scale=scale,
                          threshold=_threshold_for(rate),
                          out_scale=out_scale, has_bias=bias is not None),
        grid=(bsz, heads // hb),
        in_specs=in_specs + [specs[s] for s in more_specs],
        out_specs=[specs[s] for s in out_specs], out_shape=out_shape,
        compiler_params=_ATTN_PARAMS,
    )(*operands)


def _attn_forward(q, k, v, bias, seed, heads, statics):
    bsz, sq, _ = q.shape
    return _attn_call(
        _attn_fwd_kernel, [seed, q, k, v], bias, heads, statics, [],
        ["q", "lse"],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((bsz, heads, 1, sq), jnp.float32)])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fused_attention(q, k, v, bias, seed, heads, statics):
    """q, k, v [B, S, H*D]; statics = (scale, rate, out_scale)."""
    return _attn_forward(q, k, v, bias, seed, heads, statics)[0]


def _fused_attention_fwd(q, k, v, bias, seed, heads, statics):
    out, lse = _attn_forward(q, k, v, bias, seed, heads, statics)
    return out, (q, k, v, bias, seed, out, lse)


def _fused_attention_bwd(heads, statics, res, do):
    q, k, v, bias, seed, out, lse = res
    dq, dk, dv = _attn_call(
        _attn_bwd_kernel, [seed, q, k, v, out, lse, do.astype(q.dtype)],
        bias, heads, statics, ["q", "lse", "q"], ["q", "k", "k"],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)])
    return dq, dk, dv, None, None


_fused_attention.defvjp(_fused_attention_fwd, _fused_attention_bwd)


def _heads_to_lanes(x):
    """[B, H, S, D] -> [B, S, H*D]: the inverse of the caller's head split,
    which XLA cancels against it."""
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _fused_attention_jit(q, k, v, bias, seed, heads, statics):
    """One trace per (shapes, statics), shared by the layers of a
    program."""
    return _fused_attention(q, k, v, bias, seed, heads, statics)


def _fused_attention_keyed(q, k, v, bias, key, heads, statics):
    seed = jnp.zeros((1,), jnp.int32) if key is None else _seed_from_key(key)
    return _fused_attention_jit(q, k, v, bias, seed, heads, statics)


def fused_attention_tpu(q, k, v, bias=None, scale=None, dropout_rate=0.0,
                        dropout_key=None, dropout_upscale=True,
                        prob_scale=None, site=None):
    """softmax(q @ k.T * scale + bias) -> dropout -> @ v, q/k/v [B, H, S, D],
    ``bias`` [B or 1, 1, 1, S] passed as that row.  Dropout is active with
    a rate and a key; ``dropout_upscale`` scales the kept probabilities by
    1 / (1 - rate) (upscale_in_train), ``prob_scale`` scales all of them
    (downgrade_in_infer at test time).  Rate 0 is the same kernel without
    the PRNG.  ``site`` (``LoweringContext.kernel_site``) runs the kernel
    once per shard of the batch; the head transposes stay outside it, where
    XLA cancels them against the caller's (across a ``shard_map`` boundary
    it does not: 108 copies of 24 MiB a BERT-base step; AOT, PR 27)."""
    from .registry import KernelSite
    if scale is None:
        scale = q.shape[-1] ** -0.5
    rate = float(dropout_rate) if dropout_key is not None else 0.0
    out_scale = 1.0 if prob_scale is None else float(prob_scale)
    if rate and dropout_upscale:
        out_scale /= 1.0 - rate
    b, h, sq, d = q.shape
    out = (site or KernelSite()).call(
        _fused_attention_keyed,
        [_heads_to_lanes(q), _heads_to_lanes(k), _heads_to_lanes(v), bias],
        (True, True, True, bias is not None and bias.shape[0] == b),
        key=dropout_key if rate else None,
        static=(h, (float(scale), rate, out_scale)))
    return out.reshape(b, sq, h, d).transpose(0, 2, 1, 3)


def fused_attention_keep_mask(q_shape, sk, dropout_rate, dropout_key):
    """The uint8 [B, H, Sq, Sk] keep-mask ``fused_attention_tpu`` applies
    for this key, from a kernel that draws the same bits per (batch, head):
    for the tests and the chip roll-call, never on a training path."""
    bsz, h, sq, d = q_shape
    hb = _heads_per_step(h, sq, d)
    return pl.pallas_call(
        functools.partial(_attn_mask_kernel,
                          threshold=_threshold_for(float(dropout_rate))),
        grid=(bsz, h // hb),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((None, hb, sq, sk),
                               lambda b, j: (b, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, sq, sk), jnp.uint8),
        compiler_params=_ATTN_PARAMS,
    )(_seed_from_key(dropout_key))


# ---------------------------------------------------------------------------
# paged flash attention: decode-step attention over a block-paged KV pool.
#
# The decode plane (serving/decode.py) keeps K/V in fixed-size pages of a
# device-resident pool; a slot's logical KV window is the pool rows named by
# its page table.  The dense decode kernel would need the [B, max_len, d]
# caches materialised per slot — here each grid step walks ITS page-table row
# (scalar-prefetched to SMEM), reads one page of pool rows at a time, and
# folds them into an online-softmax accumulator, so the gathered
# [B, max_len, d] tensor never exists.  Positions >= the slot's length mask
# to -1e30 before the running max, matching the XLA lowering's
# masked-softmax exactly-0.0 contract (ops/attention.py paged_attention).
# ---------------------------------------------------------------------------

# Both pools sit in VMEM whole (copied once, not pipelined); bigger pools
# take the XLA gather lowering in ops/attention.py.
_PAGED_VMEM_BYTES = 8 << 20


def paged_attention_supported(q, k_pool, idx) -> bool:
    """Shapes the Pallas paged path covers: lane-aligned head dim, flat
    2-d pools small enough to hold in VMEM, and a per-position index row
    per batch entry."""
    if q.ndim != 2 or k_pool.ndim != 2 or idx.ndim != 2:
        return False
    d = q.shape[-1]
    if d != k_pool.shape[-1] or d % 128 != 0 or idx.shape[1] == 0:
        return False
    return 2 * k_pool.size * k_pool.dtype.itemsize <= _PAGED_VMEM_BYTES


def _paged_attn_kernel(base_ref, len_ref, q_ref, kp_ref, vp_ref, o_ref, *,
                       n_pages, page_size, scale):
    """One query row against its pages, on the vector unit in f32: the
    same mul + reduce_sum arithmetic as the XLA lowering (a one-row matmul
    would leave the MXU idle and round its f32 operands to bf16)."""
    i = pl.program_id(0)
    d = o_ref.shape[-1]
    q = q_ref[...].astype(jnp.float32)              # [1, d]
    length = len_ref[i]

    def body(j, carry):
        m, l, acc = carry
        base = base_ref[i, j]                       # page rows contiguous
        if page_size % 8 == 0:
            base = pl.multiple_of(base, 8)
        k = kp_ref[pl.ds(base, page_size), :].astype(jnp.float32)
        v = vp_ref[pl.ds(base, page_size), :].astype(jnp.float32)
        s = jnp.sum(k * q, axis=1, keepdims=True) * scale   # [page_size, 1]
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, 1), 0)
        s = jnp.where(pos < length, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)                      # masked -> exactly 0.0
        l_new = l * corr + jnp.sum(p, axis=0, keepdims=True)
        acc_new = acc * corr + jnp.sum(p * v, axis=0, keepdims=True)
        return m_new, l_new, acc_new

    m0 = jnp.full((1, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((1, 1), jnp.float32)
    acc0 = jnp.zeros((1, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, acc0))
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def paged_flash_attention_tpu(q, k_pool, v_pool, idx, lengths, scale,
                              page_size=1):
    """q: [B, d] one query row per decode slot; k_pool/v_pool: [R, d] flat
    page pools (R = n_pages * page_size); idx: [B, S] int32 pool-row index
    per logical position (page-contiguous in runs of ``page_size``);
    lengths: [B] or [B, 1] int32 valid-position counts.  Returns [B, d]."""
    b, s = idx.shape
    d = k_pool.shape[-1]
    if s % page_size != 0:
        raise ValueError(f"seq window {s} not a multiple of page_size "
                         f"{page_size}")
    # [B, 1, d] so a one-row block spans the array's whole last two dims
    # (a (1, d) block of a [B, d] array breaks the (8, 128) tiling rule)
    row = pl.BlockSpec((pl.Squeezed(), 1, d), lambda i, *_: (i, 0, 0))
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, n_pages=s // page_size,
                          page_size=page_size, scale=float(scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[row, whole, whole], out_specs=row),
        out_shape=jax.ShapeDtypeStruct((b, 1, d), q.dtype),
    )(idx[:, ::page_size].astype(jnp.int32),
      lengths.astype(jnp.int32).reshape(b), q.reshape(b, 1, d),
      k_pool, v_pool)
    return out.reshape(b, d)


# ---------------------------------------------------------------------------
# fused dropout with mask regeneration in backward
# ---------------------------------------------------------------------------

def _dropout_row_bytes(n: int) -> int:
    """One row of the widest dropout kernel: three f32 operands."""
    return 3 * 4 * n


def _dropout_block_rows(m: int, n: int) -> int:
    """A function of the shape alone — never of dtype or operand count —
    because the keep-mask is a function of (seed, block index, block
    shape): forward, backward and the mask kernel must block alike."""
    return _block_rows(m, _dropout_row_bytes(n))


def fused_dropout_supported(x) -> bool:
    """Shapes the dropout kernels cover: lane-aligned last dim, and one
    aligned block of rows within the VMEM budget."""
    if x.ndim == 0 or x.size == 0:
        return False
    n = x.shape[-1]
    rows = min(x.size // n, _ROW_ALIGN)
    return (n % 128 == 0 and
            2 * rows * _dropout_row_bytes(n) <= _PIPELINE_VMEM_BYTES)


def _keep_mask(seed_ref, shape, threshold):
    # distinct stream per grid block: hardware PRNG seeded from (seed, block)
    pltpu.prng_seed(seed_ref[0], pl.program_id(0))
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    return bits >= jnp.uint32(threshold)


def _dropped(keep, x, scale):
    return jnp.where(keep, x * x.dtype.type(scale), x.dtype.type(0.0))


def _dropout_kernel(seed_ref, x_ref, o_ref, *, threshold, scale):
    keep = _keep_mask(seed_ref, x_ref.shape, threshold)
    o_ref[...] = _dropped(keep, x_ref[...], scale)


def _dropout_mask_kernel(seed_ref, o_ref, *, threshold):
    keep = _keep_mask(seed_ref, o_ref.shape, threshold)
    o_ref[...] = keep.astype(jnp.uint8)


def _rowwise_call(kernel, seed, operands, shape, dtype):
    """One elementwise pallas_call producing an [m, n] ``dtype`` array from
    same-shape operands, the PRNG seed riding in SMEM."""
    m, n = shape
    bm = _dropout_block_rows(m, n)
    spec = pl.BlockSpec((bm, n), lambda i: (i, 0))
    return pl.pallas_call(
        kernel, grid=(pl.cdiv(m, bm),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [spec] * len(operands),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((m, n), dtype),
    )(seed, *operands)


def _run_dropout(x2d, seed, threshold, scale):
    return _rowwise_call(
        functools.partial(_dropout_kernel, threshold=threshold, scale=scale),
        seed, [x2d], x2d.shape, x2d.dtype)


def _threshold_for(rate: float) -> int:
    # P(bits >= threshold) == 1 - rate over uint32
    return min(int(rate * 4294967296.0), 4294967295)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _fused_dropout(x2d, seed, rate, upscale):
    scale = 1.0 / (1.0 - rate) if upscale else 1.0
    return _run_dropout(x2d, seed, _threshold_for(rate), scale)


def _fused_dropout_fwd(x2d, seed, rate, upscale):
    return _fused_dropout(x2d, seed, rate, upscale), seed


def _fused_dropout_bwd(rate, upscale, seed, g):
    # the SAME seed regenerates the SAME mask — no residual mask in HBM
    scale = 1.0 / (1.0 - rate) if upscale else 1.0
    return _run_dropout(g, seed, _threshold_for(rate), scale), None


_fused_dropout.defvjp(_fused_dropout_fwd, _fused_dropout_bwd)


def _seed_from_key(key):
    return jax.random.bits(key, (1,), "uint32").astype(jnp.int32)


def fused_dropout_tpu(x, key, rate, upscale_in_train):
    """Dropout with on-core PRNG mask, regenerated in backward.

    Returns (out, mask_fn) where mask_fn() materialises the uint8 keep-mask
    with a second kernel from the same seed — called only if the consumer
    actually fetches the Mask output, so XLA DCEs it otherwise.
    """
    seed = _seed_from_key(key)
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    out = _fused_dropout(x2d, seed, float(rate), bool(upscale_in_train))

    def mask_fn():
        mask = _rowwise_call(
            functools.partial(_dropout_mask_kernel,
                              threshold=_threshold_for(float(rate))),
            seed, [], x2d.shape, jnp.uint8)
        return mask.reshape(shape)

    return out.reshape(shape), mask_fn


# ---------------------------------------------------------------------------
# dropout fused with its elementwise neighbours: residual add / activation.
#
# Each pallas dropout call is an opaque boundary to XLA fusion, so the
# residual add AFTER it and the gelu BEFORE it would each cost a full extra
# HBM pass of the activation tensor.  Pulling those neighbours INTO the
# dropout kernel removes the boundary; backward regenerates the mask from
# the same on-core PRNG seed (no residual bytes), and the activation
# derivative is recomputed from the pre-activation x the matmul backward
# already keeps live.  The gain is not measured on this code.
# ---------------------------------------------------------------------------

def _dropout_add_kernel(seed_ref, x_ref, r_ref, o_ref, *, threshold, scale):
    keep = _keep_mask(seed_ref, x_ref.shape, threshold)
    o_ref[...] = _dropped(keep, x_ref[...], scale) + r_ref[...]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_dropout_add(x2d, r2d, seed, rate, upscale):
    scale = 1.0 / (1.0 - rate) if upscale else 1.0
    return _rowwise_call(
        functools.partial(_dropout_add_kernel,
                          threshold=_threshold_for(rate), scale=scale),
        seed, [x2d, r2d], x2d.shape, x2d.dtype)


def _fused_dropout_add_fwd(x2d, r2d, seed, rate, upscale):
    return _fused_dropout_add(x2d, r2d, seed, rate, upscale), seed


def _fused_dropout_add_bwd(rate, upscale, seed, g):
    # d/dx: same regenerated mask applied to g; d/dresidual: g unchanged
    scale = 1.0 / (1.0 - rate) if upscale else 1.0
    return _run_dropout(g, seed, _threshold_for(rate), scale), g, None


_fused_dropout_add.defvjp(_fused_dropout_add_fwd, _fused_dropout_add_bwd)


def fused_dropout_add_tpu(x, residual, key, rate, upscale_in_train):
    """out = dropout(x) + residual in one kernel pass; backward
    regenerates the mask and passes the residual cotangent through."""
    seed = _seed_from_key(key)
    shape = x.shape
    n = shape[-1]
    out = _fused_dropout_add(x.reshape(-1, n), residual.reshape(-1, n),
                             seed, float(rate), bool(upscale_in_train))
    return out.reshape(shape)


def _erf(x):
    """In-kernel erf: Abramowitz & Stegun 7.1.26 (|err| <= 1.5e-7).
    lax.erf has no Mosaic/Pallas-TPU lowering (KernelType.TC rejects it);
    this uses only mul/add/exp, all of which lower."""
    a1, a2, a3, a4, a5 = (0.254829592, -0.284496736, 1.421413741,
                          -1.453152027, 1.061405429)
    p = 0.3275911
    s = jnp.sign(x)
    ax = jnp.abs(x)
    t = 1.0 / (1.0 + p * ax)
    poly = ((((a5 * t + a4) * t + a3) * t + a2) * t + a1) * t
    return s * (1.0 - poly * jnp.exp(-ax * ax))


def _act_fns(act):
    if act == "relu":
        return (lambda x: jnp.maximum(x, x.dtype.type(0.0)),
                # f32 compare: v5e's VPU has no bf16 comparison
                lambda x: (x.astype(jnp.float32) > 0).astype(x.dtype))
    if act == "gelu":                   # erf form (paddle default)
        c = 1.0 / math.sqrt(2.0)
        cpdf = 1.0 / math.sqrt(2.0 * math.pi)

        def f(x):
            xf = x.astype(jnp.float32)
            return (0.5 * xf * (1.0 + _erf(xf * c))).astype(x.dtype)

        def df(x):
            xf = x.astype(jnp.float32)
            phi = 0.5 * (1.0 + _erf(xf * c))
            return (phi + xf * cpdf * jnp.exp(-0.5 * xf * xf)) \
                .astype(x.dtype)
        return f, df
    raise ValueError(f"fused_act_dropout: unsupported act '{act}'")


def _act_dropout_kernel(seed_ref, x_ref, o_ref, *, threshold, scale, act):
    keep = _keep_mask(seed_ref, x_ref.shape, threshold)
    f, _ = _act_fns(act)
    o_ref[...] = _dropped(keep, f(x_ref[...]), scale)


def _act_dropout_bwd_kernel(seed_ref, x_ref, g_ref, o_ref, *, threshold,
                            scale, act):
    keep = _keep_mask(seed_ref, x_ref.shape, threshold)
    _, df = _act_fns(act)
    o_ref[...] = _dropped(keep, g_ref[...], scale) * df(x_ref[...])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused_act_dropout(x2d, seed, rate, upscale, act):
    scale = 1.0 / (1.0 - rate) if upscale else 1.0
    return _rowwise_call(
        functools.partial(_act_dropout_kernel,
                          threshold=_threshold_for(rate), scale=scale,
                          act=act),
        seed, [x2d], x2d.shape, x2d.dtype)


def _fused_act_dropout_fwd(x2d, seed, rate, upscale, act):
    # residuals: pre-activation x (a matmul output the AD graph already
    # holds) + the seed; the mask itself is never materialised
    return _fused_act_dropout(x2d, seed, rate, upscale, act), (x2d, seed)


def _fused_act_dropout_bwd(rate, upscale, act, res, g):
    x2d, seed = res
    scale = 1.0 / (1.0 - rate) if upscale else 1.0
    dx = _rowwise_call(
        functools.partial(_act_dropout_bwd_kernel,
                          threshold=_threshold_for(rate), scale=scale,
                          act=act),
        seed, [x2d, g], x2d.shape, x2d.dtype)
    return dx, None


_fused_act_dropout.defvjp(_fused_act_dropout_fwd, _fused_act_dropout_bwd)


def fused_act_dropout_tpu(x, key, rate, upscale_in_train, act):
    """out = dropout(act(x)) in one kernel; backward fuses act'(x) with
    the regenerated mask (one kernel, no saved mask/activation)."""
    seed = _seed_from_key(key)
    shape = x.shape
    n = shape[-1]
    out = _fused_act_dropout(x.reshape(-1, n), seed, float(rate),
                             bool(upscale_in_train), act)
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# hyper-connection mixers: one pass over the streams per kernel.
#
# A token's ``n`` streams lie side by side in one float32 row [n * d] (56 KiB
# at n = 4, d = 3584), and the two mixer ops around a branch touch every
# stream of every token.  As XLA fuses the ``jnp`` spelling it reads the row
# once for the projection, once for the mean square, once for the branch
# input, and again for each in backward: 6.5 / 11.3 / 2.9 / 6.3 passes for
# mix, its gradient, merge, its gradient (ledger, PR 30).  Here a grid step
# holds a tile of ``_HC_TILE`` whole rows in VMEM and does everything the op
# needs of them in one visit: 1.25 / 3.5 / 2.25 / 3.5 passes.
#
# Small per-token arrays (the 24 projections, pre, post, the n x n matrix)
# travel with the tokens on the LAST axis, [rows, T]: lane-dense in HBM (a
# [T, 4] array pads to 128 lanes there) and elementwise on the core.  One
# aligned transpose per tile (``_rows_to_cols``) turns them into per-token
# columns for the stream arithmetic, one more brings the per-token reductions
# back.  The stream arithmetic walks the tile in chunks
# of ``_HC_ROWS`` rows x ``_HC_CHUNK`` lanes so that its operands stay in
# registers.
#
# Precision: everything float32, the three matmuls of the mix (x Phi, its two
# transposes in backward) at ``Precision.HIGHEST``, 20 Sinkhorn iterations,
# rows before columns: ``decoder_ops.hyper_connection_coefficients_t``, which
# the forward kernel calls on its tile and XLA differentiates on the [24, T]
# arrays in backward (the iterations' gradient stays out of the kernels).
# ---------------------------------------------------------------------------

# tokens per grid step: whole lane groups, because the small arrays are
# blocked [rows, _HC_TILE] with the tokens on the lanes
_HC_TILE = 128
_HC_ROWS = 8
_HC_CHUNK = 512
# the widest kernel (merge backward) pipelines three row-wide and two
# stream-wide float32 blocks, 49 MiB double-buffered at n = 4, d = 3584: they
# and the matmuls' temporaries stay under the limit.  The limit is half the
# core's 128 MiB and no more: what a call reserves XLA cannot prefetch into
# around it, and with 100 MiB reserved the training step's schedule held
# 77 MB more of HBM (AOT, PR 31)
_HC_PIPELINE_BYTES = 56 << 20
_HC_VMEM_LIMIT_BYTES = 64 << 20
_HIGHEST = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _round_up(x, m):
    return -(-x // m) * m


def _hc_rows(n):
    """(k, kp, cp): the projections a token has, and the sublane-padded row
    counts of the two packed small arrays: [kp, T] holds the k projections
    and up to n + 1 rows more (the mean square's rsqrt; pre and the rsqrt's
    gradient term in backward), [cp, T] holds post (n) and C (n * n)."""
    k = n * n + 2 * n
    return k, _round_up(k + n + 1, 8), _round_up(n * n + n, 8)


def hyper_connection_supported(x, n) -> bool:
    """Do the mixer kernels cover streams ``x`` [..., n * d]: float32, each
    stream whole 128-lane groups, at least one tile of ``_HC_TILE`` tokens
    (the last may be ragged), a tile of the widest kernel's blocks inside the
    VMEM budget, and few enough coefficients a token that one 128-wide
    transpose carries them."""
    if x.ndim < 2 or x.dtype != jnp.float32 or n < 1 or x.shape[-1] % n:
        return False
    d = x.shape[-1] // n
    tokens = x.size // x.shape[-1] if x.shape[-1] else 0
    return (d > 0 and d % _LANES == 0 and tokens >= _HC_TILE
            and _hc_rows(n)[1] <= _LANES
            and 2 * _HC_TILE * (3 * n + 2) * d * 4 <= _HC_PIPELINE_BYTES)


def _rows_to_cols(rows):
    """[r, t] (r <= 128, tokens on the lanes) -> [t, 128] whose column i is
    row i: one aligned transpose."""
    r, t = rows.shape
    if r < _LANES:
        rows = jnp.concatenate(
            [rows, jnp.zeros((_LANES - r, t), rows.dtype)], axis=0)
    return jnp.transpose(rows)


def _place_cols(cols):
    """[rows, 1] columns -> [rows, 128] with column i in lane i."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (cols[0].shape[0], _LANES), 1)
    out = jnp.zeros(lane.shape, jnp.float32)
    for i, col in enumerate(cols):
        out = jnp.where(lane == i, col, out)
    return out


def _hc_chunks(d):
    return [(s, min(_HC_CHUNK, d - s)) for s in range(0, d, _HC_CHUNK)]


def _fold_lanes(a):
    """[r, m * 128] -> [r, 128]: the lane groups added up (vector adds; the
    one cross-lane reduction comes after the last chunk)."""
    out = a[:, :_LANES]
    for g in range(1, a.shape[1] // _LANES):
        out = out + a[:, g * _LANES:(g + 1) * _LANES]
    return out


def _for_row_chunks(tile, body):
    """``body(rows)`` for the tile's chunks of ``_HC_ROWS`` token rows."""
    def step(r, carry):
        body(pl.ds(pl.multiple_of(r * _HC_ROWS, _HC_ROWS), _HC_ROWS))
        return carry
    jax.lax.fori_loop(0, tile // _HC_ROWS, step, 0)


def _valid_lanes(tile, tokens):
    """[1, tile] bool: which of the grid step's tokens exist (the last tile
    of a ragged grid is padded with whatever the buffer held)."""
    at = pl.program_id(0) * tile + jax.lax.broadcasted_iota(
        jnp.int32, (1, tile), 1)
    return at < tokens


def _hc_mix_fwd_kernel(alpha_ref, x_ref, phit_ref, bt_ref, y_ref, m_ref,
                       coef_ref, err_ref, cols_ref, *, n, statics, tokens):
    epsilon, iters, hc_eps, clamp = statics
    tile, width = x_ref.shape
    d = width // n
    k = _hc_rows(n)[0]

    def mean_square(rows):
        acc = jnp.zeros((_HC_ROWS, _LANES), jnp.float32)
        for s, w in _hc_chunks(width):
            xs = x_ref[rows, s:s + w]
            acc = acc + _fold_lanes(xs * xs)
        cols_ref[rows, :] = jnp.broadcast_to(
            jnp.sum(acc, axis=1, keepdims=True) / width, acc.shape)

    _for_row_chunks(tile, mean_square)
    inv = jax.lax.rsqrt(jnp.transpose(cols_ref[...])[:1] + epsilon)
    mt = jax.lax.dot_general(phit_ref[...], x_ref[...], _NT,
                             precision=_HIGHEST,
                             preferred_element_type=jnp.float32) * inv
    from .decoder_ops import hyper_connection_coefficients_t
    pre, post, c, row_error = hyper_connection_coefficients_t(
        mt[:k], alpha_ref, bt_ref[:k], n, iters, hc_eps, clamp)
    m_ref[...] = mt
    m_ref[k:k + 1, :] = inv
    coef_ref[...] = jnp.zeros(coef_ref.shape, jnp.float32)
    coef_ref[:n, :] = post
    coef_ref[n:n + n * n, :] = c
    if tokens % tile:
        row_error = jnp.where(_valid_lanes(tile, tokens), row_error, 0.0)

    @pl.when(pl.program_id(0) == 0)
    def _first():
        err_ref[...] = jnp.zeros(err_ref.shape, jnp.float32)

    err_ref[...] = jnp.maximum(
        err_ref[...], jnp.max(row_error, axis=(0, 1), keepdims=True))
    cols_ref[...] = _rows_to_cols(pre)

    def branch_input(rows):
        pre_c = cols_ref[rows, :]
        for s, w in _hc_chunks(d):
            y_ref[rows, s:s + w] = sum(
                pre_c[:, i:i + 1] * x_ref[rows, i * d + s:i * d + s + w]
                for i in range(n))

    _for_row_chunks(tile, branch_input)


def _hc_mix_dpre_kernel(x_ref, dy_ref, dpre_ref, cols_ref, *, n):
    tile, d = dy_ref.shape

    def reduce(rows):
        acc = [jnp.zeros((_HC_ROWS, _LANES), jnp.float32) for _ in range(n)]
        for s, w in _hc_chunks(d):
            dy = dy_ref[rows, s:s + w]
            for i in range(n):
                acc[i] = acc[i] + _fold_lanes(
                    dy * x_ref[rows, i * d + s:i * d + s + w])
        cols_ref[rows, :] = _place_cols(
            [jnp.sum(a, axis=1, keepdims=True) for a in acc])

    _for_row_chunks(tile, reduce)
    dpre_ref[...] = jnp.transpose(cols_ref[...])[:dpre_ref.shape[0]]


def _hc_mix_dx_kernel(x_ref, dy_ref, g_ref, phit_ref, dx_ref, dphit_ref,
                      cols_ref, *, n, tokens):
    """``g_ref`` [kp, tile]: rows [0, k) the projections' gradient times the
    rsqrt, rows [k, k + n) pre, row k + n the rsqrt's own term; ``phit_ref``
    has zeros from row k on, so the extra rows add nothing to dX (their rows
    of dPhi^T are dropped by the caller)."""
    tile, d = dy_ref.shape
    k = _hc_rows(n)[0]
    g, x = g_ref[...], x_ref[...]
    if tokens % tile:
        valid = _valid_lanes(tile, tokens)
        g = jnp.where(valid, g, 0.0)
        x = jnp.where(_row_to_col(valid.astype(jnp.float32)) > 0, x, 0.0)

    @pl.when(pl.program_id(0) == 0)
    def _first():
        dphit_ref[...] = jnp.zeros(dphit_ref.shape, jnp.float32)

    dphit_ref[...] += jax.lax.dot_general(
        g, x, _NN, precision=_HIGHEST, preferred_element_type=jnp.float32)
    dx_ref[...] = jax.lax.dot_general(
        g, phit_ref[...], _TN, precision=_HIGHEST,
        preferred_element_type=jnp.float32)
    cols_ref[...] = _rows_to_cols(g)

    def finish(rows):
        col = cols_ref[rows, :]
        rs = col[:, k + n:k + n + 1]
        for s, w in _hc_chunks(d):
            dy = dy_ref[rows, s:s + w]
            for i in range(n):
                at = slice(i * d + s, i * d + s + w)
                dx_ref[rows, at] += (col[:, k + i:k + i + 1] * dy
                                     + rs * x_ref[rows, at])

    _for_row_chunks(tile, finish)


def _hc_merge_fwd_kernel(x_ref, z_ref, coef_ref, o_ref, cols_ref, *, n):
    tile, d = z_ref.shape
    cols_ref[...] = _rows_to_cols(coef_ref[...])

    def merge(rows):
        col = cols_ref[rows, :]
        for s, w in _hc_chunks(d):
            z = z_ref[rows, s:s + w].astype(jnp.float32)
            xs = [x_ref[rows, j * d + s:j * d + s + w] for j in range(n)]
            for i in range(n):
                o_ref[rows, i * d + s:i * d + s + w] = col[:, i:i + 1] * z \
                    + sum(col[:, n + i * n + j:n + i * n + j + 1] * xs[j]
                          for j in range(n))

    _for_row_chunks(tile, merge)


def _hc_merge_bwd_kernel(do_ref, x_ref, z_ref, coef_ref, dx_ref, dz_ref,
                         dcoef_ref, cols_ref, red_ref, *, n):
    tile, d = z_ref.shape
    cols_ref[...] = _rows_to_cols(coef_ref[...])

    def step(rows):
        col = cols_ref[rows, :]
        acc = [jnp.zeros((_HC_ROWS, _LANES), jnp.float32)
               for _ in range(n + n * n)]
        for s, w in _hc_chunks(d):
            z = z_ref[rows, s:s + w].astype(jnp.float32)
            dos = [do_ref[rows, i * d + s:i * d + s + w] for i in range(n)]
            dz_ref[rows, s:s + w] = sum(
                col[:, i:i + 1] * dos[i] for i in range(n)
            ).astype(dz_ref.dtype)
            for i in range(n):
                acc[i] = acc[i] + _fold_lanes(dos[i] * z)
            for j in range(n):
                xj = x_ref[rows, j * d + s:j * d + s + w]
                dx_ref[rows, j * d + s:j * d + s + w] = sum(
                    col[:, n + i * n + j:n + i * n + j + 1] * dos[i]
                    for i in range(n))
                for i in range(n):
                    acc[n + i * n + j] = acc[n + i * n + j] \
                        + _fold_lanes(dos[i] * xj)
        red_ref[rows, :] = _place_cols(
            [jnp.sum(a, axis=1, keepdims=True) for a in acc])

    _for_row_chunks(tile, step)
    dcoef_ref[...] = jnp.transpose(red_ref[...])[:dcoef_ref.shape[0]]


def _hc_call(kernel, tokens, tile, in_specs, out_specs, out_shape, scratch,
             operands, smem_first=False):
    """One grid step per tile of tokens, in order (the accumulators of the
    mix need it; v5e has one core).  Specs are ``("row", width)`` for a
    [T, width] array blocked by token rows, ``("lane", rows)`` for a
    [rows, T] array blocked by token lanes, ``("whole", shape)`` for an
    array every step sees whole."""
    def spec(kind, arg):
        if kind == "row":
            return pl.BlockSpec((tile, arg), lambda i: (i, 0))
        if kind == "lane":
            return pl.BlockSpec((arg, tile), lambda i: (0, i))
        return pl.BlockSpec(arg, lambda i: (0,) * len(arg))
    specs = [spec(*s) for s in in_specs]
    if smem_first:
        specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
    return pl.pallas_call(
        kernel, grid=(pl.cdiv(tokens, tile),), in_specs=specs,
        out_specs=[spec(*s) for s in out_specs], out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((tile, _LANES), jnp.float32)
                        for _ in range(scratch)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_HC_VMEM_LIMIT_BYTES),
    )(*operands)


def _hc_phit(phi, n):
    """Phi [n * d, k] as the kernels hold it: transposed and padded to
    [kp, n * d] (1.8 MiB; as it stands it would pad to 128 lanes, 7 MiB)."""
    k, kp, _ = _hc_rows(n)
    return jnp.pad(phi.astype(jnp.float32).T, ((0, kp - k), (0, 0)))


def _hc_mix_forward(x, phi, alpha, b, n, statics, tile):
    tokens, width = x.shape
    k, kp, cp = _hc_rows(n)
    f32 = jnp.float32
    bt = jnp.pad(b.astype(f32), (0, kp - k)).reshape(kp, 1)
    y, m, coef, err = _hc_call(
        functools.partial(_hc_mix_fwd_kernel, n=n, statics=statics,
                          tokens=tokens),
        tokens, tile,
        [("row", width), ("whole", (kp, width)), ("whole", (kp, 1))],
        [("row", width // n), ("lane", kp), ("lane", cp),
         ("whole", (8, _LANES))],
        [jax.ShapeDtypeStruct((tokens, width // n), f32),
         jax.ShapeDtypeStruct((kp, tokens), f32),
         jax.ShapeDtypeStruct((cp, tokens), f32),
         jax.ShapeDtypeStruct((8, _LANES), f32)],
        1, [alpha.astype(f32), x, _hc_phit(phi, n), bt], smem_first=True)
    return (y, coef[:n].T, coef[n:n + n * n].T, err[0, :1]), m


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _hc_mix(x, phi, alpha, b, n, statics, tile):
    return _hc_mix_forward(x, phi, alpha, b, n, statics, tile)[0]


def _hc_mix_fwd(x, phi, alpha, b, n, statics, tile):
    out, m = _hc_mix_forward(x, phi, alpha, b, n, statics, tile)
    return out, (x, phi, alpha, b, m)


def _hc_mix_bwd(n, statics, tile, res, cts):
    x, phi, alpha, b, m = res
    dy, dpost, dc, _ = cts
    tokens, width = x.shape
    k, kp, _ = _hc_rows(n)
    f32 = jnp.float32
    _, iters, hc_eps, clamp = statics
    dpre, = _hc_call(
        functools.partial(_hc_mix_dpre_kernel, n=n), tokens, tile,
        [("row", width), ("row", width // n)],
        [("lane", _round_up(n, 8))],
        [jax.ShapeDtypeStruct((_round_up(n, 8), tokens), f32)],
        1, [x, dy])
    # the 20 iterations again, and their gradient, on [24, T] arrays in XLA
    mt, inv = m[:k], m[k:k + 1]
    from .decoder_ops import hyper_connection_coefficients_t
    (pre, _, _, _), vjp = jax.vjp(
        lambda mt, alpha, bt: hyper_connection_coefficients_t(
            mt, alpha, bt, n, iters, hc_eps, clamp),
        mt, alpha.astype(f32), b.astype(f32).reshape(k, 1))
    dmt, dalpha, dbt = vjp((dpre[:n], dpost.T.astype(f32),
                            dc.T.astype(f32), jnp.zeros((n, tokens), f32)))
    # m = (x Phi) inv, inv = rsqrt(mean(x^2) + epsilon): the gradient
    # reaches x through the matmul (g) and through inv (rs x)
    g = dmt * inv
    rs = -jnp.sum(dmt * mt, axis=0, keepdims=True) * inv * inv / width
    packed = jnp.concatenate(
        [g, pre, rs, jnp.zeros((kp - k - n - 1, tokens), f32)], axis=0)
    dx, dphit = _hc_call(
        functools.partial(_hc_mix_dx_kernel, n=n, tokens=tokens),
        tokens, tile,
        [("row", width), ("row", width // n), ("lane", kp),
         ("whole", (kp, width))],
        [("row", width), ("whole", (kp, width))],
        [jax.ShapeDtypeStruct((tokens, width), f32),
         jax.ShapeDtypeStruct((kp, width), f32)],
        1, [x, dy, packed, _hc_phit(phi, n)])
    return (dx, dphit[:k].T.astype(phi.dtype), dalpha.astype(alpha.dtype),
            dbt.reshape(k).astype(b.dtype))


_hc_mix.defvjp(_hc_mix_fwd, _hc_mix_bwd)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _hc_mix_jit(x, phi, alpha, b, n, statics, tile):
    """One trace per (shapes, statics), shared by a program's mixers."""
    return _hc_mix(x, phi, alpha, b, n, statics, tile)


def hyper_connection_mix_tpu(x, phi, alpha, b, n, epsilon, iters, hc_eps,
                             clamp, tile=None):
    """The mixer before a branch (``hyper_connection_mix``'s contract):
    ``x`` [T, n * d] float32 streams -> (Y [T, d], Post [T, n], C [T, n *
    n], RowSumError [1]).  Forward is one kernel (1.25 passes over the
    streams); backward is two with the coefficients' gradient between them
    in XLA (3.5 passes)."""
    statics = (float(epsilon), int(iters), float(hc_eps),
               (float(clamp[0]), float(clamp[1])))
    return _hc_mix_jit(x, phi, alpha, b, int(n), statics, tile or _HC_TILE)


def _hc_merge_coef(post, c, n):
    """Post [T, n], C [T, n * n] -> the packed [cp, T] the kernels read."""
    cp = _hc_rows(n)[2]
    f32 = jnp.float32
    return jnp.pad(jnp.concatenate([post.astype(f32).T, c.astype(f32).T]),
                   ((0, cp - n - n * n), (0, 0)))


def _hc_merge_forward(x, z, post, c, tile):
    tokens, width = x.shape
    n = post.shape[-1]
    cp = _hc_rows(n)[2]
    out, = _hc_call(
        functools.partial(_hc_merge_fwd_kernel, n=n), tokens, tile,
        [("row", width), ("row", width // n), ("lane", cp)],
        [("row", width)],
        [jax.ShapeDtypeStruct((tokens, width), jnp.float32)],
        1, [x, z, _hc_merge_coef(post, c, n)])
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _hc_merge(x, z, post, c, tile):
    return _hc_merge_forward(x, z, post, c, tile)


def _hc_merge_fwd(x, z, post, c, tile):
    return _hc_merge_forward(x, z, post, c, tile), (x, z, post, c)


def _hc_merge_bwd(tile, res, do):
    x, z, post, c = res
    tokens, width = x.shape
    n = post.shape[-1]
    cp = _hc_rows(n)[2]
    f32 = jnp.float32
    dx, dz, dcoef = _hc_call(
        functools.partial(_hc_merge_bwd_kernel, n=n), tokens, tile,
        [("row", width), ("row", width), ("row", width // n), ("lane", cp)],
        [("row", width), ("row", width // n), ("lane", cp)],
        [jax.ShapeDtypeStruct((tokens, width), f32),
         jax.ShapeDtypeStruct(z.shape, z.dtype),
         jax.ShapeDtypeStruct((cp, tokens), f32)],
        2, [do.astype(f32), x, z, _hc_merge_coef(post, c, n)])
    return (dx, dz, dcoef[:n].T.astype(post.dtype),
            dcoef[n:n + n * n].T.astype(c.dtype))


_hc_merge.defvjp(_hc_merge_fwd, _hc_merge_bwd)


@functools.partial(jax.jit, static_argnums=(4,))
def _hc_merge_jit(x, z, post, c, tile):
    """One trace per shapes, shared by a program's mixers."""
    return _hc_merge(x, z, post, c, tile)


def hyper_connection_merge_tpu(x, z, post, c, tile=None):
    """The mixer after a branch (``hyper_connection_merge``'s contract):
    ``x`` [T, n * d] float32, ``z`` [T, d] in the dtype it arrives in
    (widened on the core), ``post`` [T, n], ``c`` [T, n * n] -> Out [T, n *
    d], ``Out[i] = post_i z + sum_j c[i, j] x[j]``: 2.25 passes forward,
    3.5 backward (one kernel each)."""
    return _hc_merge_jit(x, z, post, c, tile or _HC_TILE)


# ---------------------------------------------------------------------------
# the expert layer's segment sum (parallel/moe.py ``_sum_by_token``).  The
# held experts' rows lie grouped by tile of tokens, a tile's rows contiguous;
# each token wants the weighted sum of its own (at most top_k) rows.  XLA
# gathered [T, top_k, D] for it, a row at a time, padding included.  Here a
# grid step holds one block of rows and one tile of tokens, builds the
# [tokens, rows] matrix that has a row's weight where the row is the token's
# (top_k compares of the tokens' row numbers against the block's) and lets
# the matmul unit add: the rows are read once, at stream speed, and only the
# blocks that hold rows are visited (a work list like megablox's: one entry
# per block of a tile, a tile without rows gets one entry to write its zeros).
# A float32 weight enters as three bfloat16 terms whose sum it is exactly;
# products and sums are float32.
# ---------------------------------------------------------------------------

_SEGSUM_ROWS = 256            # rows of one grid step


def held_rows_sum_supported(rows, num_tokens, token_tile) -> bool:
    """bfloat16 rows in whole blocks, tokens in whole tiles, a lane-aligned
    width."""
    return (rows.dtype == jnp.bfloat16 and rows.shape[0] % _SEGSUM_ROWS == 0
            and num_tokens % token_tile == 0 and token_tile % 8 == 0
            and rows.shape[1] % 128 == 0)


def _held_rows_sum_kernel(offsets_ref, tiles_ref, blocks_ref, at_ref, w_ref,
                          rows_ref, o_ref, acc_ref, *, unit_weights):
    i = pl.program_id(0)
    tile = tiles_ref[i]
    before = tiles_ref[jnp.maximum(i - 1, 0)]
    after = tiles_ref[jnp.minimum(i + 1, pl.num_programs(0) - 1)]

    @pl.when((i == 0) | (before != tile))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(offsets_ref[tile + 1] > offsets_ref[tile])
    def _():
        tokens, block = at_ref.shape[0], rows_ref.shape[0]
        row = blocks_ref[i] * block + jax.lax.broadcasted_iota(
            jnp.int32, (tokens, block), 1)
        at = at_ref[...]
        w = w_ref[...]
        picks = jnp.zeros((tokens, block), jnp.float32)
        for k in range(at.shape[1]):
            picks = jnp.where(at[:, k:k + 1] == row,
                              1.0 if unit_weights else w[:, k:k + 1], picks)
        rows = rows_ref[...]
        for _ in range(1 if unit_weights else 3):
            term = picks.astype(rows.dtype)
            acc_ref[...] += jnp.dot(term, rows,
                                    preferred_element_type=jnp.float32)
            picks = picks - term.astype(jnp.float32)

    @pl.when((i == pl.num_programs(0) - 1) | (after != tile))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(4,))
def held_rows_sum_tpu(rows, at, weights, sizes, token_tile):
    """out[t] = sum_k weights[t, k] * rows[at[t, k]] over the ``k`` with
    ``at[t, k] >= 0``: ``rows`` [R, D] bfloat16, finite everywhere, the rows
    of the tokens of tile ``g`` (``token_tile`` tokens) contiguous and the
    tiles in order, ``sizes[g]`` rows each; ``at`` [T, top_k] int32;
    ``weights`` [T, top_k] float32 or None (1).  [T, D] in ``rows``'s dtype,
    accumulated in float32."""
    total, width = rows.shape
    num_tokens, top_k = at.shape
    groups = num_tokens // token_tile
    unit = weights is None
    if unit:
        weights = jnp.ones((num_tokens, 1), jnp.float32)
    # the work list: a tile's steps are the blocks its rows lie in (its one
    # step, where it has no row, writes its zeros); step s is the tile that
    # begins last at or before s, and that tile's first block plus the rest
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    lo = (ends - sizes) // _SEGSUM_ROWS
    count = jnp.where(sizes > 0, (ends - 1) // _SEGSUM_ROWS - lo, 0) + 1
    first = jnp.cumsum(count) - count
    step = jnp.arange(total // _SEGSUM_ROWS + groups, dtype=jnp.int32)
    tiles = jnp.sum(step[:, None] >= first[None, :], axis=1,
                    dtype=jnp.int32) - 1
    blocks = jnp.minimum(lo[tiles] + step - first[tiles],
                         total // _SEGSUM_ROWS - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    steps = jnp.sum(count)
    return pl.pallas_call(
        functools.partial(_held_rows_sum_kernel, unit_weights=unit),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(steps,),
            in_specs=[
                pl.BlockSpec((token_tile, top_k),
                             lambda i, o, t, b: (t[i], 0)),
                pl.BlockSpec((token_tile, weights.shape[1]),
                             lambda i, o, t, b: (t[i], 0)),
                pl.BlockSpec((_SEGSUM_ROWS, width),
                             lambda i, o, t, b: (b[i], 0))],
            out_specs=pl.BlockSpec((token_tile, width),
                                   lambda i, o, t, b: (t[i], 0)),
            scratch_shapes=[pltpu.VMEM((token_tile, width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((num_tokens, width), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(offsets, tiles, blocks, at, weights.astype(jnp.float32), rows)


# ---------------------------------------------------------------------------
# fused CTR embedding: gather + pool forward, weighted scatter-add backward.
#
# The kernel-tier pass (fluid/passes/kernel_tier.py fuse_sparse_embedding)
# rewrites lookup_table(+sequence_pool) chains onto the fused_embedding_pool
# op; on TPU, for a table that fits VMEM, its lowering lands here.  The naive
# chain materialises the [B, S, D] gathered tensor in HBM just to collapse it
# one op later — here the ids and weights are scalar-prefetched to SMEM, the
# table sits in VMEM whole, and each batch row's S table rows accumulate into
# its pooled [1, D] result in registers, so the intermediate never exists.
# The backward is the PaddleBox fused gradient: a weighted scatter-add
# (segment-sum) straight into the dW buffer, one pass, no [B, S, D]
# cotangent.  TPU grid steps run sequentially, so the read-modify-write
# scatter is race-free by construction.
# ---------------------------------------------------------------------------

# The table (forward) or the dW block (backward) must fit VMEM; bigger
# tables take the XLA take/segment_sum lowering in ops/ctr_ops.py.
_EMB_VMEM_BYTES = 4 << 20
_EMB_ROWS = 8                 # batch rows per grid step: one f32 sublane tile


def fused_embedding_pool_supported(w, ids) -> bool:
    """Shapes the Pallas embedding path covers: lane-aligned f32 row dim,
    2-d ids, and a table that fits one VMEM block."""
    if w.ndim != 2 or ids.ndim != 2 or ids.shape[1] == 0:
        return False
    return (w.dtype == jnp.float32 and w.shape[1] % 128 == 0
            and w.size * w.dtype.itemsize <= _EMB_VMEM_BYTES)


def _pad_batch(ids, wgt, *rows):
    """Flatten ids/weights for SMEM and pad the batch to whole
    ``_EMB_ROWS`` blocks; padding positions carry weight 0."""
    pad = (-ids.shape[0]) % _EMB_ROWS
    if pad:
        ids = jnp.pad(ids, ((0, pad), (0, 0)))
        wgt = jnp.pad(wgt, ((0, pad), (0, 0)))
        rows = tuple(jnp.pad(r, ((0, pad), (0, 0))) for r in rows)
    return (ids.astype(jnp.int32).reshape(-1),
            wgt.astype(jnp.float32).reshape(-1)) + rows


def _gather_pool_kernel(ids_ref, wgt_ref, w_ref, o_ref, *, n_ids):
    d = o_ref.shape[-1]
    first = pl.program_id(0) * _EMB_ROWS
    for r in range(_EMB_ROWS):
        at = (first + r) * n_ids

        def body(j, acc, at=at):
            row = w_ref[pl.ds(ids_ref[at + j], 1), :]
            return acc + row * wgt_ref[at + j]

        o_ref[r:r + 1, :] = jax.lax.fori_loop(
            0, n_ids, body, jnp.zeros((1, d), o_ref.dtype))


def fused_embedding_pool_tpu(w, ids, wgt):
    """out[i] = sum_j w[ids[i, j]] * wgt[i, j] — gather and pool in one
    kernel.  ``wgt`` carries the pooling semantics (0 for padding_idx /
    beyond-length positions, 1/len for mean pooling)."""
    b, s = ids.shape
    d = w.shape[1]
    ids_f, wgt_f = _pad_batch(ids, wgt)
    bp = ids_f.size // s
    out = pl.pallas_call(
        functools.partial(_gather_pool_kernel, n_ids=s),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bp // _EMB_ROWS,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((_EMB_ROWS, d), lambda i, *_: (i, 0))),
        out_shape=jax.ShapeDtypeStruct((bp, d), w.dtype),
    )(ids_f, wgt_f, w)
    return out[:b]


def _scatter_grad_kernel(ids_ref, wgt_ref, g_ref, o_ref, *, n_ids):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    for r in range(_EMB_ROWS):
        at = (i * _EMB_ROWS + r) * n_ids
        g = g_ref[r:r + 1, :]

        def body(j, carry, at=at, g=g):
            row = pl.ds(ids_ref[at + j], 1)
            o_ref[row, :] = o_ref[row, :] + g * wgt_ref[at + j]
            return carry

        jax.lax.fori_loop(0, n_ids, body, 0)


def embedding_pool_grad_tpu(g, ids, wgt, vocab):
    """dW[ids[i, j]] += g[i] * wgt[i, j]: the fused gradient scatter-add.
    The whole dW buffer is the (sequentially-gridded) output block, so the
    accumulation never materialises per-position cotangent rows."""
    s = ids.shape[1]
    d = g.shape[-1]
    ids_f, wgt_f, g = _pad_batch(ids, wgt, g)
    return pl.pallas_call(
        functools.partial(_scatter_grad_kernel, n_ids=s),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(g.shape[0] // _EMB_ROWS,),
            in_specs=[pl.BlockSpec((_EMB_ROWS, d), lambda i, *_: (i, 0))],
            out_specs=pl.BlockSpec((vocab, d), lambda i, *_: (0, 0))),
        out_shape=jax.ShapeDtypeStruct((vocab, d), g.dtype),
    )(ids_f, wgt_f, g)


# ---------------------------------------------------------------------------
# the selective scan of a state-space layer (ops/selective_scan.py):
# s_t = exp(dt_t (x) A) * s_{t-1} + (dt_t * x_t) (x) B_t, y_t = s_t C_t + D x_t.
# No matmul covers it (A differs per channel and state): S sequential steps
# of elementwise work.  The state of a block of 512 channels, [N, 512]
# float32 with the channels on the lanes (8 vector registers at N = 16),
# stays in registers over a chunk of 256 tokens and in VMEM from one grid
# step to the next; the grid is (batch, channel blocks, chunks), the chunks
# last and in order.  x, dt are read and y written once, a token's row at a
# time; B and C come as [S / 8, N, 8] tiles (eight tokens' vectors down the
# sublanes), so a token's is a static lane of a tile, broadcast over the
# channels.  The state after every chunk is written out ([S / 256, N, Di]
# float32, 5 MB a layer): all that backward needs besides the operands.
# Backward visits the chunks last to first: it computes a chunk's states
# again from the state before it into VMEM ([257, N, 512] float32, 8.4 MB),
# then sweeps the chunk in reverse with the state's gradient in registers,
# giving the gradients of x and dt a row a token, of B and C a lane a token
# (per channel block, added up outside), and of A and D summed on the core
# over the sequence (per batch row, added up outside).
# ---------------------------------------------------------------------------

_SSM_GROUP = 8           # tokens unrolled in the loop body: a sublane tile


def _ssm_channel_block(di):
    return next((b for b in (512, 256, 128) if di % b == 0), 0)


def selective_scan_supported(x, a) -> bool:
    """Does the scan kernel cover X [B, S, Di], A [Di, N]: channels in whole
    128-lane groups, states in whole sublane tiles and few enough that a
    block's state stays in registers, at least one chunk of tokens (a ragged
    last chunk is padded with steps of size 0)."""
    return (x.ndim == 3 and a.ndim == 2 and a.shape[0] == x.shape[2]
            and _ssm_channel_block(x.shape[2]) > 0
            and a.shape[1] % 8 == 0 and a.shape[1] <= 32
            and x.shape[1] >= _SSM_CHUNK)


def _ssm_params(name):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name=name)


def _ssm_step(s, xt, dtt, a, bcol):
    return jnp.exp(dtt * a) * s + (dtt * xt) * bcol


def _ssm_fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref,
                    end_ref, s_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    a, skip = a_ref[...], d_ref[...]

    def group(g, s):
        r0 = pl.multiple_of(g * _SSM_GROUP, _SSM_GROUP)
        xg = x_ref[pl.ds(r0, _SSM_GROUP), :]
        dtg = dt_ref[pl.ds(r0, _SSM_GROUP), :]
        bg, cg = b_ref[g], c_ref[g]
        for j in range(_SSM_GROUP):
            xt, dtt = xg[j:j + 1], dtg[j:j + 1]
            s = _ssm_step(s, xt, dtt, a, bg[:, j:j + 1])
            y_ref[pl.ds(r0 + j, 1), :] = jnp.sum(
                s * cg[:, j:j + 1], axis=0, keepdims=True) + skip * xt
        return s

    s = jax.lax.fori_loop(0, x_ref.shape[0] // _SSM_GROUP, group, s_ref[...])
    s_ref[...] = s
    end_ref[...] = s


def _ssm_bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, dy_ref,
                    start_ref, dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                    dd_ref, hist_ref, g_ref):
    k = pl.program_id(2)              # 0 is the LAST chunk of the sequence
    groups = x_ref.shape[0] // _SSM_GROUP

    @pl.when(k == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    a, skip = a_ref[...], d_ref[...]
    # the first chunk starts from no state (its block of ``starts`` is a
    # stand-in)
    s0 = jnp.where(k == pl.num_programs(2) - 1, 0.0, start_ref[...])
    hist_ref[0] = s0

    def again(g, s):
        r0 = pl.multiple_of(g * _SSM_GROUP, _SSM_GROUP)
        xg = x_ref[pl.ds(r0, _SSM_GROUP), :]
        dtg = dt_ref[pl.ds(r0, _SSM_GROUP), :]
        bg = b_ref[g]
        for j in range(_SSM_GROUP):
            s = _ssm_step(s, xg[j:j + 1], dtg[j:j + 1], a, bg[:, j:j + 1])
            hist_ref[r0 + j + 1] = s
        return s

    jax.lax.fori_loop(0, groups, again, s0)

    lane = jax.lax.broadcasted_iota(jnp.int32, b_ref.shape[1:], 1)

    def back(i, carry):
        gs, da = carry                # d loss / d s_t from later tokens; dA
        g = groups - 1 - i
        r0 = pl.multiple_of(g * _SSM_GROUP, _SSM_GROUP)
        xg = x_ref[pl.ds(r0, _SSM_GROUP), :]
        dtg = dt_ref[pl.ds(r0, _SSM_GROUP), :]
        dyg = dy_ref[pl.ds(r0, _SSM_GROUP), :]
        bg, cg = b_ref[g], c_ref[g]
        dbg = jnp.zeros(lane.shape, jnp.float32)
        dcg = jnp.zeros(lane.shape, jnp.float32)
        for j in reversed(range(_SSM_GROUP)):
            xt, dtt, dyt = xg[j:j + 1], dtg[j:j + 1], dyg[j:j + 1]
            decay = jnp.exp(dtt * a)
            gs = gs + dyt * cg[:, j:j + 1]
            into = jnp.sum(gs * bg[:, j:j + 1], axis=0, keepdims=True)
            w = gs * hist_ref[r0 + j] * decay          # d loss / d (dt A)
            dx_ref[pl.ds(r0 + j, 1), :] = dyt * skip + dtt * into
            ddt_ref[pl.ds(r0 + j, 1), :] = jnp.sum(
                w * a, axis=0, keepdims=True) + xt * into
            da = da + w * dtt
            dbg = jnp.where(lane == j, jnp.sum(gs * (dtt * xt), axis=1,
                                               keepdims=True), dbg)
            dcg = jnp.where(lane == j, jnp.sum(hist_ref[r0 + j + 1] * dyt,
                                               axis=1, keepdims=True), dcg)
            gs = decay * gs
        db_ref[g] = dbg
        dc_ref[g] = dcg
        return gs, da

    gs, da = jax.lax.fori_loop(0, groups, back, (g_ref[...], da_ref[...]))
    g_ref[...] = gs
    da_ref[...] = da
    dd_ref[...] += jnp.sum(dy_ref[...] * x_ref[...], axis=0, keepdims=True)


def _ssm_operands(x, dt, a, b, c, d):
    """The operands as the kernels read them, float32, the sequence padded
    to whole chunks with steps of size 0: x, dt [B, S, Di]; A^T [N, Di]; B,
    C as [B, S / 8, N, 8]; D [1, Di]."""
    pad = -x.shape[1] % _SSM_CHUNK
    f32 = jnp.float32

    def rows(v):
        return jnp.pad(v.astype(f32), ((0, 0), (0, pad), (0, 0)))

    def tiles(v):
        v = rows(v)
        return v.reshape(v.shape[0], -1, _SSM_GROUP, v.shape[2]) \
            .transpose(0, 1, 3, 2)
    return (rows(x), rows(dt), a.astype(f32).T, tiles(b), tiles(c),
            d.astype(f32).reshape(1, -1))


def _ssm_specs(di, n, chunks, reverse):
    """BlockSpecs of (a [S, Di] operand, A^T or a [N, Di] sum, a [S / 8, N,
    8] operand, D) on the grid (batch, channel block, chunk); ``reverse``
    visits the chunks last to first."""
    blk = _ssm_channel_block(di)

    def at(k):
        return chunks - 1 - k if reverse else k
    return (pl.BlockSpec((None, _SSM_CHUNK, blk),
                         lambda i, j, k: (i, at(k), j)),
            pl.BlockSpec((n, blk), lambda i, j, k: (0, j)),
            pl.BlockSpec((None, _SSM_CHUNK // _SSM_GROUP, n, _SSM_GROUP),
                         lambda i, j, k: (i, at(k), 0, 0)),
            pl.BlockSpec((1, blk), lambda i, j, k: (0, j)))


def _ssm_forward(ops):
    x, dt, at, bt, ct, d = ops
    bsz, seq, di = x.shape
    n, chunks, blk = at.shape[0], seq // _SSM_CHUNK, _ssm_channel_block(di)
    row, state, tile, skip = _ssm_specs(di, n, chunks, False)
    return pl.pallas_call(
        _ssm_fwd_kernel,
        grid=(bsz, di // blk, chunks),
        in_specs=[row, row, state, tile, tile, skip],
        out_specs=[row, pl.BlockSpec((None, None, n, blk),
                                     lambda i, j, k: (i, k, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((bsz, seq, di), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, chunks, n, di), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, blk), jnp.float32)],
        **_ssm_params("selective_scan_fwd"))(x, dt, at, bt, ct, d)


@jax.custom_vjp
def selective_scan_tpu(x, dt, a, b, c, d):
    """(y [B, S, Di] float32, the state after each chunk [B, S / 256, N, Di]
    float32) of ``ops.selective_scan``'s recurrence; no gradient passes
    through the states."""
    return _selective_scan_fwd(x, dt, a, b, c, d)[0]


def _selective_scan_fwd(x, dt, a, b, c, d):
    ops = _ssm_operands(x, dt, a, b, c, d)
    y, ends = _ssm_forward(ops)
    return (y[:, :x.shape[1]], ends), (x, dt, a, b, c, d, ends)


def _selective_scan_bwd(res, cotangents):
    x, dt, a, b, c, d, ends = res
    dy = cotangents[0]
    xs, dts, at, bt, ct, skip = _ssm_operands(x, dt, a, b, c, d)
    dy = jnp.pad(dy.astype(jnp.float32),
                 ((0, 0), (0, xs.shape[1] - dy.shape[1]), (0, 0)))
    bsz, seq, di = xs.shape
    n, chunks, blk = at.shape[0], seq // _SSM_CHUNK, _ssm_channel_block(di)
    row, state, tile, skip_spec = _ssm_specs(di, n, chunks, True)
    f32 = jnp.float32
    dx, ddt, da, db, dc, dd = pl.pallas_call(
        _ssm_bwd_kernel,
        grid=(bsz, di // blk, chunks),
        in_specs=[row, row, state, tile, tile, skip_spec, row,
                  # the state BEFORE chunk c is the one after chunk c - 1
                  pl.BlockSpec((None, None, n, blk),
                               lambda i, j, k: (
                                   i, jnp.maximum(chunks - 2 - k, 0), 0, j))],
        out_specs=[row, row,
                   pl.BlockSpec((None, n, blk), lambda i, j, k: (i, 0, j)),
                   pl.BlockSpec(
                       (None, None, _SSM_CHUNK // _SSM_GROUP, n, _SSM_GROUP),
                       lambda i, j, k: (i, j, chunks - 1 - k, 0, 0)),
                   pl.BlockSpec(
                       (None, None, _SSM_CHUNK // _SSM_GROUP, n, _SSM_GROUP),
                       lambda i, j, k: (i, j, chunks - 1 - k, 0, 0)),
                   pl.BlockSpec((None, 1, blk), lambda i, j, k: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((bsz, seq, di), f32),
                   jax.ShapeDtypeStruct((bsz, seq, di), f32),
                   jax.ShapeDtypeStruct((bsz, n, di), f32),
                   jax.ShapeDtypeStruct(
                       (bsz, di // blk, seq // _SSM_GROUP, n, _SSM_GROUP),
                       f32),
                   jax.ShapeDtypeStruct(
                       (bsz, di // blk, seq // _SSM_GROUP, n, _SSM_GROUP),
                       f32),
                   jax.ShapeDtypeStruct((bsz, 1, di), f32)],
        scratch_shapes=[pltpu.VMEM((_SSM_CHUNK + 1, n, blk), f32),
                        pltpu.VMEM((n, blk), f32)],
        **_ssm_params("selective_scan_bwd"))(xs, dts, at, bt, ct, skip, dy,
                                             ends)
    true = x.shape[1]

    def vectors(g):          # [B, blocks, S / 8, N, 8] -> [B, S, N]
        g = jnp.sum(g, axis=1).transpose(0, 1, 3, 2)
        return g.reshape(bsz, seq, n)[:, :true]
    return (dx[:, :true].astype(x.dtype), ddt[:, :true].astype(dt.dtype),
            jnp.sum(da, axis=0).T.astype(a.dtype),
            vectors(db).astype(b.dtype), vectors(dc).astype(c.dtype),
            jnp.sum(dd, axis=(0, 1)).astype(d.dtype))


selective_scan_tpu.defvjp(_selective_scan_fwd, _selective_scan_bwd)
