"""Fused attention ops.

Reference: paddle/fluid/operators/fused/multihead_matmul_op.cu (fused
transformer attention) and math/bert_encoder_functor.cu (SURVEY §2.5 fused/).
TPU-native: one `fused_multihead_attention` op; the `fuse_attention` pass
(fluid/passes/kernel_tier.py, in every pipeline) PRODUCES it from the naive
matmul→softmax→matmul chain wherever the op would lower to a kernel, so
plain static programs get the kernels without touching model code.  Where
a kernel runs is answered in two places and nowhere else (docs/passes.md
"Where a kernel runs"): `KernelSite.on` (ops/registry.py) says how a call
sees the batch on the program's mesh, `attention_path` here says which of
five paths one chip's operands take:

* `splash_kernel` — `pallas_kernels.splash_attention_tpu`: causal attention
  from `_STREAM_MIN_SEQ` (1024) up, and every length with a sliding `window`
  or with fewer key/value heads than query heads; only the blocks inside the
  causal band are visited, nothing of size [S, S] exists;
* `fused_kernel` — `pallas_kernels.fused_attention_tpu`: key lengths up to
  512 (BERT's), whole score rows on the core, dropout on the probabilities
  from the on-core PRNG, the padding bias passed as its [B, 1, 1, S] row;
  in a program partitioned on the batch alone (`dp`, `fsdp`) once per
  chip under `shard_map`, judged on the chip's own rows
  (`LoweringContext.kernel_site`);
* `flash_kernel` — jax's flash kernel, K/V streamed through VMEM: from
  `_STREAM_MIN_SEQ` up, dropout-free;
* `selected_kernel` — `pallas_kernels.selected_attention_tpu`: a call that
  brings a `Selection` (a bit a (query, key) pair, [S, S / 8] uint8 a
  sequence, chosen on the device by the `sparse_attention_index` op: ops/sparse_attention.py), the
  splash kernel with that mask as data, shared by all the heads;
* `xla` — `_reference_attention`, the XLA softmax(QK^T)V: the CPU, a
  program partitioned any other way (`tp`, a mesh with further axes: a
  Mosaic call cannot be partitioned automatically, and only the fused
  kernel has been taken under `shard_map`), and every shape the kernels do
  not cover.  With a `window` or grouped heads it is
  `_banded_attention`: blocks of queries against the keys of their band,
  each block recomputed in backward, so that no [S, S] scores exist there
  either; with a `Selection` `sparse_attention.selected_attention`, blocks
  of queries likewise.

`attention.lowering.<path>` in `trace.metrics()` counts the picks, once per
lowering of an op (a training program lowers each attention once: its grad
op applies the vjp the forward op kept; twice where the grad op has to trace
the forward again, `backward.vjp_retraced`); a causal op also counts
`attention.lowering.<path>.window` or `.full_causal`; a call with a
selection counts `sparse_attention.lowering.<path>` as well.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .registry import KernelSite, register_op

# From this length up causal attention takes the splash kernel and
# dropout-free non-causal attention jax's flash kernel, both streaming K/V
# blocks through VMEM.  Untimed: the crossover against XLA has not been
# measured on this code (no cell has a non-causal call this long; the
# causal cell sits at 8192).
_STREAM_MIN_SEQ = 1024

# Shortest sequence that takes the fused kernel: it beats XLA's chain at
# every length it covers (v5e, forward + backward of one BERT-base layer
# with dropout 0.1, same 16,384 tokens: [128, 12, 128, 64] 0.76 ms against
# 1.83, [32, 12, 512, 64] 1.32 against 7.70; my chip runs, PR 25), so the
# floor is the shortest lane-aligned length.
_FUSED_MIN_SEQ = 128


def _reference_attention(q, k, v, mask, scale, causal,
                         dropout_rate=0.0, dropout_key=None,
                         dropout_upscale=True, prob_scale=None):
    # q,k,v: [B, H, T, D]
    acc = jnp.float32
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=acc) * scale
    if causal:
        t = s.shape[-1]
        neg = jnp.finfo(acc).min
        causal_mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(causal_mask[None, None], s, neg)
    if mask is not None:
        s = s + mask.astype(acc)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    # attention dropout ON THE PROBABILITIES, spelled exactly like the
    # standalone dropout lowering (ops/nn_ops.py) so a kernel-tier rewrite
    # that absorbed a dropout op reproduces the identical mask from the
    # identical key — CPU-fallback parity is bit-level, not just allclose
    if dropout_rate and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_rate, p.shape)
        if dropout_upscale:
            p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0).astype(p.dtype)
        else:
            p = jnp.where(keep, p, 0.0).astype(p.dtype)
    elif prob_scale is not None:
        # downgrade_in_infer at test time: probs scaled by (1 - rate)
        p = (p * p.dtype.type(prob_scale)).astype(p.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# queries a block of `_banded_attention`: [B, H, 512, band] float32 scores
_BAND_BLOCK = 512


def _banded_attention(q, k, v, scale, window):
    """Causal attention in XLA without [S, S] scores: q [B, Hq, S, D], k/v
    [B, Hkv, S, D] (each key/value head shared by Hq / Hkv query heads),
    position i attending j <= i and, with ``window`` > 0, i - j < window.
    Each block of queries sees only the keys of its band and is recomputed
    in backward (``jax.checkpoint``)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    qg = q.reshape(b, hkv, hq // hkv, s, d)
    dv = v.shape[-1]
    acc = jnp.float32

    @jax.checkpoint
    def block(qb, kb, vb, r0, c0):
        sc = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kb,
                        preferred_element_type=acc) * scale
        i = r0 + jnp.arange(qb.shape[3])[:, None]
        j = c0 + jnp.arange(kb.shape[2])[None, :]
        keep = j <= i
        if window:
            keep &= i - j < window
        sc = jnp.where(keep, sc, jnp.finfo(acc).min)
        p = jax.nn.softmax(sc, axis=-1).astype(qb.dtype)
        return jnp.einsum("bhgqk,bhkd->bhgqd", p, vb)

    step = min(_BAND_BLOCK, s)
    out = []
    for r0 in range(0, s, step):
        r1 = min(r0 + step, s)
        c0 = max(0, r0 - window + 1) if window else 0
        out.append(block(qg[:, :, :, r0:r1], k[:, :, c0:r1], v[:, :, c0:r1],
                         r0, c0))
    return jnp.concatenate(out, axis=3).reshape(b, hq, s, dv)


def _bias_broadcastable(mask, q, k) -> bool:
    """Can ``mask`` serve as the Pallas kernel's additive-bias ``ab``
    argument — i.e. broadcast to [B, H, Tq, Tk]?"""
    if mask is None or mask.ndim != 4:
        return False
    target = (q.shape[0], q.shape[1], q.shape[2], k.shape[2])
    return all(m == 1 or m == t for m, t in zip(mask.shape, target))


def attention_path(q, k, v, mask, causal, drop_active, window=0,
                   selection=None) -> str:
    """Which lowering attention over one chip's operands takes on a chip:
    ``splash_kernel``, ``fused_kernel``, ``flash_kernel``,
    ``selected_kernel`` (only a call that brings a ``selection``, and every
    such call the kernel covers) or ``xla``.  A function of shapes and
    dtypes alone (the operands may be ShapeDtypeStructs, or a block's
    declared variables)."""
    if selection is not None:
        from .pallas_kernels import selected_attention_supported
        return "selected_kernel" if mask is None and not drop_active \
            and selected_attention_supported(q, k, v, selection) else "xla"
    seq = q.shape[-2]
    grouped = k.shape[1] != q.shape[1]
    if (window or grouped or seq >= _STREAM_MIN_SEQ) and causal \
            and not drop_active:
        from .pallas_kernels import splash_attention_supported
        if splash_attention_supported(q, k, v, mask):
            return "splash_kernel"
    if window or grouped:
        return "xla"
    if not causal and seq >= _FUSED_MIN_SEQ:
        from .pallas_kernels import fused_attention_supported
        if fused_attention_supported(q, k, v, mask):
            return "fused_kernel"
    if seq >= _STREAM_MIN_SEQ and not drop_active and k.shape == v.shape \
            and (mask is None or _bias_broadcastable(mask, q, k)):
        return "flash_kernel"
    return "xla"


def path_at(site, q, k, v, mask, causal, drop_active, window=0,
            selection=None) -> str:
    """The path of a call whose kernel would run at ``site``
    (``KernelSite.on`` / ``LoweringContext.kernel_site``; None: XLA):
    ``attention_path`` of the rows one call sees.  Only the fused kernel
    has been taken under ``shard_map``; the others keep XLA per shard."""
    if site is None:
        return "xla"
    batched_mask = mask is not None and mask.shape[0] == q.shape[0]
    path = attention_path(site.local(q), site.local(k), site.local(v),
                          site.local(mask) if batched_mask else mask,
                          causal, drop_active, window,
                          None if selection is None
                          else site.local(selection))
    if site.shards > 1 and path != "fused_kernel":
        return "xla"
    return path


def flash_attention(q, k, v, mask=None, scale=None, causal=False,
                    dropout_rate=0.0, dropout_key=None,
                    dropout_upscale=True, prob_scale=None, window=0,
                    site=None, selection=None):
    """Dispatch to a Pallas TPU kernel where one covers the call, else XLA
    (``path_at``).  ``site`` says where the call's kernel runs: None (the
    default) is XLA; an op lowering passes ``ctx.kernel_site(q)``; a
    caller that is already inside a per-chip body (the ``shard_map``
    bodies in parallel/) passes ``registry.chip_site()``.

    The flash kernel takes additive-bias masks through its ``ab`` argument
    (anything broadcastable to [B, H, Tq, Tk], materialised at that size)
    and has no dropout; the fused kernel takes the [B, 1, 1, Tk] bias row
    as it is and drops probabilities in-kernel.

    ``window`` > 0 (with ``causal``) keeps 0 <= i - j < window; ``k`` and
    ``v`` may have fewer heads than ``q``, each shared by a group of query
    heads.  Both are causal, mask-free and dropout-free: the splash kernel,
    or ``_banded_attention`` in XLA.

    ``selection`` [B, S, S / 8] uint8 (with ``causal``; no window, mask or
    dropout) keeps, for query t, the keys s whose bit of row t is set
    (ops/sparse_attention.py chooses them among s <= t): the selected
    kernel, or ``sparse_attention.selected_attention`` in XLA.  Such a call
    returns ``(out, lse)``, ``lse`` [B, Hq, S] float32 the log-sum-exp of
    every query's scaled scores over its keys (no gradient passes through
    it): what the indexer's loss forms the probabilities from.
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    drop_active = bool(dropout_rate) and dropout_key is not None
    window = int(window or 0)
    banded = bool(window) or k.shape[1] != q.shape[1]
    if banded and (not causal or mask is not None or drop_active
                   or prob_scale is not None):
        raise ValueError("attention with a window or grouped key/value "
                         "heads is causal, without mask or dropout")
    if selection is not None and (window or not causal
                                  or mask is not None or drop_active
                                  or prob_scale is not None):
        raise ValueError("attention over a selection is causal, without "
                         "window, mask or dropout")
    path = path_at(site, q, k, v, mask, causal, drop_active, window,
                   selection)
    if path in ("flash_kernel", "splash_kernel", "selected_kernel") \
            and (prob_scale is not None or scale == 0.0):
        path = "xla"
    from ..fluid import trace
    trace.metrics().counter(f"attention.lowering.{path}").inc()
    if selection is not None:
        trace.metrics().counter(f"sparse_attention.lowering.{path}").inc()
        if path == "selected_kernel":
            from .pallas_kernels import selected_attention_tpu as attend
        else:
            from .sparse_attention import selected_attention as attend
        return attend(q, k, v, selection, scale)
    if causal:
        trace.metrics().counter(
            f"attention.lowering.{path}."
            + ("window" if window else "full_causal")).inc()
    if path == "splash_kernel":
        from .pallas_kernels import splash_attention_tpu
        return splash_attention_tpu(q, k, v, scale=scale, window=window)
    if banded:
        return _banded_attention(q, k, v, scale, window)
    if path == "fused_kernel":
        from .pallas_kernels import fused_attention_tpu
        return fused_attention_tpu(
            q, k, v, mask, scale=scale,
            dropout_rate=dropout_rate if drop_active else 0.0,
            dropout_key=dropout_key, dropout_upscale=dropout_upscale,
            prob_scale=prob_scale, site=site)
    if path == "flash_kernel":
        from .pallas_kernels import flash_attention_tpu
        ab = None
        if mask is not None:
            # the Pallas kernel computes softmax((QKᵀ + ab)·scale); our
            # contract is softmax(QKᵀ·scale + mask), so the bias rides in
            # pre-divided by the scale
            ab = (jnp.broadcast_to(
                mask, (q.shape[0], q.shape[1], q.shape[2], k.shape[2])
            ).astype(jnp.float32) / scale).astype(q.dtype)
        return flash_attention_tpu(q, k, v, scale=scale, causal=causal,
                                   ab=ab)
    return _reference_attention(q, k, v, mask, scale, causal,
                                dropout_rate if drop_active else 0.0,
                                dropout_key, dropout_upscale, prob_scale)


@register_op("fused_multihead_attention",
             nondiff_inputs=("Mask", "Selection"), nondiff_outputs=("LSE",))
def _fused_mha(ins, attrs, ctx):
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    mask = ins["Mask"][0] if ins.get("Mask") else None
    # attention-dropout attrs stamped by the fuse_attention pass when it
    # absorbs a dropout op: same op_seed -> same ctx key -> same mask as
    # the unrewritten program on the XLA path
    rate = float(attrs.get("dropout_rate", 0.0) or 0.0)
    dropout_key = None
    prob_scale = None
    upscale = attrs.get("dropout_implementation",
                        "downgrade_in_infer") == "upscale_in_train"
    if rate:
        is_test = attrs.get("dropout_is_test", False) or ctx.is_test
        if is_test:
            if not upscale:
                prob_scale = 1.0 - rate
        else:
            dropout_key = ctx.key_for(attrs.get("dropout_seed", 0))
    selection = ins["Selection"][0] if ins.get("Selection") else None
    out = flash_attention(q, k, v, mask,
                          scale=attrs.get("scale", None),
                          causal=attrs.get("causal", False),
                          dropout_rate=rate, dropout_key=dropout_key,
                          dropout_upscale=upscale, prob_scale=prob_scale,
                          window=attrs.get("window", 0),
                          site=ctx.kernel_site(q), selection=selection)
    if selection is not None:
        return {"Out": [out[0]], "LSE": [out[1]]}
    return {"Out": [out]}


def _paged_reference(q, kp, vp, idx, valid, scale, neg):
    """The XLA fallback: bit-for-bit the op-by-op lowering of the paged
    decode attend chain (serving/decode.py demo paged program) —
    gather → reshape → mul+reduce_sum scores → scale → masked add →
    softmax → mul+reduce_sum context.  The fuse_paged_attention pass
    (fluid/passes/kernel_tier.py) swaps the chain for this op, so every
    spelling here must reproduce the individual op lowerings exactly
    (jnp.take for gather, the same reduce axes, ``x * scale + bias`` for
    scale) or the rewrite would not be bit-transparent on CPU."""
    b = q.shape[0]
    s_len = valid.shape[1]
    d = kp.shape[-1]
    ii = idx.astype(jnp.int32)
    kg = jnp.take(kp, ii, axis=0).reshape(b, s_len, d)
    vg = jnp.take(vp, ii, axis=0).reshape(b, s_len, d)
    s = jnp.sum(jnp.multiply(kg, q.reshape(b, 1, d)), axis=(2,))
    s = s * scale + 0.0
    s = jnp.add(jnp.multiply(s, valid), valid * neg + (-neg))
    p = jax.nn.softmax(s, axis=-1)
    return jnp.sum(jnp.multiply(vg, p.reshape(b, s_len, 1)), axis=(1,))


@register_op("paged_attention", nondiff_inputs=("Index", "Valid"))
def _paged_attention(ins, attrs, ctx):
    """Decode-step attention over a block-paged KV pool.

    Q [B, d]; KPool/VPool [R, d] flat page pools; Index [B*S] (or [B, S])
    int32 pool-row per logical position; Valid [B, S] float 0/1 mask.
    On TPU, with a lane-aligned head dim and pools that fit VMEM
    (pallas_kernels.paged_attention_supported), the lowering is the
    Pallas paged flash kernel; otherwise the XLA gather lowering, which
    mirrors the unfused chain bit-for-bit."""
    q = ins["Q"][0]
    kp, vp = ins["KPool"][0], ins["VPool"][0]
    idx, valid = ins["Index"][0], ins["Valid"][0]
    scale = float(attrs.get("scale", 1.0))
    neg = float(attrs.get("neg", 1e30))
    b, s_len = valid.shape
    idx2 = idx.reshape(b, s_len)
    if ctx.pallas_ok():
        from .pallas_kernels import (paged_attention_supported,
                                     paged_flash_attention_tpu)
        if paged_attention_supported(q, kp, idx2):
            ps = int(attrs.get("page_size", 1) or 1)
            if s_len % ps != 0:
                ps = 1
            lengths = jnp.sum(valid, axis=1).astype(jnp.int32)
            return {"Out": [paged_flash_attention_tpu(
                q, kp, vp, idx2, lengths, scale, page_size=ps)]}
    return {"Out": [_paged_reference(q, kp, vp, idx.reshape(-1), valid,
                                     scale, neg)]}


@register_op("multihead_matmul", nondiff_inputs=("BiasQK",))
def _multihead_matmul(ins, attrs, ctx):
    """Reference multihead_matmul_op.cu API: packed QKV input."""
    x = ins["Input"][0]            # [B, T, 3*H*D]
    bias_qk = ins["BiasQK"][0] if ins.get("BiasQK") else None
    h = attrs["head_number"]
    b, t, c3 = x.shape
    d = c3 // 3 // h
    qkv = x.reshape(b, t, 3, h, d).transpose(2, 0, 3, 1, 4)
    out = flash_attention(qkv[0], qkv[1], qkv[2], bias_qk,
                          scale=attrs.get("alpha", None),
                          site=KernelSite() if ctx.pallas_ok() else None)
    return {"Out": [out.transpose(0, 2, 1, 3).reshape(b, t, h * d)]}
