"""Ring attention — sequence/context parallelism over an ICI ring.

No reference analog: qingshui/Paddle (2020) has no sequence parallelism
(SURVEY §2.9 "NOT PRESENT"); this is the new-capability half of the build
plan (SURVEY §7 step 7).  Design follows the ring-attention recipe: the
sequence dimension is sharded over the `sp` mesh axis; each device holds a
Q block and ring-rotates K/V blocks with `lax.ppermute`, maintaining an
online-softmax accumulator (running max `m`, normalizer `l`, numerator `o`)
so the result is exact full attention with O(T/n) memory per device and
compute/communication overlap on ICI.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _block_attend(q, k, v, m, l, o, scale, mask_bias):
    """One online-softmax accumulation step.

    q: [B, H, Tq, D]; k,v: [B, H, Tk, D]; m,l: [B, H, Tq]; o: [B, H, Tq, D].
    mask_bias: additive [..., Tq, Tk] bias (or None).
    """
    acc = jnp.float32
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=acc)
    s = s * scale
    if mask_bias is not None:
        s = s + mask_bias.astype(acc)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard fully-masked rows (max = -inf)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
    corr = jnp.where(jnp.isfinite(m), corr, 0.0)
    l_new = l * corr + p.sum(axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(acc), preferred_element_type=acc)
    return m_new, l_new, o_new


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None):
    """Exact attention with sequence sharded over `axis_name`.

    q/k/v: [B, H, T_local, D] — the local sequence shard of this sp rank.
    Must be called inside shard_map/pjit with `axis_name` bound.
    Returns [B, H, T_local, D].
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    t_local = q.shape[-2]
    acc = jnp.float32

    m0 = jnp.full(q.shape[:-1], -jnp.inf, acc)
    l0 = jnp.zeros(q.shape[:-1], acc)
    o0 = jnp.zeros(q.shape, acc)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(step, carry):
        m, l, o, kb, vb = carry
        # kb/vb arrived from rank (my - step) % n — their global block index
        src = (my - step) % n
        if causal:
            qpos = my * t_local + jnp.arange(t_local)
            kpos = src * t_local + jnp.arange(t_local)
            bias = jnp.where(qpos[:, None] >= kpos[None, :], 0.0, -jnp.inf)
            bias = bias[None, None]
        else:
            bias = None
        m, l, o = _block_attend(q, kb, vb, m, l, o, scale, bias)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return m, l, o, kb, vb

    m, l, o = m0, l0, o0
    kb, vb = k, v
    # static unroll: n is a compile-time mesh constant, and unrolling lets
    # XLA overlap each ppermute with the next block's einsum
    for step in range(n):
        m, l, o, kb, vb = body(step, (m, l, o, kb, vb))
    out = o / jnp.maximum(l, 1e-20)[..., None]
    return out.astype(q.dtype)


def local_or_ring_attention(q, k, v, axis_name=None, causal=False, scale=None,
                            mask=None):
    """Dispatch: ring attention when an sp axis is live, else fused local."""
    if axis_name is not None:
        return ring_attention(q, k, v, axis_name, causal=causal, scale=scale)
    from ..ops.attention import flash_attention
    from ..ops.registry import chip_site
    return flash_attention(q, k, v, mask=mask, scale=scale, causal=causal,
                           site=chip_site())
