"""Ulysses-style (all-to-all) sequence parallelism.

The second half of the long-context story next to ring attention
(parallel/ring_attention.py): instead of ring-rotating K/V blocks, two
`lax.all_to_all`s re-shard the activations from sequence-sharded to
HEAD-sharded, run ordinary full attention on each device's head subset
(any kernel — XLA fusion or the pallas flash path), and shard back.

Trade-off vs ring attention (why both exist): Ulysses moves 3 tensors
twice over ICI but keeps attention completely local and kernel-agnostic —
best when heads >= sp and the per-device full-sequence scores fit; ring
keeps memory at O(T/n) per device and overlaps compute with transfer —
best at extreme sequence lengths.  No reference analog (SURVEY §2.9 "NOT
PRESENT"; 2020 predates both).
"""
from __future__ import annotations

import math
from typing import Callable, Optional

from jax import lax


def _seq_to_heads(x, axis_name):
    """[B, H, T/n, D] -> [B, H/n, T, D]: split heads over the axis, gather
    the full sequence."""
    return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def _heads_to_seq(x, axis_name):
    """[B, H/n, T, D] -> [B, H, T/n, D]: the inverse re-shard."""
    return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def _default_attention(q, k, v, scale, causal):
    # the single-device dispatcher: pallas flash kernel on TPU when
    # profitable, XLA-fused reference attention otherwise — this is what
    # makes Ulysses kernel-agnostic for free
    from ..ops.attention import flash_attention
    from ..ops.registry import chip_site
    return flash_attention(q, k, v, scale=scale, causal=causal,
                           site=chip_site())


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      scale: Optional[float] = None,
                      attn_fn: Optional[Callable] = None):
    """Exact attention with the sequence sharded over `axis_name`.

    q/k/v: [B, H, T_local, D] — this rank's sequence shard; H must be
    divisible by the axis size.  Must run inside shard_map/pjit with the
    axis bound.  Returns [B, H, T_local, D].

    attn_fn(q, k, v, scale, causal) overrides the local attention kernel
    (e.g. the pallas flash path) — it sees head-sharded, full-sequence
    tensors, so any single-device kernel drops in.
    """
    n = lax.axis_size(axis_name)
    h = q.shape[1]
    if h % n != 0:
        raise ValueError(f"ulysses needs heads ({h}) divisible by the "
                         f"'{axis_name}' axis size ({n})")
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    qh = _seq_to_heads(q, axis_name)
    kh = _seq_to_heads(k, axis_name)
    vh = _seq_to_heads(v, axis_name)
    fn = attn_fn if attn_fn is not None else _default_attention
    oh = fn(qh, kh, vh, scale, causal)
    return _heads_to_seq(oh, axis_name)
