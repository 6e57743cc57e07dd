"""Coalesced (fused) optimizer updates.

Reference: the fluid stack fuses per-parameter optimizer ops into a single
kernel over one contiguous buffer — `coalesce_tensor_op` packs grads and
`fuse_adam_op_pass` / `fuse_sgd_op_pass` / `fuse_momentum_op_pass`
(framework/ir/fuse_optimizer_ops_pass/) rewrite N small optimizer ops into one.
Without this a BERT-base step runs ~200 small update kernels, and XLA is
free to fuse an elementwise Adam update INTO the weight-gradient matmul it
consumes (what that costs the matmul is not measured on this code).  The
TPU-native equivalent is therefore:

  1. `jax.lax.optimization_barrier` between the backward pass and the update,
     so the optimizer never fuses into gradient matmuls, and
  2. one coalesced f32 master buffer for params / moments, updated by a single
     elementwise kernel, sliced back into per-parameter views for the next
     forward (the coalesce_tensor analog).

The buffer is shaped (rows, LANE*8) with every parameter's segment row-aligned
— a flat 1D buffer tempts XLA's remat compression into a bf16[N,2] layout that
pads 64x on TPU tiles.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

_ROW = 1024          # 8 sublanes x 128 lanes — one full f32 tile row


class FlatSpec:
    """Shapes and row-aligned offsets of a coalesced parameter buffer."""

    def __init__(self, shapes: Sequence[Tuple[int, ...]], dtypes=None):
        self.shapes = [tuple(s) for s in shapes]
        self.sizes = [int(np.prod(s)) if s else 1 for s in self.shapes]
        self.row_offsets = []
        row = 0
        for size in self.sizes:
            self.row_offsets.append(row)
            row += -(-size // _ROW)          # ceil-div: rows per parameter
        self.rows = row
        self.dtypes = list(dtypes) if dtypes is not None else None

    def flatten(self, arrays: Sequence[jax.Array],
                dtype=jnp.float32) -> jax.Array:
        if not arrays:
            return jnp.zeros((0, _ROW), dtype)
        pieces = []
        for a, size in zip(arrays, self.sizes):
            flat = jnp.ravel(a).astype(dtype)
            pad = -(-size // _ROW) * _ROW - size
            if pad:
                flat = jnp.pad(flat, (0, pad))
            pieces.append(flat.reshape(-1, _ROW))
        return jnp.concatenate(pieces, axis=0)

    def unflatten(self, buf: jax.Array) -> List[jax.Array]:
        out = []
        for i, (shape, size) in enumerate(zip(self.shapes, self.sizes)):
            nrows = -(-size // _ROW)
            piece = jax.lax.dynamic_slice(
                buf, (self.row_offsets[i], 0), (nrows, _ROW))
            piece = piece.reshape(-1)[:size].reshape(shape)
            if self.dtypes is not None:
                piece = piece.astype(self.dtypes[i])
            out.append(piece)
        return out


_COALESCE_MAX = 1 << 20      # params above 1M elements update individually


def make_fused_adam(param_values: Sequence[jax.Array], lr=1e-4, beta1=0.9,
                    beta2=0.999, epsilon=1e-8, weight_decay=0.0):
    """Build (state, spec, update_fn) for a coalesced Adam/AdamW.

    Small parameters (the ~200 biases/norm scales whose individual update
    kernels are pure launch overhead) are packed into one (rows, 1024) f32
    buffer and updated by a single kernel; large parameters update in place —
    coalescing them costs extra HBM copies and a giant slice graph for XLA
    to compile.

    state = (params_list, m_list, v_list, small_state, t).
    update_fn(state, grads) -> (new_state, params_list).
    """
    small_ix = [i for i, p in enumerate(param_values)
                if int(np.prod(p.shape)) <= _COALESCE_MAX]
    large_ix = [i for i, p in enumerate(param_values)
                if int(np.prod(p.shape)) > _COALESCE_MAX]
    spec = FlatSpec([param_values[i].shape for i in small_ix],
                    [param_values[i].dtype for i in small_ix])
    sbuf = spec.flatten([param_values[i] for i in small_ix])
    sm = jnp.zeros_like(sbuf)
    sv = jnp.zeros_like(sbuf)
    lp = [param_values[i].astype(jnp.float32) for i in large_ix]
    lm = [jnp.zeros_like(p) for p in lp]
    lv = [jnp.zeros_like(p) for p in lp]
    t = jnp.zeros((), jnp.int32)
    state0 = (lp, lm, lv, (sbuf, sm, sv), t)

    def _adam(p, g, m, v, c1, c2):
        m = beta1 * m + (1.0 - beta1) * g
        v = beta2 * v + (1.0 - beta2) * g * g
        step = lr * (m / c1) / (jnp.sqrt(v / c2) + epsilon)
        if weight_decay:
            step = step + lr * weight_decay * p
        return p - step, m, v

    def params_of(state):
        lp, _, _, (sbuf, _, _), _ = state
        smalls = spec.unflatten(sbuf)
        params = [None] * len(param_values)
        for j, i in enumerate(small_ix):
            params[i] = smalls[j]
        for j, i in enumerate(large_ix):
            params[i] = lp[j].astype(param_values[i].dtype)
        return params

    def update(state, grads):
        lp, lm, lv, (sbuf, sm, sv), t = state
        grads = jax.lax.optimization_barrier(list(grads))
        t = t + 1
        tf = t.astype(jnp.float32)
        c1 = 1.0 - beta1 ** tf
        c2 = 1.0 - beta2 ** tf
        sg = spec.flatten([grads[i] for i in small_ix])
        sbuf, sm, sv = _adam(sbuf, sg, sm, sv, c1, c2)
        nlp, nlm, nlv = [], [], []
        for p, g, m, v in zip(lp, (grads[i] for i in large_ix), lm, lv):
            p2, m2, v2 = _adam(p, g.astype(jnp.float32), m, v, c1, c2)
            nlp.append(p2); nlm.append(m2); nlv.append(v2)
        smalls = spec.unflatten(sbuf)
        params = [None] * len(param_values)
        for j, i in enumerate(small_ix):
            params[i] = smalls[j]
        for j, i in enumerate(large_ix):
            params[i] = nlp[j].astype(param_values[i].dtype)
        return (nlp, nlm, nlv, (sbuf, sm, sv), t), params

    update.params_of = params_of
    return state0, spec, update
