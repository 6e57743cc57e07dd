"""The selective scan of a state-space layer (Mamba-1, arXiv:2312.00752) and
the causal depthwise convolution before it.

``selective_scan``: X, Dt [B, S, Di] (the convolved, activated input and the
step size, after its softplus), A [Di, N] (negative: ``-exp(A_log)``), B, C
[B, S, N] (the token's input and output vectors), D [Di] (the skip) -> Y [B,
S, Di]:

    s_t = exp(dt_t (x) A) * s_{t-1} + (dt_t * x_t) (x) B_t,     s_{-1} = 0
    y_t = s_t C_t + D * x_t

``A`` differs per channel and state, so no matmul formulation covers the
recurrence: it is S sequential steps of elementwise work over [Di, N].  A
state a token, [S, Di, N] float32, is 1.34 GB a layer at 4096 x 5120 x 16 and
never exists, forward or backward, on either lowering: the sequence is cut
into chunks, only the state at each chunk's end is kept ([S / chunk, N, Di]
float32, 5 MB a layer at chunks of 256), and backward computes a chunk's
states again from the state before it and sweeps the chunk in reverse.

Two lowerings, chosen as every kernel is (``ctx.pallas_ok()``: on the chip,
outside a partitioned program; ``docs/passes.md`` "Where a kernel runs"):

* ``selective_scan_xla`` — a ``lax.scan`` over chunks, the per-step
  recurrence inside, each chunk under ``jax.checkpoint`` (autodiff keeps the
  chunks' first states and recomputes the rest): the CPU's, and a
  partitioned program's;
* ``pallas_kernels.selective_scan_tpu`` — the state of a block of channels
  stays on the core across a sequential grid over time; its backward is a
  second kernel (``docs/state_space.md``).

Everything inside is float32 whatever the operands' dtype: the state, the
``exp``, the sums.  ``ssm.lowering.<pallas|xla>`` in ``trace.metrics()``
counts the picks, one a lowering of the op or of a gradient that has to
trace it again.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register_op

# tokens a chunk: what backward recomputes at a time, and how far apart the
# kept states lie.  The Pallas backward holds a chunk's states in VMEM
# ([256 + 1, 16, 512] float32: 8.4 MB).
CHUNK = 256


def _pad_time(a, pad):
    return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))


def selective_scan_xla(x, dt, a, b, c, d, chunk=CHUNK):
    """(y [B, S, Di] float32, ends [B, S / chunk, N, Di] float32: the state
    after each chunk).  A sequence that is not whole chunks is padded with
    steps of size 0, which leave the state as it is."""
    bsz, seq, di = x.shape
    chunk = min(chunk, seq)
    pad = -seq % chunk
    x, dt, b, c = (_pad_time(v.astype(jnp.float32), pad)
                   for v in (x, dt, b, c))
    at = a.astype(jnp.float32).T                             # [N, Di]
    skip = d.astype(jnp.float32)

    def step(s, tok):
        xt, dtt, bt, ct = tok                 # [B, Di], [B, Di], [B, N] x 2
        s = jnp.exp(dtt[:, None, :] * at) * s \
            + (dtt * xt)[:, None, :] * bt[:, :, None]
        return s, jnp.sum(s * ct[:, :, None], axis=1) + skip * xt

    @jax.checkpoint
    def one_chunk(s, toks):
        s, y = jax.lax.scan(step, s, toks)
        return s, (y, s)

    def chunks(v):                            # [B, S, w] -> [K, chunk, B, w]
        return jnp.moveaxis(v, 1, 0).reshape(-1, chunk, bsz, v.shape[-1])

    _, (y, ends) = jax.lax.scan(
        one_chunk, jnp.zeros((bsz, at.shape[0], di), jnp.float32),
        (chunks(x), chunks(dt), chunks(b), chunks(c)))
    y = jnp.moveaxis(y.reshape(-1, bsz, di), 0, 1)[:, :seq]
    return y, jnp.moveaxis(ends, 0, 1)


@register_op("selective_scan", nondiff_outputs=("StateAbsMax", "DtMean"))
def _selective_scan(ins, attrs, ctx):
    """X, Dt [B, S, Di], A [Di, N], B, C [B, S, N], D [Di] -> Y [B, S, Di]
    in X's dtype.  StateAbsMax [1] (the largest |state| at a chunk's end)
    and DtMean [1], where the op has those outputs, stay on the device and
    are read by the host when a runner drains: a state that blows up or a
    step size that collapses shows without a debugger."""
    from ..fluid import trace
    from . import pallas_kernels as pk
    x, dt, a, b, c, d = (ins[slot][0] for slot in "X Dt A B C D".split())
    use = ctx.pallas_ok() and pk.selective_scan_supported(x, a)
    trace.metrics().counter(
        f"ssm.lowering.{'pallas' if use else 'xla'}").inc()
    scan = pk.selective_scan_tpu if use else selective_scan_xla
    y, ends = scan(x, dt, a, b, c, d)
    ends = jax.lax.stop_gradient(ends)
    return {"Y": [y.astype(x.dtype)],
            "StateAbsMax": [jnp.max(jnp.abs(ends)).reshape(1)],
            "DtMean": [jnp.mean(jax.lax.stop_gradient(dt).astype(
                jnp.float32)).reshape(1)]}


@register_op("causal_conv1d")
def _causal_conv1d(ins, attrs, ctx):
    """Depthwise causal convolution over time: X [B, S, C], W [K, C], Bias
    [C] -> Out[t] = Bias + sum_k W[k] * X[t - (K - 1) + k], zeros before the
    first token: token t sees itself and the K - 1 before it, never the
    next.  K shifted multiplies (K is 4); float32 sums, X's dtype out."""
    x, w = ins["X"][0], ins["W"][0].astype(jnp.float32)
    k, seq = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(w[i] * padded[:, i:i + seq] for i in range(k))
    if ins.get("Bias"):
        out = out + ins["Bias"][0].astype(jnp.float32)
    return {"Out": [out.astype(x.dtype)]}
