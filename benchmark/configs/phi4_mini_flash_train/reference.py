"""Plain float32 ``jax.numpy`` reference of the training loss of the held
layers of Phi-4-mini-flash-reasoning (SambaY, arXiv:2507.06607, with
differential attention, arXiv:2410.05258), from the published
``config.json``.  No kernels, no mixed precision, nothing shared with the
code under test but the parameter names of ``model.py``.  The harness
differentiates it (``compare.reference_loss_and_grads``, matmuls at
``highest`` precision).

``h`` = 2560, residual stream ``x`` [S, h] float32, ``LN`` = LayerNorm with
scale and bias, eps 1e-5.  No positional encoding, no dropout.  Layer ``i``
(PUBLISHED index, 0..31):

    x' = x + Mixer_i(LN_1(x));   x_next = x' + MLP(LN_2(x'))
    MLP(u) = W_down (silu(g) * p),   [g ; p] = W_gate_up u

* ``i`` even, ``i`` <= 16: **Mamba** (``d_inner`` 5120, ``d_state`` 16,
  ``dt_rank`` 160, ``d_conv`` 4).  ``[xs ; z] = W_in u``; ``xc =
  silu(conv1d(xs))`` depthwise and causal (token t sees t-3..t), with bias;
  ``[dl ; B ; C] = W_x xc``; ``dt = softplus(W_dt dl + b_dt)``; ``A =
  -exp(A_log)``; ``s_t = exp(dt_t (x) A) * s_{t-1} + (dt_t * xc_t) (x) B_t``
  from ``s = 0``; ``y_t = s_t C_t + D * xc_t``; ``out = W_out (y *
  silu(z))``.  Layer 16 also hands on ``m = y`` (before the gate).
* ``i`` odd, ``i`` <= 17: **differential attention**, causal, window 512
  below layer 16 and none on layer 17.  ``[q ; k ; v] = W_qkv u + b``, 40
  query and 20 key/value heads of 64; the heads pair up, ``q1, q2 = q[pair,
  0], q[pair, 1]`` and likewise k and v; query pair p reads key/value pair p
  // 2.  ``a_j = softmax over the allowed keys of (q_j k_j^T / 8) [v1 |
  v2]``; ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``, ``lam0 = 0.8 -
  0.6 exp(-0.3 i)``; ``o = RMSNorm_128(a1 - lam a2; scale g, eps 1e-5) * (1 -
  lam0)``; ``out = W_o o + b_o``.  Layer 17 also hands on its k and v.
* ``i`` even, ``i`` >= 18: **gated memory unit** ``W_out (m * silu(W_in
  u))``.
* ``i`` odd, ``i`` >= 19: **differential cross attention**: its own queries
  (``W_q u + b``) over layer 17's k and v, causal, its own lambdas, sub-norm
  and ``W_o``.
* The head is tied: logits = ``LN_f(x) E^T`` over the held rows of ``E``;
  the loss is the mean cross entropy, labels the inputs shifted by one.

Departures from the published model, each also under ``assumed`` or
``reduced`` in ``config.json``: ``held_layers`` of the 32 layers and the held
rows of the vocabulary; the Mamba sizes and initial values are Mamba-1's
defaults; the memory is ``y`` with the ``D`` skip and before the gate;
``lam0`` by the published index.  Memory only, same arithmetic: the scan in
blocks of tokens, attention in blocks of queries against all keys, the
head's loss in blocks of tokens, and those blocks and each layer recomputed
in backward (``jax.checkpoint``), so the comparison at 4096 tokens fits
beside the training state.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_SCAN_BLOCK = 64         # x 5120 x 16 float32 states kept a block: 21 MB
_QUERY_BLOCK = 512       # x 4096 keys x 20 heads of float32 scores: 168 MB
_TOKEN_BLOCK = 1024      # x 25008 float32 logits: 102 MB

def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def _kind(cfg, i):
    """(mixer, window) of published layer ``i``."""
    half = cfg["published"]["num_hidden_layers"] // 2
    if i % cfg["mb_per_layer"] == 0:
        return ("mamba" if i <= half else "gmu"), 0
    if i <= half + 1:
        return "attention", (cfg["sliding_window"] if i < half else 0)
    return "cross", 0


def _step(state, tok, a, d):
    """One token of the recurrence: state [di, n] -> (state, y_t [di])."""
    xt, dtt, bt, ct = tok
    state = jnp.exp(dtt[:, None] * a) * state \
        + (dtt * xt)[:, None] * bt[None, :]
    return state, state @ ct + d * xt


def _scan(xc, dt, a, b, c, d):
    """One sequence.  xc, dt [s, di]; a [di, n]; b, c [s, n]; d [di] -> y
    [s, di]: the recurrence a token at a time, in blocks recomputed in
    backward."""
    s, di = xc.shape

    @jax.checkpoint
    def block(state, toks):
        return jax.lax.scan(lambda state, tok: _step(state, tok, a, d),
                            state, toks)

    n = min(_SCAN_BLOCK, s)

    def blocks(v):
        return v.reshape((s // n, n) + v.shape[1:])
    _, y = jax.lax.scan(block, jnp.zeros((di, a.shape[1]), jnp.float32),
                        (blocks(xc), blocks(dt), blocks(b), blocks(c)))
    return y.reshape(s, di)


def _conv(xs, w):
    """xs [b, s, di] -> silu(conv1d(xs)): depthwise, token t sees t - (width
    - 1) .. t, the tap on the current token last."""
    width, s = w["ssm.conv.w"].shape[0], xs.shape[1]
    padded = jnp.pad(xs, ((0, 0), (width - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w["ssm.conv.w"][k] * padded[:, k:k + s]
                           for k in range(width)) + w["ssm.conv.b"])


def _step_size(dl, w):
    return jax.nn.softplus(dl @ w["ssm.dt_proj.w"] + w["ssm.dt_proj.b"])


def _mamba(u, w, cfg):
    """u [b, s, h] -> (the mixer's output, y before the gate)."""
    m = cfg["mamba"]
    di, n, rank = (m["expand"] * cfg["hidden_size"], m["d_state"],
                   m["dt_rank"])
    xz = u @ w["ssm.in_proj.w"]
    xs, z = xz[..., :di], xz[..., di:]
    xc = _conv(xs, w)
    dbc = xc @ w["ssm.x_proj.w"]
    dl, b, c = dbc[..., :rank], dbc[..., rank:rank + n], dbc[..., rank + n:]
    dt = _step_size(dl, w)
    a = -jnp.exp(w["ssm.A_log"])
    y = jnp.stack([_scan(xc[i], dt[i], a, b[i], c[i], w["ssm.D"])
                   for i in range(xc.shape[0])])
    return (y * jax.nn.silu(z)) @ w["ssm.out_proj.w"], y


def _subln(d, g, lam0, eps):
    """RMSNorm over a pair's 128 numbers, scale g, times (1 - lam0)."""
    return d * jax.lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True) + eps) \
        * g * (1.0 - lam0)


def _differential(q, k, v, w, i, window, cfg):
    """One sequence.  q [s, 40, 64], k, v [s, 20, 64] -> [s, 2560]."""
    s, dh = q.shape[0], q.shape[-1]
    q = q.reshape(s, -1, 2, dh)
    k = k.reshape(s, -1, 2, dh)
    v = v.reshape(s, -1, 2 * dh)                  # [v1 | v2] of every pair
    group = q.shape[1] // k.shape[1]              # query pair p reads p // 2
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    lam0 = _lambda_init(i)
    lam = jnp.exp(jnp.sum(w["attention.lambda_q1"] * w["attention.lambda_k1"])) \
        - jnp.exp(jnp.sum(w["attention.lambda_q2"]
                          * w["attention.lambda_k2"])) + lam0
    eps = cfg["layer_norm_eps"]

    @jax.checkpoint
    def block(qb, start, k, v, lam, g):
        row = start + jnp.arange(qb.shape[0])[:, None]
        col = jnp.arange(s)[None, :]
        allowed = col <= row
        if window:
            allowed &= row - col < window

        def attend(j):
            logits = jnp.einsum("rhd,shd->hrs", qb[:, :, j], k[:, :, j]) \
                / math.sqrt(dh)
            p = jax.nn.softmax(jnp.where(allowed[None], logits, -jnp.inf),
                               axis=-1)
            return jnp.einsum("hrs,shd->rhd", p, v)
        return _subln(attend(0) - lam * attend(1), g, lam0, eps)

    n = min(_QUERY_BLOCK, s)
    out = jax.lax.map(
        lambda a: block(a[0], a[1], k, v, lam, w["attention.subln.scale"]),
        (q.reshape((s // n, n) + q.shape[1:]), jnp.arange(0, s, n)))
    return out.reshape(s, -1)


def _attention(u, w, i, window, cfg, kv=None):
    """u [b, s, h] -> (the mixer's output, (k, v) [b, s, 20, 64]).  With
    ``kv`` (a cross layer) the queries alone are this layer's."""
    b, s, _ = u.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // heads
    if kv is None:
        qkv = u @ w["attention.qkv.w"] + w["attention.qkv.b"]
        q, k, v = jnp.split(qkv, [heads * dh, (heads + kv_heads) * dh], -1)
        kv = (k.reshape(b, s, kv_heads, dh), v.reshape(b, s, kv_heads, dh))
    else:
        q = u @ w["attention.q.w"] + w["attention.q.b"]
    q = q.reshape(b, s, heads, dh)
    o = jnp.stack([_differential(q[n], kv[0][n], kv[1][n], w, i, window, cfg)
                   for n in range(b)])
    return o @ w["attention.out_proj.w"] + w["attention.out_proj.b"], kv


def _layer(x, w, i, cfg, memory, kv):
    """(the layer's output, what it hands on or None)."""
    eps = cfg["layer_norm_eps"]
    kind, window = _kind(cfg, i)
    u = _layer_norm(x, w["input_norm.scale"], w["input_norm.bias"], eps)
    handed = None
    if kind == "mamba":
        branch, handed = _mamba(u, w, cfg)
    elif kind == "attention":
        branch, handed = _attention(u, w, i, window, cfg)
    elif kind == "gmu":
        branch = (memory * jax.nn.silu(u @ w["gmu.in_proj.w"])) \
            @ w["gmu.out_proj.w"]
    else:
        branch, _ = _attention(u, w, i, 0, cfg, kv)
    x = x + branch
    u = _layer_norm(x, w["post_mixer_norm.scale"], w["post_mixer_norm.bias"],
                    eps)
    gp = u @ w["mlp.gate_up.w"]
    half = gp.shape[-1] // 2
    return x + (jax.nn.silu(gp[..., :half]) * gp[..., half:]) \
        @ w["mlp.down.w"], handed


def _layer_weights(params, i):
    pre = f"layer_{i}."
    return {n[len(pre):]: a for n, a in params.items() if n.startswith(pre)}


def _memory_layer(cfg):
    """The published index of the Mamba layer whose y every gated memory
    unit reads; the attention layer after it hands on its k and v."""
    return cfg["published"]["num_hidden_layers"] // 2


def _kv_layer(cfg):
    return _memory_layer(cfg) + 1


@jax.checkpoint
def _summed_loss(h, labels, head):
    logp = jax.nn.log_softmax(h @ head.T, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def loss(params, batch, cfg):
    """The mean next-token cross entropy of one batch (dict of the feeds of
    ``model.py``: ``input_ids`` and ``labels``, [b, s]) under ``params``
    (name -> float32 array)."""
    p = params
    ids = batch["input_ids"].astype(jnp.int32)
    b, s = ids.shape
    held = cfg.get("held_layers") or range(cfg["num_hidden_layers"])
    x = p["embed_tokens"][ids]
    handed = {}
    for i in held:
        x, handed[i] = jax.checkpoint(
            lambda x, w, memory, kv, i=i: _layer(x, w, i, cfg, memory, kv))(
                x, _layer_weights(p, i), handed.get(_memory_layer(cfg)),
                handed.get(_kv_layer(cfg)))
    h = _layer_norm(x, p["final_norm.scale"], p["final_norm.bias"],
                    cfg["layer_norm_eps"]).reshape(b * s, -1)
    labels = batch["labels"].astype(jnp.int32).reshape(b * s)
    head = p["embed_tokens"]                       # tied
    step = min(_TOKEN_BLOCK, b * s)
    total, _ = jax.lax.scan(
        lambda total, a: (total + _summed_loss(*a, head), None),
        jnp.zeros((), jnp.float32),
        (h.reshape(-1, step, h.shape[-1]), labels.reshape(-1, step)))
    return total / (b * s)
