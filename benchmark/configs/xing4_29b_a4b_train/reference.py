"""Plain float32 ``jax.numpy`` reference of the loss of one chip's share of
Xing4.0-29B-A4B, from the published ``config.json`` (the layer equations are
in ISSUE 30 and PERF.md section 4): manifold-constrained hyper-connections
(arXiv 2512.24880 over 2409.19606) around latent attention (MLA) and
sigmoid-routed experts with a shared expert, DeepSeek-V3's forms.

No kernels, no mixed precision, nothing shared with the code under test but
the parameter names of ``model.py``.  The harness differentiates it
(``compare.reference_loss_and_grads``, matmuls at ``highest`` precision).

Departures from the published model, each also under ``assumed`` or
``reduced`` in ``config.json``:

* the share: of the 64 routed experts only the ``n_routed_experts`` held
  ones (from ``first_expert``) add to a token's result; the router still
  scores all 64 and keeps the top 4 of ``sigmoid + bias``, weighted by the
  chosen sigmoids over their sum times ``routed_scaling_factor``; the shared
  expert is whole.  Embedding, head, ids and loss are over the held rows of
  the vocabulary;
* ``num_hidden_layers`` layers of the 40, ``first_k_dense_replace`` of them
  dense; the MTP module only where ``num_nextn_predict_layers`` is 1;
* the rotate-half rotary (the published interleaved one under a fixed
  permutation of the rotary columns of ``q_b`` and ``kv_a``); the streams
  start as copies of the embedding and end as their sum; rows before
  columns in the Sinkhorn iteration, ``hc_eps`` added to each sum; the
  correction bias is read from the parameters (zero) and gets no gradient;
  no auxiliary loss;
* memory only, same arithmetic: attention in blocks of queries and the
  head's loss in blocks of tokens, and those blocks, each expert and each
  layer recomputed in backward (``jax.checkpoint``), so the comparison at
  4096 tokens fits beside the training state.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_QUERY_BLOCK = 256       # x 4096 keys x 32 heads of float32 scores: 134 MB
_TOKEN_BLOCK = 1024      # x 16384 float32 logits: 67 MB


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _inv_freq(cfg):
    """The qk_rope_head_dim / 2 inverse frequencies: YaRN (Peng et al.,
    arXiv:2309.00071) as DeepSeek-V3's modelling file spells it."""
    rope = cfg["rope_scaling"]
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    factor = float(rope["factor"])
    orig = rope["original_max_position_embeddings"]
    pos_freqs = base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def correction_dim(num_rotations):
        return dim * math.log(orig / (num_rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) \
        * (1.0 - ramp)


def _rotary(x, cos, sin):
    """x [b, s, heads, d]; cos, sin [s, d]."""
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + rotated * sin[None, :, None, :]


def _causal_attention(q, k, v, scale):
    """Causal softmax attention, q and k [b, s, h, dqk], v [b, s, h, dv], in
    blocks of queries against all keys."""
    b, s, h, _ = q.shape

    @jax.checkpoint
    def block(qb, start):
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        i = start + jnp.arange(qb.shape[1])[:, None]
        j = jnp.arange(s)[None, :]
        scores = jnp.where((j <= i)[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    # one block after the other (lax.map), so that one block's scores are
    # live at a time, in forward and in backward
    step = min(_QUERY_BLOCK, s)
    blocks = q.reshape(b, s // step, step, h, -1).swapaxes(0, 1)
    out = jax.lax.map(lambda a: block(*a), (blocks, jnp.arange(0, s, step)))
    return out.swapaxes(0, 1).reshape(b, s, h, v.shape[-1])


def _mla(h, w, cfg):
    """Latent attention over h [b, s, hidden] under the matrices ``w`` of
    one ``layer_<i>.attention.``."""
    b, s, _ = h.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope_dim = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rope = cfg["rope_scaling"]
    # cos and sin carry mscale / mscale_all_dim (1 here), the scores the
    # square of mscale_all_dim's factor
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.tile(_inv_freq(cfg), 2)
    on_cos_sin = _yarn_mscale(rope["factor"], rope["mscale"]) \
        / _yarn_mscale(rope["factor"], rope["mscale_all_dim"])
    cos, sin = jnp.cos(angle) * on_cos_sin, jnp.sin(angle) * on_cos_sin
    scale = (nope + rope_dim) ** -0.5 \
        * _yarn_mscale(rope["factor"], rope["mscale_all_dim"]) ** 2

    c_q = _rms_norm(h @ w["q_a.w"], w["q_a_norm.scale"], eps)
    q = (c_q @ w["q_b.w"]).reshape(b, s, heads, nope + rope_dim)
    kv_a = h @ w["kv_a.w"]
    c_kv, k_rope = kv_a[..., :cfg["kv_lora_rank"]], \
        kv_a[..., cfg["kv_lora_rank"]:]
    kv = (_rms_norm(c_kv, w["kv_a_norm.scale"], eps) @ w["kv_b.w"]).reshape(
        b, s, heads, nope + cfg["v_head_dim"])
    k_rope = _rotary(k_rope[:, :, None, :], cos, sin)       # one for all heads
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], cos, sin)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (b, s, heads, rope_dim))],
                        -1)
    ctx = _causal_attention(q, k, kv[..., nope:], scale)
    return ctx.reshape(b, s, -1) @ w["output.w"]


def _gated(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _experts(h, w, cfg):
    """h [b, s, hidden] -> the held routed experts' and the shared experts'
    part of each token's result."""
    x = h.reshape(-1, h.shape[-1])
    scores = jax.nn.sigmoid(x @ w["router.w"])              # [t, 64]
    _, chosen = jax.lax.top_k(scores + w["router.bias"],
                              cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) \
        * cfg["routed_scaling_factor"]

    @jax.checkpoint
    def expert(x, w_gate, w_up, w_down, weight):
        return _gated(x, w_gate, w_up, w_down) * weight[:, None]

    def add_expert(out, held):
        e, w_gate, w_up, w_down = held
        routed = chosen == cfg["first_expert"] + e          # [t, top_k]
        weight = jnp.sum(jnp.where(routed, weights, 0.0), axis=-1)
        return out + expert(x, w_gate, w_up, w_down, weight), None

    # one held expert after the other, over all tokens with a token mask
    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (jnp.arange(w["experts.gate"].shape[0]), w["experts.gate"],
         w["experts.up"], w["experts.down"]))
    if cfg["n_shared_experts"]:
        out = out + _gated(x, w["shared.gate.w"], w["shared.up.w"],
                           w["shared.down.w"])
    return out.reshape(h.shape)


def _doubly_stochastic(logits, cfg):
    """Sinkhorn-Knopp of exp(logits) [..., n, n]: ``hc_sinkhorn_iters``
    times rows over (their sum + hc_eps), then columns likewise."""
    m = jnp.exp(logits)
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + cfg["hc_eps"])
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + cfg["hc_eps"])
    return m


def _mixed(x, w, branch, cfg):
    """One branch of the hyper-connected residual path: x [b, s, n, hidden]
    -> the new streams, under the mixer ``w`` (``hc.phi``, ``hc.alpha``,
    ``hc.b``, ``norm.scale``)."""
    n, eps = cfg["hc_mult"], cfg["rms_norm_eps"]
    flat = x.reshape(x.shape[:2] + (-1,))
    m = (flat @ w["hc.phi"]) * jax.lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
    alpha, b = w["hc.alpha"], w["hc.b"]
    pre = jax.nn.sigmoid(alpha[0] * m[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * m[..., n:2 * n] + b[n:2 * n])
    logits = jnp.clip(alpha[2] * m[..., 2 * n:] + b[2 * n:],
                      cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    c = _doubly_stochastic(logits.reshape(logits.shape[:2] + (n, n)), cfg)
    y = jnp.einsum("bsn,bsnd->bsd", pre, x)
    z = branch(_rms_norm(y, w["norm.scale"], eps))
    return post[..., None] * z[:, :, None, :] \
        + jnp.einsum("bsij,bsjd->bsid", c, x)


def _sub(w, prefix):
    return {n[len(prefix):]: a for n, a in w.items() if n.startswith(prefix)}


def _layer(x, w, dense_ffn, cfg):
    x = _mixed(x, _sub(w, "attn."),
               lambda h: _mla(h, _sub(w, "attention."), cfg), cfg)
    if dense_ffn:
        return _mixed(x, _sub(w, "ffn."), lambda h: _gated(
            h, w["ffn.gate.w"], w["ffn.up.w"], w["ffn.down.w"]), cfg)
    return _mixed(x, _sub(w, "ffn."), lambda h: _experts(h, w, cfg), cfg)


def _mean_cross_entropy(h, labels, head, valid):
    """Mean over the ``valid`` positions of -log softmax(h head)[label], in
    blocks of tokens."""
    @jax.checkpoint
    def summed(h, labels, valid, head):
        logp = jax.nn.log_softmax(h @ head, axis=-1)
        picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(valid, picked, 0.0))

    step = min(_TOKEN_BLOCK, h.shape[0])
    total, _ = jax.lax.scan(
        lambda total, a: (total + summed(*a, head), None),
        jnp.zeros((), jnp.float32),
        (h.reshape(-1, step, h.shape[-1]), labels.reshape(-1, step),
         valid.reshape(-1, step)))
    return total / jnp.sum(valid)


def loss(params, batch, cfg):
    """The training loss of one batch (dict of the feeds of ``model.py``:
    ``input_ids`` and ``labels``, [b, s]) under ``params`` (name -> float32
    array)."""
    p = params
    n, eps = cfg["hc_mult"], cfg["rms_norm_eps"]
    ids = batch["input_ids"].astype(jnp.int32)
    labels = batch["labels"].astype(jnp.int32)
    b, s = ids.shape

    def run_layer(x, pre, dense_ffn):
        return jax.checkpoint(lambda x, w: _layer(x, w, dense_ffn, cfg))(
            x, _sub(p, pre))

    def start(e):
        return jnp.broadcast_to(e[:, :, None, :], e.shape[:2] + (n,)
                                + e.shape[2:])

    x = start(p["embed_tokens"][ids])
    for i in range(cfg["num_hidden_layers"]):
        x = run_layer(x, f"layer_{i}.", i < cfg["first_k_dense_replace"])
    h = jnp.sum(x, axis=2)
    everywhere = jnp.ones((b * s,), bool)
    total = _mean_cross_entropy(
        _rms_norm(h, p["final_norm.scale"], eps).reshape(b * s, -1),
        labels.reshape(-1), p["lm_head.w"], everywhere)
    if cfg["num_nextn_predict_layers"]:
        joined = jnp.concatenate(
            [_rms_norm(h, p["mtp.h_norm.scale"], eps),
             _rms_norm(p["embed_tokens"][labels], p["mtp.embed_norm.scale"],
                       eps)], axis=-1)
        x2 = run_layer(start(joined @ p["mtp.eh_proj.w"]), "mtp.", False)
        h2 = _rms_norm(jnp.sum(x2, axis=2), p["final_norm.scale"], eps)
        # position t predicts labels[t + 1]; the last position has no target
        after_next = jnp.concatenate([labels[:, 1:], labels[:, -1:]], axis=1)
        has_target = jnp.broadcast_to(jnp.arange(s) < s - 1, (b, s))
        total = total + cfg["mtp_loss_weight"] * _mean_cross_entropy(
            h2.reshape(b * s, -1), after_next.reshape(-1), p["lm_head.w"],
            has_target.reshape(-1))
    return total

