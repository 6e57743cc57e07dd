"""Plain float32 ``jax.numpy`` reference of the next-token loss of one chip's
share of Mellum2-12B-A2.5B, from the published ``config.json`` (the layer
equations are in ISSUE 26 and PERF.md section 4).

No kernels, no mixed precision, nothing shared with the code under test but
the parameter names of ``model.py``.  The harness differentiates it
(``compare.reference_loss_and_grads``, matmuls at ``highest`` precision).

Departures from the published model, each also under ``assumed`` or
``reduced`` in ``config.json``:

* the share: of the 64 experts only the ``num_experts`` held ones (from
  ``first_expert``) add to a token's result; the router still scores all 64
  and keeps the top 8, with the softmax over those 8.  Embedding, head, ids
  and loss are over the held rows of the vocabulary;
* ``num_hidden_layers`` layers of the 28 (one period: three sliding, one
  full); no MTP head; no q/k norm; no auxiliary router loss;
* memory only, same arithmetic: attention in blocks of queries and the
  head's loss in blocks of tokens, and those blocks, each expert and each
  layer recomputed in backward (``jax.checkpoint``), so the comparison at
  8192 tokens fits beside the training state.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_QUERY_BLOCK = 256       # x 8192 keys x 32 heads of float32 scores: 268 MB
_TOKEN_BLOCK = 1024      # x 24576 float32 logits: 101 MB


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _inv_freq(cfg, layer_type):
    """([head_dim / 2] inverse frequencies, factor on cos and sin)."""
    rope = cfg["rope_parameters"][layer_type]
    dim = cfg["head_dim"]
    i = jnp.arange(0, dim, 2, dtype=jnp.float32)
    pos_freqs = float(rope["rope_theta"]) ** (i / dim)
    if rope["rope_type"] == "default":
        return 1.0 / pos_freqs, 1.0
    # YaRN (Peng et al., arXiv:2309.00071), as transformers'
    # _compute_yarn_parameters spells it
    base, factor = float(rope["rope_theta"]), float(rope["factor"])
    orig = rope["original_max_position_embeddings"]

    def correction_dim(num_rotations):
        return dim * math.log(orig / (num_rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp
    inv_freq = (1.0 / (factor * pos_freqs)) * (1.0 - extrapolation) \
        + (1.0 / pos_freqs) * extrapolation
    return inv_freq, float(rope["attention_factor"])


def _rotary(x, cos, sin):
    """x [b, s, heads, d]; cos, sin [s, d]."""
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + rotated * sin[None, :, None, :]


def _attention(q, k, v, window):
    """Causal softmax attention, q [b, s, hq, d], k/v [b, s, hkv, d], each
    key/value head shared by hq / hkv consecutive query heads; ``window`` > 0
    keeps 0 <= i - j < window.  In blocks of queries against all keys."""
    b, s, hq, d = q.shape
    group = hq // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)

    @jax.checkpoint
    def block(qb, start):
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d)
        i = start + jnp.arange(qb.shape[1])[:, None]
        j = jnp.arange(s)[None, :]
        allowed = j <= i
        if window:
            allowed = allowed & (i - j < window)
        scores = jnp.where(allowed[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    # one block after the other (lax.map), so that one block's scores are
    # live at a time, in forward and in backward
    step = min(_QUERY_BLOCK, s)
    blocks = q.reshape(b, s // step, step, hq, d).swapaxes(0, 1)
    out = jax.lax.map(lambda a: block(*a),
                      (blocks, jnp.arange(0, s, step)))
    return out.swapaxes(0, 1).reshape(b, s, hq, d)


def _held_experts(x, router, gate, up, down, top_k, first_expert):
    """x [t, h] -> the held experts' part of each token's result."""
    logits = x @ router                                     # [t, 64]
    top, chosen = jax.lax.top_k(logits, top_k)
    weights = jax.nn.softmax(top, axis=-1)                  # over the top_k

    @jax.checkpoint
    def expert(x, w_gate, w_up, w_down, weight):
        return ((jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down) \
            * weight[:, None]

    def add_expert(out, held):
        e, w_gate, w_up, w_down = held
        routed = chosen == first_expert + e                 # [t, top_k]
        weight = jnp.sum(jnp.where(routed, weights, 0.0), axis=-1)
        return out + expert(x, w_gate, w_up, w_down, weight), None

    # one held expert after the other, over all tokens with a token mask
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                          (jnp.arange(gate.shape[0]), gate, up, down))
    return out


def loss(params, batch, cfg):
    """Mean next-token cross-entropy of one batch (dict of the feeds of
    ``model.py``: ``input_ids`` and ``labels``, [b, s]) under ``params``
    (name -> float32 array)."""
    p = params
    eps = cfg["rms_norm_eps"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["head_dim"]
    ids = batch["input_ids"].astype(jnp.int32)
    b, s = ids.shape
    positions = jnp.arange(s, dtype=jnp.float32)

    def layer(x, w, kind):
        inv_freq, factor = _inv_freq(cfg, kind)
        angle = positions[:, None] * jnp.concatenate([inv_freq, inv_freq])
        cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
        h = _rms_norm(x, w["input_norm.scale"], eps)
        q = (h @ w["attention.query.w"]).reshape(b, s, heads, dh)
        k = (h @ w["attention.key.w"]).reshape(b, s, kv_heads, dh)
        v = (h @ w["attention.value.w"]).reshape(b, s, kv_heads, dh)
        window = cfg["sliding_window"] if kind == "sliding_attention" else 0
        ctx = _attention(_rotary(q, cos, sin), _rotary(k, cos, sin), v,
                         window)
        x = x + ctx.reshape(b, s, heads * dh) @ w["attention.output.w"]
        h = _rms_norm(x, w["post_attention_norm.scale"], eps)
        moe = _held_experts(h.reshape(b * s, -1), w["router.w"],
                            w["experts.gate"], w["experts.up"],
                            w["experts.down"], cfg["num_experts_per_tok"],
                            cfg["first_expert"])
        return x + moe.reshape(b, s, -1)

    x = p["embed_tokens"][ids]
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layer_{i}."
        w = {n[len(pre):]: a for n, a in p.items() if n.startswith(pre)}
        x = jax.checkpoint(layer, static_argnums=2)(x, w,
                                                    cfg["layer_types"][i])
    h = _rms_norm(x, p["final_norm.scale"], eps).reshape(b * s, -1)
    labels = batch["labels"].astype(jnp.int32).reshape(b * s)

    @jax.checkpoint
    def summed_loss(h, labels, head):
        logp = jax.nn.log_softmax(h @ head, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))

    step = min(_TOKEN_BLOCK, b * s)
    total, _ = jax.lax.scan(
        lambda total, a: (total + summed_loss(*a, p["lm_head.w"]), None),
        jnp.zeros((), jnp.float32),
        (h.reshape(-1, step, h.shape[-1]), labels.reshape(-1, step)))
    return total / (b * s)
