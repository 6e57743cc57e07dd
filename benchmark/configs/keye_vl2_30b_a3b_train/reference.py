"""Plain float32 ``jax.numpy`` reference of the training objective of one
chip's share of Keye-VL-2.0-30B-A3B's language model, from the published
``config.json``.  No kernels, no mixed precision, nothing shared with the
code under test but the parameter names of ``model.py``.  The harness
differentiates it (``compare.reference_loss_and_grads``, matmuls at
``highest`` precision).

The layer (``x`` [S, 2048] float32 residual, ``h = RMSNorm(x)``, eps 1e-6,
learned scale):

* Main heads.  ``q = h W_q`` -> [S, 32, 128], ``k = h W_k``, ``v = h W_v``
  -> [S, 4, 128]; per-head RMSNorm over the 128 with learned scales on ``q``
  and ``k``; rotary over all 64 pairs of the head, pair ``i`` turning by
  ``pos[r(i), t] * 1e7^(-2i/128)`` with ``r(i)`` = 0 for pairs 0..15, 1 for
  16..39, 2 for 40..63 (``mrope_section``), rotate-half convention.  ``pos``
  is [3, S]; text has three equal rows 0..S-1.
* Indexer, on ``hb = stop_gradient(h)``: ``qI = hb W_Iq`` -> [S, 16, 64];
  ``kI = LayerNorm(hb W_Ik)`` -> [S, 64] (one key a token); ``w = hb W_Iw *
  16^-1/2 * 64^-1/2`` -> [S, 16]; rotary on the leading 32 of the 64 numbers
  of ``qI`` and ``kI`` by ``pos[0]`` at the same theta.  Score of query ``t``
  for key ``s <= t``: ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``.
* Selection.  ``S_t`` = the ``min(t + 1, 2048)`` keys ``s <= t`` with the
  largest ``I[t, s]``, ties to the smaller ``s``.  Exact (``lax.top_k``
  sorts; equal values come lower index first).  One set a query token,
  shared by all 32 heads.
* Attention.  ``o[t, a] = sum over S_t of A[t, a, s] v[s, g(a)]``, ``A[t, a,
  .]`` = softmax over ``S_t`` of ``q[t, a] . k[s, g(a)] / sqrt(128)``; ``g``
  maps a query head to its group of 8.  ``x <- x + o W_o``.
* Indexer loss.  ``p[t, s] = stop_gradient(mean_a A[t, a, s])`` on ``S_t``;
  ``L_I(layer) = mean_t sum over S_t of p (log p - log softmax_{S_t}(I[t,
  .]))``.
* Experts.  Softmax over 128, top-8, renormalised over the chosen, width
  768, no shared expert, no token dropped.  ``x <- x + MoE(RMSNorm(x))``.
* Objective.  ``L = L_LM + index_loss_weight * sum over layers of L_I``,
  ``L_LM`` the mean next-token cross-entropy over the held slice.  ``L_LM``
  reaches every parameter except the indexer's (``W_Iq``, ``W_Ik``, ``W_Iw``,
  the key's LayerNorm); ``L_I`` reaches only those.

Departures from the published model, each also under ``assumed`` or
``reduced`` in ``config.json``:

* the share: of the 128 experts only the ``num_experts`` held ones (from
  ``first_expert``) add to a token's result; the router still scores all 128
  and keeps the top 8.  Embedding, head, ids and loss are over the held rows
  of the vocabulary; ``num_hidden_layers`` of the 48 layers; no vision tower
  (text positions);
* the config has no key for them: the q/k norm, rotate-half, the indexer's
  LayerNorm (with bias, eps 1e-6), its two scalings and its partial rotary
  (DeepSeek-V3.2-Exp's forms), the indexer loss and its weight (V3.2's sparse
  training stage); ``q_chunk_size`` / ``kv_chunk_size`` are read as tiling
  and change no number;
* memory only, same arithmetic: index scores, selection, attention and the
  indexer loss in blocks of queries against all keys, the head's loss in
  blocks of tokens, and those blocks, each expert and each layer recomputed
  in backward (``jax.checkpoint``), so the comparison at 16384 tokens fits
  beside the training state.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_QUERY_BLOCK = 128       # x 16384 keys x 32 heads of float32 scores: 256 MB
_TOKEN_BLOCK = 1024      # x 18992 float32 logits: 78 MB

# the two places where a wrong program would differ in one call; under these
# names so that ``benchmark/tools/keye_check.py`` can show that its limits
# catch an indexer without its ReLU and an indexer input that is not detached
_index_activation = jax.nn.relu
_indexer_input = jax.lax.stop_gradient


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _rotary(x, angle):
    """x [b, s, heads, d]; angle [s, d / 2]: rotate-half."""
    half = x.shape[-1] // 2
    cos = jnp.cos(jnp.concatenate([angle, angle], -1))[None, :, None, :]
    sin = jnp.sin(jnp.concatenate([angle, angle], -1))[None, :, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _angles(cfg, positions, dim, sections):
    """[s, dim / 2]: pair i turns by positions[r(i)] * theta^(-2i/dim)."""
    inv_freq = float(cfg["rope_theta"]) ** (
        -jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    row = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections),
                     total_repeat_length=dim // 2)
    return positions[row].T * inv_freq[None, :]


def _select(scores, start, topk):
    """scores [r, s] of the queries start.. -> [r, s] bool: per query the
    min(t + 1, topk) largest of its keys s <= t, ties to the smaller s."""
    r, s = scores.shape
    t = start + jnp.arange(r)[:, None]
    causal = jnp.arange(s)[None, :] <= t
    top, index = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                               min(topk, s))
    return jnp.zeros((r, s), bool).at[jnp.arange(r)[:, None], index].set(
        top > -jnp.inf)


def _sparse_attention(q, k, v, qi, ki, w, topk, return_selection=False):
    """One sequence.  q [s, hq, d], k/v [s, hkv, d], qi [s, hi, di], ki [s,
    di], w [s, hi] -> (o [s, hq, d], the indexer's KL of every query [s]).
    In blocks of queries against all keys."""
    s, hq, d = q.shape
    group = hq // k.shape[1]
    kk, vv = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)

    @jax.checkpoint
    def block(qb, qib, wb, start):
        z = jnp.einsum("rjd,sd->rjs", qib, ki)
        index = jnp.sum(_index_activation(z) * wb[:, :, None], axis=1)
        chosen = _select(jax.lax.stop_gradient(index), start, topk)
        logits = jnp.einsum("rhd,shd->hrs", qb, kk) / math.sqrt(d)
        a = jax.nn.softmax(jnp.where(chosen[None], logits, -jnp.inf), -1)
        out = jnp.einsum("hrs,shd->rhd", a, vv)
        p = jax.lax.stop_gradient(jnp.mean(a, axis=0))
        log_i = jax.nn.log_softmax(jnp.where(chosen, index, -jnp.inf), -1)
        held = chosen & (p > 0)
        kl = jnp.sum(jnp.where(held, p * (jnp.log(jnp.where(held, p, 1.0))
                                          - jnp.where(held, log_i, 0.0)),
                               0.0), axis=-1)
        return out, kl, chosen

    step = min(_QUERY_BLOCK, s)

    def blocks(x):
        return x.reshape((s // step, step) + x.shape[1:])
    out, kl, chosen = jax.lax.map(
        lambda a: block(*a),
        (blocks(q), blocks(qi), blocks(w), jnp.arange(0, s, step)))
    if return_selection:
        return chosen.reshape(s, s)
    return out.reshape(s, hq, d), kl.reshape(s)


def _held_experts(x, router, gate, up, down, top_k, first_expert):
    """x [t, h] -> the held experts' part of each token's result."""
    logits = x @ router                                     # [t, 128]
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

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                          (jnp.arange(gate.shape[0]), gate, up, down))
    return out


def _layer(x, w, cfg, positions, selection_only=False):
    """(the layer's output [b, s, h], its L_I)."""
    b, s, _ = x.shape
    eps = cfg["rms_norm_eps"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, sa = cfg["head_dim"], cfg["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    sections = cfg["rope_scaling"]["mrope_section"]

    h = _rms_norm(x, w["input_norm.scale"], eps)
    angle = _angles(cfg, positions, dh, sections)
    q = _rms_norm((h @ w["attention.query.w"]).reshape(b, s, heads, dh),
                  w["attention.q_norm.scale"], eps)
    k = _rms_norm((h @ w["attention.key.w"]).reshape(b, s, kv_heads, dh),
                  w["attention.k_norm.scale"], eps)
    v = (h @ w["attention.value.w"]).reshape(b, s, kv_heads, dh)
    q, k = _rotary(q, angle), _rotary(k, angle)

    hb = _indexer_input(h)
    rope_i = idim // 2
    angle_i = _angles(cfg, positions[:1], rope_i, [rope_i // 2])

    def turned(x):
        return jnp.concatenate([_rotary(x[..., :rope_i], angle_i),
                                x[..., rope_i:]], axis=-1)
    qi = turned((hb @ w["attention.indexer.query.w"]).reshape(b, s, ih, idim))
    ki = turned(_layer_norm(hb @ w["attention.indexer.key.w"],
                            w["attention.indexer.key_norm.scale"],
                            w["attention.indexer.key_norm.bias"],
                            cfg["indexer_layer_norm_eps"])[:, :, None, :]
                )[:, :, 0, :]
    wi = (hb @ w["attention.indexer.weights.w"]) * (ih ** -0.5
                                                    * idim ** -0.5)
    if selection_only:
        return jnp.stack([_sparse_attention(
            q[i], k[i], v[i], qi[i], ki[i], wi[i], sa["topk"], True)
            for i in range(b)])
    each = [_sparse_attention(q[i], k[i], v[i], qi[i], ki[i], wi[i],
                              sa["topk"]) for i in range(b)]
    ctx = jnp.stack([o for o, _ in each]).reshape(b, s, heads * dh)
    index_loss = jnp.mean(jnp.stack([kl for _, kl in each]))
    x = x + ctx @ w["attention.output.w"]

    h = _rms_norm(x, w["post_attention_norm.scale"], eps)
    moe = _held_experts(h.reshape(b * s, -1), w["router.w"],
                        w["experts.gate"], w["experts.up"],
                        w["experts.down"], cfg["num_experts_per_tok"],
                        cfg["first_expert"])
    return x + moe.reshape(b, s, -1), index_loss


def _layer_weights(params, i):
    pre = f"layer_{i}."
    return {n[len(pre):]: a for n, a in params.items() if n.startswith(pre)}


def _positions(s):
    return jnp.tile(jnp.arange(s, dtype=jnp.float32), (3, 1))


def losses(params, batch, cfg):
    """(L_LM, the sum over the layers of L_I) of one batch (dict of the
    feeds of ``model.py``: ``input_ids`` and ``labels``, [b, s]) under
    ``params`` (name -> float32 array)."""
    p = params
    ids = batch["input_ids"].astype(jnp.int32)
    b, s = ids.shape
    positions = _positions(s)
    x = p["embed_tokens"][ids]
    index_loss = jnp.zeros((), jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x, li = jax.checkpoint(
            lambda x, w: _layer(x, w, cfg, positions))(
                x, _layer_weights(p, i))
        index_loss = index_loss + li
    h = _rms_norm(x, p["final_norm.scale"], cfg["rms_norm_eps"]) \
        .reshape(b * s, -1)
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
    return total / (b * s), index_loss


def loss(params, batch, cfg):
    """The objective ``L_LM + index_loss_weight * sum of L_I``."""
    lm, index = losses(params, batch, cfg)
    return lm + cfg["index_loss_weight"] * index


def selections(params, batch, cfg):
    """[layers, b, s, s] bool: the key set of every query in every layer
    (what the chip check compares the program's selection with)."""
    p = params
    ids = batch["input_ids"].astype(jnp.int32)
    positions = _positions(ids.shape[1])
    x = p["embed_tokens"][ids]
    out = []
    for i in range(cfg["num_hidden_layers"]):
        w = _layer_weights(p, i)
        out.append(_layer(x, w, cfg, positions, selection_only=True))
        x, _ = _layer(x, w, cfg, positions)
    return jnp.stack(out)
