"""Plain float32 ``jax.numpy`` reference of the BERT pretraining loss.

Forward and loss as published (google-research/bert ``modeling.py``,
``run_pretraining.py``); no dropout, no kernels, no mixed precision.  The
harness differentiates it (``compare.reference_loss_and_grads``, matmuls at
``highest`` precision).  Independent of the code under test: it shares only
the parameter names of ``model.py``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def loss(params, batch, cfg):
    """Masked-LM + next-sentence loss of one batch (dict of the feeds of
    ``model.py``) under ``params`` (name -> float32 array)."""
    p = params
    heads = cfg["num_attention_heads"]
    eps = cfg["layer_norm_eps"]
    ids = batch["input_ids"].astype(jnp.int32)
    b, s = ids.shape
    dh = cfg["hidden_size"] // heads

    def dense(x, name):
        return x @ p[name + ".w"] + p[name + ".b"]

    def ln(x, name):
        return _layer_norm(x, p[name + ".scale"], p[name + ".bias"], eps)

    def split(x):
        return x.reshape(b, s, heads, dh).transpose(0, 2, 1, 3)

    x = (p["word_embedding"][ids]
         + p["token_type_embedding"][batch["segment_ids"].astype(jnp.int32)]
         + p["position_embedding"][:s][None])
    x = ln(x, "embeddings.layer_norm")
    bias = (batch["input_mask"].astype(jnp.float32)[:, None, None, :]
            - 1.0) * 10000.0
    for i in range(cfg["num_hidden_layers"]):
        pre = f"layer_{i}."
        q = split(dense(x, pre + "attention.query"))
        k = split(dense(x, pre + "attention.key"))
        v = split(dense(x, pre + "attention.value"))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(dh) + bias
        ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, heads * dh)
        x = ln(dense(ctx, pre + "attention.output") + x,
               pre + "attention.layer_norm")
        mid = _gelu(dense(x, pre + "ffn.intermediate"))
        x = ln(dense(mid, pre + "ffn.output") + x, pre + "ffn.layer_norm")

    pooled = jnp.tanh(dense(x[:, 0], "pooler"))
    nsp = _xent(dense(pooled, "nsp"),
                batch["next_sentence_labels"].astype(jnp.int32)[:, 0])
    pos = batch["masked_lm_positions"].astype(jnp.int32)
    picked = jnp.take_along_axis(x, pos[..., None], axis=1)
    h = ln(_gelu(dense(picked, "mlm.transform")), "mlm.layer_norm")
    logits = h @ p["word_embedding"].T + p["mlm.output_bias"]
    per_pos = _xent(logits, batch["masked_lm_ids"].astype(jnp.int32))
    w = batch["masked_lm_weights"].astype(jnp.float32)
    return jnp.sum(per_pos * w) / (jnp.sum(w) + 1e-5) + jnp.mean(nsp)

