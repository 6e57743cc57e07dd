"""Mellum2-12B-A2.5B as a fluid training Program: one chip's share of a job in
which four chips share each layer, spelled from ``fluid.layers``.

Per layer ``h <- h + Attn(RMSNorm(h))`` then ``h <- h + MoE(RMSNorm(h))``:
32 query heads over 4 key/value heads of 128, rotary over the whole head
(plain frequencies on sliding layers, YaRN's on full ones), causal attention
with a window of 1024 on the sliding layers, and 64-way top-8 routing of which
this chip holds ``num_experts`` experts (``fluid.layers.expert_layer``: no
token is dropped; the experts not held add nothing here).  Embedding and head
are the held rows of the vocabulary.  The residual stream stays float32 under
AMP (each branch's bf16 output is cast before it is added): a pre-norm stream
that starts at the embedding's scale would round a branch's small
contribution away in bfloat16.  Every parameter has a fixed name, so
``reference.py`` reads the same weights from the scope.

Also here, because they belong to this configuration: the parameters, the
operations and the bytes one training step requires, from its shapes, and the
operations and bytes of the two kernels the configuration brings.
"""
from __future__ import annotations

import math


def rotary_frequencies(cfg, layer_type):
    """(the head_dim/2 inverse frequencies, the factor on cos and sin) of a
    layer type, from ``rope_parameters``: ``theta^(-2i/d)``, and for ``yarn``
    the per-dimension blend of that and that over ``factor`` along the linear
    ramp between the correction dimensions of ``beta_fast`` and
    ``beta_slow``."""
    rope = cfg["rope_parameters"][layer_type]
    dim, base = cfg["head_dim"], float(rope["rope_theta"])
    plain = [base ** (-2.0 * i / dim) for i in range(dim // 2)]
    if rope["rope_type"] == "default":
        return plain, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not spelled")
    factor, orig = rope["factor"], rope["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    span = max(high - low, 1e-3)
    freqs = []
    for i, f in enumerate(plain):
        ramp = min(max((i - low) / span, 0.0), 1.0)     # 0: keep, 1: / factor
        freqs.append(f / factor * ramp + f * (1.0 - ramp))
    return freqs, float(rope["attention_factor"])


def build(cfg, mix, train=True):
    """The Program for ``cfg`` (config.json) under ``mix`` (a traffic file).

    ``train=True``: forward, backward, Adam.  ``train=False``: forward and
    backward only, for the comparison with the reference; ``grads`` then maps
    parameter name -> gradient variable name.  Returns a dict with ``main``,
    ``startup``, ``loss``, ``grads``.
    """
    from paddle_tpu.fluid.layers import expert_layer    # absent: fail at once
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers as L
    from paddle_tpu.fluid.initializer import (ConstantInitializer,
                                              TruncatedNormalInitializer)
    from paddle_tpu.fluid.param_attr import ParamAttr

    hidden, dh = cfg["hidden_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    seq = mix["seq_len"]
    eps = cfg["rms_norm_eps"]
    if cfg["hidden_act"] != "silu" or cfg["attention_bias"] \
            or not cfg["norm_topk_prob"] or cfg["tie_word_embeddings"]:
        raise ValueError("only the published spelling is built: silu gate, "
                         "no attention bias, renormalised top-k, untied head")

    def weight(name, std=cfg["initializer_range"]):
        return ParamAttr(name=name,
                         initializer=TruncatedNormalInitializer(0.0, std))

    # the projections that write into the residual stream start smaller by
    # sqrt(2 x the published depth): see "assumed" in config.json
    out_std = cfg["initializer_range"] \
        / math.sqrt(2 * cfg["published"]["num_hidden_layers"])

    def dense(x, size, name, std=cfg["initializer_range"]):
        return L.fc(x, size, num_flatten_dims=2,
                    param_attr=weight(name, std), bias_attr=False)

    def norm(x, name):
        return L.rms_norm(x, epsilon=eps, param_attr=ParamAttr(
            name=name + ".scale", initializer=ConstantInitializer(1.0)))

    def split_heads(x, n):
        return L.transpose(L.reshape(x, [0, 0, n, dh]), [0, 2, 1, 3])

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        input_ids = fluid.data("input_ids", [-1, seq], dtype="int64")
        labels = fluid.data("labels", [-1, seq], dtype="int64")
        x = L.embedding(input_ids, [cfg["vocab_size"], hidden],
                        param_attr=weight(
                            "embed_tokens",
                            cfg["embedding_initializer_range"]))

        for i in range(cfg["num_hidden_layers"]):
            pre = f"layer_{i}."
            kind = cfg["layer_types"][i]
            if cfg["mlp_layer_types"][i] != "sparse":
                raise ValueError("a dense FFN layer is not spelled here")
            freqs, factor = rotary_frequencies(cfg, kind)
            h = norm(x, pre + "input_norm")
            q = split_heads(dense(h, heads * dh, pre + "attention.query.w"),
                            heads)
            k = split_heads(dense(h, kv_heads * dh, pre + "attention.key.w"),
                            kv_heads)
            v = split_heads(dense(h, kv_heads * dh,
                                  pre + "attention.value.w"), kv_heads)
            ctx = L.fused_multihead_attention(
                L.rotary_embedding(q, freqs, factor),
                L.rotary_embedding(k, freqs, factor), v,
                scale=dh ** -0.5, causal=True,
                window=cfg["sliding_window"]
                if kind == "sliding_attention" else 0)
            ctx = L.reshape(L.transpose(ctx, [0, 2, 1, 3]),
                            [0, 0, heads * dh])
            x = x + L.cast(dense(ctx, hidden, pre + "attention.output.w",
                                 out_std), "float32")

            tokens = L.reshape(norm(x, pre + "post_attention_norm"),
                               [-1, hidden])
            moe = expert_layer(
                tokens, cfg["published"]["num_experts"],
                cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
                first_expert=cfg["first_expert"],
                num_held=cfg["num_experts"],
                router_attr=weight(pre + "router.w"),
                gate_attr=weight(pre + "experts.gate"),
                up_attr=weight(pre + "experts.up"),
                down_attr=weight(pre + "experts.down", out_std),
                name=pre + "moe")
            x = x + L.cast(L.reshape(moe, [-1, seq, hidden]), "float32")

        logits = dense(norm(x, "final_norm"), cfg["vocab_size"], "lm_head.w")
        loss = L.mean(L.softmax_with_cross_entropy(
            logits, L.unsqueeze(labels, [2])))

        grads = {}
        if train:
            o = cfg["optimizer"]
            if o["type"] != "adam":
                raise ValueError(f"optimizer {o['type']!r}: only adam here")
            fluid.optimizer.AdamOptimizer(
                learning_rate=o["learning_rate"], beta1=o["beta1"],
                beta2=o["beta2"], epsilon=o["epsilon"]).minimize(loss)
        else:
            from paddle_tpu.fluid.backward import append_backward
            grads = {p.name: g.name for p, g in append_backward(loss)}
    return {"main": main, "startup": startup, "loss": loss, "grads": grads}


# ---------------------------------------------------------------------------
# shapes functions
# ---------------------------------------------------------------------------

def _layer_matrices(cfg):
    """(attention projections, router, one expert) parameters of a layer."""
    h, dh = cfg["hidden_size"], cfg["head_dim"]
    attention = 2 * h * dh * (cfg["num_attention_heads"]
                              + cfg["num_key_value_heads"])
    router = h * cfg["published"]["num_experts"]
    return attention, router, 3 * h * cfg["moe_intermediate_size"]


def _held_rows_per_token(cfg):
    """Assignments a token sends to the held experts at the deployment's
    even routing: top_k x held / all experts."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["published"]["num_experts"]


def param_count(cfg):
    """Parameters of what ``cfg`` holds: ``num_experts`` experts a layer and
    ``vocab_size`` rows of embedding and head.  With the three ``reduced``
    keys at their ``published`` values it is the whole model's."""
    h = cfg["hidden_size"]
    attention, router, expert = _layer_matrices(cfg)
    layer = attention + 2 * h + router + cfg["num_experts"] * expert
    return cfg["num_hidden_layers"] * layer + 2 * cfg["vocab_size"] * h + h


def attended_pairs(seq, window):
    """Unmasked (query, key) pairs of one causal head over ``seq`` positions,
    ``window`` > 0 keeping 0 <= i - j < window."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def _layer_windows(cfg):
    return [cfg["sliding_window"] if kind == "sliding_attention" else 0
            for kind in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def attention_flops_per_sample(cfg, mix):
    """Forward FLOPs of the attention ops of one sequence: the two matmuls
    of every head over the unmasked pairs only."""
    per_pair = 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    return float(sum(attended_pairs(mix["seq_len"], w) * per_pair
                     for w in _layer_windows(cfg)))


def attention_bytes_per_sample(cfg, mix):
    """HBM bytes the attention ops of one sequence cannot avoid, forward and
    backward, in bfloat16: forward reads q, k, v and writes the output;
    backward reads those four and the output's gradient and writes the
    gradients of q, k and v."""
    q = mix["seq_len"] * cfg["num_attention_heads"] * cfg["head_dim"]
    kv = mix["seq_len"] * cfg["num_key_value_heads"] * cfg["head_dim"]
    return 2.0 * cfg["num_hidden_layers"] * ((2 * q + 2 * kv)
                                             + (4 * q + 4 * kv))


def flops_per_sample(cfg, mix):
    """Forward + backward FLOPs one sequence requires of this share (2 per
    multiply-add, backward = 2 x forward): projections and router for every
    token, the expert FFNs at the deployment's mean share (top_k * held /
    all experts a token), attention over the unmasked pairs, the head over
    the held rows; nothing recomputed, nothing elementwise."""
    s = mix["seq_len"]
    attention, router, expert = _layer_matrices(cfg)
    layer = 2 * (attention + router + _held_rows_per_token(cfg) * expert)
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    forward = s * (cfg["num_hidden_layers"] * layer + head) \
        + attention_flops_per_sample(cfg, mix)
    return 3.0 * forward


def bytes_per_step(cfg, mix, batch):
    """HBM bytes one training step of ``batch`` sequences on one chip cannot
    avoid, by the BERT configuration's convention: 40 B a parameter
    (parameters read in forward and backward as stored, gradients written and
    read once, Adam reads and writes parameter and both moments), plus what
    backward needs of each layer without recomputing, written once and read
    once in bfloat16: both norms' outputs, q, k, v, the attention output,
    and for each of the mean ``_held_rows_per_token`` rows a token sends here
    the dispatched row, the three grouped matmuls' outputs and the gated
    product; the head's input, and its float32 logits."""
    h, dh = cfg["hidden_size"], cfg["head_dim"]
    tokens = batch * mix["seq_len"]
    rows = _held_rows_per_token(cfg)
    qkv = dh * (cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])
    per_token_layer = 2 * h + qkv + dh * cfg["num_attention_heads"] \
        + rows * (2 * h + 3 * cfg["moe_intermediate_size"])
    acts = 2 * 2 * cfg["num_hidden_layers"] * tokens * per_token_layer
    head = 2 * tokens * (2 * h + 4 * cfg["vocab_size"])
    return 40.0 * param_count(cfg) + acts + head


def moe_gmm_flops_and_bytes(cfg, assignments):
    """(FLOPs, HBM bytes) a step's grouped matmuls require over all layers
    when ``assignments`` rows a layer reach the held experts: three matmuls
    of 2 * hidden * expert width a row, forward and twice that backward; the
    held experts' bfloat16 weights read in forward and twice in backward and
    their float32 gradients written, and the rows read and written in
    bfloat16 (forward: x twice, gate, up, the product, the output; backward
    the same once as operand and once as gradient)."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    layers = cfg["num_hidden_layers"]
    flops = 3.0 * layers * assignments * 3 * 2 * h * f
    weights = cfg["num_experts"] * 3 * h * f
    rows = assignments * (3 * h + 3 * f)
    return flops, float(layers * (weights * (3 * 2 + 4) + 3 * 2 * rows))
