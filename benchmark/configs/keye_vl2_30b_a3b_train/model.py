"""Keye-VL-2.0-30B-A3B's language model as a fluid training Program: one
chip's share of a job in which eight chips share each layer, spelled from
``fluid.layers``.

Per layer ``x <- x + Attn(RMSNorm(x))`` then ``x <- x + MoE(RMSNorm(x))``.
The attention is over a key set that a learned scorer chooses per query
(``sa_config``: a DeepSeek-sparse-attention indexer): 32 query heads over 4
key/value heads of 128 with a per-head RMSNorm on queries and keys and
rotary over the whole head under three rows of positions
(``mrope_section``); beside them, on a DETACHED copy of the layer's normed
input, 16 index heads of 64 and one index key a token score every causal
pair, the ``topk`` best keys of each query are selected exactly
(``fluid.layers.sparse_attention_index``), all 32 heads attend those keys
only (``fused_multihead_attention(selection=)``), and the indexer is trained
by a loss of its own, the KL from the heads' mean attention to the softmax
of the index scores over the set (``sparse_attention_index_loss``).  The
language-model loss gives the indexer nothing and the indexer's loss gives
the model nothing.  The experts are 128-way top-8 routed, of which this chip
holds ``num_experts``.  Embedding and head are the held rows of the
vocabulary.  The residual stream stays float32 under AMP.  Every parameter
has a fixed name, so ``reference.py`` reads the same weights from the scope;
every op of the attention branch names its output ``layer_<i>.attention.…``
and every op of the indexer ``layer_<i>.attention.indexer.…``, which is how
``kernel.dsa_indexer_ms_per_step`` finds its rows.

Also here, because they belong to this configuration: the parameters, the
operations and the bytes one training step requires, from its shapes, and
the operations and bytes of the selected attention and of the indexer.
"""
from __future__ import annotations

import math

import numpy as np

INDEXER_PARAMETERS = ("attention.indexer.query.w", "attention.indexer.key.w",
                      "attention.indexer.key_norm.scale",
                      "attention.indexer.key_norm.bias",
                      "attention.indexer.weights.w")


def rotary_frequencies(cfg, dim):
    """``rope_theta^(-2i/dim)`` for the ``dim / 2`` pairs of a rotary part
    of ``dim`` numbers."""
    if cfg["rope_scaling"]["rope_type"] != "default":
        raise ValueError("only the default rotary is spelled")
    base = float(cfg["rope_theta"])
    return [base ** (-2.0 * i / dim) for i in range(dim // 2)]


def indexer_rope_dim(cfg):
    """The leading numbers of an index head that turn: half of it."""
    return cfg["sa_config"]["indexer_head_dim"] // 2


def held_rows_bound(cfg, tokens):
    """Rows of the buffers of held assignments: ``expert_rows_bound`` times
    the even share ``tokens * top_k * held / all``, in whole 512-row
    tiles."""
    even = tokens * cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["published"]["num_experts"]
    rows = int(math.ceil(cfg["expert_rows_bound"] * even / 512.0)) * 512
    return min(rows, tokens * cfg["num_experts_per_tok"])


def build(cfg, mix, train=True):
    """The Program for ``cfg`` (config.json) under ``mix`` (a traffic file).

    ``train=True``: forward, backward, Adam.  ``train=False``: forward and
    backward only, for the comparison with the reference; ``grads`` then maps
    parameter name -> gradient variable name.  Returns a dict with ``main``,
    ``startup``, ``loss`` (the objective), ``grads``, and the objective's two
    parts ``lm_loss`` and ``index_loss`` (the weighted sum over the layers).
    """
    # absent in a tree before this configuration: fail at once
    from paddle_tpu.fluid.layers import sparse_attention_index  # noqa: F401
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers as L
    from paddle_tpu.fluid.initializer import (ConstantInitializer,
                                              TruncatedNormalInitializer)
    from paddle_tpu.fluid.param_attr import ParamAttr

    hidden, dh = cfg["hidden_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    seq, eps = mix["seq_len"], cfg["rms_norm_eps"]
    if cfg["hidden_act"] != "silu" or cfg["attention_bias"] \
            or not cfg["norm_topk_prob"] or cfg["tie_word_embeddings"] \
            or cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"] \
            or cfg["use_sliding_window"] or sa["indexer_num_kv_heads"] != 1:
        raise ValueError("only the published spelling is built: silu gate, "
                         "no attention bias, renormalised top-k, untied "
                         "head, every layer sparse, no window, one index key")
    sections = cfg["rope_scaling"]["mrope_section"]
    freqs = rotary_frequencies(cfg, dh)
    rope_i = indexer_rope_dim(cfg)
    freqs_i = rotary_frequencies(cfg, rope_i)
    scale = dh ** -0.5

    def weight(name, std=cfg["initializer_range"]):
        return ParamAttr(name=name,
                         initializer=TruncatedNormalInitializer(0.0, std))

    def ones(name):
        return ParamAttr(name=name, initializer=ConstantInitializer(1.0))

    # the projections that write into the residual stream start smaller by
    # sqrt(2 x the published depth): see "assumed" in config.json
    out_std = cfg["initializer_range"] \
        / math.sqrt(2 * cfg["published"]["num_hidden_layers"])

    def dense(x, size, name, std=cfg["initializer_range"]):
        return L.fc(x, size, num_flatten_dims=2, param_attr=weight(name, std),
                    bias_attr=False, name=name[:-2])

    def norm(x, name):
        return L.rms_norm(x, epsilon=eps, name=name,
                          param_attr=ones(name + ".scale"))

    def head_major(x, n, width, name):
        """[B, S, n * width] -> [B, n, S, width]."""
        return L.transpose(L.reshape(x, [0, 0, n, width],
                                     name=name + "_rows"),
                           [0, 2, 1, 3], name=name + "_heads")

    def attention(h, positions, pre, gauges):
        """``pre`` is ``layer_<i>.attention.``; returns (the branch's output
        [B, S, hidden], the layer's weighted indexer loss [1])."""
        def normed_heads(x, n, name):
            rows = L.reshape(x, [0, 0, n, dh], name=pre + name + "_rows")
            return L.transpose(norm(rows, pre + name + "_norm"),
                               [0, 2, 1, 3], name=pre + name + "_heads")

        def rotary(x, name):
            return L.rotary_embedding(x, freqs, name=pre + name,
                                      positions=positions,
                                      sections=sections)
        q = rotary(normed_heads(dense(h, heads * dh, pre + "query.w"),
                                heads, "q"), "q_rope")
        k = rotary(normed_heads(dense(h, kv_heads * dh, pre + "key.w"),
                                kv_heads, "k"), "k_rope")
        v = head_major(dense(h, kv_heads * dh, pre + "value.w"), kv_heads,
                       dh, pre + "v")

        # the indexer reads a copy no gradient passes through
        ipre = pre + "indexer."
        hb = L.assign(h)
        hb.stop_gradient = True
        temporal = L.slice(positions, axes=[0], starts=[0], ends=[1])

        def turned(x, name):
            """Rotary on the leading ``rope_i`` numbers, by the temporal
            row of the positions."""
            lead, rest = L.split(x, [rope_i, idim - rope_i], dim=-1,
                                 name=ipre + name + "_split")
            lead = L.rotary_embedding(lead, freqs_i, positions=temporal,
                                      name=ipre + name + "_rope")
            return L.concat([lead, rest], axis=-1, name=ipre + name)
        qi = turned(head_major(dense(hb, ih * idim, ipre + "query.w"), ih,
                               idim, ipre + "q"), "qi")
        ki = turned(L.layer_norm(
            dense(hb, idim, ipre + "key.w"), begin_norm_axis=2,
            epsilon=cfg["indexer_layer_norm_eps"],
            param_attr=ones(ipre + "key_norm.scale"),
            bias_attr=ParamAttr(name=ipre + "key_norm.bias"),
            name=ipre + "key_norm"), "ki")
        w = L.scale(dense(hb, ih, ipre + "weights.w"),
                    scale=ih ** -0.5 * idim ** -0.5, name=ipre + "w")
        selection = L.sparse_attention_index(
            qi, ki, w, sa["topk"], gauges=gauges, name=ipre + "selection")
        ctx, lse = L.fused_multihead_attention(
            q, k, v, scale=scale, causal=True, selection=selection,
            return_lse=True, name=pre + "kernel")
        index_loss = L.sparse_attention_index_loss(
            qi, ki, w, q, k, lse, selection, scale,
            weight=cfg["index_loss_weight"], gauges=gauges,
            name=ipre + "loss")
        ctx = L.reshape(L.transpose(ctx, [0, 2, 1, 3], name=pre + "ctx"),
                        [0, 0, heads * dh], name=pre + "ctx_rows")
        return dense(ctx, hidden, pre + "output.w", out_std), index_loss

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        input_ids = fluid.data("input_ids", [-1, seq], dtype="int64")
        labels = fluid.data("labels", [-1, seq], dtype="int64")
        # text: the three rows of positions are equal, 0..S-1
        positions = L.expand(L.assign(
            np.arange(seq, dtype="float32")[None, :]), [3, 1])
        positions.stop_gradient = True
        x = L.embedding(input_ids, [cfg["vocab_size"], hidden],
                        param_attr=weight(
                            "embed_tokens",
                            cfg["embedding_initializer_range"]))
        index_losses = []
        for i in range(cfg["num_hidden_layers"]):
            pre = f"layer_{i}."
            branch, index_loss = attention(
                norm(x, pre + "input_norm"), positions, pre + "attention.",
                f"layer_{i}")
            index_losses.append(index_loss)
            x = x + L.cast(branch, "float32")

            tokens = L.reshape(norm(x, pre + "post_attention_norm"),
                               [-1, hidden], name=pre + "moe.tokens")
            moe = L.expert_layer(
                tokens, cfg["published"]["num_experts"],
                cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
                first_expert=cfg["first_expert"],
                num_held=cfg["num_experts"],
                router_attr=weight(pre + "router.w"),
                gate_attr=weight(pre + "experts.gate"),
                up_attr=weight(pre + "experts.up"),
                down_attr=weight(pre + "experts.down", out_std),
                max_held_rows=held_rows_bound(
                    cfg, mix["samples_per_chip"] * seq),
                name=pre + "moe")
            x = x + L.cast(L.reshape(moe, [-1, seq, hidden]), "float32")

        # head and loss in blocks of tokens: the [16384, 18992] float32
        # logits and their softmax are 2.3 GiB the step has no room for
        lm_loss = L.mean(L.linear_cross_entropy(
            norm(x, "final_norm"), L.unsqueeze(labels, [2]),
            cfg["vocab_size"], param_attr=weight("lm_head.w"),
            name="lm_head"))
        index_loss = L.sums(index_losses)
        loss = L.sums([lm_loss, index_loss])

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
    return {"main": main, "startup": startup, "loss": loss, "grads": grads,
            "lm_loss": lm_loss, "index_loss": index_loss}


# ---------------------------------------------------------------------------
# shapes functions
# ---------------------------------------------------------------------------

def _layer_matrices(cfg):
    """(attention projections, indexer projections, router, one expert)
    matrix parameters of a layer."""
    h, dh = cfg["hidden_size"], cfg["head_dim"]
    sa = cfg["sa_config"]
    attention = 2 * h * dh * (cfg["num_attention_heads"]
                              + cfg["num_key_value_heads"])
    indexer = h * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                   + sa["indexer_head_dim"] + sa["indexer_num_heads"])
    router = h * cfg["published"]["num_experts"]
    return attention, indexer, router, 3 * h * cfg["moe_intermediate_size"]


def _held_rows_per_token(cfg):
    """Assignments a token sends to the held experts at the deployment's
    even routing: top_k x held / all experts."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["published"]["num_experts"]


def param_count(cfg):
    """Parameters of what ``cfg`` holds: ``num_experts`` experts a layer and
    ``vocab_size`` rows of embedding and head, norm scales and the index
    key's LayerNorm included.  With the three ``reduced`` keys at their
    ``published`` values it is the whole language model's."""
    h = cfg["hidden_size"]
    attention, indexer, router, expert = _layer_matrices(cfg)
    norms = 2 * h + 2 * cfg["head_dim"] \
        + 2 * cfg["sa_config"]["indexer_head_dim"]
    layer = attention + indexer + norms + router \
        + cfg["num_experts"] * expert
    return cfg["num_hidden_layers"] * layer + 2 * cfg["vocab_size"] * h + h


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def selected_pairs(seq, topk):
    """(query, key) pairs one sequence attends: query t keeps min(t + 1,
    topk) keys."""
    k = min(seq, topk)
    return k * (k + 1) // 2 + (seq - k) * k


def attention_flops_per_sample(cfg, mix):
    """Forward FLOPs of the selected attention of one sequence: the two
    matmuls of every head over the SELECTED pairs only (a lowering that
    computes every causal pair and masks earns nothing for the rest)."""
    per_pair = 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    return float(cfg["num_hidden_layers"] * per_pair
                 * selected_pairs(mix["seq_len"], cfg["sa_config"]["topk"]))


def attention_bytes_per_sample(cfg, mix):
    """HBM bytes the attention ops of one sequence cannot avoid, forward and
    backward, in bfloat16 (forward reads q, k, v and writes the output;
    backward reads those four and the output's gradient and writes the
    gradients of q, k and v), and the selection read once each way at a
    bit a causal pair."""
    q = mix["seq_len"] * cfg["num_attention_heads"] * cfg["head_dim"]
    kv = mix["seq_len"] * cfg["num_key_value_heads"] * cfg["head_dim"]
    return cfg["num_hidden_layers"] * (
        2.0 * ((2 * q + 2 * kv) + (4 * q + 4 * kv))
        + 2 * causal_pairs(mix["seq_len"]) / 8.0)


def indexer_flops_and_bytes(cfg, mix):
    """(FLOPs, HBM bytes) a step's index scores, selection and indexer loss
    require of one sequence over all layers: the scores of every causal
    pair forward (indexer heads x head dim x 2 a pair: each must be known
    before the best can be chosen), their gradient over the selected pairs
    only (two matmuls; an unselected pair's is exactly zero); the index
    queries, keys and weights read forward and read and written backward in
    bfloat16, and the selection written once at a bit a causal pair.  The
    attention probabilities the loss compares with are the attention's own:
    computing them again is no required work.  The indexer's projections are
    the program's ``mul`` ops and are counted in ``flops_per_sample``."""
    sa = cfg["sa_config"]
    seq = mix["seq_len"]
    per_pair = 2 * sa["indexer_num_heads"] * sa["indexer_head_dim"]
    flops = per_pair * (causal_pairs(seq)
                        + 2 * selected_pairs(seq, sa["topk"]))
    operands = seq * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                      + sa["indexer_head_dim"] + sa["indexer_num_heads"])
    nbytes = 2.0 * 3 * operands + causal_pairs(seq) / 8.0
    return (float(cfg["num_hidden_layers"] * flops),
            float(cfg["num_hidden_layers"] * nbytes))


def flops_per_sample(cfg, mix):
    """Forward + backward FLOPs one sequence requires of this share (2 per
    multiply-add): the projections (attention's and the indexer's) and the
    router for every token, the expert FFNs at the deployment's mean share,
    the head over the held rows, each backward twice its forward; the
    selected attention over the selected pairs, likewise; the index scores
    over every causal pair forward and over the selected pairs backward
    (``indexer_flops_and_bytes``).  Nothing recomputed, nothing
    elementwise."""
    s = mix["seq_len"]
    attention, indexer, router, expert = _layer_matrices(cfg)
    layer = 2 * (attention + indexer + router
                 + _held_rows_per_token(cfg) * expert)
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    forward = s * (cfg["num_hidden_layers"] * layer + head) \
        + attention_flops_per_sample(cfg, mix)
    return 3.0 * forward + indexer_flops_and_bytes(cfg, mix)[0]


def bytes_per_step(cfg, mix, batch):
    """HBM bytes one training step of ``batch`` sequences on one chip cannot
    avoid, by the BERT configuration's convention: 40 B a parameter, plus
    what backward needs of each layer without recomputing, written once and
    read once in bfloat16: both norms' outputs, q, k, v, the attention
    output, the index queries, keys and weights, the selection at a bit a
    causal pair, and for each of the mean ``_held_rows_per_token`` rows a
    token sends here the dispatched row, the three grouped matmuls' outputs
    and the gated product; the head's input, and its float32 logits."""
    h, dh = cfg["hidden_size"], cfg["head_dim"]
    sa = cfg["sa_config"]
    tokens = batch * mix["seq_len"]
    rows = _held_rows_per_token(cfg)
    qkv = dh * (cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])
    index = sa["indexer_num_heads"] * sa["indexer_head_dim"] \
        + sa["indexer_head_dim"] + sa["indexer_num_heads"]
    per_token_layer = 2 * h + qkv + dh * cfg["num_attention_heads"] + index \
        + rows * (2 * h + 3 * cfg["moe_intermediate_size"])
    acts = 2 * 2 * cfg["num_hidden_layers"] * tokens * per_token_layer
    selection = 2 * cfg["num_hidden_layers"] * batch \
        * causal_pairs(mix["seq_len"]) / 8.0
    head = 2 * tokens * (2 * h + 4 * cfg["vocab_size"])
    return 40.0 * param_count(cfg) + acts + selection + head
