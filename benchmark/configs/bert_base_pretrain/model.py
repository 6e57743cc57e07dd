"""BERT pretraining as a fluid Program: the published graph, spelled from
``fluid.layers`` (google-research/bert ``modeling.py`` + ``run_pretraining.py``).

12 x {self-attention, 3072 gelu FFN, two post-LayerNorms}, pooler, masked-LM
head over the gathered predicted positions with the decoder tied to the word
embedding, next-sentence head; loss = masked-LM + next-sentence.  Every
parameter has a fixed name, so ``reference.py`` reads the same weights from
the scope.  Attention is spelled matmul -> scale -> +mask -> softmax ->
dropout -> matmul, the chain plain layers emit (and the one the program's
``fuse_attention`` pass matches, should a later PR make it a default).

Also here, because they belong to this configuration: the operations and
bytes one training step requires, computed from its shapes.
"""
from __future__ import annotations

def build(cfg, mix, train=True):
    """The Program for ``cfg`` (config.json) under ``mix`` (a traffic file).

    ``train=True``: forward, backward, Adam, dropout on.  ``train=False``:
    the dropout-free forward and backward only, for the comparison with the
    reference; ``grads`` then maps parameter name -> gradient variable name.
    Returns a dict with ``main``, ``startup``, ``loss``, ``grads``.
    """
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers as L
    from paddle_tpu.fluid.initializer import (ConstantInitializer,
                                              TruncatedNormalInitializer)
    from paddle_tpu.fluid.param_attr import ParamAttr

    hidden = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    head_dim = hidden // heads
    seq = mix["seq_len"]
    n_pred = mix["max_predictions_per_seq"]
    eps = cfg["layer_norm_eps"]
    p_hidden = cfg["hidden_dropout_prob"] if train else 0.0
    p_attn = cfg["attention_probs_dropout_prob"] if train else 0.0
    if cfg["hidden_act"] != "gelu":
        raise ValueError(f"hidden_act {cfg['hidden_act']!r}: only gelu is "
                         f"spelled here")
    if seq > cfg["max_position_embeddings"]:
        raise ValueError(f"seq_len {seq} exceeds max_position_embeddings")

    def weight(name):
        return ParamAttr(name=name, initializer=TruncatedNormalInitializer(
            0.0, cfg["initializer_range"]))

    def zeros(name):
        return ParamAttr(name=name, initializer=ConstantInitializer(0.0))

    def dense(x, size, name, act=None, flatten=2):
        return L.fc(x, size, num_flatten_dims=flatten, act=act,
                    param_attr=weight(name + ".w"), bias_attr=zeros(name + ".b"))

    def layer_norm(x, name):
        return L.layer_norm(
            x, begin_norm_axis=len(x.shape) - 1, epsilon=eps,
            param_attr=ParamAttr(name=name + ".scale",
                                 initializer=ConstantInitializer(1.0)),
            bias_attr=zeros(name + ".bias"))

    def dropout(x, p):
        if not p:
            return x
        return L.dropout(x, p, dropout_implementation="upscale_in_train")

    def split_heads(x):
        x = L.reshape(x, [0, 0, heads, head_dim])
        return L.transpose(x, [0, 2, 1, 3])                 # [B, heads, S, dh]

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        input_ids = fluid.data("input_ids", [-1, seq], dtype="int64")
        input_mask = fluid.data("input_mask", [-1, seq], dtype="float32")
        segment_ids = fluid.data("segment_ids", [-1, seq], dtype="int64")
        positions = fluid.data("masked_lm_positions", [-1, n_pred],
                               dtype="int64")
        mlm_ids = fluid.data("masked_lm_ids", [-1, n_pred], dtype="int64")
        mlm_weights = fluid.data("masked_lm_weights", [-1, n_pred],
                                 dtype="float32")
        nsp_labels = fluid.data("next_sentence_labels", [-1, 1],
                                dtype="int64")

        # -- embeddings ---------------------------------------------------
        x = L.embedding(input_ids, [cfg["vocab_size"], hidden],
                        param_attr=weight("word_embedding"))
        word_table = main.global_block().var("word_embedding")
        x = x + L.embedding(segment_ids, [cfg["type_vocab_size"], hidden],
                            param_attr=weight("token_type_embedding"))
        pos_table = L.create_parameter(
            [cfg["max_position_embeddings"], hidden], "float32",
            attr=weight("position_embedding"))
        x = x + L.slice(pos_table, axes=[0], starts=[0], ends=[seq])
        x = dropout(layer_norm(x, "embeddings.layer_norm"), p_hidden)

        # additive attention bias: 0 where attended, -10000 where padded
        bias = L.scale(L.reshape(input_mask, [0, 1, 1, seq]), scale=10000.0,
                       bias=-1.0, bias_after_scale=False)

        # -- encoder ------------------------------------------------------
        for i in range(cfg["num_hidden_layers"]):
            pre = f"layer_{i}."
            q = split_heads(dense(x, hidden, pre + "attention.query"))
            k = split_heads(dense(x, hidden, pre + "attention.key"))
            v = split_heads(dense(x, hidden, pre + "attention.value"))
            scores = L.matmul(q, k, transpose_y=True)
            scores = L.scale(scores, scale=head_dim ** -0.5) + bias
            probs = dropout(L.softmax(scores), p_attn)
            ctx = L.transpose(L.matmul(probs, v), [0, 2, 1, 3])
            ctx = L.reshape(ctx, [0, 0, hidden])
            attn = dropout(dense(ctx, hidden, pre + "attention.output"),
                           p_hidden)
            x = layer_norm(attn + x, pre + "attention.layer_norm")
            mid = dense(x, cfg["intermediate_size"], pre + "ffn.intermediate",
                        act="gelu")
            out = dropout(dense(mid, hidden, pre + "ffn.output"), p_hidden)
            x = layer_norm(out + x, pre + "ffn.layer_norm")

        # -- heads --------------------------------------------------------
        first = L.squeeze(L.slice(x, axes=[1], starts=[0], ends=[1]), [1])
        pooled = dense(first, hidden, "pooler", act="tanh", flatten=1)
        nsp_logits = dense(pooled, 2, "nsp", flatten=1)
        nsp_loss = L.mean(L.softmax_with_cross_entropy(nsp_logits,
                                                       nsp_labels))

        # rows of x at the predicted positions: [B,P,S] one-hot x [B,S,H]
        picked = L.matmul(L.one_hot(L.unsqueeze(positions, [2]), seq), x)
        h = layer_norm(dense(picked, hidden, "mlm.transform", act="gelu"),
                       "mlm.layer_norm")
        out_bias = L.create_parameter([cfg["vocab_size"]], "float32",
                                      attr=zeros("mlm.output_bias"))
        logits = L.matmul(h, word_table, transpose_y=True) + out_bias
        per_pos = L.softmax_with_cross_entropy(logits,
                                               L.unsqueeze(mlm_ids, [2]))
        w = L.unsqueeze(mlm_weights, [2])
        mlm_loss = L.reduce_sum(per_pos * w) / (L.reduce_sum(w) + 1e-5)
        loss = mlm_loss + nsp_loss

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


def param_count(cfg):
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    emb = (cfg["vocab_size"] + cfg["max_position_embeddings"]
           + cfg["type_vocab_size"]) * h + 2 * h
    layer = 4 * (h * h + h) + 2 * h + (h * i + i) + (i * h + h) + 2 * h
    heads = (h * h + h) + (h * h + h) + 2 * h + cfg["vocab_size"] \
        + (2 * h + 2)
    return emb + cfg["num_hidden_layers"] * layer + heads


def flops_per_sample(cfg, mix):
    """Forward + backward FLOPs one sequence requires (2 per multiply-add,
    backward = 2 x forward): the matrix multiplications of the published
    graph and nothing recomputed.  The masked-LM head counts only the
    predicted positions.  Elementwise work (softmax, LayerNorm, gelu,
    dropout, Adam) and the one-hot gather are not counted."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    s, p = mix["seq_len"], mix["max_predictions_per_seq"]
    per_token_layer = 2 * (4 * h * h + 2 * h * i) + 2 * (2 * s * h)
    encoder = cfg["num_hidden_layers"] * s * per_token_layer
    heads = 2 * h * h + 2 * h * 2 + p * (2 * h * h + 2 * h * cfg["vocab_size"])
    return 3.0 * (encoder + heads)


def bytes_per_step(cfg, mix, batch):
    """HBM bytes one training step of ``batch`` sequences on one chip cannot
    avoid.  Parameters are read in forward and in backward as stored
    (float32), gradients written once and read once, Adam reads and writes
    parameter and both moments (40 B per parameter in all).  Activations:
    what backward needs of each layer without recomputing — the inputs of
    its six matmuls, the attention probabilities and the gelu output —
    written once and read once in bfloat16."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    s = mix["seq_len"]
    per_token_layer = 6 * h + i + cfg["num_attention_heads"] * s
    acts = 2 * 2 * cfg["num_hidden_layers"] * batch * s * per_token_layer
    return 40.0 * param_count(cfg) + acts
