"""Xing4.0-29B-A4B as a fluid training Program: one chip's share of a job in
which eight chips share each layer, spelled from ``fluid.layers``.

The residual path is ``hc_mult`` = 4 float32 streams side by side, X [B, S,
4 * hidden].  A layer is two branches, each between a mixer's two ops
(``hyper_connection_mix`` gives the branch its input and the token's
coefficients, ``hyper_connection_merge`` writes the new streams): latent
attention (MLA: queries through a 768-wide latent, keys and values through
a 512-wide one, 32 heads that score over 128 + 64 rotary numbers and carry
values of 128, the one rotary key shared by all heads), then a gated FFN:
dense (9216) in the leading layers, after them 64-way sigmoid-routed top-4
experts of which this chip holds ``n_routed_experts``, beside a shared
expert every token takes.  Embedding and head are the held rows of the
vocabulary.  With ``num_nextn_predict_layers`` 1 the MTP module is built
too (the chip's configuration holds 0: config.json says why).  Every
parameter has a fixed name, so ``reference.py`` reads the same weights from
the scope; every op of the attention branch names its output
``layer_<i>.attention.…`` and every op of an expert layer (the shared
expert's too) ``layer_<i>.moe.…``, which is how ``kernel.mla_ms_per_step``
and ``kernel.expert_layer_ms_per_step`` find their rows.

Also here, because they belong to this configuration: the parameters, the
operations and the bytes one training step requires, from its shapes, and
the operations and bytes of the attention kernel and of a mixer.
"""
from __future__ import annotations

import math


def rotary_frequencies(cfg):
    """The qk_rope_head_dim / 2 inverse frequencies of the rotary part:
    YaRN's per-dimension blend of ``theta^(-2i/d)`` and that over ``factor``
    along the linear ramp between the correction dimensions of ``beta_fast``
    and ``beta_slow``.  cos and sin are not scaled (``mscale`` equals
    ``mscale_all_dim``): the factor sits in ``softmax_scale``."""
    rope = cfg["rope_scaling"]
    if rope["type"] != "yarn":
        raise ValueError(f"rope_scaling type {rope['type']!r} is not spelled")
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    factor, orig = rope["factor"], rope["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    span = max(high - low, 1e-3)
    freqs = []
    for i in range(dim // 2):
        f = base ** (-2.0 * i / dim)
        ramp = min(max((i - low) / span, 0.0), 1.0)     # 0: keep, 1: / factor
        freqs.append(f / factor * ramp + f * (1.0 - ramp))
    return freqs


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg):
    """``(qk_nope + qk_rope)^-1/2 * (0.1 mscale_all_dim ln factor + 1)^2``."""
    rope = cfg["rope_scaling"]
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return width ** -0.5 * _yarn_mscale(rope["factor"],
                                        rope["mscale_all_dim"]) ** 2


def rotary_cos_sin_factor(cfg):
    """The factor on cos and sin: mscale / mscale_all_dim's, 1 here."""
    rope = cfg["rope_scaling"]
    return _yarn_mscale(rope["factor"], rope["mscale"]) \
        / _yarn_mscale(rope["factor"], rope["mscale_all_dim"])


def build(cfg, mix, train=True):
    """The Program for ``cfg`` (config.json) under ``mix`` (a traffic file).

    ``train=True``: forward, backward, Adam.  ``train=False``: forward and
    backward only, for the comparison with the reference; ``grads`` then maps
    parameter name -> gradient variable name.  Returns a dict with ``main``,
    ``startup``, ``loss``, ``grads``.
    """
    # absent in a tree before this configuration: fail at once
    from paddle_tpu.fluid.layers import hyper_connection_mix  # noqa: F401
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers as L
    from paddle_tpu.fluid.initializer import (ConstantInitializer,
                                              TruncatedNormalInitializer)
    from paddle_tpu.fluid.layer_helper import LayerHelper
    from paddle_tpu.fluid.param_attr import ParamAttr

    hidden, n = cfg["hidden_size"], cfg["hc_mult"]
    heads = cfg["num_attention_heads"]
    nope, rope_dim = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    v_dim = cfg["v_head_dim"]
    seq, eps = mix["seq_len"], cfg["rms_norm_eps"]
    if cfg["hidden_act"] != "silu" or cfg["attention_bias"] \
            or not cfg["norm_topk_prob"] or cfg["tie_word_embeddings"] \
            or cfg["scoring_func"] != "sigmoid" \
            or cfg["topk_method"] != "noaux_tc" or cfg["n_group"] != 1 \
            or cfg["topk_group"] != 1 or cfg["moe_layer_freq"] != 1 \
            or cfg["num_key_value_heads"] != heads \
            or cfg["num_nextn_predict_layers"] not in (0, 1):
        raise ValueError("only the published spelling is built: silu gate, "
                         "no attention bias, sigmoid scores renormalised "
                         "over an ungrouped top-k, untied head, at most one "
                         "MTP module")
    freqs, cos_sin = rotary_frequencies(cfg), rotary_cos_sin_factor(cfg)
    scale = softmax_scale(cfg)

    def weight(name, std=cfg["initializer_range"]):
        return ParamAttr(name=name,
                         initializer=TruncatedNormalInitializer(0.0, std))

    # the projections that write into the residual streams start smaller by
    # sqrt(2 x the published depth): see "assumed" in config.json
    out_std = cfg["initializer_range"] \
        / math.sqrt(2 * cfg["published"]["num_hidden_layers"])

    def dense(x, size, name, std=cfg["initializer_range"]):
        return L.fc(x, size, num_flatten_dims=2, param_attr=weight(name, std),
                    bias_attr=False, name=name[:-2])

    def norm(x, name, scale_var=None):
        """RMSNorm under the parameter ``<name>.scale``, or under a scale
        that is already in the program (the MTP module shares the final
        norm)."""
        if scale_var is None:
            return L.rms_norm(x, epsilon=eps, name=name, param_attr=ParamAttr(
                name=name + ".scale", initializer=ConstantInitializer(1.0)))
        helper = LayerHelper("rms_norm", name=name)
        out = helper.create_variable_for_type_inference(dtype=x.dtype)
        helper.append_op("rms_norm", inputs={"X": [x], "Scale": [scale_var]},
                         outputs={"Y": [out]}, attrs={"epsilon": eps})
        return out

    def attention(h, pre):
        """MLA over ``h`` [B, S, hidden]; every output is named under
        ``pre`` (``layer_<i>.attention.``)."""
        c_q = norm(dense(h, cfg["q_lora_rank"], pre + "q_a.w"),
                   pre + "q_a_norm")
        q = dense(c_q, heads * (nope + rope_dim), pre + "q_b.w")
        q = L.transpose(L.reshape(q, [0, 0, heads, nope + rope_dim],
                                  name=pre + "q_rows"),
                        [0, 2, 1, 3], name=pre + "q_heads")
        q_nope, q_rope = L.split(q, [nope, rope_dim], dim=3,
                                 name=pre + "q_split")
        kv_a = dense(h, cfg["kv_lora_rank"] + rope_dim, pre + "kv_a.w")
        c_kv, k_rope = L.split(kv_a, [cfg["kv_lora_rank"], rope_dim], dim=2,
                               name=pre + "kv_a_split")
        kv = dense(norm(c_kv, pre + "kv_a_norm"), heads * (nope + v_dim),
                   pre + "kv_b.w")
        kv = L.transpose(L.reshape(kv, [0, 0, heads, nope + v_dim],
                                   name=pre + "kv_rows"),
                         [0, 2, 1, 3], name=pre + "kv_heads")
        k_nope, v = L.split(kv, [nope, v_dim], dim=3, name=pre + "kv_split")
        # the one rotary key, rotated once and shared by all the heads
        k_rope = L.rotary_embedding(
            L.unsqueeze(k_rope, [1], name=pre + "k_rope_head"), freqs,
            cos_sin, name=pre + "k_rope")
        q = L.concat([q_nope, L.rotary_embedding(q_rope, freqs, cos_sin,
                                                 name=pre + "q_rope")],
                     axis=3, name=pre + "q")
        k = L.concat([k_nope, L.expand(k_rope, [1, heads, 1, 1],
                                       name=pre + "k_rope_heads")],
                     axis=3, name=pre + "k")
        ctx = L.fused_multihead_attention(q, k, v, scale=scale, causal=True,
                                          name=pre + "kernel")
        ctx = L.reshape(L.transpose(ctx, [0, 2, 1, 3], name=pre + "ctx"),
                        [0, 0, heads * v_dim], name=pre + "ctx_rows")
        return dense(ctx, hidden, pre + "output.w", out_std)

    def gated(h, width, pre):
        return L.gated_ffn(h, width, weight(pre + "gate.w"),
                           weight(pre + "up.w"),
                           weight(pre + "down.w", out_std))

    def experts(h, pre):
        tokens = L.reshape(h, [-1, hidden], name=pre + "moe.tokens")
        out = L.expert_layer(
            tokens, cfg["published"]["n_routed_experts"],
            cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
            first_expert=cfg["first_expert"],
            num_held=cfg["n_routed_experts"], scoring="sigmoid",
            routed_scaling_factor=cfg["routed_scaling_factor"],
            router_attr=weight(pre + "router.w"),
            correction_bias_attr=ParamAttr(name=pre + "router.bias"),
            gate_attr=weight(pre + "experts.gate"),
            up_attr=weight(pre + "experts.up"),
            down_attr=weight(pre + "experts.down", out_std),
            shared_size=cfg["n_shared_experts"]
            * cfg["moe_intermediate_size"],
            shared_gate_attr=weight(pre + "shared.gate.w"),
            shared_up_attr=weight(pre + "shared.up.w"),
            shared_down_attr=weight(pre + "shared.down.w", out_std),
            name=pre + "moe")
        return L.reshape(out, [-1, seq, hidden])

    def mixed(stream, pre, branch):
        """One branch between its mixer's two ops; ``pre`` is
        ``layer_<i>.attn`` or ``layer_<i>.ffn``."""
        y, post, c = L.hyper_connection_mix(
            stream, n, epsilon=eps, sinkhorn_iters=cfg["hc_sinkhorn_iters"],
            hc_eps=cfg["hc_eps"],
            clamp=(cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]),
            alpha_init=cfg["hc_alpha_init"],
            res_init_diagonal=cfg["hc_res_init_diagonal"],
            phi_attr=weight(pre + ".hc.phi"),
            alpha_attr=ParamAttr(name=pre + ".hc.alpha"),
            b_attr=ParamAttr(name=pre + ".hc.b"), name=pre)
        return L.hyper_connection_merge(stream, branch(norm(y, pre + ".norm")),
                                        post, c, name=pre)

    def layer(stream, pre, dense_ffn):
        stream = mixed(stream, pre + "attn",
                       lambda h: attention(h, pre + "attention."))
        if dense_ffn:
            return mixed(stream, pre + "ffn",
                         lambda h: gated(h, cfg["intermediate_size"],
                                         pre + "ffn."))
        return mixed(stream, pre + "ffn", lambda h: experts(h, pre))

    def start(e):
        return L.expand(e, [1, 1, n])

    def collapse(stream):
        return L.sums(L.split(stream, n, dim=2))

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        input_ids = fluid.data("input_ids", [-1, seq], dtype="int64")
        labels = fluid.data("labels", [-1, seq], dtype="int64")
        stream = start(L.embedding(
            input_ids, [cfg["vocab_size"], hidden],
            param_attr=weight("embed_tokens",
                              cfg["embedding_initializer_range"])))
        for i in range(cfg["num_hidden_layers"]):
            stream = layer(stream, f"layer_{i}.",
                           i < cfg["first_k_dense_replace"])
        h = collapse(stream)
        logits = dense(norm(h, "final_norm"), cfg["vocab_size"], "lm_head.w")
        loss = L.mean(L.softmax_with_cross_entropy(
            logits, L.unsqueeze(labels, [2])))

        if cfg["num_nextn_predict_layers"]:
            block = main.global_block()
            table, head = block.var("embed_tokens"), block.var("lm_head.w")
            helper = LayerHelper("embedding")
            nxt = helper.create_variable_for_type_inference(dtype="float32")
            helper.append_op("lookup_table_v2",
                             inputs={"W": [table], "Ids": [labels]},
                             outputs={"Out": [nxt]},
                             attrs={"padding_idx": -1, "is_sparse": False})
            joined = L.concat([norm(h, "mtp.h_norm"),
                               norm(nxt, "mtp.embed_norm")], axis=2)
            s2 = layer(start(L.cast(dense(joined, hidden, "mtp.eh_proj.w"),
                                    "float32")), "mtp.", False)
            h2 = norm(collapse(s2), "mtp.final_norm",
                      scale_var=block.var("final_norm.scale"))
            logits2 = L.mul(h2, head, x_num_col_dims=2)
            # position t predicts the token after next, labels[t + 1]; the
            # last position has none and is left out of the mean
            after_next = L.concat(
                [L.slice(labels, [1], [1], [seq]),
                 L.slice(labels, [1], [seq - 1], [seq])], axis=1)
            ce2 = L.softmax_with_cross_entropy(
                logits2, L.unsqueeze(after_next, [2]))
            loss = loss + L.scale(
                L.mean(L.slice(ce2, [1], [0], [seq - 1])),
                scale=float(cfg["mtp_loss_weight"]))

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

def _attention_matrices(cfg):
    """Parameters of the five latent projections of one MLA."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * qk \
        + h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        + cfg["kv_lora_rank"] * heads * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"]) \
        + heads * cfg["v_head_dim"] * h


def _mixer_matrix(cfg):
    n = cfg["hc_mult"]
    return n * cfg["hidden_size"] * (n * n + 2 * n)


def _expert(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _held_rows_per_token(cfg):
    """Assignments a token sends to the held experts at the deployment's
    even routing: top_k x held / all experts."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["published"]["n_routed_experts"]


def _layer_params(cfg, dense_ffn):
    """One layer: MLA with its two latent norms, two mixers (phi, alpha, b),
    two block norms, and the FFN: dense, or router with its correction
    bias, the held routed experts and the shared ones."""
    h, n = cfg["hidden_size"], cfg["hc_mult"]
    common = _attention_matrices(cfg) + cfg["q_lora_rank"] \
        + cfg["kv_lora_rank"] + 2 * (_mixer_matrix(cfg) + 3 + n * n + 2 * n) \
        + 2 * h
    if dense_ffn:
        return common + 3 * h * cfg["intermediate_size"]
    experts_all = cfg["published"]["n_routed_experts"]
    return common + h * experts_all + experts_all \
        + (cfg["n_routed_experts"] + cfg["n_shared_experts"]) * _expert(cfg)


def param_count(cfg):
    """Parameters of what ``cfg`` holds: ``first_k_dense_replace`` dense and
    the other of ``num_hidden_layers`` expert layers with
    ``n_routed_experts`` experts each, ``vocab_size`` rows of embedding and
    head, the final norm, and per MTP module the joining projection, its two
    norms and one expert layer.  With the ``reduced`` keys at their
    ``published`` values it is the whole model's."""
    h = cfg["hidden_size"]
    dense_layers = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    sparse = cfg["num_hidden_layers"] - dense_layers
    mtp = cfg["num_nextn_predict_layers"] * (2 * h * h + 2 * h
                                             + _layer_params(cfg, False))
    return dense_layers * _layer_params(cfg, True) \
        + sparse * _layer_params(cfg, False) + mtp \
        + 2 * cfg["vocab_size"] * h + h


def attention_flops_per_sample(cfg, mix):
    """Forward FLOPs of the attention kernels of one sequence: scores over
    qk_nope + qk_rope numbers and values of v_head_dim, every head, over the
    unmasked (query, key) pairs of a causal layer only, every layer (an MTP
    module's too); a head padded to a wider one earns nothing."""
    s = mix["seq_len"]
    per_pair = 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    layers = cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]
    return float(layers * (s * (s + 1) // 2) * per_pair)


def attention_bytes_per_sample(cfg, mix):
    """HBM bytes the attention kernels of one sequence cannot avoid,
    forward and backward, in bfloat16: forward reads q, k (score width) and
    v and writes the output (value width); backward reads those four and
    the output's gradient and writes the gradients of q, k and v."""
    rows = mix["seq_len"] * cfg["num_attention_heads"]
    qk = rows * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
    v = rows * cfg["v_head_dim"]
    layers = cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]
    return 2.0 * layers * ((2 * qk + 2 * v) + (4 * qk + 4 * v))


def hyper_connection_flops_and_bytes_per_sample(cfg, mix):
    """(FLOPs, HBM bytes) the mixers of one sequence require, forward and
    backward, every mixer of every layer.  FLOPs: the projection x phi and
    its two backward products.  Bytes, float32: the streams read and written
    once forward (one fused pass could compute the projection, the mean
    square and the branch input from one read, and the merge writes the new
    streams), read twice and written once backward (the old streams for the
    coefficients' gradients, the new streams' gradient, the old streams'
    gradient written), phi read once."""
    tokens, layers = mix["seq_len"], cfg["num_hidden_layers"] \
        + cfg["num_nextn_predict_layers"]
    stream = 4.0 * tokens * cfg["hc_mult"] * cfg["hidden_size"]
    mixers = 2 * layers
    return (3.0 * mixers * tokens * 2 * _mixer_matrix(cfg),
            mixers * (5 * stream + 4.0 * _mixer_matrix(cfg)))


def flops_per_sample(cfg, mix):
    """Forward + backward FLOPs one sequence requires of this share (2 per
    multiply-add, backward = 2 x forward): the latent projections, the
    mixers' projections, dense FFN, router and shared expert for every
    token, the routed experts at the deployment's mean share (top_k * held /
    all experts a token), attention over the unmasked pairs at its two
    widths, the head over the held rows (twice with an MTP module, whose
    joining projection counts too); nothing recomputed, nothing
    elementwise."""
    s, h = mix["seq_len"], cfg["hidden_size"]
    common = _attention_matrices(cfg) + 2 * _mixer_matrix(cfg)
    dense_layer = common + 3 * h * cfg["intermediate_size"]
    sparse_layer = common + h * cfg["published"]["n_routed_experts"] \
        + (cfg["n_shared_experts"] + _held_rows_per_token(cfg)) * _expert(cfg)
    dense_layers = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    mtp = cfg["num_nextn_predict_layers"]
    matrices = dense_layers * dense_layer \
        + (cfg["num_hidden_layers"] - dense_layers + mtp) * sparse_layer \
        + (1 + mtp) * h * cfg["vocab_size"] + mtp * 2 * h * h
    forward = 2.0 * s * matrices + attention_flops_per_sample(cfg, mix)
    return 3.0 * forward


def bytes_per_step(cfg, mix, batch):
    """HBM bytes one training step of ``batch`` sequences on one chip cannot
    avoid, by the BERT configuration's convention: 40 B a parameter
    (parameters read in forward and backward as stored, gradients written and
    read once, Adam reads and writes parameter and both moments), the
    mixers' float32 stream traffic, what backward needs of each layer's
    branches without recomputing, written once and read once in bfloat16
    (both block norms' outputs, both latents, q, k, v, the attention output,
    the FFN's gate, up and product at the mean rows a token sends here), the
    head's input and its float32 logits."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    tokens = batch * mix["seq_len"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attention = cfg["q_lora_rank"] + cfg["kv_lora_rank"] \
        + heads * (2 * qk + 2 * cfg["v_head_dim"])
    dense_layers = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    sparse = cfg["num_hidden_layers"] - dense_layers \
        + cfg["num_nextn_predict_layers"]
    rows = cfg["n_shared_experts"] + _held_rows_per_token(cfg)
    per_token = (dense_layers + sparse) * (2 * h + attention) \
        + dense_layers * 3 * cfg["intermediate_size"] \
        + sparse * rows * (h + 3 * cfg["moe_intermediate_size"])
    acts = 2 * 2 * tokens * per_token
    head = (1 + cfg["num_nextn_predict_layers"]) * 2 * tokens \
        * (2 * h + 4 * cfg["vocab_size"])
    mixers = batch * hyper_connection_flops_and_bytes_per_sample(cfg, mix)[1]
    return 40.0 * param_count(cfg) + mixers + acts + head
