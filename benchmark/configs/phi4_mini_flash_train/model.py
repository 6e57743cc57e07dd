"""Phi-4-mini-flash-reasoning (the SambaY decoder-hybrid-decoder of
arXiv:2507.06607 with differential attention, arXiv:2410.05258) as a fluid
training Program: the ``held_layers`` of its 32 layers and the held rows of
its vocabulary, spelled from ``fluid.layers``.

Per layer ``x <- x + Mixer_i(LN_1(x))`` then ``x <- x + MLP(LN_2(x))``
(LayerNorm with bias; ``MLP(u) = W_down(silu(g) * p)``, ``[g ; p] =
W_gate_up u``).  The mixer by PUBLISHED index ``i`` of N = 32 layers
(``layer_kind``): a Mamba-1 state-space layer (``fluid.layers.
selective_scan`` behind a causal depthwise convolution) on the even layers
of the first half, differential attention with a 512 window on the odd ones;
layer N/2 a Mamba layer that also hands on its scan output ``y`` (before the
gate) as the memory; layer N/2 + 1 differential attention without a window
that also hands on its keys and values; after them gated memory units on
the memory (even) and differential cross attention over layer N/2 + 1's keys
and values (odd).  Memory, keys and values are ordinary Program variables
read by later layers: ``append_backward`` sums what their readers send back.
Differential attention is ONE ``fused_multihead_attention`` call a layer: the
40 query heads are 20 pairs (q1, q2), the 20 key heads 10 pairs (k1, k2),
the 20 value heads 10 pairs carried side by side ``[v1 | v2]`` (128 wide);
the call's 40 heads are q1 of every pair, then q2 of every pair, over the
keys k1 of every pair, then k2, and the values ``[v1 | v2]`` twice: scores
over 64, values of 128, each softmax once.  No positional encoding.  The
head is tied: ``linear_cross_entropy(tied_to=embedding)``.  The residual
stream stays float32 under AMP.

Every parameter has a fixed name, so ``reference.py`` reads the same weights
from the scope; every op output of a mixer is named ``layer_<i>.ssm.…``,
``layer_<i>.attention.…`` or ``layer_<i>.gmu.…`` (its pre-norm included) and
of the MLP ``layer_<i>.mlp.…``, ``<i>`` the published index: that is how the
per-layer readers find their rows.

Also here, because they belong to this configuration: the parameters, the
operations and the bytes one training step requires, from its shapes, and
the operations and bytes of the attention calls and of the scans.
"""
from __future__ import annotations

import math

import numpy as np

def held_layers(cfg):
    """The published indices of the layers ``cfg`` holds, ascending."""
    held = cfg.get("held_layers")
    n = cfg["num_hidden_layers"]
    if held is None or len(held) != n:
        if n != cfg["published"]["num_hidden_layers"]:
            raise ValueError(f"held_layers {held} for {n} layers")
        held = range(n)
    return [int(i) for i in held]


def layer_kind(cfg, i):
    """The mixer of published layer ``i``: (kind, window, hands_on)."""
    n = cfg["published"]["num_hidden_layers"]
    if cfg["mb_per_layer"] != 2 or n % 2:
        raise ValueError("only mb_per_layer 2 over an even depth is spelled")
    half = n // 2
    if i % 2 == 0:
        return ("ssm" if i <= half else "gmu"), 0, i == half
    if i <= half + 1:
        return "attention", (cfg["sliding_window"] if i < half else 0), \
            i == half + 1
    return "cross", 0, False


def lambda_init(i):
    """Differential attention's ``lam0`` of published layer ``i``."""
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def ssm_sizes(cfg):
    """(d_inner, d_state, dt_rank, d_conv): Mamba-1's, under ``assumed``."""
    m = cfg["mamba"]
    return (m["expand"] * cfg["hidden_size"], m["d_state"], m["dt_rank"],
            m["d_conv"])


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def build(cfg, mix, train=True):
    """The Program for ``cfg`` (config.json) under ``mix`` (a traffic file).

    ``train=True``: forward, backward, Adam.  ``train=False``: forward and
    backward only, for the comparison with the reference; ``grads`` then maps
    parameter name -> gradient variable name.  Returns a dict with ``main``,
    ``startup``, ``loss``, ``grads``.
    """
    # absent in a tree before this configuration: fail at once
    from paddle_tpu.fluid.layers import selective_scan  # noqa: F401
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers as L
    from paddle_tpu.fluid.initializer import (ConstantInitializer,
                                              NormalInitializer,
                                              NumpyArrayInitializer,
                                              UniformInitializer)
    from paddle_tpu.fluid.param_attr import ParamAttr

    hidden, seq = cfg["hidden_size"], mix["seq_len"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = head_dim(cfg)
    di, ds, dt_rank, d_conv = ssm_sizes(cfg)
    eps = cfg["layer_norm_eps"]
    if cfg["hidden_act"] != "silu" or not cfg["tie_word_embeddings"] \
            or cfg["mlp_bias"] or cfg["lm_head_bias"] or cfg["embd_pdrop"] \
            or cfg["resid_pdrop"] or heads % 2 or kv_heads % 2 \
            or heads % kv_heads:
        raise ValueError("only the published spelling is built: silu gates, "
                         "a tied head, no MLP or head bias, no dropout, "
                         "heads in pairs")
    held = held_layers(cfg)
    half = cfg["published"]["num_hidden_layers"] // 2
    needs = {"gmu": half, "cross": half + 1}
    for i in held:
        producer = needs.get(layer_kind(cfg, i)[0])
        if producer is not None and producer not in held:
            raise ValueError(f"layer {i} reads what layer {producer} hands "
                             f"on, which is not held")

    def weight(name, std=cfg["initializer_range"]):
        return ParamAttr(name=name, initializer=NormalInitializer(0.0, std))

    def const(name, value):
        return ParamAttr(name=name, initializer=ConstantInitializer(value))

    def dense(x, size, name, bias=None):
        """``bias``: None (no bias), or its initializer's attr."""
        return L.fc(x, size, num_flatten_dims=2,
                    param_attr=weight(name + ".w"),
                    bias_attr=False if bias is None else bias, name=name)

    def zeros(name):
        return const(name + ".b", 0.0)

    def norm(x, param, name):
        return L.layer_norm(x, begin_norm_axis=2, epsilon=eps,
                            param_attr=const(param + ".scale", 1.0),
                            bias_attr=const(param + ".bias", 0.0), name=name)

    def parameter(name, shape, initializer):
        return L.create_parameter(shape, "float32", name=name,
                                  default_initializer=initializer)

    def ssm(h, pre, i, hands_on):
        """``pre`` is ``layer_<i>.ssm.``; returns (the mixer's output [B, S,
        hidden], the scan's output ``y`` before the gate or None)."""
        xs, z = L.split(dense(h, 2 * di, pre + "in_proj"), 2, dim=-1,
                        name=pre + "xz")
        xc = L.silu(L.causal_conv1d(
            xs, d_conv, param_attr=ParamAttr(
                name=pre + "conv.w", initializer=UniformInitializer(
                    -d_conv ** -0.5, d_conv ** -0.5)),
            bias_attr=const(pre + "conv.b", 0.0), name=pre + "conv"),
            name=pre + "conv_act")
        dl, b, c = L.split(dense(xc, dt_rank + 2 * ds, pre + "x_proj"),
                           [dt_rank, ds, ds], dim=-1, name=pre + "dl_b_c")
        # Mamba's start for the step size: the bias starts as log(step),
        # step log-uniform in dt_init; the startup program turns it into
        # the inverse softplus of step (below)
        dt = L.softplus(dense(dl, di, pre + "dt_proj", bias=ParamAttr(
            name=pre + "dt_proj.b", initializer=UniformInitializer(
                *(math.log(v) for v in cfg["mamba"]["dt_init"])))),
            name=pre + "dt")
        a = L.scale(L.exp(parameter(
            pre + "A_log", [di, ds], NumpyArrayInitializer(np.tile(np.log(
                np.arange(1, ds + 1, dtype="float32")), (di, 1)))),
            name=pre + "A_exp"), scale=-1.0, name=pre + "A")
        y = L.selective_scan(
            xc, dt, a, b, c,
            parameter(pre + "D", [di], ConstantInitializer(1.0)),
            gauges=f"layer_{i}", name=pre + "scan")
        gated = L.swiglu(z, y, name=pre + "gate")
        return dense(gated, hidden, pre + "out_proj"), \
            (y if hands_on else None)

    def pairs_first(x, n, width, name):
        """[B, S, n * width], head (pair p, half j) at p * 2 + j -> [B, n,
        S, width] with the first halves of every pair, then the second."""
        x = L.reshape(x, [0, 0, n // 2, 2, width], name=name + "_rows")
        x = L.transpose(x, [0, 3, 2, 1, 4], name=name + "_halves")
        return L.reshape(x, [0, n, seq, width], name=name + "_heads")

    def lam(pre, i):
        """exp(lq1 . lk1) - exp(lq2 . lk2) + lam0 [1], published on the
        device as ``diff_attention.layer_<i>.lambda``."""
        def dot(j):
            q, k = (parameter(f"{pre}lambda_{s}{j}", [dh], NormalInitializer(
                0.0, cfg["lambda_std"])) for s in "qk")
            return L.exp(L.reduce_sum(
                L.elementwise_mul(q, k, name=f"{pre}lambda_{j}_qk"),
                dim=[0], keep_dim=True, name=f"{pre}lambda_{j}_dot"),
                name=f"{pre}lambda_{j}_exp")
        value = L.scale(L.elementwise_sub(dot(1), dot(2),
                                          name=pre + "lambda_diff"),
                        bias=lambda_init(i), name=pre + "lambda")
        gauge = main.global_block().create_var(
            name=f"layer_{i}.lambda", shape=[1], dtype="float32",
            persistable=True, stop_gradient=True)
        main._hints.setdefault("device_counters", {})[gauge.name] = \
            "diff_attention." + gauge.name
        L.assign(value, output=gauge)
        return value

    def attend(q, k, v, pre, i, window):
        """q [B, heads, S, dh] (first halves, then second), k [B, kv, S,
        dh] likewise, v [B, kv, S, 2 dh] (``[v1 | v2]`` twice) -> the
        mixer's output [B, S, hidden]."""
        ctx = L.fused_multihead_attention(
            q, k, v, scale=dh ** -0.5, causal=True, window=window,
            name=pre + "kernel")
        a1, a2 = L.split(ctx, 2, dim=1, name=pre + "a1_a2")
        diff = L.elementwise_sub(
            L.cast(a1, "float32"),
            L.elementwise_mul(L.cast(a2, "float32"), lam(pre, i),
                              name=pre + "lambda_a2"),
            name=pre + "diff")
        normed = L.scale(
            L.rms_norm(diff, epsilon=eps, name=pre + "subln",
                       param_attr=const(pre + "subln.scale", 1.0)),
            scale=1.0 - lambda_init(i), name=pre + "subln_scaled")
        rows = L.reshape(L.transpose(normed, [0, 2, 1, 3], name=pre + "ctx"),
                         [0, 0, hidden], name=pre + "ctx_rows")
        return dense(rows, hidden, pre + "out_proj",
                     bias=zeros(pre + "out_proj"))

    def attention(h, pre, i, window, hands_on):
        """``pre`` is ``layer_<i>.attention.``; returns (the output, (k, v)
        as the kernel call reads them or None)."""
        q, k, v = L.split(
            dense(h, (heads + 2 * kv_heads) * dh, pre + "qkv",
                  bias=zeros(pre + "qkv")),
            [heads * dh, kv_heads * dh, kv_heads * dh], dim=-1,
            name=pre + "q_k_v")
        q = pairs_first(q, heads, dh, pre + "q")
        k = pairs_first(k, kv_heads, dh, pre + "k")
        # a pair's two value heads lie side by side already
        v = L.transpose(L.reshape(v, [0, 0, kv_heads // 2, 2 * dh],
                                  name=pre + "v_rows"),
                        [0, 2, 1, 3], name=pre + "v_pairs")
        v = L.concat([v, v], axis=1, name=pre + "v_heads")
        return attend(q, k, v, pre, i, window), \
            ((k, v) if hands_on else None)

    def cross(h, kv, pre, i):
        q = pairs_first(dense(h, heads * dh, pre + "q",
                              bias=zeros(pre + "q")), heads, dh, pre + "q")
        return attend(q, kv[0], kv[1], pre, i, 0)

    def gmu(h, memory, pre):
        gated = L.swiglu(dense(h, di, pre + "in_proj"), memory,
                         name=pre + "gate")
        return dense(gated, hidden, pre + "out_proj")

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        input_ids = fluid.data("input_ids", [-1, seq], dtype="int64")
        labels = fluid.data("labels", [-1, seq], dtype="int64")
        x = L.embedding(input_ids, [cfg["vocab_size"], hidden],
                        param_attr=weight("embed_tokens"))
        embedding = main.global_block().var("embed_tokens")
        memory = kv = None
        for i in held:
            kind, window, hands_on = layer_kind(cfg, i)
            pre = f"layer_{i}.{'attention' if kind == 'cross' else kind}."
            h = norm(x, f"layer_{i}.input_norm", pre + "norm")
            if kind == "ssm":
                branch, handed = ssm(h, pre, i, hands_on)
                memory = handed if hands_on else memory
            elif kind == "attention":
                branch, handed = attention(h, pre, i, window, hands_on)
                kv = handed if hands_on else kv
            elif kind == "gmu":
                branch = gmu(h, memory, pre)
            else:
                branch = cross(h, kv, pre, i)
            x = x + L.cast(branch, "float32")

            pre = f"layer_{i}.mlp."
            g, p = L.split(
                dense(norm(x, f"layer_{i}.post_mixer_norm", pre + "norm"),
                      2 * cfg["intermediate_size"], pre + "gate_up"),
                2, dim=-1, name=pre + "g_p")
            x = x + L.cast(dense(L.swiglu(g, p, name=pre + "gate"), hidden,
                                 pre + "down"), "float32")

        loss = L.mean(L.linear_cross_entropy(
            norm(x, "final_norm", "final_norm"), L.unsqueeze(labels, [2]),
            cfg["vocab_size"], tied_to=embedding, name="lm_head"))

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

    # b_dt = step + log(1 - e^-step), the inverse softplus of the step that
    # its initializer drew in the log domain
    with fluid.program_guard(startup, fluid.Program()):
        for i in held:
            if layer_kind(cfg, i)[0] == "ssm":
                b_dt = startup.global_block().var(f"layer_{i}.ssm.dt_proj.b")
                step = L.exp(b_dt)
                L.assign(step + L.log(L.scale(
                    L.exp(L.scale(step, scale=-1.0)), scale=-1.0, bias=1.0)),
                    output=b_dt)
    return {"main": main, "startup": startup, "loss": loss, "grads": grads}


# ---------------------------------------------------------------------------
# shapes functions
# ---------------------------------------------------------------------------

def _mixer_parameters(cfg, kind):
    """(matrix parameters, all parameters) of one mixer of ``kind``."""
    h, dh = cfg["hidden_size"], head_dim(cfg)
    di, ds, dt_rank, d_conv = ssm_sizes(cfg)
    q = cfg["num_attention_heads"] * dh
    kv = 2 * cfg["num_key_value_heads"] * dh
    lambdas = 4 * dh + 2 * dh              # four vectors, the sub-norm scale
    if kind == "ssm":
        matrices = h * 2 * di + di * (dt_rank + 2 * ds) + dt_rank * di \
            + di * h
        return matrices, matrices + di * d_conv + di + di + di * ds + di
    if kind == "attention":
        matrices = h * (q + kv) + q * h
        return matrices, matrices + (q + kv) + h + lambdas
    if kind == "cross":
        matrices = h * q + q * h
        return matrices, matrices + q + h + lambdas
    return 2 * h * di, 2 * h * di          # gmu


def _count(cfg, which):
    h = cfg["hidden_size"]
    mlp = 3 * h * cfg["intermediate_size"]
    total = 0
    for i in held_layers(cfg):
        matrices, every = _mixer_parameters(cfg, layer_kind(cfg, i)[0])
        total += mlp + (matrices if which == "matrices"
                        else every + 4 * h)        # two norms with bias
    return total


def param_count(cfg):
    """Parameters of what ``cfg`` holds: its layers, the held rows of the
    tied embedding (counted once) and the final norm.  With the ``reduced``
    keys at their ``published`` values (and no ``held_layers``) it is the
    whole model's 3,852,562,944."""
    h = cfg["hidden_size"]
    return _count(cfg, "all") + cfg["vocab_size"] * h + 2 * h


def causal_pairs(seq, window=0):
    """(query, key) pairs of one sequence with j <= i and, with ``window``,
    i - j < window."""
    w = min(seq, window or seq)
    return w * (w + 1) // 2 + (seq - w) * w


def _attention_layers(cfg):
    """[(kind, window)] of the held layers that call the attention
    kernel."""
    return [(k, w) for k, w, _ in (layer_kind(cfg, i)
                                   for i in held_layers(cfg))
            if k in ("attention", "cross")]


def attention_flops_per_sample(cfg, mix):
    """Forward FLOPs of the attention calls of one sequence: for every
    allowed pair and each of the 40 query heads (20 pairs x two softmaxes) a
    score over ``head_dim`` 64 numbers and a value of 128 (``[v1 | v2]``):
    2 x 64 + 2 x 128 a head a pair.  Windowed pairs on the windowed layers,
    causal pairs on the full and the cross layers; a lowering that pads the
    score head to 128 or computes each softmax twice earns nothing."""
    dh = head_dim(cfg)
    per_pair = cfg["num_attention_heads"] * (2 * dh + 2 * 2 * dh)
    return float(per_pair * sum(causal_pairs(mix["seq_len"], w)
                                for _, w in _attention_layers(cfg)))


def attention_bytes_per_sample(cfg, mix):
    """HBM bytes the attention calls of one sequence cannot avoid, forward
    and backward, in bfloat16: forward reads q, k, ``[v1 | v2]`` and writes
    the output (128 a query head); backward reads those four and the
    output's gradient and writes the gradients of q, k and v.  The values
    are counted once a pair, not once a softmax; a cross layer reads the
    keys and values another layer made."""
    dh = head_dim(cfg)
    q = mix["seq_len"] * cfg["num_attention_heads"] * dh
    out = 2 * q
    kv = 2 * mix["seq_len"] * cfg["num_key_value_heads"] * dh
    return float(len(_attention_layers(cfg))
                 * 2.0 * ((q + kv + out) + (2 * q + 2 * kv + 2 * out)))


def ssm_scan_flops_and_bytes(cfg, mix):
    """(elementwise operations, HBM bytes) a step's selective scans require
    of one sequence over all held state-space layers.  Operations: 7 a
    (token, channel, state) forward (the decay's product and ``exp``, two
    products and a sum for the state, a product and a sum for the output)
    and twice that backward; they are vector-unit work, in no FLOP count of
    ``flops_per_sample``.  Bytes, in bfloat16: forward x and dt read, y
    written, B and C read; backward x, dt, B, C and y's gradient read, the
    gradients of x, dt, B and C written.  ``harness/peaks.py`` has no
    vector-unit peak, so a roofline from these is bound by the bytes."""
    di, ds, _, _ = ssm_sizes(cfg)
    layers = sum(layer_kind(cfg, i)[0] == "ssm" for i in held_layers(cfg))
    tokens = mix["seq_len"]
    ops = 3 * 7 * tokens * di * ds
    nbytes = 2.0 * tokens * ((3 * di + 2 * ds) + (3 * di + 2 * ds)
                             + (2 * di + 2 * ds))
    return float(layers * ops), float(layers * nbytes)


def flops_per_sample(cfg, mix):
    """Forward + backward FLOPs one sequence requires of this share (2 per
    multiply-add): every matrix of the held layers and the tied head over
    the held rows for every token, the depthwise convolutions, the attention
    calls over their allowed pairs (``attention_flops_per_sample``), each
    backward twice its forward.  Nothing recomputed; the scans' elementwise
    work (``ssm_scan_flops_and_bytes``) is not in here."""
    s = mix["seq_len"]
    di, _, _, d_conv = ssm_sizes(cfg)
    convs = sum(layer_kind(cfg, i)[0] == "ssm"
                for i in held_layers(cfg)) * 2 * d_conv * di
    per_token = 2 * (_count(cfg, "matrices")
                     + cfg["vocab_size"] * cfg["hidden_size"]) + convs
    return 3.0 * (s * per_token + attention_flops_per_sample(cfg, mix))


def bytes_per_step(cfg, mix, batch):
    """HBM bytes one training step of ``batch`` sequences on one chip cannot
    avoid, by the BERT configuration's convention: 40 B a parameter, plus
    what backward needs of each layer without recomputing, written once and
    read once: the float32 inputs of the two norms, their bfloat16 outputs,
    ``gate_up`` and the gated product; of a state-space mixer ``in_proj``'s
    output, the convolved input, dt (float32) and y (float32), B, C and the
    gated product; of an attention mixer q, k, v, the output and the
    sub-norm's input; of a gated memory unit its projection and product; of
    a cross layer q, the output and the sub-norm's input; the head's input
    and its float32 logits."""
    h, dh = cfg["hidden_size"], head_dim(cfg)
    di, ds, dt_rank, _ = ssm_sizes(cfg)
    tokens = batch * mix["seq_len"]
    q = cfg["num_attention_heads"] * dh
    kv = 2 * cfg["num_key_value_heads"] * dh
    mixer = {"ssm": 2 * (2 * di + di + dt_rank + 2 * ds + di) + 4 * 2 * di,
             "attention": 2 * (q + kv + 2 * q) + 4 * 2 * q,
             "cross": 2 * (q + 2 * q) + 4 * 2 * q,
             "gmu": 2 * 2 * di}
    per_token = sum(2 * 4 * h + 2 * 2 * h
                    + 2 * 3 * cfg["intermediate_size"]
                    + mixer[layer_kind(cfg, i)[0]]
                    for i in held_layers(cfg))
    head = tokens * (2 * h + 4 * cfg["vocab_size"])
    return 40.0 * param_count(cfg) + 2.0 * (tokens * per_token + head)
