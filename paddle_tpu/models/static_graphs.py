"""Static-graph demo programs for the kernel tier.

The kernel-tier passes (fluid/passes/kernel_tier.py) rewrite *naive* op
chains — these builders spell BERT attention and the CTR embedding path
exactly the way plain fluid layers emit them (matmul → scale → +mask →
softmax → dropout → matmul; lookup_table_v2 → sequence_pool), so the
same programs serve as the rewrite targets for chip_smoke.py's Executor
leg, bench.py's demo legs and tests/test_kernel_tier.py.
Reference: the qingshui fork's BERT/ERNIE encoder and the PaddleBox
wide&deep CTR net (PAPER.md layers 2 and 6).
"""
from __future__ import annotations

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers as L


def _naive_attention(x, hidden, heads, mask=None, dropout=0.0):
    """One multi-head self-attention block in the head-split spelling
    BERT emits: fc → reshape2 → transpose2 per Q/K/V, then the naive
    score chain the fuse_attention pass matches."""
    dh = hidden // heads

    def split(t):
        t = L.reshape(t, [0, 0, heads, dh])
        return L.transpose(t, [0, 2, 1, 3])       # [B, H, T, dh]

    q = split(L.fc(x, hidden, num_flatten_dims=2))
    k = split(L.fc(x, hidden, num_flatten_dims=2))
    v = split(L.fc(x, hidden, num_flatten_dims=2))
    s = L.matmul(q, k, transpose_y=True)
    s = L.scale(s, scale=dh ** -0.5)
    if mask is not None:
        s = s + mask                              # additive [B,1,1,T] bias
    p = L.softmax(s)
    if dropout:
        p = L.dropout(p, dropout,
                      dropout_implementation="upscale_in_train")
    ctx = L.matmul(p, v)
    ctx = L.transpose(ctx, [0, 2, 1, 3])
    return L.reshape(ctx, [0, 0, hidden])


def build_bert_train_program(vocab=64, hidden=32, heads=4, seq=16,
                             layers=2, dropout=0.0, with_mask=True,
                             lr=1e-3):
    """BERT-shaped classifier over ``layers`` naive attention blocks +
    Adam.  Returns (main, startup, loss).  Feeds: ids [B, seq] int64,
    labels [B, 1] int64, and (with_mask) attn_mask [B, seq] float 1/0."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", [-1, seq], dtype="int64")
        labels = fluid.data("labels", [-1, 1], dtype="int64")
        mask = None
        if with_mask:
            am = fluid.data("attn_mask", [-1, seq])
            am = L.reshape(am, [0, 1, 1, seq])
            # (m - 1) * 10000: zeros where attended, -1e4 where padded
            mask = L.scale(am, scale=10000.0, bias=-1.0,
                           bias_after_scale=False)
        h = L.embedding(ids, size=[vocab, hidden])
        for _ in range(layers):
            h = _naive_attention(h, hidden, heads, mask=mask,
                                 dropout=dropout)
            h = L.fc(h, hidden, num_flatten_dims=2, act="relu")
        pooled = L.reduce_mean(h, dim=[1])
        logits = L.fc(pooled, 2)
        loss = L.mean(L.softmax_with_cross_entropy(logits, labels))
        fluid.optimizer.AdamOptimizer(lr).minimize(loss)
    return main, startup, loss


def build_ctr_train_program(slots=4, vocab=128, dim=16, seq=5, lr=0.05,
                            optimizer="adam"):
    """Wide&deep CTR net in the classic PaddleBox spelling: one
    lookup_table_v2 → sequence_pool(sum) chain per slot, concat with the
    dense features, fc tower + wide head.  Returns (main, startup,
    loss).  Feeds: ids_<i> [B, seq] int64 per slot, dense [B, 13],
    label [B, 1]."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        dense = fluid.data("dense", [-1, 13])
        label = fluid.data("label", [-1, 1])
        pooled = []
        for i in range(slots):
            ids = fluid.data(f"ids_{i}", [-1, seq], dtype="int64")
            emb = L.embedding(ids, size=[vocab, dim])
            pooled.append(L.sequence_pool(emb, "sum"))
        deep = L.concat(pooled + [dense], axis=1)
        h = L.fc(deep, 32, act="relu")
        wide = L.fc(dense, 1)
        logit = L.fc(h, 1) + wide
        loss = L.mean(L.sigmoid_cross_entropy_with_logits(logit, label))
        if optimizer == "momentum":
            fluid.optimizer.MomentumOptimizer(lr, 0.9).minimize(loss)
        else:
            fluid.optimizer.AdamOptimizer(lr).minimize(loss)
    return main, startup, loss


def bert_demo_feed(rng, batch=8, seq=16, vocab=64, with_mask=True):
    feed = {"ids": rng.randint(0, vocab, (batch, seq)).astype("int64"),
            "labels": rng.randint(0, 2, (batch, 1)).astype("int64")}
    if with_mask:
        m = (rng.rand(batch, seq) > 0.2).astype("float32")
        m[:, 0] = 1.0                  # never mask everything out
        feed["attn_mask"] = m
    return feed


def ctr_demo_feed(rng, batch=16, slots=4, vocab=128, seq=5):
    feed = {"dense": rng.randn(batch, 13).astype("float32"),
            "label": rng.randint(0, 2, (batch, 1)).astype("float32")}
    for i in range(slots):
        feed[f"ids_{i}"] = rng.randint(
            0, vocab, (batch, seq)).astype("int64")
    return feed
