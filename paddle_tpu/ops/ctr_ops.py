"""CTR / ads ops (the qingshui/PaddleBox fork's flagship op family).

Reference (SURVEY §A.1 "CTR/ads" + §A.4): operators/cvm_op.{cc,h},
operators/fused/fused_seqpool_cvm_op.cc, operators/batch_fc_op.cc,
operators/rank_attention_op.cc, operators/scaled_fc_op.cc,
operators/cross_norm_hadamard_op.cc, operators/filter_by_instag_op.cc,
operators/hash_op.cc, operators/pyramid_hash_op.cc, operators/tdm_child_op.cc,
operators/tdm_sampler_op.cc, operators/shuffle_batch_op.cc (already in
random_ops), operators/pull_box_sparse_op.cc, operators/push_dense_op.cc.

TPU-native design: the ragged LoD batches of the reference become padded
[B, T, D] + Length tensors (sequence_lod.py convention); the GPU scatter
kernels of BoxPS pull/push become host-side table lookups staged through the
PS tier (distributed/ps) — the device-side ops here are the dense compute.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register_op, wide_int


def _x(ins, slot="X"):
    return ins[slot][0]


# --- CVM (continuous value model: show/click statistics) --------------------
def _cvm_fwd(x, use_cvm):
    # cvm_op.h CvmComputeKernel: col0=log(show+1), col1=log(click+1)-col0;
    # use_cvm=False drops the two leading statistic columns.
    if use_cvm:
        c0 = jnp.log(x[:, 0:1] + 1.0)
        c1 = jnp.log(x[:, 1:2] + 1.0) - c0
        return jnp.concatenate([c0, c1, x[:, 2:]], axis=1)
    return x[:, 2:]


# --- fused sparse embedding: gather + pool in one op ------------------------
# Reference: operators/fused/fused_embedding_seq_pool_op.cc (the PaddleBox
# CTR hot path).  Produced by the kernel-tier fuse_sparse_embedding pass
# (fluid/passes/kernel_tier.py) from lookup_table(+sequence_pool/reduce_sum)
# chains; on TPU, for an f32 table that fits VMEM
# (pallas_kernels.fused_embedding_pool_supported), the lowering is the Pallas
# fused gather+pool kernel with a fused scatter-add (segment-sum) gradient;
# otherwise an XLA take + masked sum that mirrors the unfused chain
# bit-for-bit.

def _emb_pool_prep(ins, attrs):
    """(w, ids, wgt, denom-applied weights): the per-(row, position)
    contribution weight folds padding_idx zeroing, the Length mask, and
    mean-pool division into one [B, S] tensor."""
    w, ids = _x(ins, "W"), _x(ins, "Ids").astype(jnp.int32)
    if attrs.get("squeeze_ids") and ids.shape[-1] == 1:
        ids = ids.reshape(ids.shape[:-1])     # lookup_table [.., 1] squeeze
    b, s = ids.shape
    pool = str(attrs.get("pooltype", "SUM")).upper()
    padding_idx = attrs.get("padding_idx", -1)
    length = ins["Length"][0] if ins.get("Length") else None
    if length is not None:
        wgt = (jnp.arange(s)[None, :]
               < length.reshape(-1, 1)).astype(w.dtype)
        denom = jnp.maximum(length.reshape(-1, 1).astype(w.dtype), 1)
    else:
        wgt = jnp.ones((b, s), w.dtype)
        denom = jnp.full((b, 1), float(s), w.dtype)
    if padding_idx is not None and padding_idx >= 0:
        wgt = wgt * (ids != padding_idx).astype(w.dtype)
    if pool == "AVERAGE":
        wgt = wgt / denom
    return w, ids, wgt


def _fused_embedding_pool_grad(ins, outs, out_grads, attrs, ctx):
    """Fused gradient: dW via one weighted scatter-add — the SelectedRows
    sparse grad of the reference's fused_embedding_seq_pool, as a dense
    segment-sum.  Never materialises the [B, S, D] per-position cotangent."""
    w, ids, wgt = _emb_pool_prep(ins, attrs)
    g = out_grads.get("Out")
    if g is None:
        return {"W": [jnp.zeros_like(w)]}
    g = g.astype(w.dtype)
    vocab = w.shape[0]
    if ctx.pallas_ok():
        from .pallas_kernels import (embedding_pool_grad_tpu,
                                     fused_embedding_pool_supported)
        if fused_embedding_pool_supported(w, ids):
            return {"W": [embedding_pool_grad_tpu(g, ids, wgt, vocab)]}
    rows = g[:, None, :] * wgt[:, :, None]          # [B, S, D]
    dw = jax.ops.segment_sum(rows.reshape(-1, g.shape[-1]),
                             ids.reshape(-1), num_segments=vocab)
    return {"W": [dw.astype(w.dtype)]}


@register_op("fused_embedding_pool", nondiff_inputs=("Ids", "Length"),
             custom_grad=_fused_embedding_pool_grad)
def _fused_embedding_pool(ins, attrs, ctx):
    w, ids, wgt = _emb_pool_prep(ins, attrs)
    if ctx.pallas_ok():
        from .pallas_kernels import (fused_embedding_pool_supported,
                                     fused_embedding_pool_tpu)
        if fused_embedding_pool_supported(w, ids):
            return {"Out": [fused_embedding_pool_tpu(w, ids, wgt)]}
    # the XLA lowering mirrors the unfused lookup_table + sequence_pool
    # chain (take -> zero padding rows -> masked sum); for sum pooling the
    # elementwise structure is identical, so a kernel-tier rewrite matches
    # the unrewritten program bit-for-bit (mean folds the divide into the
    # weights — allclose, one rounding step apart)
    gathered = jnp.take(w, ids, axis=0)
    padding_idx = attrs.get("padding_idx", -1)
    if padding_idx is not None and padding_idx >= 0:
        gathered = jnp.where((ids == padding_idx)[..., None], 0.0, gathered)
    return {"Out": [jnp.sum(gathered * wgt[..., None], axis=1)]}


@register_op("cvm", nondiff_inputs=("CVM",))
def _cvm(ins, attrs, ctx):
    return {"Y": [_cvm_fwd(_x(ins), attrs.get("use_cvm", True))]}


@register_op("continuous_value_model", nondiff_inputs=("CVM",))
def _continuous_value_model(ins, attrs, ctx):
    return {"Y": [_cvm_fwd(_x(ins), attrs.get("use_cvm", True))]}


@register_op("fused_seqpool_cvm", nondiff_inputs=("CVM", "Length"))
def _fused_seqpool_cvm(ins, attrs, ctx):
    """SUM-pool each padded slot sequence then apply CVM.

    Reference fused_seqpool_cvm_op.cc: a vector of LoD slot tensors is pooled
    and CVM-transformed in one kernel.  Padded layout: every X input is
    [B, T, D] with a shared Length [B]; outputs are [B, D(-2)].
    """
    use_cvm = attrs.get("use_cvm", True)
    pad_value = attrs.get("pad_value", 0.0)
    length = ins["Length"][0] if ins.get("Length") else None
    outs = []
    for x in ins["X"]:
        if length is not None:
            m = (jnp.arange(x.shape[1])[None, :] <
                 length.reshape(-1, 1)).astype(x.dtype)[..., None]
            pooled = jnp.sum(x * m, axis=1)
            # empty sequences pool to pad_value (fused_seqpool_cvm_op.cc)
            empty = (length.reshape(-1, 1) == 0)
            pooled = jnp.where(empty, pad_value, pooled)
        else:
            pooled = jnp.sum(x, axis=1)
        outs.append(_cvm_fwd(pooled, use_cvm))
    return {"Out": outs}


# --- batched / scaled FC -----------------------------------------------------
@register_op("batch_fc")
def _batch_fc(ins, attrs, ctx):
    """Per-slot batched FC (batch_fc_op.cc): Input [S, N, in], W [S, in, out],
    Bias [S, out] -> relu(Input @ W + Bias)."""
    x, w, b = _x(ins, "Input"), _x(ins, "W"), _x(ins, "Bias")
    out = jnp.einsum("sni,sio->sno", x, w,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    out = out + b[:, None, :]
    return {"Out": [jax.nn.relu(out)]}


@register_op("scaled_fc")
def _scaled_fc(ins, attrs, ctx):
    """scaled_fc_op.cc: inputs and bias are pre-scaled (int8-friendly CTR
    trick): out = relu((x*input_scale) @ w + b*bias_scale)."""
    x, w, b = _x(ins, "Input"), _x(ins, "W"), _x(ins, "Bias")
    isf = attrs.get("input_scale_factor", 1.0)
    bsf = attrs.get("bias_scale_factor", 1.0)
    out = (x * isf) @ w + b * bsf
    return {"Out": [jax.nn.relu(out)]}


@register_op("rank_attention", nondiff_inputs=("RankOffset",))
def _rank_attention(ins, attrs, ctx):
    """rank_attention_op.cc: every instance picks per-(its-rank, other-rank)
    parameter blocks from RankParam and contracts its features against them.

    X: [N, x_dim]; RankOffset: [N, 1+2*max_rank] int — col0 = ins rank
    (1-based, 0 = absent), then (other_rank, param_row_index) pairs;
    RankParam: [max_size, x_dim * para_col] — block row per index.
    Out: [N, para_col] = mean over present pairs of X[i] @ block.
    """
    x = _x(ins)
    rank_offset = ins["RankOffset"][0].astype(jnp.int32)
    param = _x(ins, "RankParam")
    max_rank = attrs.get("MaxRank", 3)
    n, x_dim = x.shape
    para_col = param.shape[1] // x_dim
    blocks = param.reshape(param.shape[0], x_dim, para_col)

    idx = rank_offset[:, 2::2]                      # [N, max_rank] block rows
    present = (rank_offset[:, 1::2] >= 0) & (rank_offset[:, 0:1] > 0)
    safe = jnp.maximum(idx, 0)
    sel = blocks[safe]                              # [N, max_rank, x_dim, pc]
    contrib = jnp.einsum("ni,nrip->nrp", x, sel,
                         preferred_element_type=jnp.float32)
    w = present.astype(contrib.dtype)[..., None]
    out = jnp.sum(contrib * w, axis=1) / jnp.maximum(
        jnp.sum(w, axis=1), 1.0)
    return {"Out": [out.astype(x.dtype)],
            "InputHelp": [x], "ParamHelp": [param],
            "InsRank": [rank_offset[:, 0:1].astype(x.dtype)]}


@register_op("cross_norm_hadamard")
def _cross_norm_hadamard(ins, attrs, ctx):
    """cross_norm_hadamard_op.cc: paired fields [a, b] of width fields_num ->
    concat(a, b, a*b) per pair, then (x-mean)/scale normalization using
    SummaryInput running stats."""
    x = _x(ins, "Input")
    summary = _x(ins, "SummaryInput")
    fields = attrs.get("fields_num", 1)
    embed = attrs.get("embed_dim", x.shape[1] // (2 * fields))
    pairs = x.reshape(x.shape[0], fields, 2, embed)
    a, b = pairs[:, :, 0], pairs[:, :, 1]
    had = jnp.concatenate([a, b, a * b], axis=-1)   # [N, fields, 3*embed]
    out = had.reshape(x.shape[0], -1)
    mean, scale = summary[0], jnp.maximum(summary[1], 1e-6)
    return {"Out": [(out - mean) / scale],
            "CudaMeans": [mean], "CudaScales": [scale]}


# --- instag filtering --------------------------------------------------------
@register_op("filter_by_instag",
             nondiff_inputs=("Ins_tag", "Filter_tag"), differentiable=False)
def _filter_by_instag(ins, attrs, ctx):
    """filter_by_instag_op.cc: keep rows whose tag set intersects filter tags.
    Static-shape version: rows failing the filter are zeroed and LossWeight=0
    (out_val_if_empty analog), instead of compacting the batch — the mask is
    what downstream loss-weighting consumes."""
    rows = ins["Ins"][0]
    tags = ins["Ins_tag"][0]          # [N, T] padded tag ids (-1 pad)
    filt = ins["Filter_tag"][0]       # [F]
    if tags.ndim == 1:
        tags = tags[:, None]
    hit = (tags[:, :, None] == filt[None, None, :]).any(axis=(1, 2))
    w = hit.astype(rows.dtype)
    shaped = w.reshape((-1,) + (1,) * (rows.ndim - 1))
    return {"Out": [rows * shaped],
            "LossWeight": [w.reshape(-1, 1)],
            "IndexMap": [jnp.stack([jnp.arange(rows.shape[0])] * 2, 1)]}


# --- hashing -----------------------------------------------------------------
def _xxhash_like(x, mod, seed):
    import jax
    if jax.config.jax_enable_x64:
        # mix the high word first so full 64-bit ids keep their entropy
        xu = x.astype(jnp.uint64)
        lo = (xu & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = (xu >> jnp.uint64(32)).astype(jnp.uint32)
        lo = lo ^ (hi * jnp.uint32(2246822519))
    else:
        # x64 off: ids are at most 32-bit on device (the executor refuses
        # truncating int64 feeds), so hash the one word we actually have
        lo = x.astype(jnp.uint32)
    h = lo * jnp.uint32(2654435761) + jnp.uint32(seed)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(2246822519)
    h = h ^ (h >> 13)
    return (h % jnp.uint32(mod)).astype(wide_int())


@register_op("hash", differentiable=False)
def _hash(ins, attrs, ctx):
    """hash_op.cc: num_hash hashes of each int id row into [0, mod_by)."""
    x = _x(ins)
    num_hash = attrs.get("num_hash", 1)
    mod = attrs.get("mod_by", 1)
    outs = [_xxhash_like(x, mod, seed) for seed in range(num_hash)]
    return {"Out": [jnp.stack(outs, axis=-1)]}


@register_op("pyramid_hash", nondiff_inputs=("X",))
def _pyramid_hash(ins, attrs, ctx):
    """pyramid_hash_op.cc: hash n-gram windows of token ids into an embedding
    table (search-ads text matching).  Padded [B, T] ids; sums the embeddings
    of all (space_len) n-grams per sequence."""
    x = _x(ins).astype(wide_int())
    w = _x(ins, "W")
    num_emb = attrs.get("num_emb", w.shape[1])
    space_len = attrs.get("space_len", w.shape[0])
    pyramid_layer = attrs.get("pyramid_layer", 2)
    b, t = x.shape[:2]
    acc = jnp.zeros((b, num_emb), w.dtype)
    for n in range(2, 2 + pyramid_layer):
        if t < n:
            break
        for s in range(t - n + 1):
            gram = x[:, s:s + n]
            h = jnp.sum(gram * (jnp.arange(n) + 1)[None, :], axis=1)
            idx = (h % space_len).astype(jnp.int32)
            acc = acc + w[idx][:, :num_emb]
    return {"Out": [acc]}


# --- TDM (tree-based deep match) --------------------------------------------
@register_op("tdm_child", nondiff_inputs=("X", "TreeInfo"),
             differentiable=False)
def _tdm_child(ins, attrs, ctx):
    """tdm_child_op.cc: look up each node's children in the TreeInfo table.
    TreeInfo rows: [item_id, layer_id, parent_id, child_0..child_{n-1}]."""
    x = _x(ins).astype(jnp.int32)
    tree = ins["TreeInfo"][0].astype(jnp.int32)
    child_nums = attrs.get("child_nums", tree.shape[1] - 3)
    children = tree[:, 3:3 + child_nums]
    out = children[x.reshape(-1)].reshape(x.shape + (child_nums,))
    leaf = (out == 0).astype(jnp.int32)
    return {"Child": [out], "LeafMask": [1 - leaf]}


@register_op("tdm_sampler", nondiff_inputs=("X", "Travel", "Layer"),
             differentiable=False, stateful_rng=True)
def _tdm_sampler(ins, attrs, ctx):
    """tdm_sampler_op.cc: for each item, emit its travel path node per tree
    layer plus `neg_samples_num_list[i]` negatives sampled from that layer."""
    x = _x(ins).astype(jnp.int32).reshape(-1)
    travel = ins["Travel"][0].astype(jnp.int32)     # [n_items, n_layers]
    layer = ins["Layer"][0].astype(jnp.int32)       # [n_layers, width] padded
    negs = attrs.get("neg_samples_num_list", [1] * travel.shape[1])
    n = x.shape[0]
    outs, labels, masks = [], [], []
    key = ctx.key_for(attrs.get("op_seed", attrs.get("seed", 0) or 0))
    for li in range(travel.shape[1]):
        pos = travel[x][:, li:li + 1]
        k = jax.random.fold_in(key, li)
        neg_idx = jax.random.randint(k, (n, negs[li]), 0, layer.shape[1])
        neg = layer[li][neg_idx]
        outs.append(jnp.concatenate([pos, neg], axis=1))
        labels.append(jnp.concatenate(
            [jnp.ones((n, 1), jnp.int32), jnp.zeros((n, negs[li]), jnp.int32)],
            axis=1))
        masks.append((outs[-1] != 0).astype(jnp.int32))
    out = jnp.concatenate(outs, axis=1)
    return {"Out": [out.reshape(n, -1, 1)],
            "Labels": [jnp.concatenate(labels, 1).reshape(n, -1, 1)],
            "Mask": [jnp.concatenate(masks, 1).reshape(n, -1, 1)]}


@register_op("store_q_value", differentiable=False)
def _store_q_value(ins, attrs, ctx):
    """store_q_value_op (qingshui): passthrough that snapshots Q values for
    the AucRunner — device side is identity; persistence happens host-side."""
    return {"Out": [ins["Input"][0]]}


# --- sparse PS pull/push (device-side dense halves) --------------------------
@register_op("pull_box_sparse", nondiff_inputs=("Ids",))
def _pull_box_sparse(ins, attrs, ctx):
    """pull_box_sparse_op.cc device half: gather rows of the (HBM-cached)
    table for each id tensor.  The host BoxPS tier keeps W fresh between
    passes (distributed/ps HBM cache — BoxWrapper::PullSparse analog)."""
    w = ins["W"][0]
    outs = [w[ids.astype(jnp.int32)] for ids in ins["Ids"]]
    return {"Out": outs}


@register_op("push_box_sparse", differentiable=False)
def _push_box_sparse(ins, attrs, ctx):
    """Grad-side of pull_box_sparse: scatter-add grads into the table slot.
    Emitted explicitly by the PS meta-optimizer; returns the dense delta."""
    w = ins["W"][0]
    delta = jnp.zeros_like(w)
    for ids, g in zip(ins["Ids"], ins["Grad"]):
        delta = delta.at[ids.astype(jnp.int32)].add(g.astype(w.dtype))
    return {"Out": [delta]}


@register_op("pull_sparse", nondiff_inputs=("Ids",))
def _pull_sparse(ins, attrs, ctx):
    w = ins["W"][0]
    outs = [w[ids.astype(jnp.int32)] for ids in ins["Ids"]]
    return {"Out": outs}


@register_op("push_dense", differentiable=False)
def _push_dense(ins, attrs, ctx):
    """push_dense_op: device half is identity — the trainer runtime ships the
    grads to the PS (distributed/ps tables) after the step."""
    return {"Out": list(ins["Ids"]) if ins.get("Ids") else [ins["X"][0]]}


@register_op("merge_ids", nondiff_inputs=("Ids", "Rows"),
             differentiable=False)
def _merge_ids(ins, attrs, ctx):
    """merge_ids_op: re-interleave rows pulled from sharded tables back into
    the original id order (PS sharded-lookup plumbing)."""
    ids = ins["Ids"][0].astype(jnp.int32).reshape(-1)
    parts = ins["X"]
    n_shard = len(parts)
    dim = parts[0].shape[-1]
    stacked = jnp.concatenate(parts, axis=0)
    shard = ids % n_shard
    # position of each id within its shard, in arrival order
    offsets = jnp.zeros_like(ids)
    for s in range(n_shard):
        in_s = (shard == s).astype(jnp.int32)
        offsets = offsets + in_s * (jnp.cumsum(in_s) - 1)
    base = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(jnp.asarray([p.shape[0] for p in parts[:-1]],
                                jnp.int32))])
    return {"Out": [stacked[base[shard] + offsets].reshape(
        ids.shape + (dim,))]}


@register_op("ps_lookup_rows", nondiff_inputs=("Ids",))
def _ps_lookup_rows(ins, attrs, ctx):
    """Device half of a PS-served embedding lookup: `Rows` is the per-batch
    host feed of rows pulled for each (flattened) id position — the XLA
    analog of DownpourWorker FillSparseValue (downpour_worker.cc:183)
    writing pulled values into the lookup output.  The vjp w.r.t. Rows is
    exactly the per-position row gradient the trainer pushes back
    (downpour_worker.cc:765); padding_idx positions are zeroed so their
    pushed grad is zero.  Emitted by distributed/ps/program_pass.py."""
    rows = ins["Rows"][0]
    ids = ins["Ids"][0]
    if attrs.get("v1") and ids.shape and ids.shape[-1] == 1:
        ids = ids.reshape(ids.shape[:-1])   # lookup_table squeezes [.., 1]
    dim = rows.shape[-1]
    out = rows.reshape(tuple(ids.shape) + (dim,))
    pad = attrs.get("padding_idx", -1)
    if pad is not None and pad >= 0:
        out = jnp.where((ids == pad)[..., None], 0.0, out)
    return {"Out": [out]}


@register_op("data_norm",
             nondiff_inputs=("BatchSize", "BatchSum", "BatchSquareSum"),
             nondiff_outputs=("Means", "Scales", "BatchSizeOut",
                              "BatchSumOut", "BatchSquareSumOut"))
def _data_norm(ins, attrs, ctx):
    """CTR feature normalization with PERSISTABLE summary statistics
    (operators/data_norm_op.cc:292-303 forward; :650-698 stat
    accumulation).  Unlike batch_norm, the normalizer comes from the
    running summary (means = batch_sum/batch_size, scales =
    sqrt(batch_size/batch_square_sum)) and the backward treats it as a
    constant — d_x = d_y * scales falls out of the vjp because the stats
    are nondiff inputs.  TPU-native: the reference routes stat deltas
    through grad-op outputs + a PS summary accessor; here the op itself
    emits the decayed running update (summary_decay_rate) as write-back
    outputs, which the executor persists — one mechanism for single-chip
    and PS runs.  slot_dim > 0 replicates the show!=0 gating: instances
    whose slot's first element (the show count) is ~zero are skipped in
    the stat update (:655-663)."""
    x = ins["X"][0]
    bsize, bsum, bsq = (ins["BatchSize"][0], ins["BatchSum"][0],
                        ins["BatchSquareSum"][0])
    means = bsum / bsize
    scales = jnp.sqrt(bsize / bsq)
    y = (x - means) * scales
    if ins.get("ScaleW"):
        y = y * ins["ScaleW"][0] + ins["Bias"][0]
    outs = {"Y": [y], "Means": [means], "Scales": [scales]}
    if getattr(ctx, "is_test", False):
        return outs
    eps = attrs.get("epsilon", 1e-4)
    decay = attrs.get("summary_decay_rate", 0.9999999)
    slot_dim = int(attrs.get("slot_dim", -1))
    n, c = x.shape[0], x.shape[-1]
    if slot_dim > 0 and c % slot_dim == 0:
        xm = x.reshape(n, c // slot_dim, slot_dim)
        live = (jnp.abs(xm[:, :, 0]) > 1e-7)[..., None]      # show != 0
        cnt_s = live.sum(0).astype(x.dtype)                  # [slots, 1]
        cnt = jnp.broadcast_to(cnt_s, (c // slot_dim, slot_dim)).reshape(c)
        ssum = (xm * live).sum(0).reshape(c)
        ssq = (((xm - means.reshape(c // slot_dim, slot_dim)) ** 2)
               * live).sum(0).reshape(c)
        # per-batch normalization to size 1 (data_norm_op.cc:672-683)
        safe = jnp.maximum(cnt, 1.0)
        d_size = jnp.where(cnt >= 1, 1.0, 0.0)
        d_sum = jnp.where(cnt >= 1, ssum / safe, 0.0)
        d_sq = jnp.where(cnt >= 1, ssq / safe + cnt * eps, 0.0)
    else:
        d_size = jnp.full((c,), float(n), x.dtype)
        d_sum = x.reshape(-1, c).sum(0)
        d_sq = ((x - means) ** 2).reshape(-1, c).sum(0) + n * eps
    outs["BatchSizeOut"] = [decay * bsize + d_size]
    outs["BatchSumOut"] = [decay * bsum + d_sum]
    outs["BatchSquareSumOut"] = [decay * bsq + d_sq]
    return outs
