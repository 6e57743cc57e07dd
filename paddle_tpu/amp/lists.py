"""AMP op lists (reference python/paddle/fluid/contrib/mixed_precision/
fp16_lists.py:28-39 black/white lists, adapted bf16-first for TPU MXU).

Audited against the op registry (ops/registry.py): every registered op in
the matmul/conv family — the ops whose lowering is MXU-bound — must be
classified white (bf16 compute), black (fp32 compute), or explicitly
fp32-fallback.  `unclassified_family_ops()` names the stragglers; the
amp_bf16 pass treats them as fp32 with a one-shot trace warning instead
of a silent skip, and tests/test_amp_plane.py keeps the set empty.
"""
import re

# white: consume bf16, MXU systolic-array path; fp32 accumulation rides
# the lowerings' preferred_element_type (ops/math.py) / XLA's bf16-conv
# f32 accumulator (ops/nn_ops.py).
WHITE_OPS = {
    "matmul", "matmul_v2", "mul", "bmm", "mv", "conv2d",
    "depthwise_conv2d", "conv2d_transpose", "conv3d", "conv3d_transpose",
    "conv_fusion", "fc", "batch_fc", "scaled_fc", "multihead_matmul",
    "fused_multihead_attention", "var_conv_2d", "sequence_conv",
    "row_conv",
    # sparse experts (ops/decoder_ops.py): the grouped matmuls take bf16
    # operands with float32 accumulation, and the rows they read are
    # gathered in bf16 (half the bytes of the largest buffer of the layer)
    # the combine gathers the experts' bf16 rows and sums them in float32
    # under the routing weights, which stay float32 (KEEP_FP32_SLOTS)
    "moe_grouped_matmul", "moe_dispatch", "moe_combine",
    # attention over a learned key set (ops/sparse_attention.py): the index
    # scores take bf16 queries and keys with float32 accumulation (the
    # published models run their indexers in fp8), and the indexer's loss
    # the attention's own bf16 q and k; the ReLU, the per-head weights, the
    # sum over heads, the selection and the loss's softmaxes are float32
    # inside the lowerings.  Both ops see the same casts, so the loss scores
    # the pairs exactly as the selection did.  (The loss's float32 output is
    # summed by `sum`, a black op.)
    "sparse_attention_index", "sparse_attention_index_loss",
    # a decoder's head with its loss (ops/decoder_ops.py): the matmul as
    # `mul` runs it, bf16 operands and float32 logits; the log-sum-exp and
    # the loss are float32 inside the lowering
    "linear_cross_entropy",
    # the depthwise causal convolution before a selective scan (ops/
    # selective_scan.py): bf16 in and out as the projection around it, its
    # four products summed in float32 inside the lowering
    "causal_conv1d",
}
# input slots of white ops that keep float32 all the same: small operands
# whose precision decides the result
KEEP_FP32_SLOTS = {"moe_combine": ("TopKWeight",),
                   # positions up to the context length: bfloat16 holds
                   # whole numbers up to 256 only (at 16384 it rounds to
                   # multiples of 64 and every angle is wrong)
                   "rotary_embedding": ("Positions",),
                   "sparse_attention_index": ("W",),
                   "sparse_attention_index_loss": ("W",)}
BLACK_OPS = {
    "softmax", "log_softmax", "cross_entropy", "softmax_with_cross_entropy",
    "reduce_mean", "reduce_sum", "mean", "sum", "exp",
    "log", "rsqrt", "sqrt", "square", "sigmoid_cross_entropy_with_logits",
    "cumsum", "p_norm", "l2_normalize", "softplus",
    # a decoder's pre-norm feeds the router, whose eighth-best logit a bf16
    # input would move; the router's own matmul, top-k and softmax are
    # float32
    "rms_norm", "moe_route",
    # the residual path of several streams: the streams, the token's mixing
    # coefficients and the 20 normalisations behind them stay float32 (a
    # branch's bf16 output is cast as it is merged in)
    "hyper_connection_mix", "hyper_connection_merge",
    # a state-space layer's recurrence: 4096 steps of exp(dt A) compound in
    # the state, and dt (a softplus, black already) sits in an exponent;
    # state, decay and sums are float32 inside the lowering whatever
    # arrives, and black keeps bf16 from being the operands' precision
    "selective_scan",
}
# matmul/conv-family ops deliberately kept fp32: recurrent cells whose
# hidden-state chains drift in bf16, int8-quantized kernels, gather-heavy
# deformable/tree variants, and fusions that embed a norm (stats must be
# f32) — classified so the registry audit can tell "decided fp32" from
# "nobody looked".
FP32_FAMILY_OPS = {
    "attention_lstm", "fused_embedding_fc_lstm", "multi_gru",
    # paged decode attention: the op's contract is bit-identity with the
    # unfused gather+softmax chain (serving exactness gate) — bf16 would
    # break it, and decode is latency/HBM-bound, not MXU-bound
    "paged_attention",
    "scaled_int8fc", "fused_fc_elementwise_layernorm", "deformable_conv",
    "deformable_conv_v1", "conv_shift", "rank_attention",
    "fusion_conv_inception", "fusion_repeated_fc_relu",
    "fusion_seqconv_eltadd_relu", "fusion_seqexpand_concat_fc",
    "tree_conv", "dot",
}
# NOTE: the norm family (batch/sync_batch/layer/instance/group_norm) is
# deliberately GRAY, not black: their lowerings compute statistics in f32
# INTERNALLY and cast back to the input dtype, so black-listing them only
# forced a full bf16->f32->bf16 round trip of every activation at every
# conv+BN / matmul+LN boundary.  Measured on ResNet-50 v5e: the step was
# HBM-bound at ~800GB/s with 59GB/step of traffic largely from those
# boundary converts.
# everything else: gray — runs in whatever dtype arrives

# names that MATCH the family regex but are not matmul/conv compute
# (elementwise mul, NMS "multi", comm plumbing, accumulators)
_FAMILY_FALSE_POSITIVES = {
    "elementwise_mul", "multiclass_nms", "multiclass_nms2", "multinomial",
    "multiplex", "multi_gru", "slice_multi_tensor", "average_accumulates",
    "c_comm_init_multitrainer",
}

_FAMILY_RE = re.compile(r"matmul|conv|bmm|attention|fc|gemm|^mul$|^mv$"
                        r"|^dot$|^multi")


def is_mxu_family(op_type: str) -> bool:
    """Does this op name claim matmul/conv-family compute?"""
    return (bool(_FAMILY_RE.search(op_type))
            and op_type not in _FAMILY_FALSE_POSITIVES)


def classify(op_type: str, white=None, black=None) -> str:
    """'white' | 'black' | 'fp32' | 'gray' under optional custom lists.
    Custom lists EXTEND the defaults and WIN over them — a custom white
    entry moves an op out of the default black list (reference
    fp16_lists semantics: custom_white_list overrides), and custom black
    wins custom-white overlaps.  This is the single source of truth for
    the taxonomy: AmpBf16Pass delegates here, so
    BuildStrategy.amp_custom_white_list/_black_list get exactly these
    semantics."""
    custom_black = set(black or ())
    if op_type in custom_black:
        return "black"
    if op_type in set(white or ()) - custom_black:
        return "white"
    if op_type in BLACK_OPS:
        return "black"
    if op_type in WHITE_OPS:
        return "white"
    if op_type in FP32_FAMILY_OPS:
        return "fp32"
    if is_mxu_family(op_type):
        return "unclassified"      # family op nobody classified — caller
    return "gray"                  # warns once and runs it fp32


def unclassified_family_ops():
    """Registered matmul/conv-family ops missing from every list — the
    registry-audit surface (kept empty by tests/test_amp_plane.py)."""
    from ..ops.registry import all_ops
    return sorted(op for op in all_ops()
                  if is_mxu_family(op)
                  and op not in WHITE_OPS
                  and op not in BLACK_OPS
                  and op not in FP32_FAMILY_OPS)
