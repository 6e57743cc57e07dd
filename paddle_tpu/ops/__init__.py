"""Op lowering library — importing this package registers every op.

The registry (registry.py) is the analog of the reference's static-init
REGISTER_OPERATOR tables (paddle/fluid/framework/op_registry.h).
"""
from . import registry
from .registry import register_op, get_op, has_op, all_ops, LoweringContext

from . import math            # noqa: F401  elementwise/activation/matmul
from . import manipulation    # noqa: F401  reshape/gather/creation
from . import reduction       # noqa: F401  reductions/topk/sort
from . import nn_ops          # noqa: F401  conv/pool/norm/dropout
from . import loss_ops        # noqa: F401  losses/metrics
from . import random_ops      # noqa: F401  RNG ops
from . import optimizer_ops   # noqa: F401  optimizer updates + AMP
from . import collective_ops  # noqa: F401  ICI collectives
from . import attention       # noqa: F401  fused attention (Pallas/XLA)
from . import ctr_ops         # noqa: F401  CTR/ads ops (qingshui family)
from . import quant_ops       # noqa: F401  fake-quant / dequant (QAT, PTQ)
from . import rnn_ops         # noqa: F401  lstm/gru/cudnn_lstm scans
from . import nlp_ops         # noqa: F401  CRF/CTC/beam-search/NCE
from . import detection_ops   # noqa: F401  RoI/anchor/proposal/deformable
from . import misc_ops        # noqa: F401  optimizer variants + stragglers
from . import sequence_extra  # noqa: F401  sequence_conv/pad/slice/...
from . import plumbing_ops    # noqa: F401  tensor arrays/LoD/queues/save-load
from . import fused_extra_ops # noqa: F401  nn tail + fused compositions
from . import catalog_tail_ops # noqa: F401  fc/py_func/rnn/detection tail
from . import decoder_ops     # noqa: F401  rms_norm/rotary/sparse experts
from . import sparse_attention  # noqa: F401  indexer / selection / its loss
from . import selective_scan  # noqa: F401  state-space scan, causal conv

# stamp per-op exclusion reasons onto non-differentiable registrations
# (test_op_grads_auto.py enforces full coverage of the audit)
from .nondiff_reasons import apply_reasons as _apply_nondiff_reasons
_apply_nondiff_reasons()

def builtin_ops():
    """The framework's op catalog: everything registered except user
    custom-op plugins, which load_op_library marks OpDef.custom and the
    catalog/grad-audit sweeps exclude."""
    from .registry import _OP_REGISTRY
    return frozenset(t for t, d in _OP_REGISTRY.items() if not d.custom)
