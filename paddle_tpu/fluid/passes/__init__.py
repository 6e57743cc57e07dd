"""paddle_tpu.fluid.passes — the Program-IR pass framework.

Reference: paddle/fluid/framework/ir/ (Pass/PassRegistry over ir::Graph,
134 registered passes) + build_strategy.cc wiring knobs to pass lists.
Here passes rewrite the Program/Block IR in place through the
version-bumping Block mutators, CompiledProgram applies the
BuildStrategy-selected pipeline before the Executor caches the lowered
function, and every pass run lands in the observability plane
(``pass::<name>`` spans, ``pass.<name>.*`` counters).

See docs/passes.md for the catalog and how to register a custom pass.
"""
from .core import (Pass, PassContext, PassRegistry, PassPipeline,
                   register_pass, create_pass, get_pass_names)
from .pattern import (Pattern, PVar, POp, Match, PatternRewritePass)
from .graphviz import program_to_dot, dump_program
from . import builtin  # registers the built-in pass catalog
from . import amp      # registers amp_bf16 + prune_redundant_casts
from . import inference as inference_preset  # registers fold_batch_norm
from . import kernel_tier  # registers the Pallas kernel-tier passes
from .builtin import passes_for_build_strategy
from .amp import AmpBf16Pass, PruneRedundantCastsPass
from .inference import (FoldBatchNormPass, inference_passes,
                        INFERENCE_PASS_NAMES)
from .kernel_tier import FuseAttentionPass, FuseSparseEmbeddingPass

__all__ = [
    "Pass", "PassContext", "PassRegistry", "PassPipeline",
    "register_pass", "create_pass", "get_pass_names",
    "Pattern", "PVar", "POp", "Match", "PatternRewritePass",
    "program_to_dot", "dump_program", "passes_for_build_strategy",
    "AmpBf16Pass", "PruneRedundantCastsPass",
    "FoldBatchNormPass", "inference_passes", "INFERENCE_PASS_NAMES",
    "FuseAttentionPass", "FuseSparseEmbeddingPass",
]
