"""paddle_tpu.fluid — the fluid-compatible front-end, TPU-native underneath.

Public surface per python/paddle/fluid/__init__.py (SURVEY A.6): Program /
Executor / layers / optimizer / backward / io / initializer / ParamAttr ...
"""
from .. import ops as _ops  # registers all lowering rules

from . import core
from .core import (CPUPlace, TPUPlace, CUDAPlace, TPUPinnedPlace, Scope,
                   global_scope, scope_guard, set_flags, get_flags,
                   is_compiled_with_cuda, is_compiled_with_tpu)
from .framework import (Program, Variable, Parameter, program_guard,
                        default_main_program, default_startup_program,
                        in_dygraph_mode, convert_dtype,
                        cpu_places, device_guard)
from .executor import Executor
from . import async_pipeline
from .async_pipeline import AsyncStepRunner, FetchHandle, StepFuture
from .backward import append_backward, gradients
from . import initializer
from .initializer import Constant, Uniform, Normal, Xavier, MSRA
from .param_attr import ParamAttr, WeightNormParamAttr
from . import layers
from . import optimizer
from . import regularizer
from . import clip
from .layers.tensor import data
from . import io
from .io import save_persistables, load_persistables, save_params, load_params
from . import checkpoint
from .checkpoint import CheckpointManager
from . import nets
from .compiler import CompiledProgram, BuildStrategy, ExecutionStrategy
from . import passes
from . import dygraph
from ..contrib import memory_usage_calc as _muc  # noqa: F401 (cycle guard)
from .. import contrib                            # fluid.contrib alias
from .. import incubate                           # fluid.incubate alias
from . import transpiler
from .transpiler import (DistributeTranspiler, DistributeTranspilerConfig,
                         memory_optimize, release_memory)
from .data_feeder import DataFeeder
from . import metrics
from . import dataset
from .dataset import DatasetFactory, InMemoryDataset, QueueDataset
from . import trace
from . import goodput
from . import flight_recorder
from . import profiler
from . import monitor
from .reader import DataLoader

core.init_signal_handlers()

# SLO watchdog (fluid/watchdog.py): env-gated like the export plane —
# `FLAGS_watchdog=1 python serve.py` arms stall/breach/crash/OOM
# detection with post-mortem diagnostic bundles, no code changes.
if core.get_flag("watchdog"):
    try:
        from . import watchdog as _watchdog
        _watchdog.apply_flags()
    except Exception as _e:             # noqa: BLE001 — forensics are
        import sys as _sys              # advisory, never block import
        print(f"paddle_tpu: WARNING: watchdog failed to start: "
              f"{type(_e).__name__}: {_e}", file=_sys.stderr)

# live metrics export (fluid/metrics_export.py): env-gated like the trace
# plane — `FLAGS_metrics_port=9090 python train.py` serves /metrics with
# no code changes, and a snapshot path starts the JSONL writer.  Lazy:
# the module is only imported when a flag asks for it.
if core.get_flag("metrics_port") or core.get_flag("metrics_snapshot_path"):
    try:
        from . import metrics_export as _metrics_export
        _metrics_export.apply_flags()
    except Exception as _e:             # noqa: BLE001 — export is advisory
        import sys as _sys
        print(f"paddle_tpu: WARNING: metrics export failed to start: "
              f"{type(_e).__name__}: {_e}", file=_sys.stderr)


def name_scope(prefix=None):
    import contextlib
    return contextlib.nullcontext()


embedding = layers.embedding
one_hot = layers.one_hot

# --- reference fluid module surface (round-4 global __all__ closure) ---
from . import average                 # noqa: E402,F401
from . import communicator            # noqa: E402,F401
from . import data_feed_desc          # noqa: E402,F401
from .data_feed_desc import DataFeedDesc  # noqa: E402,F401
from . import dataloader              # noqa: E402,F401
from . import default_scope_funcs     # noqa: E402,F401
from . import device_worker           # noqa: E402,F401
from . import trainer_desc            # noqa: E402,F401
from . import trainer_factory         # noqa: E402,F401
from . import entry_attr              # noqa: E402,F401
from .entry_attr import ProbabilityEntry, CountFilterEntry  # noqa: E402,F401
from . import evaluator               # noqa: E402,F401
from . import generator               # noqa: E402,F401
from .generator import Generator      # noqa: E402,F401
from . import install_check           # noqa: E402,F401
from . import layer_helper_base       # noqa: E402,F401
from .layer_helper_base import LayerHelperBase  # noqa: E402,F401
from . import lod_tensor              # noqa: E402,F401
from .lod_tensor import create_lod_tensor, create_random_int_lodtensor  # noqa: E402,F401
from . import log_helper              # noqa: E402,F401
from . import parallel_executor       # noqa: E402,F401
from .parallel_executor import ParallelExecutor  # noqa: E402,F401
from . import unique_name             # noqa: E402,F401
from . import wrapped_decorator       # noqa: E402,F401
from . import distributed             # noqa: E402,F401
from .average import WeightedAverage  # noqa: E402,F401
from .communicator import Communicator, LargeScaleKV  # noqa: E402,F401
from .framework import (cuda_places, cpu_places,  # noqa: E402,F401
                        cuda_pinned_places, require_version,
                        load_op_library)
from .initializer import set_global_initializer  # noqa: E402,F401
