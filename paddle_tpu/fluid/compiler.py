"""CompiledProgram / BuildStrategy / ExecutionStrategy facades.

Reference: python/paddle/fluid/compiler.py:87 CompiledProgram,
with_data_parallel:163 -> C++ ParallelExecutor + BuildStrategy's 30+ knobs
(framework/details/build_strategy.h:71-195).  TPU-native: data parallelism is
a sharding decision, not a graph rewrite — with_data_parallel() attaches a
jax.sharding.Mesh over the local chips and the Executor jits the SAME step
function with batch-sharded inputs; XLA inserts the gradient all-reduce that
AllReduceOpHandle (details/all_reduce_op_handle.cc:60) performed explicitly.
Most BuildStrategy knobs are therefore accepted-and-ignored: fusion/memory
passes are XLA's job (SURVEY §7 step 5).
"""
from __future__ import annotations

from typing import Optional

from . import trace


class ReduceStrategy:
    AllReduce = 0
    Reduce = 1


class BuildStrategy:
    """Knob container (details/build_strategy.h).  Since the pass
    framework landed (fluid/passes/, docs/passes.md) the rewrite knobs
    are REAL: each one selects a registered Program-IR pass that
    CompiledProgram applies before the Executor caches the lowered
    function (passes.passes_for_build_strategy is the
    build_strategy.cc AppendPass analog).  Knobs that map to XLA concepts
    (enable_inplace -> buffer donation, sync_batch_norm) keep their
    executor-side meaning; the remainder stay settable for API parity."""

    def __init__(self):
        self.reduce_strategy = ReduceStrategy.AllReduce
        self.gradient_scale_strategy = 0
        # directory: the pipeline dumps one Graphviz .dot per pass stage
        self.debug_graphviz_path = ""
        self.enable_inplace = True          # -> buffer donation (default on)
        # True -> constant_fold + prune_identity + dce passes (the 1.x
        # memory_optimize contract: shrink the live set / op stream)
        self.memory_optimize = None
        # parity only: upstream scripts set it; XLA already fuses each
        # update into its dW matmul, so there is nothing to bucket
        self.fuse_all_optimizer_ops = False
        self.fuse_all_reduce_ops = False     # -> coalesce_allreduce pass
        self.fuse_grad_size_in_num = 32      # allreduce bucket size (ops)
        self.fuse_elewise_add_act_ops = False  # -> fuse_elewise_add_act
        self.fuse_bn_act_ops = False           # -> fuse_bn_act
        # Pallas kernel tier (fluid/passes/kernel_tier.py).  The attention
        # chain needs no field: fuse_attention is in every pipeline and
        # rewrites a chain where its kernel runs (docs/passes.md "Where a
        # kernel runs").  The two below rewrite the paged decode chain
        # onto paged_attention and lookup_table+pool chains onto
        # fused_embedding_pool; each waits for a cell to judge it.
        self.fuse_paged_attention = False      # -> fuse_paged_attention
        self.fuse_sparse_embedding = False     # -> fuse_sparse_embedding
        self.enable_dce = False                # -> dce pass (fetch-seeded)
        self.constant_folding = False          # -> constant_fold pass
        # bf16 mixed precision as a compiler plane (passes/amp.py):
        # amp -> amp_bf16 pass (white/black-list cast insertion with the
        # grad halves kept dtype-consistent), followed by the
        # prune_redundant_casts cleanup unless disabled
        self.amp = False
        self.amp_dtype = "bfloat16"
        self.amp_custom_white_list = None
        self.amp_custom_black_list = None
        self.prune_redundant_casts = True
        # the unified SPMD sharding plane (parallel/sharding.py,
        # docs/sharding.md): "dp" | "tp" | "fsdp" lower a regex
        # PartitionSpec rule set over every param/grad/optimizer
        # accumulator, the executor compiles the WHOLE step as one
        # sharded (pjit) executable with buffer donation, and the
        # shard_collectives pass rewrites Fleet's ring-id allreduce ops
        # into sharding constraints (0 dispatched collectives).  A custom
        # [(regex, PartitionSpec), ...] list is accepted too.
        self.sharding = None
        # optional {"axis": size, ...} mesh override; default is a
        # 1-axis mesh over all local devices (dp/fsdp -> "dp", tp -> "tp")
        self.sharding_mesh = None
        self.enable_sequential_execution = False
        self.remove_unnecessary_lock = True
        self.sync_batch_norm = False        # -> sync_batch_norm op psum
        self.num_trainers = 1
        self.trainer_id = 0
        self.trainers_endpoints = []
        self.collective_mode = None
        self.nccl_comm_num = 1


class ExecutionStrategy:
    def __init__(self):
        self.num_threads = 0                # XLA schedules; inert
        self.num_iteration_per_drop_scope = 1
        # num_iteration_per_run is REAL since the async pipeline landed
        # (fluid/async_pipeline.py): K > 1 stamps the program's
        # steps_per_dispatch hint, and the AsyncStepRunner drives K steps
        # through one lax.scan executable per Python dispatch — the
        # reference's "run K iterations per PE invocation" contract
        self.num_iteration_per_run = 1
        self.allow_op_delay = False


class CompiledProgram:
    def __init__(self, program_or_graph, build_strategy: Optional[BuildStrategy] = None,
                 exec_strategy: Optional[ExecutionStrategy] = None):
        self._program = getattr(program_or_graph, "_program", program_or_graph)
        self._build_strategy = build_strategy or BuildStrategy()
        self._mesh = None
        self._sharding_plan = None
        self._is_data_parallel = False
        self._ir_passes_applied = False
        # forwarded so Executor.run can treat us like a Program
        self._hints = self._program._hints
        if exec_strategy is not None:
            self._apply_exec_strategy(exec_strategy)
        trace.metrics().counter("compiler.compiled_programs").inc()

    def _apply_exec_strategy(self, exec_strategy):
        k = int(getattr(exec_strategy, "num_iteration_per_run", 1) or 1)
        if k > 1:
            self._program._hints["steps_per_dispatch"] = k
        else:
            # explicit k=1 must undo an earlier strategy's hint — the
            # hints dict is shared with the underlying Program
            self._program._hints.pop("steps_per_dispatch", None)

    def _ensure_sharding_plan(self):
        """Lower ``BuildStrategy.sharding`` into a ShardingPlan once, at
        first run (the program's params and shapes exist by then).  The
        mesh defaults to the shared process mesh or a fresh 1-axis mesh
        over all local devices (``sharding_mesh`` overrides); the plan is
        what the executor's sharded-compile path consumes."""
        mode = getattr(self._build_strategy, "sharding", None)
        if not mode or self._sharding_plan is not None:
            return self._sharding_plan
        from ..parallel import sharding as shard_plane
        from ..parallel import mesh as mesh_registry
        mesh = self._mesh
        axes = getattr(self._build_strategy, "sharding_mesh", None)
        if mesh is None and axes:
            mesh = mesh_registry.build_mesh(dict(axes))
        self._sharding_plan = shard_plane.build_plan(
            program=self._program, mode=mode, mesh=mesh)
        self._program._hints["sharding"] = self._sharding_plan.describe()
        if trace.enabled():
            trace.instant("sharding_plan", cat="compile",
                          args=self._sharding_plan.describe())
        return self._sharding_plan

    def _apply_ir_passes(self, fetch_names=()):
        """Run the BuildStrategy-selected pass pipeline over the program,
        once, before the executor fingerprints it (the reference applies
        build-strategy passes when ParallelExecutor materialises the
        graph).  Called by Executor.run with the first run's fetch list —
        the DCE seed and the rewrite protection set.  The rewrite is
        in-place and version-bumped, so every executor cache keyed on the
        old fingerprint is dead the moment a pass mutates."""
        if self._ir_passes_applied:
            return
        self._ir_passes_applied = True
        from . import passes
        plist = passes.passes_for_build_strategy(self._build_strategy)
        gv = self._build_strategy.debug_graphviz_path or None
        if not plist and not gv:
            return
        pipe = passes.PassPipeline(plist, graphviz_path=gv)
        if any(p.name == "dce" for p in plist):
            # DCE permanently removes ops unreachable from THIS fetch set;
            # the executor uses the recorded seed to turn a later fetch of
            # a pruned var into an actionable error instead of a bare
            # KeyError deep in the trace
            self._program._hints["ir_pass_dce_targets"] = \
                [str(n) for n in fetch_names]
        _t0 = trace.now() if trace.enabled() else 0
        stats = pipe.apply(self._program, targets=fetch_names,
                           build_strategy=self._build_strategy,
                           sharding_plan=self._sharding_plan)
        if _t0:
            trace.complete("compiler::apply_ir_passes", _t0, cat="compile",
                           args={p: dict(s) for p, s in stats.items()})
        return stats

    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, share_vars_from=None,
                           places=None):
        """Local multi-chip DP: build a 1-axis device mesh over the chips."""
        if build_strategy is not None:
            self._build_strategy = build_strategy
        if exec_strategy is not None:
            self._apply_exec_strategy(exec_strategy)
        from ..parallel.mesh import build_data_parallel_mesh
        _t0 = trace.now() if trace.enabled() else 0
        self._mesh = build_data_parallel_mesh(places)
        if _t0:
            trace.complete("compiler::with_data_parallel", _t0,
                           cat="compile",
                           args={"devices": int(self._mesh.size)})
        self._is_data_parallel = True
        if self._build_strategy.sync_batch_norm:
            self._program._hints["sync_batch_norm"] = True
        return self

    def _with_inference_optimize(self, config):
        return self

    @property
    def program(self):
        return self._program
