"""Parallelism layer: mesh registry, SPMD wrappers, strategies
(reference SURVEY §2.9 parallelism inventory)."""
from .mesh import (build_mesh, build_data_parallel_mesh, current_mesh,
                   set_current_mesh, register_ring, ring_axes, axis_size,
                   axis_for_ring,
                   RING_DP, RING_TP, RING_PP, RING_SP, RING_EP)
from .api import (wrap_with_mesh, shard_map_step, param_sharding,
                  compat_shard_map, resolved_mesh)
from .sharding import (ShardingPlan, build_plan, match_partition_rules,
                       make_shard_and_gather_fns, rules_for,
                       tp_rules_for_program)
from .ring_attention import ring_attention
from .ulysses import ulysses_attention
from .moe import expert_layer, moe_partition_rules
