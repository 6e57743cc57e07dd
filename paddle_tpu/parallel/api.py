"""Mesh execution wrappers: how a compiled block runs SPMD.

Two modes, mirroring the two ways the reference parallelises (SURVEY §2.9):

* auto (GSPMD/pjit)   — the ParallelExecutor-DP analog.  Params carry
  PartitionSpec annotations (replicated for pure DP, sharded for TP/ZeRO);
  feeds shard on the batch axis; XLA's sharding propagation inserts the
  gradient all-reduce that AllReduceOpHandle issued by hand.  Explicit
  c_allreduce ops in the program lower to identity here (their ring has no
  bound axis), so fleet-style programs stay correct without double-reducing.
  The rule-driven generalisation of this mode is parallel/sharding.py
  (``BuildStrategy.sharding`` — whole-step pjit from regex PartitionSpec
  rules); wrap_with_mesh remains the legacy per-Parameter-annotation path.

* explicit (shard_map) — the collective-op path.  ring_id -> axis bindings
  are live, c_* ops lower to lax.psum/all_gather/ppermute on ICI.  Used for
  tensor/sequence parallel layers and ring attention where communication
  placement is the point.

Both planes share ONE process mesh: every wrapper funnels its mesh through
:func:`resolved_mesh`, which registers it in parallel/mesh.py — so a plan
built by sharding.py and a shard_map step built here resolve the same
``jax.sharding.Mesh`` object, never two twins over the same devices.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import mesh as mesh_registry

def resolved_mesh(mesh: Optional[Mesh] = None) -> Optional[Mesh]:
    """THE mesh both planes share.  With an explicit mesh, install it as
    the process mesh (parallel/mesh.py) and return it; otherwise return
    the current process mesh (None when nothing built one yet).
    sharding.py's plan builder and the executor's auto-mode wrapper
    resolve through here, so the sharding plane and the mesh registry can
    never hold two different Mesh objects over the same devices.
    One-off explicit wrappers (``compat_shard_map`` over an ad-hoc mesh)
    deliberately do NOT install — a temporary two-device shard_map must
    not hijack the process default every later plan adopts."""
    if mesh is not None:
        if mesh_registry.current_mesh() is not mesh:
            mesh_registry.set_current_mesh(mesh)
        return mesh
    return mesh_registry.current_mesh()


def param_sharding(mesh: Mesh, program) -> Dict[str, NamedSharding]:
    """Build per-parameter NamedShardings from Parameter.sharding specs."""
    out = {}
    for v in program.global_block().vars.values():
        spec = getattr(v, "sharding", None)
        if spec is not None:
            out[v.name] = NamedSharding(mesh, P(*spec))
    return out


def wrap_with_mesh(fn, mesh: Mesh, program, batch_axis: str = "dp",
                   donate: bool = True):
    """Auto-mode wrapper for Executor step functions:
    fn(mut_params, ro_params, feeds, key) -> (fetches, new_vals)."""
    mesh = resolved_mesh(mesh)
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P(batch_axis))
    psh = param_sharding(mesh, program)

    def shard_of(name):
        return psh.get(name, repl)

    def wrapped(mut_params, ro_params, feeds, key):
        mut = {k: jax.device_put(v, shard_of(k)) for k, v in mut_params.items()}
        ro = {k: jax.device_put(v, shard_of(k)) for k, v in ro_params.items()}
        fd = {k: jax.device_put(v, data) for k, v in feeds.items()}
        return _inner(mut, ro, fd, key)

    _inner = jax.jit(fn, donate_argnums=(0,) if donate else ())
    return wrapped


def compat_shard_map(fn, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` with replication checking off by default (the
    collective lowerings in ops/collective_ops.py do not carry varying-
    manual-axes types).  The mesh is used as passed — an ad-hoc shard_map
    never mutates the shared process mesh (resolved_mesh)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def shard_map_step(fn, mesh: Mesh, in_specs, out_specs):
    """Explicit-mode: shard_map with collective ops live on their axes."""
    return jax.jit(compat_shard_map(fn, mesh=mesh, in_specs=in_specs,
                                    out_specs=out_specs, check_vma=False))
