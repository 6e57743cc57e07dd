"""Pass framework core: Pass base class, registry, pipeline.

Reference: paddle/fluid/framework/ir/pass.h (Pass::Apply over ir::Graph,
RegisterPass macros populating a global PassRegistry, 134 registered
passes) and build_strategy.cc AppendPass wiring BuildStrategy knobs to a
pass list.  TPU-native differences: passes rewrite the *Program/Block IR*
directly (there is no separate ir::Graph — the Block op list IS the graph;
SSA-ness comes from trace-time env threading in executor.run_block_ops),
and the payoff is host-side: fewer dispatched ops per trace (the per-op
span loop PR 1 measures), a smaller jaxpr (the compile tax PR 2 measures),
and collective launches XLA will not merge on its own.

Contract notes:

* Every mutation goes through the Block mutators (``append_op`` /
  ``_insert_op`` / ``_insert_op_obj`` / ``_remove_op`` / ``set_attr``) so
  the program's ``_version`` bumps and the executor's cached fingerprint
  (executor._fingerprint) can never serve a stale executable.  The
  pipeline *enforces* this: a pass that changed the op stream without a
  version bump is a hard error, not a silent cache hazard.
* Passes declare read/write sets over IR aspects ({"ops", "attrs",
  "vars"}).  A pass with an empty write set is an analysis/no-op pass and
  the pipeline asserts it did not mutate.
* Every pass run emits a ``pass::<name>`` span (cat="pass") plus
  ``pass.<name>.<stat>`` counters through the PR 1 trace plane.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence, Tuple, Type

from .. import trace

__all__ = ["Pass", "PassContext", "PassRegistry", "register_pass",
           "create_pass", "get_pass_names", "PassPipeline"]

IR_ASPECTS = frozenset({"ops", "attrs", "vars"})


class PassContext:
    """Per-application state shared by the passes of one pipeline run.

    ``targets`` are the fetch var names the caller will ask the executor
    for — the DCE seed and the protection set: a pass must never remove or
    re-alias the producer of a target (the fetch would KeyError).
    """

    def __init__(self, program, targets: Sequence[str] = (),
                 build_strategy=None, sharding_plan=None):
        self.program = program
        self.targets = [str(t) for t in (targets or ())]
        self.build_strategy = build_strategy
        # the resolved PR-10 ShardingPlan when the pipeline runs under a
        # sharded CompiledProgram (run() ensures the plan BEFORE the
        # passes) — fuse_attention reads its mesh (KernelSite.on)
        self.sharding_plan = sharding_plan
        self.stats: Dict[str, Dict[str, int]] = {}

    def is_protected(self, block, name: str) -> bool:
        """Vars a rewrite must keep producing under their own name:
        fetch targets, persistables (scope state), and data feeds."""
        if name in self.targets:
            return True
        v = block._find_var_recursive(name)
        return v is not None and (v.persistable or v.is_data)


class Pass:
    """Base class: subclass, set ``name``, declare read/write sets, and
    implement ``apply_block`` (or override ``apply`` for whole-program
    passes).  Return a dict of integer stats (``ops_removed``,
    ``ops_fused``, ...) — the pipeline turns them into trace-plane
    counters and span args."""

    name: str = "pass"
    # IR aspects this pass reads / mutates.  writes=∅ => analysis/no-op
    # pass; the pipeline asserts the program version did not move.
    reads: frozenset = frozenset({"ops"})
    writes: frozenset = frozenset({"ops"})

    def __init__(self, **options):
        self.options = options
        bad = (set(self.reads) | set(self.writes)) - IR_ASPECTS
        if bad:
            raise ValueError(
                f"pass '{self.name}' declares unknown IR aspects {bad}; "
                f"valid: {sorted(IR_ASPECTS)}")

    def apply(self, program, ctx: PassContext) -> Dict[str, int]:
        stats: Dict[str, int] = {}
        for block in program.blocks:
            for k, v in (self.apply_block(block, ctx) or {}).items():
                stats[k] = stats.get(k, 0) + int(v)
        return stats

    def apply_block(self, block, ctx: PassContext) -> Dict[str, int]:
        return {}

    def __repr__(self):
        return f"{type(self).__name__}(name={self.name!r})"


class PassRegistry:
    """name -> Pass subclass map (ir/pass.h PassRegistry analog)."""

    def __init__(self):
        self._passes: Dict[str, Type[Pass]] = {}

    def register(self, cls: Type[Pass]) -> Type[Pass]:
        name = cls.name
        if not name or name == "pass":
            raise ValueError(f"{cls.__name__} must set a unique `name`")
        if name in self._passes:
            raise ValueError(f"pass '{name}' already registered "
                             f"({self._passes[name].__name__})")
        self._passes[name] = cls
        return cls

    def get(self, name: str) -> Type[Pass]:
        if name not in self._passes:
            raise KeyError(
                f"no pass named '{name}' registered "
                f"(available: {sorted(self._passes)})")
        return self._passes[name]

    def create(self, name: str, **options) -> Pass:
        return self.get(name)(**options)

    def names(self) -> List[str]:
        return sorted(self._passes)


_registry = PassRegistry()


def register_pass(cls: Type[Pass]) -> Type[Pass]:
    """Class decorator: ``@register_pass`` above a Pass subclass."""
    return _registry.register(cls)


def create_pass(name: str, **options) -> Pass:
    return _registry.create(name, **options)


def get_pass_names() -> List[str]:
    return _registry.names()


def _n_ops(program) -> int:
    return sum(len(b.ops) for b in program.blocks)


class PassPipeline:
    """Ordered pass application with trace-plane instrumentation, version
    enforcement, and optional per-stage Graphviz dumps
    (BuildStrategy.debug_graphviz_path)."""

    def __init__(self, passes: Sequence[Pass] = (),
                 graphviz_path: Optional[str] = None):
        self.passes: List[Pass] = list(passes)
        self.graphviz_path = graphviz_path or None

    def append(self, p: Pass) -> "PassPipeline":
        self.passes.append(p)
        return self

    def _dump(self, program, stage: int, label: str) -> None:
        if not self.graphviz_path:
            return
        from .graphviz import dump_program
        os.makedirs(self.graphviz_path, exist_ok=True)
        dump_program(program, os.path.join(
            self.graphviz_path, f"{stage:02d}_{label}.dot"))

    def apply(self, program, targets: Sequence[str] = (),
              build_strategy=None,
              sharding_plan=None) -> Dict[str, Dict[str, int]]:
        """Run every pass over ``program``; returns {pass: stats}."""
        ctx = PassContext(program, targets=targets,
                          build_strategy=build_strategy,
                          sharding_plan=sharding_plan)
        self._dump(program, 0, "input")
        tr_on = trace.enabled()
        for i, p in enumerate(self.passes):
            v0, n0 = program._version, _n_ops(program)
            t0 = trace.now() if tr_on else 0
            stats = dict(p.apply(program, ctx) or {})
            n1 = _n_ops(program)
            if not p.writes and program._version != v0:
                raise RuntimeError(
                    f"pass '{p.name}' declares an empty write set but "
                    f"bumped the program version ({v0} -> "
                    f"{program._version})")
            if n1 != n0 and program._version == v0:
                # the stale-fingerprint hazard the mutator contract exists
                # to prevent — fail the pipeline, don't poison the cache
                raise RuntimeError(
                    f"pass '{p.name}' changed the op count ({n0} -> {n1}) "
                    f"without bumping the program version; rewrites must "
                    f"go through the Block mutators")
            stats.setdefault("ops_removed", max(n0 - n1, 0))
            ctx.stats[p.name] = stats
            m = trace.metrics()
            for k, v in stats.items():
                if v:
                    m.counter(f"pass.{p.name}.{k}").inc(int(v))
            if tr_on:
                trace.complete(f"pass::{p.name}", t0, cat="pass",
                               args=dict(stats, ops_before=n0,
                                         ops_after=n1))
            self._dump(program, i + 1, p.name)
        return ctx.stats
