"""Rehearsal without the chip: compile a cell's training step at its real
size for a described TPU v5e (``v5e:2x2``) on the CPU host and print what the
compiler says it needs (``memory_analysis``), how many Mosaic kernels and
which collectives the executable holds.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_compile.py --workload <cell>

What the chip's compiler would refuse (a program that does not fit, a kernel
it cannot lower or partition) it refuses here, at no chip time.  Nothing runs:
this says nothing about results or times and is never reported as a chip run.
The step function is the one ``fluid.Executor`` builds (``_prepare``), jitted
here with the donation the executor uses on the chip, and with the lowerings'
"am I on a TPU" question answered yes.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--samples-per-chip", type=int,
                    help="try another batch than the mix's")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, SingleDeviceSharding
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.core import Scope, scope_guard
    from paddle_tpu.ops.registry import LoweringContext
    from benchmark.harness.registry import Registry, load_module
    from benchmark.harness.strategy import build_strategy

    if jax.default_backend() != "cpu":
        raise SystemExit("run with JAX_PLATFORMS=cpu: this compiles for a "
                         "described chip, it does not use an attached one")
    # the lowerings ask jax.default_backend(); here the target is the TPU
    LoweringContext.pallas_ok = lambda self: not self.partitioned

    reg = Registry()
    cell = reg.cell(args.workload)
    cfg, cfg_dir = reg.config(cell["config"])
    mix = reg.mix(cell["traffic"])
    if args.samples_per_chip:
        mix["samples_per_chip"] = args.samples_per_chip
    chips = cell["chips"]
    model = load_module(os.path.join(cfg_dir, "model.py"))
    kind = reg.module("traffic_kinds", mix["kind"] + ".py")
    batch = mix["samples_per_chip"] * chips

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    built = model.build(cfg, mix, train=True)
    bs = build_strategy(cfg, mix)
    program = fluid.CompiledProgram(built["main"], build_strategy=bs)
    if bs.sharding:
        program._mesh = Mesh(np.array(topo.devices[:chips]), ("dp",))
    fetch = [built["loss"].name]
    feed = kind.generate(mix, cfg, 0, batch, n_batches=1)[0]

    exe = fluid.Executor()
    with scope_guard(Scope()):
        exe.run(built["startup"])
        plan = program._ensure_sharding_plan()
        program._apply_ir_passes(fetch)
        scope = fluid.global_scope()
        step = exe._prepare(built["main"], feed, fetch, scope, plan=plan)
        mut = {n: scope.find_var(n) for n in step.param_names
               if n in step.written_names}
        ro = {n: scope.find_var(n) for n in step.param_names
              if n not in step.written_names}
        key = jax.random.PRNGKey(0)
        if plan is None:
            # one chip: every argument on the first described device
            placed = SingleDeviceSharding(topo.devices[0])
            jitted = jax.jit(step.raw_fn, donate_argnums=(0,))
        else:
            # the plan's own in_shardings place the arguments
            from paddle_tpu.parallel.sharding import wrap_with_plan
            placed = None
            _, jitted = wrap_with_plan(
                step.raw_fn, plan, {**mut, **ro}, list(mut), list(ro), feed,
                block=built["main"].global_block(), donate=True)

        def sds(a):
            a = np.asarray(a) if not hasattr(a, "dtype") else a
            dtype = jax.dtypes.canonicalize_dtype(a.dtype)
            return jax.ShapeDtypeStruct(a.shape, dtype, sharding=placed)
        spec = jax.tree_util.tree_map(sds, (mut, ro, feed, key))
        compiled = jitted.trace(*spec).lower(
            lowering_platforms=("tpu",)).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(json.dumps({
        "workload": args.workload, "chips": chips,
        "samples_per_chip": mix["samples_per_chip"],
        "argument_gib": mem.argument_size_in_bytes / 2 ** 30,
        "output_gib": mem.output_size_in_bytes / 2 ** 30,
        "alias_gib": mem.alias_size_in_bytes / 2 ** 30,
        "temp_gib": mem.temp_size_in_bytes / 2 ** 30,
        "total_gib_per_device": total / 2 ** 30,
        "mosaic_calls": text.count('custom_call_target="tpu_custom_call"'),
        "collectives": {k: len(re.findall(rf"\b{k}(?:-start)?\(", text))
                        for k in ("all-reduce", "all-gather",
                                  "reduce-scatter", "all-to-all",
                                  "collective-permute")},
        "note": "compiled for a described v5e:2x2 on the CPU host; nothing "
                "ran",
    }))


if __name__ == "__main__":
    main()
