"""A cell's comparison that decides ``correct``, over several seeds, and the
faults that the configuration's limits have to catch.

    python3 benchmark/tools/check_seeds.py --cell phi4_flash_train_seq4096 \\
        --seeds 12 --faults 1

On the chip, through the chip tool.  For every seed: the startup program
draws the weights, the timed step's twin (``model.build(train=False)`` under
the cell's ``build_strategy``) gives the loss and the gradients of
``check.parameters``; the configuration's ``reference.py`` in float32 at the
highest matmul precision gives the same.  ``--faults 1`` then computes, on
the last seed, the reference with every matrix rounded to float8_e4m3 (the
next precision down) and, where the configuration's directory has a
``faults.py`` (``FAULTS``: the names; ``planted(name)``: a copy of the
reference with that one term wrong), each of those, and says which of the
configuration's limits each fails.  Every seed's line also carries the
gauges the program computes on the device, and one line every ``*.lowering.*``
and ``backward.*`` counter.  One JSON object a line on standard output and in
``chiprun_out/check_seeds.<cell>.jsonl``; nothing here decides ``correct``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 36)
    ap.add_argument("--faults", type=int, choices=[0, 1], default=0)
    ap.add_argument("--override",
                    help="a JSON file {\"config\": {..}, \"mix\": {..}} "
                         "laid over the cell's: a rehearsal on the CPU at a "
                         "tiny size, never a result")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import compile_cache, trace
    from paddle_tpu.fluid.core import Scope, scope_guard
    from benchmark.harness import compare
    from benchmark.harness.registry import Registry, load_module
    from benchmark.harness.strategy import build_strategy

    compile_cache.enable_jax_cache()
    reg = Registry()
    cell = reg.cell(args.cell)
    cfg, cfg_dir = reg.config(cell["config"])
    mix = reg.mix(cell["traffic"])
    if args.override:
        with open(args.override) as f:
            over = json.load(f)
        cfg.update(over.get("config", {}))
        mix.update(over.get("mix", {}))
    model = load_module(os.path.join(cfg_dir, "model.py"))
    reference = load_module(os.path.join(cfg_dir, "reference.py"))
    kind = reg.module("traffic_kinds", mix["kind"] + ".py")
    check = cfg["check"]
    wanted = list(check["parameters"])
    t0 = time.perf_counter()
    out_path = os.path.join(ROOT, "chiprun_out",
                            f"check_seeds.{args.cell}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    out_file = open(out_path, "a")

    def say(record):
        record["at_s"] = round(time.perf_counter() - t0, 1)
        record["device"] = str(jax.devices()[0].device_kind)
        line = json.dumps(record)
        print(line, flush=True)
        out_file.write(line + "\n")
        out_file.flush()

    built = model.build(cfg, mix, train=False)
    names = [p.name for p in built["main"].all_parameters()]
    fetch = [built["loss"].name] + [built["grads"][w] for w in wanted]
    program = fluid.CompiledProgram(built["main"],
                                    build_strategy=build_strategy(cfg, mix))

    def report_of(got_loss, got_grads, ref_loss, ref_grads):
        ok, report = compare.against_reference(
            got_loss, got_grads, ref_loss,
            {w: np.asarray(g) for w, g in ref_grads.items()}, check)
        tols = check["grad_rel_l2_tol"]
        failed = [w for w, e in report["grad_rel_l2"].items()
                  if not e <= (tols[w] if isinstance(tols, dict) else tols)]
        if not report["loss_rel_err"] <= check["loss_rel_tol"]:
            failed.append("loss")
        return ok, report, failed

    exe = fluid.Executor()
    with scope_guard(Scope()):
        scope = fluid.global_scope()
        for n in range(args.seeds):
            seed = args.first_seed + 1000003 * n
            built["main"].random_seed = built["startup"].random_seed = seed
            exe.run(built["startup"])
            batch = kind.generate(mix, cfg, seed, check["samples"],
                                  n_batches=1, stream=1)[0]
            got = exe.run(program, feed=batch, fetch_list=fetch)
            params = {name: scope.find_var(name) for name in names}
            ref_loss, ref_grads = compare.reference_loss_and_grads(
                reference.loss, params, batch, cfg, wanted)
            loss = float(np.asarray(got[0]).ravel()[0])
            grads = dict(zip(wanted, (np.asarray(g) for g in got[1:])))
            ok, report, failed = report_of(loss, grads, ref_loss, ref_grads)
            say({"seed": seed, "ok": ok, "failed": failed, "loss": loss,
                 "ref_loss": float(ref_loss),
                 "loss_rel_err": report["loss_rel_err"],
                 "grad_rel_l2": report["grad_rel_l2"],
                 # what a runner's drain() would publish, read in place
                 "gauges": {metric: float(np.asarray(
                     scope.find_var(var)).ravel()[0])
                     for var, metric in sorted(built["main"]._hints.get(
                         "device_counters", {}).items())}})
        say({"counters": {
            name: trace.counter_value(name) for name in trace.metrics().names()
            if ".lowering." in name or name.startswith("backward.")}})

        if args.faults:
            fp8 = {n: (v.astype(jnp.float8_e4m3fn).astype(jnp.float32)
                       if v.ndim >= 2 else v) for n, v in params.items()}
            faults = [("float8 matrices", reference, fp8)]
            if os.path.exists(os.path.join(cfg_dir, "faults.py")):
                planted = load_module(os.path.join(cfg_dir, "faults.py"))
                faults += [(name, planted.planted(name), params)
                           for name in planted.FAULTS]
            for name, module, weights in faults:
                f_loss, f_grads = compare.reference_loss_and_grads(
                    module.loss, weights, batch, cfg, wanted)
                ok, report, failed = report_of(loss, grads, f_loss, f_grads)
                say({"fault": name, "caught": not ok, "failed": failed,
                     "loss_rel_err": report["loss_rel_err"],
                     "grad_rel_l2": report["grad_rel_l2"]})
    exe.close()


if __name__ == "__main__":
    main()
