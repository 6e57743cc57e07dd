"""The chip comparison of ``keye_vl2_30b_a3b_train`` beyond what the cell's
own check (``harness/compare.py``: the objective and six gradients) prints:
the objective's two parts apart, the share of every query's key set that
program and reference have in common, over several seeds, and the faults
that the configuration's limits have to catch.

    python3 benchmark/tools/keye_check.py --seeds 12 --faults 1

On the chip, through the chip tool.  For every seed: the startup program
draws the weights, the timed step's twin (``model.build(train=False)`` under
the cell's ``build_strategy``) gives ``L_LM``, ``sum L_I``, the gradients of
``check.parameters`` and each layer's selection; ``reference.py`` in float32
at the highest matmul precision gives the same.  ``--faults 1`` then computes,
on the last seed, the reference with one thing wrong at a time (every matrix
rounded to float8_e4m3; no selection; ``topk`` 1024; the indexer without its
ReLU; the indexer's input not detached; the indexer's loss left out) and
says which of the configuration's limits each fails.  One JSON object a
line on standard output and in ``chiprun_out/keye_check.jsonl``; nothing
here decides ``correct``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "keye_vl2_train_seq16384"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 11)
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
    from paddle_tpu.fluid import compile_cache
    from paddle_tpu.fluid.core import Scope, scope_guard
    from paddle_tpu.ops.sparse_attention import unpack_selection
    from benchmark.harness import compare
    from benchmark.harness.registry import Registry, load_module
    from benchmark.harness.strategy import build_strategy

    compile_cache.enable_jax_cache()
    reg = Registry()
    cell = reg.cell(CELL)
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
    out_path = os.path.join(ROOT, "chiprun_out", "keye_check.jsonl")
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
    block = built["main"].global_block()
    selections = [op.outputs["Selection"][0] for op in block.ops
                  if op.type == "sparse_attention_index"]
    names = [p.name for p in built["main"].all_parameters()]
    fetch = [built["loss"].name, built["lm_loss"].name,
             built["index_loss"].name] \
        + [built["grads"][w] for w in wanted] + selections
    program = fluid.CompiledProgram(built["main"],
                                    build_strategy=build_strategy(cfg, mix))

    def ref_fn(sub, rest, feeds, cfg_):
        lm, index = reference.losses({**rest, **sub}, feeds, cfg_)
        return lm + cfg_["index_loss_weight"] * index, (lm, index)

    def reference_under(cfg_):
        """``(params, batch) -> ((L, (L_LM, sum L_I)), grads of wanted)`` of
        the reference as it stands now, compiled once."""
        jitted = jax.jit(jax.value_and_grad(
            lambda s, r, f: ref_fn(s, r, f, cfg_), has_aux=True))

        def run(params, batch):
            sub = {n: params[n] for n in wanted}
            rest = {n: v for n, v in params.items() if n not in sub}
            with jax.default_matmul_precision("highest"):
                return jitted(sub, rest, dict(batch))
        return run

    reference_of = reference_under(cfg)
    selections_of = jax.jit(lambda p, f: reference.selections(p, f, cfg))

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
            got = exe.run(program, feed=batch, fetch_list=fetch,
                          return_numpy=False)
            params = {name: scope.find_var(name) for name in names}
            (ref_loss, (ref_lm, ref_index)), ref_grads = reference_of(
                params, batch)
            loss, lm, index = (float(np.asarray(g).ravel()[0])
                               for g in got[:3])
            grads = dict(zip(wanted, (np.asarray(g)
                                      for g in got[3:3 + len(wanted)])))
            ok, report, failed = report_of(loss, grads, ref_loss, ref_grads)
            with jax.default_matmul_precision("highest"):
                ref_sel = selections_of(params, dict(batch))
            shared = []
            for i, sel in enumerate(got[3 + len(wanted):]):
                mine = unpack_selection(jnp.asarray(sel))
                both = jnp.sum(mine & ref_sel[i], dtype=jnp.int32)
                shared.append({
                    "layer": i, "program_keys": int(jnp.sum(
                        mine, dtype=jnp.int32)),
                    "reference_keys": int(jnp.sum(ref_sel[i],
                                                  dtype=jnp.int32)),
                    "shared_share": float(both) / float(jnp.sum(
                        ref_sel[i], dtype=jnp.int32))})
            say({"seed": seed, "ok": ok, "failed": failed,
                 "lm_loss": lm, "ref_lm_loss": float(ref_lm),
                 "lm_rel_err": compare.rel_err(lm, ref_lm),
                 "index_loss": index, "ref_index_loss": float(ref_index),
                 "index_rel_err": compare.rel_err(index, ref_index),
                 "loss_rel_err": report["loss_rel_err"],
                 "grad_rel_l2": report["grad_rel_l2"], "selection": shared})

        if args.faults:
            half = dict(cfg, sa_config=dict(cfg["sa_config"], topk=1024))
            dense = dict(cfg, sa_config=dict(cfg["sa_config"],
                                             topk=mix["seq_len"]))
            fp8 = {n: (v.astype(jnp.float8_e4m3fn).astype(jnp.float32)
                       if v.ndim >= 2 else v) for n, v in params.items()}
            keep = (reference._index_activation, reference._indexer_input)
            faults = [
                ("float8 matrices", fp8, cfg, keep),
                ("no selection (dense causal attention)", params, dense,
                 keep),
                ("topk 1024", params, half, keep),
                ("the indexer without ReLU", params, cfg,
                 (lambda z: z, keep[1])),
                ("h not detached into the indexer", params, cfg,
                 (keep[0], lambda h: h)),
                ("L_I left out", params, dict(cfg, index_loss_weight=0.0),
                 keep)]
            for name, weights, cfg_, (act, detach) in faults:
                reference._index_activation = act
                reference._indexer_input = detach
                try:
                    (f_loss, _), f_grads = reference_under(cfg_)(weights,
                                                                 batch)
                finally:
                    reference._index_activation, reference._indexer_input \
                        = keep
                ok, report, failed = report_of(loss, grads, f_loss, f_grads)
                say({"fault": name, "caught": not ok, "failed": failed,
                     "loss_rel_err": report["loss_rel_err"],
                     "grad_rel_l2": report["grad_rel_l2"]})
    exe.close()


if __name__ == "__main__":
    main()
