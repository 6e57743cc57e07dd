#!/usr/bin/env python
"""Serve a model saved by the REFERENCE framework.

The migration story in one file: a `__model__` ProgramDesc protobuf plus
binary param files laid out by the reference's save_inference_model
(python/paddle/fluid/io.py:1198) load straight into this framework's
AnalysisPredictor — no conversion step.  Run against the checked-in
fixture:

    python examples/serve_reference_model.py tests/fixtures/ref_fc_model

or point it at any reference export directory (per-var param files or a
combined file via --params).
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("model_dir")
    ap.add_argument("--params", default=None,
                    help="combined params filename (save_combine format); "
                         "default: per-var files")
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args(argv)

    from paddle_tpu.inference import AnalysisConfig, create_predictor

    cfg = AnalysisConfig(args.model_dir)
    if args.params:
        cfg.params_file = os.path.join(args.model_dir, args.params)
    pred = create_predictor(cfg)

    names = pred.get_input_names()
    print(f"inputs: {names}  outputs: {pred.get_output_names()}")
    rng = np.random.RandomState(0)
    for name in names:
        h = pred.get_input_handle(name)
        # shapes come from the model's VarDescs; -1 batch dims filled in
        var = pred._program.global_block().var(name)
        shape = [args.batch if d == -1 else d for d in (var.shape or [1])]
        h.copy_from_cpu(rng.randn(*shape).astype(var.dtype or "float32"))
    pred.run()
    for name in pred.get_output_names():
        out = pred.get_output_handle(name).copy_to_cpu()
        print(f"{name}: shape={out.shape} "
              f"first_row={np.asarray(out).reshape(out.shape[0], -1)[0]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
