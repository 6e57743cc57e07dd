"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run, on the machine it is started on.  Progress goes to
standard error; the last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, with
``--trace 1``, ``breakdown``.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics.  Where JAX finds no accelerator
or fewer chips than the cell asks for, the exit code is not 0 and no result
is printed.  What a cell is lives in data: see ``harness/registry.py``.
"""
import time

T_START = time.perf_counter()        # set-up is counted from here

import argparse                      # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import sys                           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)         # the checkout: paddle_tpu and benchmark
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmark.harness.cell import run_cell
    line = run_cell(args.workload, args.seed, args.seconds, args.trace,
                    T_START)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
