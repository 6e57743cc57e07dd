"""Layer: executor.  Seconds of set-up that are Python: over the Executor's
``compile`` records that began before the window, ``total_us`` less
``backend_us`` (Program -> step function, the jaxpr trace with every op's
lowering, the MLIR lowering)."""
from benchmark.harness import step_records


def read(ctx):
    return step_records.setup_trace_s(ctx)
