"""Layer: executor.  Median host time of a step's jitted call itself (the
step records' ``call``): flattening the arguments and the dispatch."""
from benchmark.harness import step_records


def read(ctx):
    return step_records.group_ms_p50(ctx, "call")
