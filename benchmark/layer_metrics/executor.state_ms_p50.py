"""Layer: executor.  Median host time a step spends walking the scope: the
step records' ``gather`` (``scope.find_var`` for every state array) plus
``scatter`` (``scope.set_var`` for every written one)."""
from benchmark.harness import step_records


def read(ctx):
    return step_records.group_ms_p50(ctx, "state")
