"""Layer: executor.  Median host time of one ``Executor.run`` in the window,
from the program's own step records (``run_us``): the inside twin of
``executor.dispatch_ms_p50``, which times ``AsyncStepRunner.submit`` from
outside; outside less inside is the runner's own overhead."""
from benchmark.harness import step_records


def read(ctx):
    return step_records.run_ms_p50(ctx)
