"""Layer: executor.  Median host time a step spends making the call's other
arguments: the step records' ``stage`` (feeds to arrays, the step key and
its eager PRNG programs), ``persist`` (host copies of fetches a donating
call would invalidate) and ``place`` (a sharding plan's ``device_put`` of
every argument)."""
from benchmark.harness import step_records


def read(ctx):
    return step_records.group_ms_p50(ctx, "stage")
