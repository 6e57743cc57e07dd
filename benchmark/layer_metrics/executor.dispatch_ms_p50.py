"""Layer: executor.  Median host time of one step's dispatch: the
benchmark's ``executor.submit`` span around ``AsyncStepRunner.submit`` less
the part of it the program's ``executor.host_wait_seconds`` counter says was
spent blocked on the in-flight window (that is the device's time, not the
executor's)."""
import statistics


def read(ctx):
    if not ctx["steps"]:
        return None
    return 1e3 * statistics.median(max(0.0, s[1] - s[2])
                                   for s in ctx["steps"])
