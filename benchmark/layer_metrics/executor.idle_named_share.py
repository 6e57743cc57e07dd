"""Layer: executor.  Of the idle time that the trace's reduction charged to
the loop's ``executor.submit`` span on the idlest device, the share that lies
inside a phase of one of the program's step records, in percent.  The trust
gauge of the phase metrics, as ``kernel.attributed_share`` is for the device
table: what is left lies between the loop's span and ``Executor.run`` (the
runner's window and bookkeeping).  None where the slice has no such idle
piece."""
from benchmark.harness import step_records


def read(ctx):
    return step_records.idle_named_share(ctx)
