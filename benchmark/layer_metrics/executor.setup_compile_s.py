"""Layer: executor.  Seconds of set-up inside XLA: ``backend_us`` over every
``xla_compile`` record that began before the window (compiling, or loading
from the persistent cache; the reference's executable and the small eager
programs included)."""
from benchmark.harness import step_records


def read(ctx):
    return step_records.setup_compile_s(ctx)
