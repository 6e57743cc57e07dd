"""Layer: executor.  Executables of a second or more that XLA compiled
during set-up because the persistent cache did not hold them
(``xla_compile`` records before the window with ``cache_hit`` false).  0 in
a warm run; the first thing to read when a pair's ``setup_s`` differ."""
from benchmark.harness import step_records


def read(ctx):
    return step_records.setup_cache_misses(ctx)
