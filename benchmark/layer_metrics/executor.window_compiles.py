"""Layer: executor.  Whole-block compiles inside the measured window: the
program's ``executor.compile_cache_miss`` counter, after less before.  Must be
0: every shape was warmed up in set-up."""


def read(ctx):
    return ctx["counters"].get("executor.compile_cache_miss")
