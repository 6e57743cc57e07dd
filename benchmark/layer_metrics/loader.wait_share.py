"""Layer: loader.  Share of the window the step loop stood waiting for its
next batch (the benchmark's ``loader`` span around ``Prefetcher.get``), in
percent.  Near 0 while the loader keeps ahead of the device."""


def read(ctx):
    if not ctx["steps"]:
        return None
    return 100.0 * sum(s[0] for s in ctx["steps"]) / ctx["window_s"]
