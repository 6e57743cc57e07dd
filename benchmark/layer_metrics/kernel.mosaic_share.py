"""Layer: kernels.  Share of the device's busy time inside Mosaic (Pallas)
custom calls, in percent.  0 in a partitioned program, where the ops keep
their XLA lowering."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t["busy_s"] == 0.0:
        return None
    return 100.0 * t["mosaic_s"] / t["busy_s"]
