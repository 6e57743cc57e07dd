"""Layer: device.  Milliseconds per step in which an operation ran on the
device (mean over the devices)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx["traced_steps"]:
        return None
    return 1e3 * t["busy_s"] / ctx["traced_steps"]
