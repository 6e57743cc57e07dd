"""Layer: device.  Share of the traced slice in which no operation ran on the
device, in percent; over several devices, that of the idlest."""


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s_min"] / t["window_s"])
