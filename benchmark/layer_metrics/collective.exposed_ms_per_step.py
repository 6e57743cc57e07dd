"""Layer: collective.  Milliseconds per step in which a collective operation
ran on a device while no other operation ran there (mean over the devices):
the part of the gradient exchange that compute does not hide."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx["traced_steps"] or t["collective_s"] == 0.0:
        return None
    return 1e3 * t["collective_exposed_s"] / ctx["traced_steps"]
