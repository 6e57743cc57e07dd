"""Layer: kernels.  Milliseconds per step of device time in the state-space
mixers, forward and backward: the pre-norm, ``in_proj``, the causal
convolution and its activation, ``x_proj``, ``dt_proj`` and its softplus,
``exp(A_log)``, the scan, the gate and ``out_proj``.  The configuration's
``model.py`` names every output of the mixer ``layer_<i>.ssm.…``, and a
Program op's scope carries its first output as the instance (a grad op's is
the gradient of such a variable).  ``None`` where no instance of the traced
program carries that name."""
from benchmark.harness import program_ops

BRANCH = ".ssm."


def read(ctx, branch=BRANCH):
    t = program_ops.table(ctx)
    if t is None:
        return None
    seconds = [r["seconds"] for r in t["instances"]
               if branch in r["instance"]]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / ctx["traced_steps"]
