"""Layer: kernels.  Milliseconds per step of device time in the latent
attention branch, forward and backward: the five latent projections, the two
latent norms, rotary, the head transposes, splits and concatenations around
them and the attention kernel.  The configuration's ``model.py`` names every
output of the branch ``layer_<i>.attention.…`` (an MTP module's
``mtp.attention.…``), and a Program op's scope carries its first output as
the instance; a grad op's first output is the gradient of such a variable,
or of the branch's input (``….attn.norm.…``).  ``None`` where no instance
of the traced program carries that name."""
import re

from benchmark.harness import program_ops

BRANCH = re.compile(r"\.attention\.|\.attn\.norm\.[\w.]*GRAD")


def read(ctx):
    t = program_ops.table(ctx)
    if t is None:
        return None
    seconds = [r["seconds"] for r in t["instances"]
               if BRANCH.search(r["instance"])]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / ctx["traced_steps"]
