"""Layer: kernels.  Milliseconds per step of device time in the differential
attention mixers (self and cross), forward and backward: the pre-norm, the
projections, the head transposes, the kernel call, the lambdas, ``a1 - lam
a2``, the sub-norm and the output projection.  The configuration's
``model.py`` names every output of the mixer ``layer_<i>.attention.…``
(``kernel.ssm_layer_ms_per_step`` has how an instance is found).  ``None``
where no instance of the traced program carries that name."""
import os

from benchmark.harness import registry


def read(ctx):
    return registry.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "kernel.ssm_layer_ms_per_step.py")).read(ctx, ".attention.")
