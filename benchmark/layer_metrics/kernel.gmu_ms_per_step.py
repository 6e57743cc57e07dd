"""Layer: kernels.  Milliseconds per step of device time in the gated memory
units, forward and backward: the pre-norm, the projection, the gate on
another layer's scan output and the output projection.  The configuration's
``model.py`` names every output of the unit ``layer_<i>.gmu.…``
(``kernel.ssm_layer_ms_per_step`` has how an instance is found).  ``None``
where no instance of the traced program carries that name."""
import os

from benchmark.harness import registry


def read(ctx):
    return registry.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "kernel.ssm_layer_ms_per_step.py")).read(ctx, ".gmu.")
