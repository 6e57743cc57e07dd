"""Offline Mosaic compile pre-flight for Pallas TPU kernels.

A Pallas kernel can trace and run in interpret mode and still be refused by
Mosaic: a primitive with no lowering rule (``lax.erf``), a block that breaks
the (8, 128) tiling rule, a comparison the target's vector unit lacks.  The
installed libtpu compiles for a TPU topology with no chip attached
(``jax.experimental.topologies``), so that whole class of failure is a
CPU-testable property: ``compile_for_tpu`` lowers a function for one
TPU v5e device and runs the real XLA:TPU + Mosaic compile on the host.
Nothing executes — numerics still need interpret mode or the chip.

Reference analog: the per-op kernel-availability check in
``paddle/fluid/framework/operator.cc:1161`` (ChooseKernel raises before
launch when no kernel is registered for the place) — here the "place" is
the v5e TensorCore and the check runs at test time instead of on chip.
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import SingleDeviceSharding

__all__ = ["compile_for_tpu", "mosaic_call_count",
           "assert_mosaic_lowerable", "MosaicLoweringError"]

# libtpu's default host bounds are 2x2x1, so this is the smallest v5e
# layout it describes without a chip; kernels compile for one device of it.
_TOPOLOGY = "v5e:2x2"


class MosaicLoweringError(RuntimeError):
    """A pallas kernel does not compile for the TPU."""


@functools.lru_cache(maxsize=1)
def _tpu_device():
    from jax.experimental import topologies
    return topologies.get_topology_desc(
        platform="tpu", topology_name=_TOPOLOGY).devices[0]


def compile_for_tpu(fn, *args):
    """AOT-compile ``fn(*args)`` for one TPU v5e device, Mosaic included,
    on a host with no chip.  ``args`` are arrays or ShapeDtypeStructs (only
    shape and dtype are read).  Returns the ``jax.stages.Compiled``."""
    sharding = SingleDeviceSharding(_tpu_device())
    specs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args)
    return jax.jit(fn).trace(*specs).lower(
        lowering_platforms=("tpu",)).compile()


# how a Pallas TPU kernel reads in an executable's HLO text
MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'


def mosaic_call_count(compiled) -> int:
    """Mosaic custom calls in a compiled executable's HLO."""
    return compiled.as_text().count(MOSAIC_CALL)


def assert_mosaic_lowerable(fn, *args, require_kernels=True):
    """Raise MosaicLoweringError if ``fn(*args)`` does not compile for the
    TPU; with require_kernels, also if the compiled HLO holds NO Mosaic
    call (the check would silently pass on a refactor that drops the
    kernel)."""
    try:
        compiled = compile_for_tpu(fn, *args)
    except Exception as e:      # noqa: BLE001 — jax raises several types
        raise MosaicLoweringError(
            f"does not compile for the TPU (would fail at compile time on "
            f"the chip): {type(e).__name__}: {e}") from e
    if require_kernels and mosaic_call_count(compiled) == 0:
        raise MosaicLoweringError(
            "no pallas_call found in the compiled function — preflight "
            "entry is not exercising a kernel")
