"""Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.

Copied from ``bench.DEVICE_PEAKS`` (the original is listed in PERF.md for a
later PR to delete).  A device that is not in the table is an error, never a
default: a utilization against a guessed peak is not a measurement.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
                  '16 GB HBM2e at 819 GB/s per chip',
    },
}


def peaks_of(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}: add it to "
            f"benchmark/harness/peaks.py with its source") from None


def roofline_seconds(flops, nbytes, peaks):
    """(least seconds the chip could take, which peak bounds it)."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
