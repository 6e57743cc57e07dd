"""The device as JAX reports it.  The benchmark measures the chip: where JAX
finds no accelerator, or fewer chips than the cell asks for, it stops with a
non-zero exit and prints no result.  There is no fallback to the CPU."""
from __future__ import annotations


def require(chips, allow_cpu=False):
    """The ``chips`` devices this cell runs on.  ``allow_cpu`` exists for
    ``benchmark/tests`` alone (control flow at a tiny size); ``run.py`` never
    sets it."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu" and not allow_cpu:
        raise SystemExit(
            "benchmark: JAX found no accelerator (platform cpu); a cell is "
            "measured on the chip or not at all")
    if len(devices) < chips:
        raise SystemExit(
            f"benchmark: the cell needs {chips} chips, JAX reports "
            f"{len(devices)}")
    return devices[:chips]


def describe(used):
    """The ``device`` object of the result line.  ``count`` is what JAX
    reports on this machine; ``memory_peak_bytes`` is the peak on the fullest
    of the chips the cell used (0 where the backend keeps no statistics).

    The TPU's allocator counts live arrays (``peak_bytes_in_use``) apart from
    the scratch memory it reserves for a running program
    (``peak_bytes_reserved``, the compiler's "temp" size: most of a training
    step's memory).  The peak reported is their sum: what the chip had to
    hold, exact where both peak in the same step and an upper bound within
    the size of the set-up's left-overs otherwise."""
    import jax
    every = jax.devices()
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": every[0].platform, "kind": every[0].device_kind,
            "count": len(every), "memory_peak_bytes": peak}
