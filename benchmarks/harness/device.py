"""The device the run is on: required, named, and its memory peak."""

from __future__ import annotations

import sys

import jax


def require_tpu(chips: int):
    """The first ``chips`` TPU devices, or exit non-zero with no result."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"benchmark: no TPU: jax.devices() reports platform "
                 f"{devs[0].platform!r} ({devs[0].device_kind!r} "
                 f"x{len(devs)}); the benchmark measures on the chip only")
    if len(devs) < chips:
        sys.exit(f"benchmark: the cell needs {chips} TPU device(s), "
                 f"jax.devices() reports {len(devs)}")
    return devs[:chips]


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory(devices):
    """-> (peak bytes on the fullest device, its limit) or (None, None)
    where the backend reports no memory statistics (the CPU).

    The TPU runtime counts a loaded program's temporaries apart from the
    arrays: ``peak_bytes_in_use`` holds only the arrays (0.8 GB for ResNet-50
    at batch 128), ``peak_bytes_reserved`` the programs' scratch (7.3 GB
    there); the two come out of the same ``bytes_limit`` (seen in the largest
    free block). The peak the chip held is their sum."""
    best = (None, None)
    for d in devices:
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        if peak is None:
            continue
        peak = int(peak) + int(stats.get("peak_bytes_reserved", 0))
        if best[0] is None or peak > best[0]:
            best = (peak, stats.get("bytes_limit"))
    return best
