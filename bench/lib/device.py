"""The chips a run stands on: a run that finds no TPU, or fewer chips than
its cell asks for, reports nothing."""
from __future__ import annotations

import jax


class NoChip(RuntimeError):
    pass


def require(chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (first device: {devs[0].platform})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)
