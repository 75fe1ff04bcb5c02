"""Published peaks per device kind, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
inter-chip interconnect.  Copied from the program's
``analysis/roofline.PEAKS`` so that no change to the program can move the
yardstick.  A device kind not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict, NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float      # FLOP/s
    hbm_bytes: float       # bytes/s
    hbm_capacity: float    # bytes


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes=819e9,
                         hbm_capacity=16e9),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None
