"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A copy kept with the benchmark, so that no change to the program can move
the yardstick. A kind that is not in the table is an error, never a
default: a share of a peak read against the wrong chip is not a number.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float   # FLOP/s
    hbm_bytes: float    # bytes
    hbm_bw: float       # bytes/s
    ici_bw: float       # bytes/s, the chip's interconnect in all
    source: str


#: TPU v5e: 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
#: chip-to-chip interconnect.
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(197e12, 16e9, 819e9, 200e9,
                         source='Google Cloud documentation, "TPU v5e"'),
}


def peaks(device_kind: str) -> Peaks:
    """The row for ``device_kind``; raises KeyError for a kind not in
    :data:`PEAKS`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
