"""Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.

Copied from the program's ``launch/device_peaks.py`` so that the
benchmark's roofline terms cannot move with the program.  A device that is
not in the table is an error, never a default: a roofline share against
another chip's peaks would be a wrong number with the right name.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops_bf16: float   # FLOP/s
    hbm_bw: float       # B/s
    hbm_bytes: float    # B of device memory
    source: str


PEAKS = {
    "TPU v5 lite": DevicePeaks(
        flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def device_peaks(device_kind: str) -> DevicePeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
