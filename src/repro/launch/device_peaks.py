"""Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.

The benchmark's roofline terms divide by these.  A device that is not in
the table is an error, never a default: a roofline share against another
chip's peaks would be a wrong number with the right name.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops_bf16: float   # FLOP/s
    hbm_bw: float       # B/s
    ici_link_bw: float  # B/s per inter-chip link
    source: str


PEAKS = {
    # 1,600 Gbit/s of interconnect per chip over its 4 links
    "TPU v5 lite": DevicePeaks(flops_bf16=197e12, hbm_bw=819e9,
                               ici_link_bw=50e9,
                               source="Google Cloud, TPU v5e"),
}


def device_peaks(device_kind: str) -> DevicePeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
