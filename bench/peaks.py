"""Published per-chip peak rates, keyed by `jax.Device.device_kind`, for the
roofline shares that cells running a kernel report. A device that is not in
the table is an error, not a default. Copied from the program's
`repro.analysis.roofline.PEAKS` so that the yardstick cannot move."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops: float  # bf16 FLOP/s
    hbm_bw: float  # bytes/s
    ici_bw: float  # bytes/s per chip-to-chip link
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": DevicePeaks(
        flops=197e12, hbm_bw=819e9,
        ici_bw=50e9,  # 1,600 Gbit/s of interconnect over 4 links
        hbm_bytes=16e9, source='Google Cloud documentation, "TPU v5e"'),
}


def peaks(kind: str) -> DevicePeaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}") from None
