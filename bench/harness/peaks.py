"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB
of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.  A device
kind that is not listed is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": dict(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
                        ici_bw=200e9),
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}") from None
