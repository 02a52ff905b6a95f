"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GB of HBM at 819 GB/s.

A device kind that is not in the table is an error, not a default: a
share of a peak computed against the wrong chip's peak is a wrong number.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float   # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(197e12, 819e9, 16e9,
                         "Google Cloud documentation, TPU v5e"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {device_kind!r}; "
                         "add them to bench/peaks.py with their source") from None
