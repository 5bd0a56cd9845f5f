"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s, and
1,600 Gbit/s of inter-chip interconnect.  The same constants sit in the
program's ``repro.core.hardware``; this table is the benchmark's own copy.
A kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_per_s: float  # bf16 matrix unit; no lower float32 peak is published
    hbm_bytes_per_s: float
    hbm_bytes: float
    ici_bits_per_s: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops_per_s=197e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        ici_bits_per_s=1600e9,
        source="Google Cloud documentation, TPU v5e",
    ),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}") from None
