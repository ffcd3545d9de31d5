"""The inter-chip interconnect's peak of each device the benchmark runs on.

Source: Google Cloud documentation, "TPU v5e": 1,600 Gbit/s of
chip-to-chip interconnect (ICI) bandwidth per chip, 200 GB/s.  A device
missing from the table is an error.
"""
from __future__ import annotations

ICI_BYTES_PER_S = {
    "TPU v5 lite": 200e9,
}
SOURCE = "Google Cloud, TPU v5e: 1,600 Gbps inter-chip interconnect per chip"


def ici_bytes_per_s(device_kind: str) -> float:
    try:
        return ICI_BYTES_PER_S[device_kind]
    except KeyError:
        raise KeyError(f"no interconnect peak for device kind {device_kind!r}; add it to bench/ici.py") from None
