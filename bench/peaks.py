"""Peaks of each device the benchmark runs on, and the least time an
operator's own work can take on it.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
HBM at 819 GB/s per chip.  A device missing from the table is an error.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops_per_s: float
    hbm_bytes_per_s: float
    source: str


PEAKS = {
    "TPU v5 lite": Peak(197e12, 819e9, "Google Cloud, TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM"),
}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to bench/peaks.py") from None


def least_seconds(device_kind: str, bytes_: float, flops: float) -> float:
    """max(bytes / HBM peak, flops / compute peak)."""
    p = peak(device_kind)
    return max(bytes_ / p.hbm_bytes_per_s, flops / p.flops_per_s)


def group_filter_agg_work(c: int, n: int, groups: int, aggs: int, programs: int) -> tuple[float, float]:
    """(bytes, flops) of one scan-shared grouped filter-aggregate: read the
    ``[c, n]`` f32 columns and ``[n]`` i32 keys once, and for each of
    ``programs`` requests add each row's ``aggs`` values and count into its
    group (``2 * groups * (aggs + 1)`` operations a row, as the one-hot
    product counts them)."""
    return 4.0 * (c + 1) * n, 2.0 * groups * (aggs + 1) * n * programs


def compact_bytes(c: int, n: int, count: int, cap: int) -> float:
    """Bytes of one compaction: read ``[c, n]`` f32 rows, write the
    ``[c, min(count, cap)]`` rows that qualify."""
    return 4.0 * c * (n + min(count, cap))
