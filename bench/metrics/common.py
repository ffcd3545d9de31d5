"""Readers shared by the metrics whose names split one quantity by cell."""
from __future__ import annotations

from bench import peaks
from bench.harness import Reading

#: Per served query, the shapes of its ``group_filter_agg`` scan as the
#: serving plans lay it out: (columns read, groups, aggregates).
QUERY_SHAPES = {"q1": (5, 6, 5), "q6": (4, 1, 1), "q12": (4, 7, 2)}


def idle_pct(r: Reading) -> float | None:
    """100 x (1 - device busy time / traced window)."""
    if r.trace is None or not r.trace.ops:
        return None
    return r.trace.idle_pct()


def tick_ms(r: Reading) -> float | None:
    """Mean host-clock length of ``QueryServer.step`` in the window."""
    return r.spans.mean_ms("serve.step")


def gfa_roofline_pct(r: Reading, kernels: tuple[str, ...]) -> float | None:
    """The least time of every scan the window served over the summed
    device time of the ``group_filter_agg`` kernel events."""
    if r.trace is None:
        return None
    seconds = r.trace.kernel_seconds(kernels)
    batches = r.records.get("batches")
    if not seconds or not batches:
        return None
    n = r.records["rows"]
    least = 0.0
    for query, programs in batches:
        c, g, a = QUERY_SHAPES[query]
        least += peaks.least_seconds(r.device_kind, *peaks.group_filter_agg_work(c, n, g, a, programs))
    return 100.0 * least / seconds


def compact_roofline_pct(r: Reading, kernels: tuple[str, ...]) -> float | None:
    """The least time of every compaction's own bytes over the summed
    device time of the ``block_compact`` kernel events (every chunk)."""
    if r.trace is None:
        return None
    seconds = r.trace.kernel_seconds(kernels)
    counts = r.records.get("counts")
    if not seconds or not counts:
        return None
    c, n, cap = r.records["columns"], r.records["rows"], r.records["cap"]
    least = sum(peaks.least_seconds(r.device_kind, peaks.compact_bytes(c, n, k, cap), 0.0) for k in counts)
    return 100.0 * least / seconds


def device_ms_outside(r: Reading, kernels: tuple[str, ...]) -> float | None:
    """Device milliseconds per request of every op but ``kernels``."""
    if r.trace is None or not r.trace.ops:
        return None
    counts = r.records.get("counts")
    if not counts:
        return None
    return 1e3 * (r.trace.op_total_seconds() - r.trace.kernel_seconds(kernels)) / len(counts)
