"""``group_filter_agg``'s share of its roofline: the least time of the
window's scans (bytes: the ``[C, N]`` f32 columns and ``[N]`` i32 keys one
scan reads; operations: ``2 G (A + 1) N B``) over the kernel's summed
device time.  The trace names the kernel's events after the jitted
wrappers ``group_filter_agg`` (one request) and ``group_filter_agg_multi``
(a scan-shared batch)."""
from bench.harness import Reading
from bench.metrics.common import gfa_roofline_pct

KERNELS = ("group_filter_agg", "group_filter_agg_multi")


def read(r: Reading) -> float | None:
    return gfa_roofline_pct(r, KERNELS)
