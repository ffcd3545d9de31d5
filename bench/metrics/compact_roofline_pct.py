"""``block_compact``'s share of its roofline: the bytes each compaction
must move (read the ``[4, N]`` f32 columns, write the ``[4, count]`` rows
that qualify) at the HBM peak, over the kernel's summed device time, every
chunk of the streaming variant included.  The trace names the resident and
the streaming kernels' events ``block_compact``, after the jitted wrapper."""
from bench.harness import Reading
from bench.metrics.common import compact_roofline_pct

KERNELS = ("block_compact",)


def read(r: Reading) -> float | None:
    return compact_roofline_pct(r, KERNELS)
