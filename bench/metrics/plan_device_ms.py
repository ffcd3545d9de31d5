"""Device time per scan request of every op outside the compaction kernel:
the column stack, the padding and the predicate mask of the plan."""
from bench.harness import Reading
from bench.metrics.common import device_ms_outside

KERNELS = ("block_compact",)


def read(r: Reading) -> float | None:
    return device_ms_outside(r, KERNELS)
