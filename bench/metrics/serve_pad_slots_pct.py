"""Share of the kernel's program slots that padding filled: over the
window's tick records, 100 x (slots - requests) / slots, each batch being
padded to a power of two."""
from bench.harness import Reading
from bench.metrics.program_ticks import window_ticks


def read(r: Reading) -> float | None:
    ticks = window_ticks(r)
    slots = sum(t.slots for t in ticks or ())
    return 100.0 * (slots - sum(t.requests for t in ticks)) / slots if slots else None
