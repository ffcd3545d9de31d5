"""Host milliseconds of a serving tick: the mean over the window's
``serve.tick`` spans of each tick less its ``serve.wait`` child (the
block until the kernel's results are ready)."""
from bench.harness import Reading
from bench.metrics.program_ticks import TICK, WAIT, spans


def read(r: Reading) -> float | None:
    ticks = spans(r, TICK)
    if not ticks:
        return None
    waits = spans(r, WAIT)
    host_ns = 0.0
    for t in ticks:
        inside = (w.dur_ns for w in waits if t.start_ns <= w.start_ns and w.end_ns <= t.end_ns)
        host_ns += t.dur_ns - sum(inside)
    return 1e-6 * host_ns / len(ticks)
