"""Milliseconds a request queued: over the window's tick records, the
summed wait from each request's admission to the start of the tick that
took it, over the requests."""
from bench.harness import Reading
from bench.metrics.program_ticks import window_ticks


def read(r: Reading) -> float | None:
    ticks = window_ticks(r)
    requests = sum(t.requests for t in ticks or ())
    return 1e3 * sum(t.queue_wait_s for t in ticks) / requests if requests else None
