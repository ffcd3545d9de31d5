"""Share of the traced window the host spent in garbage collections: the
summed ``serve.gc`` spans inside the window over the window.  Read only
from a program that records its ticks, so that no span reads as 0."""
from bench.harness import Reading
from bench.metrics.program_ticks import GC, TICK, spans


def read(r: Reading) -> float | None:
    if not spans(r, TICK):
        return None
    lo, hi = r.trace.window
    inside_ns = sum(max(0.0, min(s.end_ns, hi) - max(s.start_ns, lo)) for s in spans(r, GC))
    return 100.0 * inside_ns * 1e-9 / r.trace.window_s
