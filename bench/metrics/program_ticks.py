"""Readers shared by the metrics of the program's own serving spans
(``serve.tick`` and its phases, ``serve.gc``) and tick records
(``repro.core.tracing.RECORDER``).

No tick runs after the window, so the window's records are the last K of
the program's ring, K being the ``serve.tick`` spans in the trace.  A
program without these spans or records reads as nothing."""
from __future__ import annotations

from bench.harness import Reading
from bench.trace import Event

TICK, WAIT, GC = "serve.tick", "serve.wait", "serve.gc"


def spans(r: Reading, name: str) -> list[Event]:
    """The trace's spans named ``name``; none without a trace."""
    return [s for s in r.trace.spans if s.name == name] if r.trace is not None else []


def window_ticks(r: Reading) -> list | None:
    """The program's records of the window's ticks, oldest first."""
    k = len(spans(r, TICK))
    if not k:
        return None
    try:
        from repro.core.tracing import RECORDER
    except ImportError:
        return None
    ticks = list(RECORDER.ticks)[-k:]
    return ticks if len(ticks) == k else None
