"""The program's own tracing: host spans on the profiler's clock, and a
bounded ring of the query server's ticks and of garbage collections.

:func:`span` is a ``jax.profiler.TraceAnnotation``: in a profiled run it
lies on the trace's clock beside the device's ops, with its arguments as
the event's stats; with no profiler running it is an empty context.  Span
names start with ``serve.`` (the query server) or ``pushdown.`` (the
sharded scan, ``engine.ops.ShardScan``).  A span opened inside a tick
carries the tick's id and, once the tick has taken its requests, their
query and uids.

:data:`RECORDER` keeps, for the whole process, the last :data:`RING` ticks
(:class:`Tick`) and garbage collections (:class:`GcPause`).  Each sharded
scan keeps its own :class:`Exchange` counters.  The trace, the ring and
the counters are the only outputs: nothing is written to a file.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import itertools
import threading
import time
from typing import Any, Iterator, Sequence

from jax.profiler import TraceAnnotation

#: Ticks and collections each ring keeps.
RING = 65_536

_OFF = contextlib.nullcontext()


@dataclasses.dataclass
class Tick:
    """One ``QueryServer.step``: the requests it took, the kernel slots it
    filled, how long they had queued, and its span on the host clock
    (``time.perf_counter``)."""

    tick: int
    start_s: float
    end_s: float = 0.0
    query: str = ""
    requests: int = 0
    slots: int = 0  # kernel slots of its call, padding included
    queue_wait_s: float = 0.0  # summed over its requests: admission -> start_s
    args: dict[str, Any] = dataclasses.field(default_factory=dict)  # of its spans

    def take(self, batch: Sequence[Any]) -> None:
        """Record the requests the tick took (each with ``uid``, ``query``
        and ``admitted_s``) and name them on its spans."""
        self.query = batch[0].query
        self.requests = len(batch)
        self.queue_wait_s = sum(max(0.0, self.start_s - r.admitted_s) for r in batch)
        self.args.update(query=self.query, uids=[r.uid for r in batch])


@dataclasses.dataclass(frozen=True)
class GcPause:
    """One garbage collection on the host clock."""

    generation: int
    start_s: float
    end_s: float


@dataclasses.dataclass
class Exchange:
    """Counters of a sharded scan: requests launched, the bytes the
    consumer received from the other owners over the chips' links, and
    owners whose qualifying rows overflowed their capacity."""

    requests: int = 0
    bytes_exchanged: int = 0
    overflows: int = 0


class Recorder:
    """The rings of ticks and collections, and the tick open on each thread."""

    def __init__(self, capacity: int = RING):
        self.ticks: collections.deque[Tick] = collections.deque(maxlen=capacity)
        self.gc_pauses: collections.deque[GcPause] = collections.deque(maxlen=capacity)
        self._ids = itertools.count()
        self._local = threading.local()
        self._gc_open: tuple[int, float, Any] | None = None
        self._gc_hooked = False
        self._gc_lock = threading.Lock()

    def current(self) -> Tick | None:
        return getattr(self._local, "tick", None)

    @contextlib.contextmanager
    def tick(self) -> Iterator[Tick]:
        """A ``serve.tick`` span; its :class:`Tick` joins the ring when it ends."""
        t = Tick(next(self._ids), time.perf_counter())
        t.args["tick"] = t.tick
        self._local.tick = t
        annotation = span("serve.tick")
        try:
            with annotation:
                yield t
                if t.requests and annotation is not _OFF:
                    annotation.set_metadata(query=t.query, uids=t.args["uids"])
        finally:
            self._local.tick = None
            t.end_s = time.perf_counter()
            self.ticks.append(t)

    def hook_gc(self) -> None:
        """Turn each garbage collection of the process into a ``serve.gc``
        span and a :class:`GcPause`; once per recorder."""
        with self._gc_lock:
            if not self._gc_hooked:
                self._gc_hooked = True
                gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict[str, int]) -> None:
        if phase == "start":
            annotation = span("serve.gc", generation=info["generation"])
            annotation.__enter__()
            self._gc_open = (info["generation"], time.perf_counter(), annotation)
        elif self._gc_open is not None:
            generation, start_s, annotation = self._gc_open
            self._gc_open = None
            annotation.__exit__(None, None, None)
            self.gc_pauses.append(GcPause(generation, start_s, time.perf_counter()))


RECORDER = Recorder()


def span(name: str, **args: Any):
    """A ``TraceAnnotation`` named ``name`` while the profiler runs, else
    an empty context.  Inside a tick it carries the tick's arguments too."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    t = RECORDER.current()
    return TraceAnnotation(name, **(t.args | args if t is not None else args))
