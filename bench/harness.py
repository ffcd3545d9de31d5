"""What a driver hands the harness, and what the harness hands a metric's
reader.  A driver sets up one cell, measures its window and returns an
:class:`Outcome`; the harness then reads the device's memory peak, lets the
outcome check the answers against the plain reference, and passes a
:class:`Reading` to each per-layer metric's ``read``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import shutil
import sys
import tempfile
from typing import Any, Callable

from bench import trace as trace_mod

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration", "/jax/core/compile/backend_compile_duration")


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict  # bench/configs/<config>.json
    traffic: dict  # bench/workloads/<traffic>.json
    chips: int


@dataclasses.dataclass(frozen=True)
class Check:
    """One number compared with its limit; the run is correct when every
    value is at most its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return not math.isnan(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    window_start: float  # perf_counter() at the start of the measured window
    e2e: dict[str, float]  # end-to-end metrics the driver measured, by name
    attempted: int
    records: dict[str, Any]  # counts and samples of the window, for the readers
    verify: Callable[[], list[Check]]  # frees the program's state, then compares
    failed: Callable[[list[Check]], int] = lambda checks: 0


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's ``read(reading)`` may look at."""

    cell: Cell
    device_kind: str
    records: dict[str, Any]
    spans: trace_mod.Spans
    trace: trace_mod.Trace | None  # None without --trace 1


def say(**fields) -> None:
    """One line of progress on stdout (never the last line)."""
    print(json.dumps(fields), flush=True)


class Window:
    """The measured window: a WINDOW span, the profiler on around it when
    traced, and a count of the compilations that happened inside it."""

    def __init__(self, spans: trace_mod.Spans, traced: bool):
        self.spans, self.traced = spans, traced
        self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
        self.compiles = 0
        self._open = False

    def _listen(self, event: str, *args, **kwargs) -> None:
        if self._open and event in COMPILE_EVENTS:
            self.compiles += 1

    @contextlib.contextmanager
    def __call__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        if self.traced:
            trace_mod.start(self.trace_dir)
        self._open = True
        try:
            with self.spans(trace_mod.WINDOW):
                yield
        finally:
            self._open = False
            if self.traced:
                jax.profiler.stop_trace()

    def load_trace(self) -> trace_mod.Trace | None:
        if not self.traced:
            return None
        try:
            return trace_mod.load(trace_mod.find_xplane(self.trace_dir))
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


def eprint(*args) -> None:
    print(*args, file=sys.stderr, flush=True)
