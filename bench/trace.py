"""Host spans of the benchmark, and the reduction of a profiler trace to
device busy time, per-op device time and idle gaps.

Spans are ``jax.profiler.TraceAnnotation``s, so in a traced run they lie on
the profiler's clock beside the device's ops; their host-clock durations
are also summed here for the metrics that read spans alone.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import glob
import os
import re
import time

#: The span around the measured window; a trace is reduced inside it.
WINDOW = "bench.window"
#: Prefixes of the benchmark's own host spans.
SPAN_PREFIXES = ("bench.", "serve.", "scan.")
#: Device-plane lines that hold one event per executed op, and one per
#: executed program (jitted function).
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


class Spans:
    """Named host spans: ``with spans("serve.step"): ...``."""

    def __init__(self):
        self.total_s: dict[str, float] = collections.defaultdict(float)
        self.count: dict[str, int] = collections.defaultdict(int)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.total_s[name] += time.perf_counter() - t0
            self.count[name] += 1

    def mean_ms(self, name: str) -> float | None:
        n = self.count.get(name, 0)
        return 1e3 * self.total_s[name] / n if n else None


def op_base(name: str) -> str:
    """The HLO instruction of a device op's event name, without its ``%``
    and numeric suffix: ``%block_compact.1 = (...) custom-call(...)`` ->
    ``block_compact``."""
    return re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))


def is_kernel(name: str, bases) -> bool:
    """Whether an op is a Pallas kernel (a ``tpu_custom_call``) whose
    instruction is named after one of ``bases``."""
    return "tpu_custom_call" in name and op_base(name) in bases


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """Device ops per chip and the benchmark's host spans of one trace,
    cut to the measured window."""

    ops: dict[str, list[Event]]  # device plane name -> ops sorted by start
    spans: list[Event]  # the benchmark's host spans, sorted by start
    window: tuple[float, float]  # (start_ns, end_ns) of WINDOW
    modules: dict[str, list[Event]] = dataclasses.field(default_factory=dict)  # plane -> programs

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(_union_ns(ev, *self.window) for ev in self.ops.values()) * 1e-9 / len(self.ops)

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def all_ops(self) -> list[Event]:
        return [e for evs in self.ops.values() for e in evs]

    def op_seconds(self) -> dict[str, float]:
        """Device seconds per op, summed over chips and calls, keyed
        ``<program>/<op>``: the jitted function that ran it (``jit_`` and
        the hash dropped) and :func:`op_base` of the op."""
        out: dict[str, float] = collections.defaultdict(float)
        for plane, evs in self.ops.items():
            mods = self.modules.get(plane, [])
            starts = [m.start_ns for m in mods]
            for e in evs:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i].name if i >= 0 and mods[i].end_ns >= e.start_ns else "?"
                mod = re.sub(r"^jit_|\(\d+\)$", "", mod)
                out[f"{mod}/{op_base(e.name)}"] += e.dur_ns * 1e-9
        return dict(out)

    def kernel_seconds(self, bases) -> float:
        """Device seconds of the Pallas kernels named after ``bases``."""
        return sum(e.dur_ns for e in self.all_ops() if is_kernel(e.name, bases)) * 1e-9

    def op_total_seconds(self) -> float:
        return sum(e.dur_ns for e in self.all_ops()) * 1e-9

    def idle_gaps(self, top: int = 10) -> list[tuple[str, float]]:
        """The longest device-idle gaps in the window, each named by the
        innermost benchmark span open at its midpoint (``idle`` if none)."""
        gaps = []
        for evs in self.ops.values():
            cursor = self.window[0]
            for e in evs + [Event("", self.window[1], 0.0)]:
                start = max(e.start_ns, self.window[0])
                if start > cursor:
                    gaps.append((cursor, min(start, self.window[1])))
                cursor = max(cursor, min(e.end_ns, self.window[1]))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [(self._span_at((a + b) / 2), (b - a) * 1e-9) for a, b in gaps[:top] if b > a]

    def _span_at(self, t: float) -> str:
        best = None
        for s in self.spans:
            if s.start_ns > t:
                break
            if s.end_ns >= t and s.name != WINDOW and (best is None or s.start_ns >= best.start_ns):
                best = s
        return best.name if best else "idle"


def _union_ns(events: list[Event], lo: float, hi: float) -> float:
    total, cursor = 0.0, lo
    for e in events:
        a, b = max(e.start_ns, cursor), min(e.end_ns, hi)
        if b > a:
            total += b - a
            cursor = b
    return total


def start(trace_dir: str) -> None:
    """Start the profiler with the host tracer on and the Python tracer
    off: spans and device ops, without an event for every Python call."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, found {found}")
    return found[0]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb``: the ops of every TPU plane and the
    benchmark's spans, cut to the WINDOW span."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict[str, list[Event]] = {}
    modules: dict[str, list[Event]] = {}
    spans: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for lines, into in ((OP_LINES, ops), (MODULE_LINES, modules)):
                evs = [
                    Event(e.name, e.start_ns, e.duration_ns)
                    for line in plane.lines if line.name in lines for e in line.events
                ]
                if evs:
                    into[plane.name] = sorted(evs, key=lambda e: e.start_ns)
        elif plane.name.startswith("/host:"):
            spans += [
                Event(e.name, e.start_ns, e.duration_ns)
                for line in plane.lines for e in line.events
                if e.name.startswith(SPAN_PREFIXES)
            ]
    spans.sort(key=lambda e: e.start_ns)
    windows = [s for s in spans if s.name == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} span in {path}, found {len(windows)}")
    w = windows[0]
    inside = lambda e: e.end_ns > w.start_ns and e.start_ns < w.end_ns  # noqa: E731
    return Trace(
        ops={k: [e for e in v if inside(e)] for k, v in ops.items()},
        spans=[s for s in spans if inside(s)],
        window=(w.start_ns, w.end_ns),
        modules={k: [e for e in v if inside(e)] for k, v in modules.items()},
    )


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The trace's ten costliest device ops and ten longest idle gaps."""
    ops = sorted(trace.op_seconds().items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": [[n, s] for n, s in trace.idle_gaps(top)]}
