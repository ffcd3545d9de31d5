"""Readers shared by the metrics of the exchange across chips: the device
time of each chip's collective ops, and the part of it in which no other op
runs on that chip.

A collective is an op whose HLO instruction is a ``collective-permute``,
``all-gather``, ``all-to-all``, ``all-reduce`` or ``reduce-scatter``.  An
asynchronous one runs from its ``-start`` to the matching ``-done`` (the
earliest open start of its kind); a synchronous one is its own event.
Times are unions of intervals, so rounds in flight together count once."""
from __future__ import annotations

import bisect
import re

from bench.trace import Event, op_base

KINDS = ("collective-permute", "all-gather", "all-to-all", "all-reduce", "reduce-scatter")
_COLLECTIVE = re.compile(rf"^({'|'.join(KINDS)})(-start|-done)?$")


def split(ops: list[Event]) -> tuple[list[tuple[float, float]], list[tuple[float, float]]]:
    """(collective intervals, intervals of every other op) of one chip's
    ops, sorted by start."""
    coll, other, open_starts = [], [], {}
    for e in ops:
        m = _COLLECTIVE.match(op_base(e.name))
        if m is None:
            other.append((e.start_ns, e.end_ns))
        elif m.group(2) == "-start":
            open_starts.setdefault(m.group(1), []).append(e)
        elif m.group(2) == "-done" and open_starts.get(m.group(1)):
            coll.append((open_starts[m.group(1)].pop(0).start_ns, e.end_ns))
        else:
            coll.append((e.start_ns, e.end_ns))
    coll += [(e.start_ns, e.end_ns) for starts in open_starts.values() for e in starts]
    return sorted(coll), sorted(other)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Disjoint intervals covering the same time, sorted."""
    out: list[tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length_ns(intervals: list[tuple[float, float]]) -> float:
    return sum(b - a for a, b in union(intervals))


def exposed_ns(coll: list[tuple[float, float]], other: list[tuple[float, float]]) -> float:
    """Time inside ``coll`` during which no interval of ``other`` runs."""
    busy = union(other)
    starts = [x for x, _ in busy]
    covered = 0.0
    for a, b in union(coll):
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(busy) and busy[i][0] < b:
            covered += max(0.0, min(b, busy[i][1]) - max(a, busy[i][0]))
            i += 1
    return length_ns(coll) - covered
