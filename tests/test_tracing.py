"""The program's own tracing of the query server: its spans on the
profiler's clock, its per-tick records (padded slots, queue wait), the
garbage-collection hook and the bounded ring."""
from __future__ import annotations

import dataclasses
import gc
import glob
import time

import jax
import pytest
from jax.profiler import ProfileData

from repro.core import tracing
from repro.engine import datagen, queries
from repro.runtime.requests import QueryCompletion, QueryRequest
from repro.runtime.serve_query import QueryServer

ROWS = 2_000
PHASES = ["serve.coalesce", "serve.consts", "serve.launch", "serve.wait", "serve.demux"]
Q6 = [{"year": 1993 + i % 5, "discount": 0.02 + 0.01 * (i % 7), "qty": 24.0 + i % 2} for i in range(8)]


@pytest.fixture(scope="module")
def server():
    li = datagen.lineitem(jax.random.PRNGKey(0), rows=ROWS)
    plans = queries.make_serving_plans(li)
    return QueryServer(plans, max_batch=8)


def _serve(server, params, query="q6"):
    for i, p in enumerate(params):
        assert server.submit(QueryRequest(uid=100 + i, query=query, params=p))
    done = server.step()
    assert [c.uid for c in done] == [100 + i for i in range(len(params))]
    return tracing.RECORDER.ticks[-1]


def _host_spans(trace_dir) -> list[tuple[str, int, int, dict]]:
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            out += [
                (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                for line in plane.lines for e in line.events if e.name.startswith("serve.")
            ]
    return sorted(out, key=lambda s: s[1])


@pytest.mark.parametrize("batch", [1, 3])
def test_step_spans_are_named_nested_and_in_order(server, tmp_path, batch):
    _serve(server, Q6[:batch])  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        tick = _serve(server, Q6[:batch])
    finally:
        jax.profiler.stop_trace()
    spans = [s for s in _host_spans(tmp_path) if s[0] != "serve.gc"]
    assert spans[0][0] == "serve.tick"
    name, start, end, args = spans[0]
    assert args["tick"] == tick.tick and args["query"] == "q6"
    assert args["uids"] == str([100 + i for i in range(batch)])
    assert [s[0] for s in spans[1:]] == PHASES
    # One wait a tick: serve_host_ms subtracts every wait inside a tick.
    assert [s[0] for s in spans].count("serve.wait") == 1
    for phase, a, b, phase_args in spans[1:]:
        assert start <= a <= b <= end, phase
        assert phase_args["tick"] == tick.tick, phase
    for (_, _, b, _), (_, a, _, _) in zip(spans[1:], spans[2:]):
        assert b <= a  # the phases follow one another
    # Every phase after the coalescing names the requests it serves.
    assert all(s[3]["uids"] == args["uids"] for s in spans[2:])


def test_spans_cost_nothing_without_the_profiler():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert tracing.span("serve.tick", tick=1) is tracing.span("serve.wait")


@pytest.mark.parametrize("batch,slots", [(1, 1), (2, 2), (5, 8), (8, 8)])
def test_ticks_count_padded_slots(server, batch, slots):
    calls = server.kernel_calls
    tick = _serve(server, Q6[:batch])
    assert (tick.requests, tick.slots, tick.query) == (batch, slots, "q6")
    assert tick.slots - tick.requests == slots - batch  # 5 requests: 3 padding slots
    assert server.kernel_calls == calls + 1
    assert tick.start_s <= tick.end_s


def test_queue_wait_runs_from_admission_to_the_tick(server):
    for i, p in enumerate(Q6[:3]):
        server.submit(QueryRequest(uid=i, query="q6", params=p))
    admitted = [r.admitted_s for r in server.queue]
    time.sleep(0.02)
    before = time.perf_counter()
    server.step()
    tick = tracing.RECORDER.ticks[-1]
    assert all(a <= tick.start_s for a in admitted)
    assert tick.queue_wait_s == pytest.approx(sum(tick.start_s - a for a in admitted))
    assert 3 * 0.02 <= tick.queue_wait_s <= sum(before - a for a in admitted) + 3 * (tick.start_s - before)


def test_queue_wait_is_never_negative():
    t = tracing.Tick(tick=0, start_s=10.0)
    late = QueryRequest(uid=1, query="q6", params={}, admitted_s=10.5)  # admitted during the tick
    early = QueryRequest(uid=2, query="q6", params={}, admitted_s=9.0)
    t.take([late, early])
    assert t.queue_wait_s == 1.0 and t.requests == 2


def test_an_empty_step_is_a_tick_without_requests(server):
    assert len(server.queue) == 0
    n = len(tracing.RECORDER.ticks)
    assert server.step() == []
    tick = tracing.RECORDER.ticks[-1]
    assert len(tracing.RECORDER.ticks) == min(n + 1, tracing.RING)
    assert (tick.requests, tick.slots, tick.queue_wait_s) == (0, 0, 0.0)


def test_a_collection_is_one_gc_record_and_one_span(server, tmp_path):
    assert gc.callbacks.count(tracing.RECORDER._on_gc) == 1
    QueryServer(server.plans)  # a second server hooks nothing more
    assert gc.callbacks.count(tracing.RECORDER._on_gc) == 1
    t0 = time.perf_counter()
    gc.collect()
    new = [p for p in tracing.RECORDER.gc_pauses if p.start_s >= t0]
    assert [p.generation for p in new] == [2]
    assert new[0].start_s <= new[0].end_s

    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    gcs = [s for s in _host_spans(tmp_path) if s[0] == "serve.gc" and s[3]["generation"] == 2]
    assert len(gcs) == 1


def test_the_rings_stay_bounded():
    rec = tracing.Recorder(capacity=3)
    for _ in range(10):
        with rec.tick():
            pass
    assert [t.tick for t in rec.ticks] == [7, 8, 9]
    assert tracing.RECORDER.ticks.maxlen == tracing.RECORDER.gc_pauses.maxlen == tracing.RING


def test_completions_no_longer_carry_a_service_time():
    assert "service_s" not in {f.name for f in dataclasses.fields(QueryCompletion)}
