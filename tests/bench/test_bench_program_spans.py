"""The readers of the program's own serving spans and tick records, in a
traced run of ``serve_closed_16`` on the CPU at a tiny scale, on a program
that records neither (as an older one does), and on a small trace recorded
on a TPU v5e (``fixtures/serve_program_spans.xplane.pb``, written by
``bench/record_fixture.py``: three q6 ticks and two compactions over
1,048,576 rows, the ticks holding the program's spans)."""
from __future__ import annotations

import dataclasses
import importlib
import io
import json
import types
from contextlib import redirect_stdout
from pathlib import Path

import jax
import pytest

from bench import harness, run, trace
from bench.metrics import program_ticks

MAN = run.manifest()
SCALE = 0.001  # 6,001 lineitem rows, 1,500 orders
METRICS = ("serve_host_ms", "serve_queue_wait_ms", "serve_pad_slots_pct", "serve_gc_pct")
FIXTURE = Path(__file__).parent / "fixtures" / "serve_program_spans.xplane.pb"
PHASES = ("serve.coalesce", "serve.consts", "serve.launch", "serve.demux", "serve.wait")
PROGRAM = {"serve.tick", "serve.gc", *PHASES}


def _traced_run(monkeypatch, clients: int) -> tuple[dict, harness.Reading]:
    cell = run.load_cell(MAN, "serve_closed_16")
    cell = dataclasses.replace(cell, config=dict(cell.config, scale=SCALE), traffic=dict(cell.traffic, clients=clients))
    readings = []
    reader = run.reader

    def spy(name):
        read = reader(name)

        def record(r):
            readings.append(r)
            return read(r)

        return record

    monkeypatch.setattr(run, "reader", spy)
    driver = importlib.import_module("bench.drivers.serve")
    args = types.SimpleNamespace(seed=2**33 + 11, seconds=0.5, trace=1)
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.finish(MAN, cell, jax.devices(), args, driver.run) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), readings[0]


def _pad_pct(batches) -> float:
    slots = sum(1 if b == 1 else 1 << (b - 1).bit_length() for _, b in batches)
    return 100.0 * (slots - sum(b for _, b in batches)) / slots


@pytest.mark.parametrize("clients", [16, 3])
def test_a_traced_serving_run_reports_the_program_metrics(monkeypatch, clients):
    res, r = _traced_run(monkeypatch, clients)
    assert res["correct"] is True, res["checks"]
    got = {m: res["metrics"][m]["value"] for m in METRICS}
    batches = r.records["batches"]
    ticks = program_ticks.window_ticks(r)
    # The window's ticks: as many serve.tick spans as the driver counted
    # batches, and the last records of the ring are those batches.
    assert len(program_ticks.spans(r, "serve.tick")) == len(batches) > 0
    assert [(t.query, t.requests) for t in ticks] == [tuple(b) for b in batches]
    assert 0 < got["serve_host_ms"] <= res["metrics"]["serve_tick_ms"]["value"]
    assert got["serve_queue_wait_ms"] > 0
    assert got["serve_pad_slots_pct"] == pytest.approx(_pad_pct(batches))
    assert 0 <= got["serve_gc_pct"] < 100
    if clients == 3:
        assert max(b for _, b in batches) <= 3


def _reading(spans) -> harness.Reading:
    t = trace.Trace(ops={}, spans=[trace.Event(trace.WINDOW, 0, 1e9)] + spans, window=(0.0, 1e9))
    return harness.Reading(harness.Cell("serve_closed_16", {}, {}, 1), "TPU v5 lite", {}, trace.Spans(), t)


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_without_its_spans_reads_nothing(metric):
    read = run.reader(metric)
    older = [trace.Event("serve.step", 10, 100), trace.Event("serve.fetch", 110, 50), trace.Event("serve.gc", 120, 5)]
    assert read(_reading(older)) is None
    assert read(dataclasses.replace(_reading(older), trace=None)) is None


def test_host_time_is_each_tick_less_its_wait():
    ev = trace.Event
    spans = [ev("serve.step", 0, 1000), ev("serve.tick", 10, 900), ev("serve.consts", 20, 100),
             ev("serve.wait", 300, 600), ev("serve.step", 2000, 500), ev("serve.tick", 2010, 400),
             ev("serve.wait", 2100, 100), ev("serve.gc", 2600, 50_000_000)]
    r = _reading(spans)
    assert run.reader("serve_host_ms")(r) == pytest.approx(1e-6 * ((900 - 600) + (400 - 100)) / 2)
    assert run.reader("serve_gc_pct")(r) == pytest.approx(5.0)


# -- the trace recorded on the chip ---------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    return trace.load(str(FIXTURE))


def _reading_of(t) -> harness.Reading:
    steps = [s for s in t.spans if s.name == "serve.step"]
    spans = trace.Spans()
    spans.total_s["serve.step"] = sum(s.dur_ns for s in steps) * 1e-9
    spans.count["serve.step"] = len(steps)
    return harness.Reading(harness.Cell("serve_closed_16", {}, {}, 1), "TPU v5 lite", {}, spans, t)


def test_recorded_program_spans_nest_inside_the_benchmark_step(recorded):
    steps = [s for s in recorded.spans if s.name == "serve.step"]
    program = [s for s in recorded.spans if s.name in PROGRAM]
    assert len(steps) == 3
    assert sorted(s.name for s in program) == sorted(["serve.tick", *PHASES] * 3)
    for s in program:
        assert any(st.start_ns <= s.start_ns and s.end_ns <= st.end_ns for st in steps), s


def test_recorded_idle_gaps_inside_a_tick_are_named_by_program_phases(recorded):
    for tick in program_ticks.spans(_reading_of(recorded), "serve.tick"):
        gaps = dataclasses.replace(recorded, window=(tick.start_ns, tick.end_ns)).idle_gaps(top=1000)
        assert gaps and {name for name, _ in gaps} <= PROGRAM
        assert {name for name, _ in gaps} & {"serve.consts", "serve.demux"}


def test_recorded_host_time_is_positive_and_below_the_tick(recorded):
    r = _reading_of(recorded)
    assert 0 < run.reader("serve_host_ms")(r) < run.reader("serve_tick_ms")(r)
    assert run.reader("serve_gc_pct")(r) == 0.0  # no collection in these ticks


def test_recorded_kernels_keep_their_names_under_both_wrappers(recorded):
    ops = recorded.op_seconds()
    assert ops["group_filter_agg/group_filter_agg"] > 0
    assert ops["group_filter_agg_multi/group_filter_agg"] > 0  # named by the kernel, not the wrapper
    assert not any(op.endswith("/group_filter_agg_multi") for op in ops)
    gfa = recorded.kernel_seconds(("group_filter_agg", "group_filter_agg_multi"))
    assert gfa == pytest.approx(ops["group_filter_agg/group_filter_agg"] + ops["group_filter_agg_multi/group_filter_agg"])
    assert recorded.kernel_seconds(("block_compact",)) > 0
