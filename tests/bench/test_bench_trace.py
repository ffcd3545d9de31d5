"""The trace reduction on a small trace recorded on a TPU v5e
(``fixtures/serve_scan_small.xplane.pb``, written by
``bench/record_fixture.py``: three q6 serving ticks and two compactions
over 1,048,576 rows, inside the benchmark's spans)."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import harness, peaks, trace
from bench.metrics import common

FIXTURE = Path(__file__).parent / "fixtures" / "serve_scan_small.xplane.pb"
ROWS = 1 << 20
KIND = "TPU v5 lite"


@pytest.fixture(scope="module")
def t():
    return trace.load(str(FIXTURE))


def test_busy_and_idle_within_the_window(t):
    assert list(t.ops) == ["/device:TPU:0"]
    assert 0 < t.busy_s() <= t.window_s
    assert 0 <= t.idle_pct() < 100
    assert t.busy_s() <= t.op_total_seconds() + 1e-12  # a union is at most the sum


def test_kernels_are_found_by_name(t):
    gfa = t.kernel_seconds(("group_filter_agg", "group_filter_agg_multi"))
    compact = t.kernel_seconds(("block_compact",))
    assert gfa > 0 and compact > 0
    assert gfa + compact < t.op_total_seconds()
    assert t.kernel_seconds(("fusion",)) == 0  # only tpu_custom_call events are kernels


def test_breakdown_names_ops_and_the_spans_of_idle_gaps(t):
    b = trace.breakdown(t)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "_lambda/block_compact"  # program/op
    assert ["group_filter_agg_multi/group_filter_agg_multi", pytest.approx(0.000158108)] in b["device_ops"]
    assert all(" " not in name and "?" not in name for name, _ in b["device_ops"])
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    spans = {"serve.step", "serve.fetch", "scan.request", "scan.fetch_count", "idle"}
    assert {name for name, _ in b["idle_gaps"]} <= spans
    assert sum(s for _, s in b["idle_gaps"]) <= t.window_s - t.busy_s() + 1e-9


def _reading(t, records):
    cell = harness.Cell("fixture", {}, {}, 1)
    return harness.Reading(cell, KIND, records, trace.Spans(), t)


def test_rooflines_on_the_fixture_stay_at_most_100(t):
    batches = [("q6", 1), ("q6", 2), ("q6", 1)]
    gfa = common.gfa_roofline_pct(_reading(t, {"rows": ROWS, "batches": batches}),
                                  ("group_filter_agg", "group_filter_agg_multi"))
    assert 0 < gfa <= 100
    # The two compactions, charged as if every slot of their capacity
    # qualified: the most bytes they could move.
    least = sum(peaks.least_seconds(KIND, peaks.compact_bytes(4, ROWS, cap, cap), 0.0) for cap in (4096, 600_000))
    assert 0 < 100 * least / t.kernel_seconds(("block_compact",)) <= 100


def test_readers_return_nothing_without_a_trace():
    r = harness.Reading(harness.Cell("x", {}, {}, 1), KIND, {"rows": 1, "batches": [("q6", 1)], "counts": [1]},
                        trace.Spans(), None)
    assert common.idle_pct(r) is None
    assert common.gfa_roofline_pct(r, ("group_filter_agg",)) is None
    assert common.compact_roofline_pct(r, ("block_compact",)) is None
    assert common.device_ms_outside(r, ("block_compact",)) is None


def test_union_and_gaps_on_made_up_events():
    ev = trace.Event
    t = trace.Trace(
        ops={"/device:TPU:0": [ev("a", 10, 10), ev("b", 15, 10), ev("c", 40, 5)]},
        spans=[ev(trace.WINDOW, 0, 100), ev("serve.step", 0, 30), ev("serve.fetch", 30, 20)],
        window=(0.0, 100.0),
    )
    assert t.busy_s() == pytest.approx(20e-9)  # [10, 25) and [40, 45)
    assert t.idle_gaps() == [("idle", pytest.approx(55e-9)), ("serve.fetch", pytest.approx(15e-9)),
                             ("serve.step", pytest.approx(10e-9))]
    assert trace.op_base("%group_filter_agg_multi.12 = f32[8] custom-call(...)") == "group_filter_agg_multi"


def test_peaks_refuse_an_unknown_device():
    with pytest.raises(KeyError):
        peaks.peak("TPU v99")
