"""The plain references, the control and the traffic generator."""
from __future__ import annotations

import math
import random

import numpy as np
import pytest

from bench import control, datagen, loadgen, reference
from bench.drivers import serve

ROWS, ORDERS = 40_000, 10_000


@pytest.fixture(scope="module")
def tables():
    li, od = datagen.tables(2**40 + 3, ROWS, ORDERS)
    return {k: np.asarray(v) for k, v in li.items()}, {k: np.asarray(v) for k, v in od.items()}


@pytest.mark.parametrize("q", ["q1", "q6", "q12"])
def test_per_day_sums_equal_the_direct_evaluation(tables, q):
    li, od = tables
    ref = reference.ServingReference(li, od)
    r = random.Random(q)
    for _ in range(25):
        p = loadgen.sample_params(q, r)
        exact, dev = reference.compare(ref(q, p), reference.query(q, li, od, **p))
        assert exact and dev < 1e-12, (p, dev)


def test_compare_reads_counts_exactly_and_floats_relatively():
    want = {"revenue": np.float64(1000.0), "rows": np.float64(7)}
    assert reference.compare({"revenue": 1000.0005, "rows": 7}, want) == (True, pytest.approx(5e-7))
    assert reference.compare({"revenue": 1000.0, "rows": 8}, want)[0] is False
    assert reference.compare({"revenue": float("nan"), "rows": 7}, want) == (False, math.inf)
    assert reference.compare({"rows": 7}, want) == (False, math.inf)


@pytest.mark.parametrize("selectivity", [0.5, 0.001])
def test_scan_reference_counts_and_rows(tables, selectivity):
    li, _ = tables
    cols = {c: li[c] for c in reference.SCAN_COLUMNS}
    ref = reference.ScanReference(cols)
    windows = loadgen.scan_windows({"selectivity": selectivity}, 5)
    cap = int(1.5 * selectivity * ROWS)
    for _ in range(5):
        lo, hi = next(windows)
        m = (cols["l_shipdate"] >= np.float32(lo)) & (cols["l_shipdate"] < np.float32(hi))
        assert ref.count(lo, hi) == int(m.sum()) <= cap
        rows = ref.rows(lo, hi, cap)
        assert np.array_equal(rows[3, : m.sum()], cols["l_shipdate"][m])
        assert not rows[:, m.sum():].any()


@pytest.mark.parametrize("variant", control.VARIANTS)
def test_the_control_is_not_correct(tables, variant):
    """The reference on bfloat16 columns fails the cells' own checks at
    their limits, which the reference itself passes."""
    li, od = tables
    limits = {"max_rel_dev": 1e-4}
    reqs = control.serving_requests({"queries": ["q1", "q6", "q12"], "clients": 16}, 9, 60)
    low = control.reading(control.serving_control(li, od, reqs, limits, variant))
    assert low["correct"] is False and low["checks"]["max_rel_dev"]["value"] > 1e-4, low
    same = control.reading(serve.checks(reqs, {u: reference.query(q, li, od, **p) for u, (q, p) in enumerate(reqs)},
                                        reference.ServingReference(li, od), limits))
    assert same["correct"] is True, same
    cols = {c: li[c] for c in reference.SCAN_COLUMNS}
    for sel in (0.5, 0.001):
        w = loadgen.scan_windows({"selectivity": sel}, 9)
        low = control.reading(control.scan_control(cols, [next(w) for _ in range(5)], int(1.5 * sel * ROWS), 2,
                                                   variant))
        assert low["correct"] is False and low["checks"]["compacted_values_wrong"]["value"] > 0, low
        if variant == "values":  # dates exact: the counts still agree
            assert low["checks"]["requests_with_wrong_count"]["value"] == 0


def test_traffic_is_a_function_of_the_seed():
    traffic = {"queries": ["q1", "q6", "q12"], "clients": 3}
    a = control.serving_requests(traffic, 2**35 + 1, 297)
    assert a == control.serving_requests(traffic, 2**35 + 1, 297)
    b = control.serving_requests(traffic, 2**35 + 2, 297)
    assert a != b
    assert sorted(q for q, _ in a) == sorted(q for q, _ in b)  # the same mix in another order
    clients = [loadgen.client_requests(traffic, 4, c) for c in range(3)]
    firsts = [[next(g)[0] for _ in range(3)] for g in clients]
    assert all(sorted(f) == ["q1", "q12", "q6"] for f in firsts)


def test_scan_windows_span_whole_days_inside_the_data():
    w = loadgen.scan_windows({"selectivity": 0.001}, 2**33 + 1)
    for _ in range(200):
        lo, hi = next(w)
        assert lo == int(lo) and datagen.DATE_EPOCH_DAYS <= lo
        assert hi <= datagen.DATE_EPOCH_DAYS + datagen.DATE_RANGE_DAYS
        assert hi - lo == pytest.approx(2.526)


def test_large_seeds_give_distinct_tables():
    a = np.asarray(datagen.tables(2**33 + 1, 1024, 256)[0]["l_extendedprice"])
    b = np.asarray(datagen.tables(1, 1024, 256)[0]["l_extendedprice"])
    assert not np.array_equal(a, b)
