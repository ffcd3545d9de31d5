"""Fused query plans vs the unfused engine: the q*_fused variants and the
pushdown compaction plans must agree with their jnp counterparts.

Counts and integer-valued aggregates (Q12's conditional counts, Q1's group
counts and quantity sums) must match EXACTLY — they are integer sums, which
f32 accumulates without rounding at these magnitudes.  Float product-sums
agree to accumulation-order tolerance (blocked kernel accumulation vs
segment_sum ordering).
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.engine import datagen, ops, queries

KEY = jax.random.PRNGKey(21)
SUM_TOL = dict(rtol=2e-5, atol=1e-3)


@pytest.fixture(scope="module")
def li():
    return datagen.lineitem(KEY, rows=20_000)


@pytest.fixture(scope="module")
def od():
    return datagen.orders(KEY, rows=5_000)


# -- fused DBMS queries -------------------------------------------------------
def test_q1_fused_equals_q1(li):
    ref = jax.jit(queries.q1)(li)
    fused = jax.jit(queries.q1_fused)(li)
    assert set(ref) == set(fused)
    # integer-valued aggregates: exact
    np.testing.assert_array_equal(np.asarray(ref["count"]), np.asarray(fused["count"]))
    np.testing.assert_array_equal(np.asarray(ref["sum_qty"]), np.asarray(fused["sum_qty"]))
    for k in ref:
        np.testing.assert_allclose(np.asarray(ref[k]), np.asarray(fused[k]), **SUM_TOL)


def test_q1_fused_zero_delta_includes_all_rows(li):
    """delta_days far in the past: the <= cutoff predicate passes every row
    (group counts must sum to the table), exercising the all-pass path."""
    fused = jax.jit(lambda t: queries.q1_fused(t, delta_days=-10_000.0))(li)
    assert int(np.asarray(fused["count"]).sum()) == li.num_rows


def test_q6_fused_equals_q6(li):
    ref = jax.jit(queries.q6)(li)
    fused = jax.jit(queries.q6_fused)(li)
    # the program expresses all three predicates: the row count is exact
    assert int(ref["rows"]) == int(fused["rows"])
    np.testing.assert_allclose(float(ref["revenue"]), float(fused["revenue"]), rtol=2e-5)


def test_q12_fused_equals_q12_exactly(li, od):
    ref = jax.jit(queries.q12)(li, od)
    fused = jax.jit(queries.q12_fused)(li, od)
    assert set(ref) == set(fused)
    for k in ref:  # conditional counts are integer sums: bit-for-bit
        np.testing.assert_array_equal(np.asarray(ref[k]), np.asarray(fused[k]))


def test_fused_escape_hatch_matches_kernel(li, od):
    """use_pallas=False (the ref-oracle route) returns the same results the
    kernel route does — one code path for CPU smoke and TPU runs."""
    a = jax.jit(lambda t: queries.q1_fused(t, use_pallas=False))(li)
    b = jax.jit(queries.q1_fused)(li)
    for k in a:
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]), **SUM_TOL)
    c = jax.jit(lambda t, o: queries.q12_fused(t, o, use_pallas=False))(li, od)
    d = jax.jit(queries.q12_fused)(li, od)
    for k in c:
        np.testing.assert_array_equal(np.asarray(c[k]), np.asarray(d[k]))


def test_dbms_task_runs_fused_impl():
    from repro.core.registry import get
    from repro.core.task import TaskContext

    task = get("dbms")
    ctx = TaskContext(iters=1, warmup=0)
    task.prepare(ctx)
    try:
        for impl in ("unfused", "fused"):
            s = task.run(
                ctx, {"scale": "0.001", "query": "q1", "mode": "hot", "impl": impl}
            )
            assert s.times_s and s.items_per_iter == 6_000
    finally:
        task.clean(ctx)


# -- pushdown compaction plans ------------------------------------------------
def test_compact_kernel_route_matches_jnp(li):
    scanned = li.select("l_shipdate", "l_extendedprice", "l_discount", "l_quantity")
    mask = ops.pred_between(scanned["l_shipdate"], 8035.0, 8035.0 + 800.0)
    cap = int(np.asarray(mask).sum()) + 100
    out_j, cnt_j = ops.compact(scanned, mask, cap)
    out_k, cnt_k = ops.compact(scanned, mask, cap, use_pallas=True)
    assert int(cnt_j) == int(cnt_k)
    for name in scanned.names:
        np.testing.assert_array_equal(np.asarray(out_j[name]), np.asarray(out_k[name]))


def test_pushdown_plans_agree_at_every_param_point():
    """baseline / pushdown(jnp) / pushdown(kernel) / pushdown_kernel report
    the same qualifying-row count (and consistent sums) at every
    (scale, selectivity) point of the task's param_space."""
    from repro.tasks.pushdown import _SCALES, PushdownTask, _pred_bounds, aggregate_at_data

    task = PushdownTask()
    sels = task.param_space["selectivity"]
    key = jax.random.PRNGKey(7)
    for scale, rows in _SCALES.items():
        table = datagen.lineitem(key, rows=rows)
        scanned = table.select(
            "l_shipdate", "l_extendedprice", "l_discount", "l_quantity"
        )
        for sel in sels:
            lo, hi = _pred_bounds(sel)
            cap = max(1024, int(1.5 * sel * rows))
            mask = ops.pred_between(scanned["l_shipdate"], lo, hi)
            baseline_cnt = int(ops.masked_count(mask))
            baseline_sum = float(ops.masked_sum(scanned["l_extendedprice"], mask))

            out_j, cnt_j = ops.compact(scanned, mask, cap)
            out_k, cnt_k = ops.compact(scanned, mask, cap, use_pallas=True)
            assert int(cnt_j) == int(cnt_k) == baseline_cnt, (scale, sel)
            for name in scanned.names:
                np.testing.assert_array_equal(
                    np.asarray(out_j[name]), np.asarray(out_k[name])
                )

            # the fully-fused plan's count agrees too (its aggregate matches
            # to accumulation-order tolerance)
            total, count = aggregate_at_data(table, lo, hi)
            assert int(count) == baseline_cnt, (scale, sel)
            np.testing.assert_allclose(float(total), baseline_sum, rtol=2e-5)


# -- min-time measurement floor ----------------------------------------------
def test_dbms_hot_mode_honors_min_time():
    """min_time_s keeps sampling past `iters` until enough wall time has
    accumulated — microsecond-scale points stop being 1-sample noise."""
    from repro.core.registry import get
    from repro.core.task import TaskContext

    task = get("dbms")
    # warmup=1 so compile lands outside the timed samples: every measured
    # iteration is then a genuine hot-path microsecond-scale run.
    ctx = TaskContext(iters=1, warmup=1, min_time_s=0.05)
    key = jax.random.PRNGKey(3)
    ctx.scratch["li_0.001"] = datagen.lineitem(key, rows=6_000)
    ctx.scratch["od_0.001"] = datagen.orders(key, rows=1_500)
    s = task.run(ctx, {"scale": "0.001", "query": "q6", "mode": "hot", "impl": "unfused"})
    assert sum(s.times_s) >= 0.05
    assert len(s.times_s) > 1  # a hot q6 at 6k rows is far under 50 ms


def test_min_time_is_part_of_cache_identity():
    from repro.core.cache import cache_key

    base = dict(task="dbms", params={"q": 1}, platform={"name": "p"},
                iters=1, warmup=0, metrics=("items_per_s",))
    k0 = cache_key(**base)
    assert cache_key(**base, min_time_s=0.0) == k0  # unset: legacy keys intact
    assert cache_key(**base, min_time_s=0.5) != k0


def test_pushdown_task_kernel_impl_runs():
    from repro.core.registry import get
    from repro.core.task import TaskContext

    task = get("pushdown")
    ctx = TaskContext(iters=1, warmup=0)
    key = jax.random.PRNGKey(7)
    # prepare() builds all scales including 6M rows; keep this test light
    ctx.scratch["0.01"] = datagen.lineitem(key, rows=60_000)
    for impl in ("jnp", "kernel"):
        s = task.run(
            ctx,
            {"scale": "0.01", "selectivity": 0.1, "plan": "pushdown", "impl": impl},
        )
        assert s.times_s and s.extra["moved_bytes"] > 0
