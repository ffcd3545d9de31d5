"""Mini query engine: operator correctness vs numpy, query properties."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.engine import datagen, ops, queries
from repro.engine.table import Table, concat

KEY = jax.random.PRNGKey(21)


@pytest.fixture(scope="module")
def li():
    return datagen.lineitem(KEY, rows=20_000)


@pytest.fixture(scope="module")
def od():
    return datagen.orders(KEY, rows=5_000)  # matches lineitem(rows=20_000) FK range


def test_table_invariants(li):
    assert li.num_rows == 20_000
    with pytest.raises(ValueError, match="ragged"):
        Table({"a": jnp.zeros(3), "b": jnp.zeros(4)})
    t2 = li.select("l_quantity", "l_discount")
    assert t2.names == ["l_discount", "l_quantity"]
    taken = li.take(jnp.array([0, 5, 9]))
    assert taken.num_rows == 3
    cc = concat([t2, t2])
    assert cc.num_rows == 40_000


def test_filter_and_compact_vs_numpy(li):
    mask = ops.filter_mask(
        li,
        lambda t: t["l_quantity"] < 25.0,
        lambda t: ops.pred_between(t["l_discount"], 0.02, 0.08),
    )
    c = {k: np.asarray(v) for k, v in li.columns.items()}
    expect = (c["l_quantity"] < 25.0) & (c["l_discount"] >= 0.02) & (c["l_discount"] < 0.08)
    np.testing.assert_array_equal(np.asarray(mask), expect)

    out, cnt = ops.compact(li, mask, max_rows=int(expect.sum()) + 64)
    assert int(cnt) == int(expect.sum())
    got = np.sort(np.asarray(out["l_extendedprice"])[: int(cnt)])
    exp = np.sort(c["l_extendedprice"][expect])
    np.testing.assert_allclose(got, exp)


def test_group_aggregate_vs_numpy(li):
    keys = li["l_returnflag"]
    mask = li["l_quantity"] > 10
    agg = ops.group_aggregate(keys, {"qty": li["l_quantity"]}, mask, num_groups=3)
    k = np.asarray(keys)
    m = np.asarray(mask)
    q = np.asarray(li["l_quantity"])
    for g in range(3):
        sel = (k == g) & m
        np.testing.assert_allclose(float(agg["qty"][g]), q[sel].sum(), rtol=1e-5)
        assert float(agg["count"][g]) == sel.sum()


def test_fk_join_vs_numpy(li, od):
    joined = ops.fk_index_join(li, "l_orderkey", od, "o_orderkey", ("o_totalprice",))
    lk = np.asarray(li["l_orderkey"])
    tp = np.asarray(od["o_totalprice"])
    np.testing.assert_allclose(np.asarray(joined["o_totalprice"]), tp[lk], rtol=1e-6)


def test_sort_merge_join_matches_fk_join(li, od):
    j1 = ops.fk_index_join(li, "l_orderkey", od, "o_orderkey", ("o_totalprice",))
    j2, matched = ops.sort_merge_join(li, "l_orderkey", od, "o_orderkey", ("o_totalprice",))
    assert bool(jnp.all(matched))
    np.testing.assert_allclose(
        np.asarray(j1["o_totalprice"]), np.asarray(j2["o_totalprice"]), rtol=1e-6
    )


def test_q1_group_totals(li):
    res = jax.jit(queries.q1)(li)
    # counts over the 6 groups equal the number of rows passing the date filter
    c = np.asarray(li["l_shipdate"])
    cutoff = datagen.date(1998, 12, 1) - 90.0
    assert int(np.asarray(res["count"]).sum()) == int((c <= cutoff).sum())
    assert np.all(np.asarray(res["avg_disc"]) <= 0.11)


def test_q6_matches_numpy(li):
    res = jax.jit(queries.q6)(li)
    c = {k: np.asarray(v) for k, v in li.columns.items()}
    lo, hi = datagen.date(1994), datagen.date(1995)
    mask = (
        (c["l_shipdate"] >= lo) & (c["l_shipdate"] < hi)
        & (c["l_discount"] >= 0.049) & (c["l_discount"] < 0.071)
        & (c["l_quantity"] < 24)
    )
    expect = (c["l_extendedprice"][mask] * c["l_discount"][mask]).sum()
    np.testing.assert_allclose(float(res["revenue"]), expect, rtol=1e-4)
    assert int(res["rows"]) == int(mask.sum())


def test_q6_kernel_equals_engine(li):
    """The pushdown task's kernel plan against the engine's masked ops."""
    from repro.tasks.pushdown import aggregate_at_data

    lo, hi = datagen.date(1994), datagen.date(1995)
    total, count = aggregate_at_data(li, lo, hi)
    mask = ops.pred_between(li["l_shipdate"], lo, hi)
    assert int(count) == int(ops.masked_count(mask)) > 0
    np.testing.assert_allclose(float(total), float(ops.masked_sum(li["l_extendedprice"], mask)), rtol=1e-5)


def test_q12_runs_and_counts_bounded(li, od):
    res = jax.jit(queries.q12)(li, od)
    total = np.asarray(res["count"]).sum()
    high = np.asarray(res["high_line_count"]).sum()
    low = np.asarray(res["low_line_count"]).sum()
    assert high + low == pytest.approx(total)
    # only shipmodes MAIL(2) and SHIP(5) have nonzero counts
    cnt = np.asarray(res["count"])
    assert cnt[[0, 1, 3, 4, 6]].sum() == 0


# -- properties ---------------------------------------------------------------
@given(
    rows=st.integers(128, 2048),
    sel=st.floats(0.05, 0.95),
)
@settings(max_examples=10, deadline=None)
def test_compact_count_scales_with_selectivity(rows, sel):
    t = datagen.lineitem(jax.random.fold_in(KEY, rows), rows=rows)
    lo = datagen.DATE_EPOCH_DAYS
    hi = lo + sel * datagen.DATE_RANGE_DAYS
    mask = ops.pred_between(t["l_shipdate"], float(lo), float(hi))
    cnt = int(ops.masked_count(mask))
    assert 0 <= cnt <= rows
    # selectivity should land near `sel` (uniform dates) — loose bound
    assert abs(cnt / rows - sel) < 0.25


def test_datagen_deterministic():
    a = datagen.lineitem(jax.random.PRNGKey(5), rows=512)
    b = datagen.lineitem(jax.random.PRNGKey(5), rows=512)
    for n in a.names:
        np.testing.assert_array_equal(np.asarray(a[n]), np.asarray(b[n]))
