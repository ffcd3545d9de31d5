"""Plain references of the benchmark's queries and scans, in float64 numpy
over host copies of the generated columns.  Nothing here imports the
program.

* :func:`query` is the direct evaluation of Q1, Q6 and Q12 (the semantics
  of the program's ``engine.queries.QUERIES``, with predicate constants
  rounded to f32 as the device plans compare them).
* :class:`ServingReference` answers the same queries for every constant
  the traffic can draw, from per-day partial sums built in one pass per
  query, so that checking every served request costs the same however many
  were served.  The tests hold it equal to :func:`query`.
* :class:`ScanReference` counts the rows of any ship-date window from a
  per-day histogram and compacts the rows of a window directly.
"""
from __future__ import annotations

import concurrent.futures
import math

import numpy as np

from bench import datagen

f32, f64 = np.float32, np.float64
#: Result keys that are row counts, compared exactly.
COUNT_KEYS = ("count", "rows", "high_line_count", "low_line_count")
#: Q12's ship modes (MAIL, SHIP) as dictionary codes.
Q12_SHIPMODES = tuple(datagen.SHIPMODE.index(m) for m in ("MAIL", "SHIP"))
Q1_GROUPS = 6
Q12_GROUPS = len(datagen.SHIPMODE)
#: The scanned columns of the pushdown plan, in the order its output has.
SCAN_COLUMNS = ("l_discount", "l_extendedprice", "l_quantity", "l_shipdate")


def _q1_cutoff(params) -> f32:
    return f32(datagen.date(1998, 12, 1) - params.get("delta_days", 90.0))


def _year_bounds(params) -> tuple[f32, f32]:
    year = params.get("year", 1994)
    return f32(datagen.date(year)), f32(datagen.date(year + 1))


def _q6_bands(params):
    d = params.get("discount", 0.06)
    return f32(d - 0.011), f32(d + 0.011), f32(params.get("qty", 24.0))


def _q1_averages(out: dict) -> dict:
    cnt = np.maximum(out["count"], 1.0)
    out["avg_qty"] = out["sum_qty"] / cnt
    out["avg_price"] = out["sum_base_price"] / cnt
    out["avg_disc"] = out["sum_disc"] / cnt
    return out


def _q1_values(li: dict) -> dict:
    price = li["l_extendedprice"].astype(f64)
    disc = li["l_discount"].astype(f64)
    disc_price = price * (1.0 - disc)
    return {
        "sum_qty": li["l_quantity"].astype(f64), "sum_base_price": price,
        "sum_disc_price": disc_price, "sum_charge": disc_price * (1.0 + li["l_tax"].astype(f64)),
        "sum_disc": disc,
    }


def query(q: str, li: dict, od: dict, **params) -> dict:
    """Q1, Q6 or Q12 evaluated directly over host columns."""
    if q == "q1":
        m = li["l_shipdate"] <= _q1_cutoff(params)
        keys = (li["l_returnflag"] * 2 + li["l_linestatus"])[m]
        out = {k: np.bincount(keys, weights=v[m], minlength=Q1_GROUPS)
               for k, v in _q1_values(li).items()}
        out["count"] = np.bincount(keys, minlength=Q1_GROUPS).astype(f64)
        return _q1_averages(out)
    lo, hi = _year_bounds(params)
    if q == "q6":
        dlo, dhi, qty = _q6_bands(params)
        m = (
            (li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi)
            & (li["l_discount"] >= dlo) & (li["l_discount"] < dhi) & (li["l_quantity"] < qty)
        )
        price, disc = li["l_extendedprice"].astype(f64), li["l_discount"].astype(f64)
        return {"revenue": np.sum(price[m] * disc[m]), "rows": f64(np.sum(m))}
    if q != "q12":
        raise ValueError(f"unknown query {q!r}")
    prio = od["o_orderpriority"][li["l_orderkey"]]
    m = (
        np.isin(li["l_shipmode"], Q12_SHIPMODES)
        & (li["l_commitdate"] < li["l_receiptdate"]) & (li["l_shipdate"] < li["l_commitdate"])
        & (li["l_receiptdate"] >= lo) & (li["l_receiptdate"] < hi)
    )
    return {
        "high_line_count": np.bincount(li["l_shipmode"][m & (prio <= 1)], minlength=Q12_GROUPS).astype(f64),
        "low_line_count": np.bincount(li["l_shipmode"][m & (prio > 1)], minlength=Q12_GROUPS).astype(f64),
        "count": np.bincount(li["l_shipmode"][m], minlength=Q12_GROUPS).astype(f64),
    }


def _whole_days(col: np.ndarray, name: str) -> np.ndarray:
    days = col.astype(np.int64)
    if not np.array_equal(days, col):
        raise ValueError(f"{name} holds dates that are not whole days")
    return days


def _cumulative(index: np.ndarray, weights, bins: int, shape: tuple) -> np.ndarray:
    """Per-bin sums of ``weights`` reshaped to ``shape`` and accumulated
    along the first (day) axis, with a leading zero row."""
    sums = np.bincount(index, weights=weights, minlength=bins).reshape(shape)
    return np.concatenate([np.zeros((1,) + shape[1:]), np.cumsum(sums, axis=0)])


class _Days:
    """Whole days ``base .. base + n - 1`` and the prefix rows that sum the
    days on which an f32 predicate on the day holds."""

    def __init__(self, days: np.ndarray):
        self.base = int(days.min()) if days.size else 0
        self.n = int(days.max()) - self.base + 1 if days.size else 1
        self.values = np.arange(self.base, self.base + self.n).astype(f32)

    def span(self, lo=None, hi=None, le=None) -> tuple[int, int]:
        """[first, last + 1) of the day indices with lo <= day < hi (or
        day <= le): the predicate is monotone in the day."""
        ok = np.ones(self.n, bool)
        if lo is not None:
            ok &= self.values >= lo
        if hi is not None:
            ok &= self.values < hi
        if le is not None:
            ok &= self.values <= le
        idx = np.flatnonzero(ok)
        return (int(idx[0]), int(idx[-1]) + 1) if idx.size else (0, 0)


def _codes(col: np.ndarray, scale: float) -> tuple[np.ndarray, np.ndarray]:
    """(values, codes) of a column of few distinct values, each a multiple
    of ``1 / scale`` up to rounding: ``values[codes] == col`` exactly.
    Codes that no row has carry the value 0 and sum nothing."""
    codes = np.rint(col.astype(f64) * scale).astype(np.int64)
    values = np.zeros(int(codes.max()) + 1, col.dtype)
    values[codes] = col
    if not np.array_equal(values[codes], col):
        values, codes = np.unique(col, return_inverse=True)
    return values, codes


class ServingReference:
    """Q1, Q6 and Q12 for any constants, from per-day partial sums.  The
    three queries' sums are built in three threads."""

    def __init__(self, li: dict, od: dict):
        ship = _whole_days(li["l_shipdate"], "l_shipdate")
        self.ship = _Days(ship)
        d = ship - self.ship.base
        with concurrent.futures.ThreadPoolExecutor(3) as pool:
            jobs = [pool.submit(f, li, od, d) for f in (self._q1, self._q6, self._q12)]
            for j in jobs:
                j.result()

    def _q1(self, li: dict, od: dict, d: np.ndarray) -> None:
        """Per (ship day, group) sums of each aggregate."""
        group = li["l_returnflag"].astype(np.int64) * 2 + li["l_linestatus"]
        idx = d * Q1_GROUPS + group
        shape = (self.ship.n, Q1_GROUPS)
        q1 = {k: _cumulative(idx, v, self.ship.n * Q1_GROUPS, shape) for k, v in _q1_values(li).items()}
        q1["count"] = _cumulative(idx, None, self.ship.n * Q1_GROUPS, shape)
        self.q1 = q1

    def _q6(self, li: dict, od: dict, d: np.ndarray) -> None:
        """Per (ship day, discount value, quantity value) revenue and rows."""
        self.disc, dcode = _codes(li["l_discount"], 100.0)
        self.qty, qcode = _codes(li["l_quantity"], 1.0)
        nd, nq = len(self.disc), len(self.qty)
        idx = (d * nd + dcode) * nq + qcode
        shape = (self.ship.n, nd, nq)
        rev = li["l_extendedprice"].astype(f64) * li["l_discount"].astype(f64)
        self.q6_rev = _cumulative(idx, rev, self.ship.n * nd * nq, shape)
        self.q6_rows = _cumulative(idx, None, self.ship.n * nd * nq, shape)

    def _q12(self, li: dict, od: dict, d: np.ndarray) -> None:
        """Per (receipt day, ship mode, high/low priority) line counts."""
        mode = li["l_shipmode"]
        m = (mode == Q12_SHIPMODES[0]) | (mode == Q12_SHIPMODES[1])
        m &= (li["l_commitdate"] < li["l_receiptdate"]) & (li["l_shipdate"] < li["l_commitdate"])
        prio = od["o_orderpriority"][li["l_orderkey"][m]]
        receipt = _whole_days(li["l_receiptdate"][m], "l_receiptdate")
        self.receipt = _Days(receipt)
        idx = ((receipt - self.receipt.base) * Q12_GROUPS + mode[m]) * 2 + (prio > 1)
        self.q12 = _cumulative(idx, None, self.receipt.n * Q12_GROUPS * 2, (self.receipt.n, Q12_GROUPS, 2))

    def __call__(self, q: str, params: dict) -> dict:
        if q == "q1":
            a, b = self.ship.span(le=_q1_cutoff(params))
            return _q1_averages({k: v[b] - v[a] for k, v in self.q1.items()})
        lo, hi = _year_bounds(params)
        if q == "q6":
            a, b = self.ship.span(lo=lo, hi=hi)
            dlo, dhi, qty = _q6_bands(params)
            sel = np.ix_((self.disc >= dlo) & (self.disc < dhi), self.qty < qty)
            return {
                "revenue": f64(np.sum((self.q6_rev[b] - self.q6_rev[a])[sel])),
                "rows": f64(np.sum((self.q6_rows[b] - self.q6_rows[a])[sel])),
            }
        if q != "q12":
            raise ValueError(f"unknown query {q!r}")
        a, b = self.receipt.span(lo=lo, hi=hi)
        c = self.q12[b] - self.q12[a]
        return {"high_line_count": c[:, 0], "low_line_count": c[:, 1], "count": c.sum(axis=1)}


def compare(got: dict, want: dict) -> tuple[bool, float]:
    """(counts exact, largest relative float deviation) of one result.

    Counts must be equal; float entries deviate by ``|got - want| /
    max(|want|, 1)``.  A missing key, a wrong shape or a value that is not
    finite makes the counts wrong and the deviation infinite.
    """
    if set(got) != set(want):
        return False, math.inf
    exact, worst = True, 0.0
    for k, w in want.items():
        g = np.asarray(got[k], f64)
        w = np.asarray(w, f64)
        if g.shape != w.shape or not np.all(np.isfinite(g)):
            return False, math.inf
        if k in COUNT_KEYS:
            exact &= bool(np.array_equal(g, w))
        else:
            worst = max(worst, float(np.max(np.abs(g - w) / np.maximum(np.abs(w), 1.0))))
    return exact, worst


class ScanReference:
    """Counts and compacted rows of ``lo <= l_shipdate < hi`` windows."""

    def __init__(self, cols: dict):
        self.cols = cols
        ship = cols["l_shipdate"]
        self.days = _Days(_whole_days(ship, "l_shipdate"))
        self.per_day = np.bincount(ship.astype(np.int64) - self.days.base, minlength=self.days.n)

    def count(self, lo: float, hi: float) -> int:
        a, b = self.days.span(lo=f32(lo), hi=f32(hi))
        return int(self.per_day[a:b].sum())

    def rows(self, lo: float, hi: float, cap: int) -> np.ndarray:
        """[len(SCAN_COLUMNS), cap] f32: the first ``cap`` qualifying rows
        in table order, zero beyond."""
        ship = self.cols["l_shipdate"]
        idx = np.flatnonzero((ship >= f32(lo)) & (ship < f32(hi)))[:cap]
        out = np.zeros((len(SCAN_COLUMNS), cap), f32)
        for i, name in enumerate(SCAN_COLUMNS):
            out[i, : idx.size] = self.cols[name][idx]
        return out
