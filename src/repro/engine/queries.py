"""TPC-H-pattern queries over the mini engine (the paper's DBMS workload).

Q1  — scan-heavy group-by aggregate over lineitem;
Q6  — the predicate-pushdown filter+aggregate;
Q12 — join lineitem x orders + grouped conditional counts.

Each query is a jit-able Table -> dict[str, Array] function (QUERIES), the
plain jnp plan that the fused plans are tested against.

:data:`SPECS` holds each query's fused plan, once: a column layout, a
``group_filter_agg`` program, a group count and a demux; the fused queries
and the serving plans are both derived from it.  ``FUSED_QUERIES``
(``q1_fused`` ...) runs a query as ONE kernel pass under ``jit``: the
predicate program evaluates the WHERE clause in registers, derived columns
(Q1's disc_price/charge) are term products computed in-flight, and the
grouped sums/counts accumulate in a VMEM tile — instead of the unfused jnp
graph's one-HBM-pass-per-aggregate ``segment_sum`` plan.  Counts and
integer-valued aggregates match the unfused results exactly; float sums
agree to accumulation-order tolerance.

The serving plans (``make_serving_plans``, ``fused_query_batch``) run the
same kernel with constants that arrive per request: a call copies its
constant tables to the device once and the kernel's output back once, and
returns host numpy values.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.engine import datagen, ops
from repro.engine.table import Table
from repro.kernels import ops as kops
from repro.kernels.group_filter_agg import encode_aggregates, encode_predicates


def _le_bound(cutoff: float) -> float:
    """The exclusive f32 upper bound equivalent to ``col <= cutoff``."""
    return float(np.nextafter(np.float32(cutoff), np.float32(np.inf)))


def q1(lineitem: Table, delta_days: float = 90.0) -> dict[str, jax.Array]:
    """Pricing summary report: 6 (returnflag x linestatus) groups."""
    cutoff = datagen.date(1998, 12, 1) - delta_days
    mask = lineitem["l_shipdate"] <= cutoff
    keys = lineitem["l_returnflag"] * 2 + lineitem["l_linestatus"]  # 6 groups
    disc_price = lineitem["l_extendedprice"] * (1.0 - lineitem["l_discount"])
    charge = disc_price * (1.0 + lineitem["l_tax"])
    agg = ops.group_aggregate(
        keys,
        {
            "sum_qty": lineitem["l_quantity"],
            "sum_base_price": lineitem["l_extendedprice"],
            "sum_disc_price": disc_price,
            "sum_charge": charge,
            "sum_disc": lineitem["l_discount"],
        },
        mask,
        num_groups=6,
    )
    cnt = jnp.maximum(agg["count"], 1.0)
    agg["avg_qty"] = agg["sum_qty"] / cnt
    agg["avg_price"] = agg["sum_base_price"] / cnt
    agg["avg_disc"] = agg["sum_disc"] / cnt
    return agg


def q6(lineitem: Table, year: int = 1994, discount: float = 0.06, qty: float = 24.0):
    """Forecasting revenue change: one filtered product-sum."""
    lo = datagen.date(year)
    hi = datagen.date(year + 1)
    mask = ops.filter_mask(
        lineitem,
        lambda t: ops.pred_between(t["l_shipdate"], lo, hi),
        lambda t: ops.pred_between(t["l_discount"], discount - 0.011, discount + 0.011),
        lambda t: t["l_quantity"] < qty,
    )
    revenue = ops.masked_sum(lineitem["l_extendedprice"] * lineitem["l_discount"], mask)
    return {"revenue": revenue, "rows": ops.masked_count(mask)}


# Q12's shipmode IN-list, resolved against the dictionary order once so the
# fused and unfused plans can't drift apart.
Q12_SHIPMODES = tuple(datagen.SHIPMODE.index(m) for m in ("MAIL", "SHIP"))


def q12(lineitem: Table, orders: Table, year: int = 1994):
    """Shipping modes & order priority: join + grouped conditional counts."""
    lo = datagen.date(year)
    hi = datagen.date(year + 1)
    joined = ops.fk_index_join(lineitem, "l_orderkey", orders, "o_orderkey", ("o_orderpriority",))
    mask = ops.filter_mask(
        joined,
        lambda t: ops.pred_in(t["l_shipmode"], Q12_SHIPMODES),
        lambda t: t["l_commitdate"] < t["l_receiptdate"],
        lambda t: t["l_shipdate"] < t["l_commitdate"],
        lambda t: ops.pred_between(t["l_receiptdate"], lo, hi),
    )
    high = (joined["o_orderpriority"] <= 1) & mask  # 1-URGENT, 2-HIGH
    low = (joined["o_orderpriority"] > 1) & mask
    agg = ops.group_aggregate(
        joined["l_shipmode"],
        {"high_line_count": high.astype(jnp.float32), "low_line_count": low.astype(jnp.float32)},
        mask,
        num_groups=len(datagen.SHIPMODE),
    )
    return agg


# ---------------------------------------------------------------------------
# Fused plans: each query as one group_filter_agg pass.  A query's
# ``*_program`` builds its kernel program from its constants, so that the
# serving path can stack the constants of many requests into batch inputs
# (``fused_query_batch``) instead of baking them in at trace time.
def q1_program(delta_days: float = 90.0):
    """Q1's kernel program: (pred_ops, pred_consts, agg_ops, agg_consts)."""
    cutoff = datagen.date(1998, 12, 1) - delta_days
    pred = encode_predicates([("range", 0, None, _le_bound(cutoff))])  # shipdate <= cutoff
    agg = encode_aggregates(
        [
            [("col", 1)],  # sum_qty
            [("col", 2)],  # sum_base_price
            [("col", 2), ("one_minus", 3)],  # sum_disc_price
            [("col", 2), ("one_minus", 3), ("one_plus", 4)],  # sum_charge
            [("col", 3)],  # sum_disc
        ]
    )
    return (*pred, *agg)


def _q1_layout(lineitem: Table) -> tuple[jax.Array, jax.Array]:
    cols = jnp.stack(
        [
            lineitem["l_shipdate"],  # 0: predicate
            lineitem["l_quantity"],  # 1
            lineitem["l_extendedprice"],  # 2
            lineitem["l_discount"],  # 3
            lineitem["l_tax"],  # 4
        ]
    )
    keys = lineitem["l_returnflag"] * 2 + lineitem["l_linestatus"]
    return cols, keys


# The demux functions use only array methods, arithmetic and numpy
# constants, so one function serves a device array (under ``jit`` or not)
# and its host copy in numpy alike.
def _q1_demux(out):
    """Q1 result dict from one [6, 6] kernel output row-block."""
    agg = {
        "sum_qty": out[:, 0],
        "sum_base_price": out[:, 1],
        "sum_disc_price": out[:, 2],
        "sum_charge": out[:, 3],
        "sum_disc": out[:, 4],
        "count": out[:, 5],
    }
    cnt = agg["count"].clip(1.0)
    agg["avg_qty"] = agg["sum_qty"] / cnt
    agg["avg_price"] = agg["sum_base_price"] / cnt
    agg["avg_disc"] = agg["sum_disc"] / cnt
    return agg


def q6_program(year: int = 1994, discount: float = 0.06, qty: float = 24.0):
    """Q6's kernel program: three range predicates + one product-sum.

    All three predicates run in the kernel, so the row count matches
    ``q6`` exactly.
    """
    lo = datagen.date(year)
    hi = datagen.date(year + 1)
    pred = encode_predicates(
        [
            ("range", 0, lo, hi),
            ("range", 1, discount - 0.011, discount + 0.011),
            ("range", 2, None, qty),  # quantity < qty
        ]
    )
    agg = encode_aggregates([[("col", 3), ("col", 1)]])
    return (*pred, *agg)


def _q6_layout(lineitem: Table) -> tuple[jax.Array, jax.Array]:
    cols = jnp.stack(
        [
            lineitem["l_shipdate"],  # 0
            lineitem["l_discount"],  # 1
            lineitem["l_quantity"],  # 2
            lineitem["l_extendedprice"],  # 3
        ]
    )
    keys = jnp.zeros((lineitem.num_rows,), jnp.int32)
    return cols, keys


def _q6_demux(out):
    return {"revenue": out[0, 0], "rows": out[0, 1].astype(np.int32)}


def q12_program(year: int = 1994):
    """Q12's kernel program over the joined layout."""
    lo = datagen.date(year)
    hi = datagen.date(year + 1)
    pred = encode_predicates(
        [
            ("lt", 0, 1),  # commitdate < receiptdate
            ("lt", 2, 0),  # shipdate < commitdate
            ("range", 1, lo, hi),  # receiptdate in the year window
        ]
    )
    agg = encode_aggregates(
        [
            [("le", 3, 1.0)],  # high priority: 1-URGENT, 2-HIGH
            [("gt", 3, 1.0)],  # low priority
        ]
    )
    return (*pred, *agg)


def _q12_layout(lineitem: Table, orders: Table) -> tuple[jax.Array, jax.Array]:
    """Join once; the join does not depend on the predicate constants, so
    the serving path amortizes it across every request of the batch."""
    joined = ops.fk_index_join(
        lineitem, "l_orderkey", orders, "o_orderkey", ("o_orderpriority",)
    )
    cols = jnp.stack(
        [
            joined["l_commitdate"],  # 0
            joined["l_receiptdate"],  # 1
            joined["l_shipdate"],  # 2
            joined["o_orderpriority"].astype(jnp.float32),  # 3
        ]
    )
    return cols, joined["l_shipmode"]


# 1.0 for the shipmode groups Q12 selects, 0.0 for the others.
_Q12_SELECT = np.isin(np.arange(len(datagen.SHIPMODE)), Q12_SHIPMODES).astype(np.float32)


def _q12_demux(out):
    """Q12's ``shipmode IN (MAIL, SHIP)``: the kernel groups every shipmode
    (rows of other shipmodes land in other groups), and the demux keeps the
    two selected groups, so the counts stay integer-exact."""
    return {
        "high_line_count": out[:, 0] * _Q12_SELECT,
        "low_line_count": out[:, 1] * _Q12_SELECT,
        "count": out[:, 2] * _Q12_SELECT,
    }


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    """One query's fused plan; :data:`SPECS` is the only place it is
    written down.

    ``layout(*tables)`` lays the tables out as the kernel's ``[C, N]``
    columns and ``[N]`` group keys, whatever the constants; the tables are
    ``(lineitem, orders)`` if ``needs_orders``, else ``(lineitem,)``.
    ``program(**constants)`` builds ``(pred_ops, pred_consts, agg_ops,
    agg_consts)`` in numpy.  ``demux(out)`` turns one ``[G, A + 1]`` kernel
    output slot, a device or a host array, into the query's result dict.
    """

    layout: Callable[..., tuple[jax.Array, jax.Array]]
    program: Callable[..., tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]]
    num_groups: int
    demux: Callable[[Any], dict[str, Any]]
    needs_orders: bool = False


SPECS: dict[str, QuerySpec] = {
    "q1": QuerySpec(_q1_layout, q1_program, 6, _q1_demux),
    "q6": QuerySpec(_q6_layout, q6_program, 1, _q6_demux),
    "q12": QuerySpec(_q12_layout, q12_program, len(datagen.SHIPMODE), _q12_demux, needs_orders=True),
}


def _fused(name: str, spec: QuerySpec) -> Callable[..., dict[str, jax.Array]]:
    def fused(*tables: Table, use_pallas: bool = True, **constants) -> dict[str, jax.Array]:
        """The query as one ``group_filter_agg`` pass over ``tables``, its
        constants given as keywords of the spec's ``program``."""
        cols, keys = spec.layout(*tables)
        pred_ops, pred_consts, agg_ops, agg_consts = spec.program(**constants)
        out = kops.group_filter_agg_multi(
            cols, keys, pred_ops, pred_consts[None], agg_ops, agg_consts[None],
            num_groups=spec.num_groups, use_pallas=use_pallas,
        )
        return spec.demux(out[0])

    fused.__name__ = fused.__qualname__ = f"{name}_fused"
    return fused


QUERIES = {"q1": q1, "q6": q6, "q12": q12}
FUSED_QUERIES = {name: _fused(name, spec) for name, spec in SPECS.items()}
q1_fused, q6_fused, q12_fused = (FUSED_QUERIES[q] for q in ("q1", "q6", "q12"))
#: Result keys that are row counts: every plan must agree on them exactly,
#: while float sums may differ in the order of their additions.
COUNT_KEYS = ("count", "rows", "high_line_count", "low_line_count")


# ---------------------------------------------------------------------------
# Serving plans: the query-shape contract behind scan-sharing micro-batches.
@dataclasses.dataclass(frozen=True)
class ServingPlan:
    """One query shape, ready to serve requests whose constants arrive at
    run time.

    ``cols``/``keys`` are the parameter-independent column layout (for Q12
    including the join, computed once); ``pred_ops``/``agg_ops`` the shared
    opcode structure, on the device; ``program(params)`` builds one
    request's constant tables in numpy; ``demux(out)`` turns one
    ``[G, A + 1]`` kernel output slot, a device or a host array, back into
    the query's result dict.
    """

    name: str
    cols: jax.Array
    keys: jax.Array
    pred_ops: jax.Array
    agg_ops: jax.Array
    num_groups: int
    program: Callable[[dict[str, Any]], tuple[np.ndarray, np.ndarray]]
    demux: Callable[[Any], dict[str, Any]]


def _plan_program(program_fn) -> Callable[[dict[str, Any]], tuple[np.ndarray, np.ndarray]]:
    def consts(params: dict[str, Any]) -> tuple[np.ndarray, np.ndarray]:
        _, pred_consts, _, agg_consts = program_fn(**params)
        return pred_consts, agg_consts

    return consts


def make_serving_plans(
    lineitem: Table, orders: Table | None = None
) -> dict[str, ServingPlan]:
    """Serving plans for every query of :data:`SPECS` servable over these
    tables.

    Q12 needs ``orders`` for its join; without it only Q1/Q6 are planned.
    """
    plans: dict[str, ServingPlan] = {}
    for name, spec in SPECS.items():
        if spec.needs_orders and orders is None:
            continue
        cols, keys = spec.layout(*((lineitem, orders) if spec.needs_orders else (lineitem,)))
        pred_ops, _, agg_ops, _ = spec.program()
        plans[name] = ServingPlan(
            name=name,
            cols=cols,
            keys=keys,
            pred_ops=jnp.asarray(pred_ops),
            agg_ops=jnp.asarray(agg_ops),
            num_groups=spec.num_groups,
            program=_plan_program(spec.program),
            demux=spec.demux,
        )
    return plans


def fused_query_serial(
    plan: ServingPlan, params: dict[str, Any], *, use_pallas: bool = True
) -> dict[str, np.ndarray]:
    """One request: the one-slot batch of :func:`fused_query_batch`."""
    return fused_query_batch(plan, [params], use_pallas=use_pallas)[0]


def fused_query_batch(
    plan: ServingPlan,
    param_list: list[dict[str, Any]],
    *,
    use_pallas: bool = True,
) -> list[dict[str, np.ndarray]]:
    """Scan sharing: N same-shape requests, ONE kernel pass over the data.

    Each request's constants become one slot of the batched SMEM program
    tables, stacked on the host and copied to the device at once; the
    ``[B, G, A + 1]`` output comes back in one copy and demultiplexes per
    request in numpy.  Results are host numpy values.  A slot equals a
    one-slot batch of the same constants: counts exactly, float sums within
    a few ulps (every slot visits the data blocks in the same order; the
    additions inside one block's dot may be ordered differently for
    another batch size).
    """
    with tracing.span("serve.consts"):
        consts = [plan.program(p) for p in param_list]
        pred_consts, agg_consts = jax.device_put(
            (np.stack([c[0] for c in consts]), np.stack([c[1] for c in consts]))
        )
    with tracing.span("serve.launch"):
        out = kops.group_filter_agg_multi(
            plan.cols, plan.keys, plan.pred_ops, pred_consts, plan.agg_ops, agg_consts,
            num_groups=plan.num_groups, use_pallas=use_pallas,
        )
    with tracing.span("serve.wait"):
        out = jax.device_get(out)
    with tracing.span("serve.demux"):
        return [plan.demux(out[b]) for b in range(len(param_list))]
