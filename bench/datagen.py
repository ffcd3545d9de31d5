"""TPC-H lineitem/orders columns generated on the device from a seed.

A copy of the program's generator (``repro.engine.datagen`` as of the
benchmark's first version), kept here so that the data every cell measures
cannot move with the program.  Column shapes follow the TPC-H spec:
quantity 1..50, extended price 900..105000, discount and tax in cents,
ship dates over 1992-01-01..1998-12-01, commit and receipt dates around
them, dictionary-coded return flag, line status, ship mode and order
priority, and ``l_orderkey`` a foreign key into ``orders``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LINEITEM_ROWS_PER_SF = 6_001_215
ORDERS_ROWS_PER_SF = 1_500_000
RETURNFLAG = ("A", "N", "R")
SHIPMODE = ("AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK")
ORDERPRIORITY = ("1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW")
DATE_EPOCH_DAYS = 8035  # 1992-01-01 in days since 1970
DATE_RANGE_DAYS = 2526  # through 1998-12-01


def date(year: int, month: int = 1, day: int = 1) -> float:
    """Days since 1970 of a predicate constant, as the program computes it."""
    return float((year - 1970) * 365.2425 + (month - 1) * 30.44 + (day - 1))


def rows(scale: float) -> tuple[int, int]:
    """(lineitem rows, orders rows) at a TPC-H scale factor."""
    return int(LINEITEM_ROWS_PER_SF * scale), int(ORDERS_ROWS_PER_SF * scale)


def key(seed: int) -> jax.Array:
    """A PRNG key from any whole number: every bit of a seed wider than 32
    bits counts, so two large seeds never share their tables."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


@functools.partial(jax.jit, static_argnames=("n", "num_orders"))
def lineitem(k: jax.Array, n: int, num_orders: int) -> dict[str, jax.Array]:
    ks = jax.random.split(k, 10)
    shipdate = jax.random.randint(ks[4], (n,), DATE_EPOCH_DAYS, DATE_EPOCH_DAYS + DATE_RANGE_DAYS)
    return {
        "l_quantity": jax.random.randint(ks[0], (n,), 1, 51).astype(jnp.float32),
        "l_extendedprice": jax.random.uniform(ks[1], (n,), jnp.float32, 900.0, 105000.0),
        "l_discount": jnp.round(jax.random.uniform(ks[2], (n,), jnp.float32, 0.0, 0.10) * 100) / 100,
        "l_tax": jnp.round(jax.random.uniform(ks[3], (n,), jnp.float32, 0.0, 0.08) * 100) / 100,
        "l_shipdate": shipdate.astype(jnp.float32),
        "l_commitdate": (shipdate + jax.random.randint(ks[5], (n,), -60, 60)).astype(jnp.float32),
        "l_receiptdate": (shipdate + jax.random.randint(ks[6], (n,), 1, 31)).astype(jnp.float32),
        "l_returnflag": jax.random.randint(ks[7], (n,), 0, len(RETURNFLAG)).astype(jnp.int32),
        "l_linestatus": (shipdate > DATE_EPOCH_DAYS + 1460).astype(jnp.int32),
        "l_orderkey": jax.random.randint(ks[8], (n,), 0, num_orders).astype(jnp.int32),
        "l_shipmode": jax.random.randint(ks[9], (n,), 0, len(SHIPMODE)).astype(jnp.int32),
    }


@functools.partial(jax.jit, static_argnames=("n",))
def orders(k: jax.Array, n: int) -> dict[str, jax.Array]:
    ks = jax.random.split(k, 4)
    return {
        "o_orderkey": jnp.arange(n, dtype=jnp.int32),
        "o_custkey": jax.random.randint(ks[0], (n,), 0, max(n // 10, 16)).astype(jnp.int32),
        "o_totalprice": jax.random.uniform(ks[1], (n,), jnp.float32, 850.0, 560000.0),
        "o_orderdate": jax.random.randint(
            ks[2], (n,), DATE_EPOCH_DAYS, DATE_EPOCH_DAYS + DATE_RANGE_DAYS).astype(jnp.float32),
        "o_orderpriority": jax.random.randint(ks[3], (n,), 0, len(ORDERPRIORITY)).astype(jnp.int32),
    }


def tables(seed: int, n: int, num_orders: int) -> tuple[dict, dict]:
    """lineitem and orders for one seed, each made in one jitted call."""
    k_li, k_od = jax.random.split(key(seed))
    return lineitem(k_li, n, num_orders), orders(k_od, num_orders)
